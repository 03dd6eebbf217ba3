"""Compile the main path's Pallas kernels for a described TPU v5e.

Every other test runs the kernels in interpret mode, which cannot see what
the Mosaic compiler refuses: block shapes off the (8, 128) tiling, operand
layouts that disagree with XLA's, scalar stores to VMEM, unsupported
reshapes, or more VMEM than a kernel may use.  These tests compile each
kernel ahead of time for one chip of a described ``v5e:2x2`` topology at
the paper's widths (|L| = 1280 groups, g = 10 padded to 16, m = n =
12,800), which needs the TPU compiler but no TPU.  The VMEM-resident
Sinkhorn kernel compiles at the benchmark's m = n = 3,200 and at the
largest square cost its VMEM budget admits.

The topology is described only inside the module-scoped fixture below,
never at import: the TPU library can be loaded by one process at a time,
and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import gradpsi as gp
from repro.kernels import screen as sc
from repro.kernels import sinkhorn as ks

L, G, N, D, B = 1280, 16, 12800, 16, 4        # paper widths; d, B for variants
TILE_N = gp.DEFAULT_TILE_N


@pytest.fixture(scope="module")
def one_chip():
    """A sharding on one chip of a described (not attached) v5e:2x2."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these tests
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was)


def _lower(name, sds):
    """Lower one kernel entry point at paper widths (shapes only)."""
    f32, i32, i8 = jnp.float32, jnp.int32, jnp.int8
    tl = gp.resolve_tile_l(L, G, TILE_N)
    tlf = gp.resolve_tile_l_factorized(L, G, TILE_N, D)
    m, Lt, Nt = L * G, L // tl, N // TILE_N
    kw = dict(num_groups=L, group_size=G, tau=sds((L,), f32), gamma=0.1,
              tile_l=tl, tile_n=TILE_N, interpret=False)

    def screen_ops(lead=()):
        return ([sds(lead + (L, N), f32)] * 3 + [sds(lead + (L, N), i8)]
                + [sds(lead + (L,), f32)] * 3 + [sds(lead + (N,), f32),
                                                 sds(lead + (L,), f32)])

    if name in ("gradpsi_pallas_f32", "gradpsi_pallas_bf16"):
        dt = jnp.bfloat16 if name.endswith("bf16") else f32
        return gp.gradpsi_pallas.lower(
            sds((m,), f32), sds((N,), f32), sds((m, N), dt),
            sds((Lt, Nt), i32), **kw)
    if name == "gradpsi_pallas_compact":
        return gp.gradpsi_pallas_compact.lower(
            sds((m,), f32), sds((N,), f32), sds((m, N), f32),
            sds((2, Lt * Nt), i32), sds((), i32), **kw)
    if name == "gradpsi_fused_pallas":
        return gp.gradpsi_fused_pallas.lower(
            sds((m,), f32), sds((N,), f32), sds((m, N), f32),
            *screen_ops(), **kw)
    if name == "screen_pallas":
        kws = dict(tau=kw["tau"], tile_l=tl, tile_n=TILE_N, interpret=False,
                   emit_verdict=False)
        return sc.screen_pallas.lower(*screen_ops(), **kws)
    if name == "gradpsi_pallas_compact_batched":
        return gp.gradpsi_pallas_compact_batched.lower(
            sds((B, m), f32), sds((B, N), f32), sds((B, m, N), f32),
            sds((3, B * Lt * Nt), i32), sds((), i32), **kw)
    if name == "gradpsi_fused_pallas_batched":
        return gp.gradpsi_fused_pallas_batched.lower(
            sds((B, m), f32), sds((B, N), f32), sds((B, m, N), f32),
            *screen_ops((B,)), **kw)
    if name == "gradpsi_fused_fact_pallas":
        return gp.gradpsi_fused_fact_pallas.lower(
            sds((m,), f32), sds((N,), f32), sds((m, D), f32), sds((m,), f32),
            sds((N, D), f32), sds((N,), f32), *screen_ops(),
            **dict(kw, tile_l=tlf))
    if name.startswith("sinkhorn_resident"):
        side = {"sinkhorn_resident": 3200, "sinkhorn_resident_budget": 4736}[name]
        return ks.sinkhorn_resident.lower(
            sds((side, side), f32), sds((side,), f32), sds((side,), f32),
            sds((side,), f32), sds((), f32), sds((), f32), max_iters=2000)
    raise KeyError(name)


@pytest.mark.parametrize("name", [
    "gradpsi_pallas_f32",
    "gradpsi_pallas_bf16",
    "gradpsi_pallas_compact",
    "gradpsi_fused_pallas",
    "screen_pallas",
    "gradpsi_pallas_compact_batched",
    "gradpsi_fused_pallas_batched",
    "gradpsi_fused_fact_pallas",
    "sinkhorn_resident",
    "sinkhorn_resident_budget",
])
def test_kernel_compiles_for_v5e(one_chip, name):
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    # the compact kernels' compile also accepts their VMEM request of
    # COMPACT_PIPELINE_BUFFERS * VMEM_BUDGET_BYTES, and the resident Sinkhorn
    # kernel's of its cost plus working set (kernels/sinkhorn.vmem_bytes)
    compiled = _lower(name, sds).compile()
    # a Mosaic kernel, not an interpreted loop, is in the program
    assert "tpu_custom_call" in compiled.as_text()

