"""The build recorder (``repro.utils.trace``) and the Sinkhorn solver's
phase scopes: the recorder sees the persistent cache miss and then hit, on
the ``perf_counter`` clock; the scopes reach the compiled program's
metadata and leave the program and its persistent-cache key as they were.
A cost too large for the resident kernel's VMEM keeps the XLA loop and
its scopes on a TPU too."""
import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import cache_key, compiler
from jax.experimental.compilation_cache import compilation_cache

from repro.core import sinkhorn
from repro.utils import trace

SCOPES = ("sinkhorn.f_update", "sinkhorn.g_update", "sinkhorn.marginal_err", "sinkhorn.plan")
SETTINGS = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def persistent_cache(tmp_path):
    """A persistent compilation cache in a temporary directory that keeps
    every program; JAX's settings and cache state restored afterwards."""
    was = {name: getattr(jax.config, name) for name in SETTINGS}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    try:
        yield tmp_path
    finally:
        for name, value in was.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()
        jax.clear_caches()


def test_recorder_counts_a_miss_then_a_hit(persistent_cache):
    def scaled_shift(x):
        return 2.0 * x + 1.0

    x = jnp.arange(4.0)
    n0 = len(trace.builds())
    t0 = time.perf_counter()
    jax.block_until_ready(jax.jit(scaled_shift)(x))
    jax.clear_caches()                     # the next call builds again, from the cache
    jax.block_until_ready(jax.jit(scaled_shift)(x))
    t1 = time.perf_counter()
    mine = [b for b in trace.builds()[n0:] if b.fun_name == "jit(scaled_shift)"]
    assert [b.event for b in mine if b.event.startswith("cache_")] == [
        "cache_miss", "cache_hit", "cache_retrieval"]
    assert [b.event for b in mine if b.event == "compile"] == ["compile", "compile"]
    assert all(t0 <= b.stamp <= t1 for b in mine)
    assert [b.stamp for b in mine] == sorted(b.stamp for b in mine)
    assert all(b.seconds >= 0 for b in mine)
    assert any(b.event == "trace" and b.fun_name == "scaled_shift"
               for b in trace.builds()[n0:])


def _lowered(m=16, n=16):
    f32 = jnp.float32
    sds = jax.ShapeDtypeStruct
    return sinkhorn.sinkhorn_log.lower(sds((m, n), f32), sds((m,), f32), sds((n,), f32),
                                       eps=0.05, max_iters=30, tol=1e-8)


def _key(lowered):
    opts = compiler.get_compile_options(num_replicas=1, num_partitions=1)
    dev = jax.devices()[0]
    return cache_key.get(lowered.compiler_ir("stablehlo"), np.array([dev]), opts, dev.client)


def test_scopes_leave_the_program_and_its_cache_key_unchanged(monkeypatch):
    jax.clear_caches()
    scoped = _lowered()
    with monkeypatch.context() as mp:
        mp.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        jax.clear_caches()                 # trace the body again, without scopes
        plain = _lowered()
    jax.clear_caches()
    assert scoped.as_text(debug_info=False) == plain.as_text(debug_info=False)
    assert _key(scoped) == _key(plain)
    with_info = scoped.as_text(debug_info=True)
    assert all(s in with_info for s in SCOPES)
    assert not any(s in plain.as_text(debug_info=True) for s in SCOPES)


def test_compiled_solver_names_its_phases_in_metadata():
    text = _lowered().compile().as_text()
    for s in SCOPES:
        assert f'/{s}/' in text, s
    loop = [f'op_name="jit(sinkhorn_log)/while/body/{s}/' for s in SCOPES[:3]]
    assert all(s in text for s in loop)
    assert 'op_name="jit(sinkhorn_log)/while/body/sinkhorn.plan' not in text


def test_cost_over_the_vmem_budget_keeps_the_xla_loop(monkeypatch):
    """At the paper's width (m = n = 12,800, 655 MB) the cost does not fit
    VMEM, so on a TPU too ``sinkhorn_log`` compiles the XLA ``while`` loop
    with its three loop scopes, and no kernel."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()
    n0 = len(trace.routes())
    text = _lowered(12800, 12800).compile().as_text()
    jax.clear_caches()
    assert trace.routes()[n0:] == [trace.Route("sinkhorn_log", "xla", (12800, 12800))]
    assert "while(" in text
    assert "sinkhorn_resident" not in text and "custom_call" not in text
    for s in SCOPES[:3]:
        assert f'op_name="jit(sinkhorn_log)/while/body/{s}/' in text, s
