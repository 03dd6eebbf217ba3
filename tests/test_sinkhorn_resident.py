"""The VMEM-resident Sinkhorn kernel against the XLA loop, and the route
``sinkhorn_log`` takes.

The kernel runs in Pallas interpret mode here (``kernels.ops.default_interpret``
on the CPU); its Mosaic compile for a v5e is in ``tests/test_tpu_compile.py``.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sinkhorn
from repro.kernels import sinkhorn as ks
from repro.kernels.ops import default_interpret
from repro.utils import trace


def _problem(m, n, seed=0):
    rng = np.random.default_rng(seed)
    xs = rng.normal(size=(m, 2))
    xt = rng.normal(size=(n, 2)) + 0.5
    C = ((xs[:, None, :] - xt[None, :, :]) ** 2).sum(-1)
    C = jnp.asarray(C / C.max(), jnp.float32)
    return C, jnp.full((m,), 1.0 / m, jnp.float32), jnp.full((n,), 1.0 / n, jnp.float32)


def _xla(C, a, b, eps, max_iters, tol):
    """``(f, g, n_iters, err)`` of the XLA loop, as ``sinkhorn_log`` runs it."""
    loga = jnp.log(jnp.clip(a, 1e-38))
    logb = jnp.log(jnp.clip(b, 1e-38))
    return jax.jit(sinkhorn._xla_loop, static_argnums=7)(C, a, b, loga, logb, eps, tol,
                                                         max_iters)


def _plan(f, g, C, eps):
    return jnp.exp((f[:, None] + g[None, :] - C) / eps)


# (m, n, eps, max_iters, tol): on the tiling, two full row blocks; rows
# left over after the last full block (m = 200 = 128 + 72); a tolerance
# that stops early
CASES = {
    "square_on_tiling": (256, 256, 0.05, 60, 1e-8),
    "ragged_rows": (200, 128, 0.05, 40, 1e-8),
    "stops_early": (48, 128, 0.1, 500, 1e-4),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_matches_the_xla_loop(case):
    m, n, eps, max_iters, tol = CASES[case]
    C, a, b = _problem(m, n)
    want = _xla(C, a, b, eps, max_iters, tol)
    loga = jnp.log(jnp.clip(a, 1e-38))
    logb = jnp.log(jnp.clip(b, 1e-38))
    f, g, it, err = ks.sinkhorn_resident(C, a, loga, logb, eps, tol, max_iters=max_iters,
                                         interpret=default_interpret())
    assert int(it) == int(want[2])
    if case == "stops_early":
        assert int(it) < max_iters
    else:
        assert int(it) == max_iters
    np.testing.assert_allclose(f, want[0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(g, want[1], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(err, want[3], rtol=0, atol=1e-6)
    np.testing.assert_allclose(_plan(f, g, C, eps), _plan(want[0], want[1], C, eps),
                               rtol=1e-4, atol=1e-9)


@pytest.mark.parametrize("case", list(CASES))
def test_resident_route_returns_the_xla_routes_result(case, monkeypatch):
    """``sinkhorn_log`` on the resident route (forced here, and the kernel
    interpreted) returns what it returns on the XLA route, plan included."""
    m, n, eps, max_iters, tol = CASES[case]
    C, a, b = _problem(m, n, seed=1)
    jax.clear_caches()
    want = sinkhorn.sinkhorn_log(C, a, b, eps=eps, max_iters=max_iters, tol=tol)
    monkeypatch.setattr(sinkhorn, "takes_resident_route", lambda *args: True)
    monkeypatch.setattr(ks, "sinkhorn_resident", functools.partial(
        ks.sinkhorn_resident, interpret=default_interpret()))
    jax.clear_caches()
    n0 = len(trace.routes())
    got = sinkhorn.sinkhorn_log(C, a, b, eps=eps, max_iters=max_iters, tol=tol)
    jax.clear_caches()
    assert trace.routes()[n0:] == [trace.Route("sinkhorn_log", "resident", (m, n))]
    assert int(got.n_iters) == int(want.n_iters)
    np.testing.assert_allclose(got.f, want.f, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got.g, want.g, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got.err, want.err, rtol=0, atol=1e-6)
    np.testing.assert_allclose(got.plan, want.plan, rtol=1e-4, atol=1e-9)


def _sds(m, n, dtype=jnp.float32):
    sds = jax.ShapeDtypeStruct
    return sds((m, n), dtype), sds((m,), dtype), sds((n,), dtype)


# (m, n, dtype, on a TPU takes the resident route)
ROUTES = {
    "benchmark_cost": (3200, 3200, jnp.float32, True),
    "largest_square_under_budget": (4736, 4736, jnp.float32, True),
    "over_budget_next_square": (4864, 4864, jnp.float32, False),
    "over_budget_paper_width": (12800, 12800, jnp.float32, False),
    "rows_off_tiling": (100, 128, jnp.float32, False),
    "columns_off_tiling": (128, 100, jnp.float32, False),
    "bfloat16": (3200, 3200, jnp.bfloat16, False),
}


@pytest.mark.parametrize("case", list(ROUTES))
def test_route_on_a_tpu_follows_dtype_and_size(case, monkeypatch):
    m, n, dtype, resident = ROUTES[case]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert sinkhorn.takes_resident_route(*_sds(m, n, dtype)) is resident


def test_route_budget_holds_the_working_set():
    assert ks.vmem_bytes(4736, 4736) <= ks.VMEM_BUDGET_BYTES < ks.vmem_bytes(4864, 4864)
    # the cost itself is most of it
    assert 4 * 3200 * 3200 / ks.vmem_bytes(3200, 3200) > 0.85


@pytest.mark.parametrize("shape", [(64, 256), (3200, 3200)])
def test_route_on_the_cpu_is_xla(shape):
    assert jax.default_backend() == "cpu"
    assert not sinkhorn.takes_resident_route(*_sds(*shape))


def test_counter_records_each_trace():
    C, a, b = _problem(24, 40, seed=2)
    jax.clear_caches()
    n0 = len(trace.routes())
    sinkhorn.sinkhorn_log(C, a, b, eps=0.1, max_iters=7)
    sinkhorn.sinkhorn_log(C, a, b, eps=0.1, max_iters=7)      # no new trace
    sinkhorn.sinkhorn_log(C[:16], a[:16] * 1.5, b, eps=0.1, max_iters=7)
    jax.clear_caches()
    sinkhorn.sinkhorn_log(C, a, b, eps=0.1, max_iters=7)      # traced again
    assert trace.routes()[n0:] == [
        trace.Route("sinkhorn_log", "xla", (24, 40)),
        trace.Route("sinkhorn_log", "xla", (16, 40)),
        trace.Route("sinkhorn_log", "xla", (24, 40)),
    ]
