"""The plain group-sparse reference: its dual is the paper's, its float64
solve and the optimum that the check takes are SciPy's float64 L-BFGS-B
optimum, and in float32 it stops at that precision's floor (the system is
not imported)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.optimize as so

import data
import reference_gs as R

L, G = 8, 10


@pytest.fixture(scope="module")
def x64():
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


def _problem(seed=3):
    (C,) = data.costs(seed, L=L, g=G, dim=2, shift=5.0, count=1)
    n = L * G
    a = jnp.full((n,), 1.0 / n, jnp.float32)
    return C, a, a, R.from_rho(L, G, 1.0, 0.8)


def _scipy(C, a, b, reg):
    """float64 L-BFGS-B on -D, with D's gradient from the reference."""
    m = C.shape[0]
    C, a, b = (jnp.asarray(v, jnp.float64) for v in (C, a, b))

    @jax.jit
    def neg(x):
        v, ga, gb = R.dual(x[:m], x[m:], C, a, b, reg)
        return -v, -jnp.concatenate([ga, gb])

    res = so.minimize(lambda x: tuple(np.asarray(u) for u in neg(jnp.asarray(x))),
                      np.zeros(C.shape[0] + C.shape[1]), jac=True, method="L-BFGS-B",
                      options=dict(maxiter=10000, maxcor=10, gtol=1e-13, ftol=1e-16))
    x = jnp.asarray(res.x)
    return -res.fun, np.asarray(R.plan(x[:m], x[m:], C, reg))


def test_gradient_is_the_dual_derivative(x64):
    C, a, b, reg = _problem()
    C, a, b = (jnp.asarray(v, jnp.float64) for v in (C, a, b))
    key = jax.random.key(0)
    alpha = 0.5 + 0.1 * jax.random.normal(key, (C.shape[0],), jnp.float64)
    beta = 0.5 + 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (C.shape[1],), jnp.float64)
    _, ga, gb = R.dual(alpha, beta, C, a, b, reg)
    ad_a, ad_b = jax.grad(lambda u, v: R.dual(u, v, C, a, b, reg)[0], (0, 1))(alpha, beta)
    np.testing.assert_allclose(ga, ad_a, atol=1e-12)
    np.testing.assert_allclose(gb, ad_b, atol=1e-12)
    # the plan is that gradient's psi part, and every plan entry is >= 0
    T = R.plan(alpha, beta, C, reg)
    np.testing.assert_allclose(a - T.sum(1), ga, atol=1e-12)
    assert float(T.min()) >= 0


def test_float64_solve_is_scipys_optimum(x64):
    C, a, b, reg = _problem()
    value, T64 = _scipy(C, a, b, reg)
    sol = R.solve(C, a, b, 1e-12, reg=reg, max_iters=5000, dtype=jnp.float64)
    assert float(sol.residual) <= 1e-12
    assert abs(float(sol.value) - value) <= 1e-12 * abs(value)
    assert float(np.abs(np.asarray(sol.plan) - T64).sum()) < 1e-5


@pytest.mark.parametrize("f32_iters,rounds", [(3000, R.ROUNDS), (5, R.ROUNDS), (1, R.ROUNDS),
                                              (3000, 0)])
def test_optimum_is_scipys_optimum(x64, monkeypatch, f32_iters, rounds):
    """From the float32 floor (3,000 iterations at most), from float32
    points so far off (5, 1) that the working sets have to grow, and with
    no working set, every block solved in float64."""
    monkeypatch.setattr(R, "ROUNDS", rounds)
    C, a, b, reg = _problem()
    value, T64 = _scipy(C, a, b, reg)
    sol = R.optimum(C, a, b, reg=reg, max_iters=f32_iters)
    assert sol.plan.dtype == np.float64
    assert float(sol.residual) <= R.F64_GTOL
    assert abs(float(sol.value) - value) <= 1e-12 * abs(value)
    assert float(np.abs(sol.plan - T64).sum()) < 1e-5


def test_float32_solve_reaches_its_floor(x64):
    """float32 meets the marginals to what its dual variables resolve: at
    this size a plan within 1e-3 of the float64 optimum in L1."""
    C, a, b, reg = _problem()
    _, T64 = _scipy(C, a, b, reg)
    sol = R.solve(C, a, b, 0.0, reg=reg, max_iters=3000)
    assert sol.plan.dtype == jnp.float32
    assert int(sol.iters) < 3000              # stopped by the floor, not the cap
    T = np.asarray(sol.plan, np.float64)
    assert np.abs(T - T64).sum() < 1e-3
    assert np.max(np.abs(T.sum(0) - np.asarray(b)) / np.asarray(b)) < 1e-2


def test_bfloat16_is_further_from_the_optimum():
    C, a, b, reg = _problem()
    f32 = R.solve(C, a, b, 0.0, reg=reg, max_iters=3000)
    bf16 = R.solve(C, a, b, 0.0, reg=reg, max_iters=3000, dtype=jnp.bfloat16)
    gap = float(jnp.sum(jnp.abs(bf16.plan.astype(jnp.float32) - f32.plan)))
    assert gap > 1e-2
