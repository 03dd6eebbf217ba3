"""The plain reference: it finds the fixed point of the entropic problem,
and it solves what the system solves (the system is imported by the test
only)."""
import jax.numpy as jnp
import numpy as np
import pytest

import data
import reference as R


def _problem(L=8, g=10, seed=3):
    (C,) = data.costs(seed, L=L, g=g, dim=2, shift=5.0, count=1)
    n = L * g
    a = jnp.full((n,), 1.0 / n, jnp.float32)
    return C, a, a


def test_plan_is_the_entropic_optimum():
    """At the optimum ``log T_ij - (f_i + g_j - C_ij) / eps`` vanishes where
    the marginals hold; check the marginals and the Gibbs form."""
    C, a, b = _problem()
    eps = 0.05
    sol = R.sinkhorn(C, a, b, eps, 1e-6, max_iters=5000)
    assert int(sol.iters) < 5000
    assert float(R.marginal_l1(sol.plan, a, b)) < 2e-6
    # the Gibbs form: rank one after dividing by exp(-C / eps)
    K = np.asarray(sol.plan, np.float64) / np.exp(-np.asarray(C, np.float64) / eps)
    u, s, _ = np.linalg.svd(K)
    assert s[1] / s[0] < 1e-5


def test_iteration_cap_and_tolerance():
    C, a, b = _problem()
    assert int(R.sinkhorn(C, a, b, 0.01, 0.0, max_iters=7).iters) == 7
    assert int(R.sinkhorn(C, a, b, 0.01, 1.0, max_iters=7).iters) == 1


@pytest.mark.parametrize("eps", [0.01, 0.05])
def test_reference_matches_the_system(eps):
    from repro.core import sinkhorn_log

    C, a, b = _problem()
    ref = R.sinkhorn(C, a, b, eps, 1e-8, max_iters=500)
    got = sinkhorn_log(C, a, b, eps=eps, max_iters=500, tol=1e-8)
    assert int(got.n_iters) == int(ref.iters)
    assert float(jnp.sum(jnp.abs(got.plan - ref.plan))) < 1e-5
