"""Differentiable OT layer: Danskin gradients against ground truth.

Three independent referees certify ``jax.grad`` of the layer:

  * f64 central finite differences of the unscreened reference solver
    (committed in tests/fixtures/golden_diff.json; tools/gen_golden_diff.py
    regenerates them) — the strongest oracle, backend-free;
  * AD through :func:`repro.ot.diff.unrolled_value` — a plain dual-ascent
    solver written so JAX *can* differentiate through it;
  * cross-backend agreement — every grad_impl solves the same padded
    problem: the Pallas backends refine to the same value bit for bit,
    the XLA reference backends to within a few f32 ulps of it.

Plus the stochastic minibatch solver's contract: deterministic given its
seed, and converging to the exact L-BFGS objective on the golden problem.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.ot as ot
from repro.core import groups as G
from repro.core.regularizers import GroupSparseReg
from repro.ot import diff
from tests.conftest import FIXTURE_DIR

# (grad_impl, pallas_impl) combos that must match FD; the kernel ones
# (the last three) must also agree bitwise
BACKENDS = [
    ("dense", "auto"),
    ("screened", "auto"),
    ("pallas", "grid"),
    ("pallas", "compact"),
    ("fused", "grid"),
]
KERNEL_BACKENDS = BACKENDS[2:]

# the FD harness needs the dual residual at the f32 noise floor; the plain
# f32 L-BFGS line search stalls around ||g||~1e-4, so the layer appends
# fixed-step exact ascent (OTLayer.grad_refine) — see the layer docstring
PLAN_KW = dict(gtol=1e-7, max_iters=2000, ftol=1e-12)
REFINE_DENSE = 1000
REFINE_SAMPLES = 2000


@pytest.fixture(scope="module")
def golden():
    with open(os.path.join(FIXTURE_DIR, "golden_diff.json")) as f:
        data = json.load(f)
    assert data["schema_version"] == 1
    return data


def _dense_problem(golden):
    c = golden["dense"]["coords"]
    L, g, n = c["L"], c["g"], c["n"]
    rng = np.random.default_rng(c["seed"])
    C = rng.random((L * g, n), dtype=np.float32)
    reg = GroupSparseReg.from_rho(golden["dense"]["gamma"],
                                  golden["dense"]["rho"])
    return C, L, g, n, reg


def _samples_problem(golden):
    c = golden["samples"]["coords"]
    L, g, n, d = c["L"], c["g"], c["n"], c["d"]
    rng = np.random.default_rng(c["seed"])
    X = rng.normal(size=(L * g, d)).astype(np.float32)
    Y = rng.normal(size=(n, d)).astype(np.float32)
    reg = GroupSparseReg.from_rho(golden["samples"]["gamma"],
                                  golden["samples"]["rho"])
    return X, Y, L, g, n, reg


def _layer(L, g, n, reg, grad_impl, pallas_impl, **kw):
    plan = ot.ExecutionPlan(grad_impl=grad_impl, pallas_impl=pallas_impl,
                            **PLAN_KW)
    return diff.OTLayer(L, g, n, reg, plan=plan, **kw)


# -- value: bitwise parity with the façade, cross-backend, vs f64 -------------

def test_layer_value_bitwise_equals_executor(golden):
    """grad_refine=0 runs the Executor's exact jitted program."""
    C, L, g, n, reg = _dense_problem(golden)
    spec = G.GroupSpec(num_groups=L, group_size=g, sizes=(g,) * L, m=L * g)
    a = np.full(L * g, 1.0 / (L * g), np.float32)
    b = np.full(n, 1.0 / n, np.float32)
    prob = ot.Problem.from_padded(C, a, b, spec, reg)
    for grad_impl, pallas_impl in BACKENDS:
        plan = ot.ExecutionPlan(grad_impl=grad_impl, pallas_impl=pallas_impl,
                                **PLAN_KW)
        sol = ot.compile(prob, plan).solve()
        layer = diff.OTLayer(L, g, n, reg, plan=plan)
        v = layer(C)
        assert float(v) == float(sol.value), (grad_impl, pallas_impl)


@pytest.fixture(scope="module")
def refined_values(golden):
    """The refined layer value of the golden dense problem, per backend."""
    C, L, g, n, reg = _dense_problem(golden)
    return {
        (grad_impl, pallas_impl): np.float32(
            _layer(L, g, n, reg, grad_impl, pallas_impl,
                   grad_refine=REFINE_DENSE)(C)
        )
        for grad_impl, pallas_impl in BACKENDS
    }


def test_refined_value_bitwise_across_backends(golden, refined_values):
    """The three Pallas-kernel backends refine to the same f32 value, bit
    for bit, and it sits on the committed f64 optimum.

    They share one kernel body, so their oracles run one op sequence and
    their L-BFGS trajectories never part.
    """
    vals = {refined_values[bk] for bk in KERNEL_BACKENDS}
    assert len(vals) == 1, vals
    assert vals.pop() == pytest.approx(golden["dense"]["value_f64"], abs=5e-6)


def test_refined_value_reference_backends_within_ulps(golden, refined_values):
    """``dense`` and ``screened`` land a few f32 ulps from the kernels.

    Their oracles are XLA reductions whose summation order XLA picks per
    fusion, and that choice depends on the program around them: on the
    CPU the same dense oracle gives other gradient bits inside the solve
    than alone.  So their L-BFGS trajectories part from the kernels' after
    a few iterations, and the refine keeps each end point's offset along
    the dual's flat direction (alpha + t, beta - t).  Readings on the CPU:
    dense +2 ulps, screened +1 ulp from the kernels' value.
    """
    kernel = refined_values[KERNEL_BACKENDS[0]]
    for bk in BACKENDS[:2]:
        v = refined_values[bk]
        assert abs(int(v.view(np.int32)) - int(kernel.view(np.int32))) <= 4, (
            bk, v, kernel)
        assert v == pytest.approx(golden["dense"]["value_f64"], abs=5e-6)


# -- dense cost: Danskin grad vs committed f64 FD, every backend --------------

@pytest.mark.parametrize("grad_impl,pallas_impl", BACKENDS)
def test_danskin_grad_matches_f64_fd_dense(golden, grad_impl, pallas_impl):
    C, L, g, n, reg = _dense_problem(golden)
    layer = _layer(L, g, n, reg, grad_impl, pallas_impl,
                   grad_refine=REFINE_DENSE)
    val, grad = jax.jit(jax.value_and_grad(layer))(jnp.asarray(C))
    grad = np.asarray(grad)
    ginf = np.abs(grad).max()
    assert ginf > 0
    for i, j, fd in golden["dense"]["fd_probes"]:
        assert abs(grad[i, j] - fd) <= 1e-4 * ginf, (i, j, grad[i, j], fd)
    # the Danskin gradient IS the optimal plan: nonnegative, row sums = a
    assert grad.min() >= 0
    np.testing.assert_allclose(grad.sum(1), np.full(L * g, 1.0 / (L * g)),
                               atol=2e-4)


def test_ot_loss_functional_matches_layer(golden):
    C, L, g, n, reg = _dense_problem(golden)
    layer = _layer(L, g, n, reg, "screened", "auto")
    v1 = layer(C)
    v2 = ot.ot_loss(jnp.asarray(C), num_groups=L, group_size=g, reg=reg,
                    plan=layer.plan)
    assert float(v1) == float(v2)


def test_grad_wrt_marginals_are_optimal_duals(golden):
    """Danskin for the marginals: dW/da = alpha*, dW/db = beta* — checked
    against the duals the SAME refined solve reports."""
    C, L, g, n, reg = _dense_problem(golden)
    layer = _layer(L, g, n, reg, "dense", "auto", grad_refine=REFINE_DENSE)
    a = jnp.full((L * g,), 1.0 / (L * g), jnp.float32)
    b = jnp.full((n,), 1.0 / n, jnp.float32)
    ga = jax.grad(layer, argnums=1)(jnp.asarray(C), a, b)
    gb = jax.grad(layer, argnums=2)(jnp.asarray(C), a, b)
    _, alpha, beta = diff._solve_duals(layer, jnp.asarray(C), a, b)
    np.testing.assert_array_equal(np.asarray(ga), np.asarray(alpha))
    np.testing.assert_array_equal(np.asarray(gb), np.asarray(beta))


# -- dense cost: Danskin grad vs AD through an unrolled solver ----------------

def test_danskin_grad_matches_unrolled_ad(golden):
    """Differentiating THROUGH 3000 unrolled dual-ascent steps lands on the
    same gradient the envelope theorem gives in one backward pass."""
    C, L, g, n, reg = _dense_problem(golden)
    a = jnp.full((L * g,), 1.0 / (L * g), jnp.float32)
    b = jnp.full((n,), 1.0 / n, jnp.float32)

    layer = _layer(L, g, n, reg, "dense", "auto", grad_refine=REFINE_DENSE)
    v_d, g_d = jax.value_and_grad(layer)(jnp.asarray(C))

    unrolled = jax.jit(jax.value_and_grad(
        lambda Cm: diff.unrolled_value(Cm, a, b, num_groups=L, group_size=g,
                                       reg=reg)
    ))
    v_u, g_u = unrolled(jnp.asarray(C))
    assert np.all(np.isfinite(np.asarray(g_u)))
    assert float(v_u) == pytest.approx(float(v_d), abs=2e-6)
    # both sides carry their own f32 solver residual (~1e-6 each); the
    # envelope and unrolled gradients agree to the combined noise floor
    assert float(jnp.abs(g_u - g_d).max()) <= 1e-5


# -- samples mode: materialization-free pullback vs committed f64 FD ----------

@pytest.mark.parametrize("grad_impl,pallas_impl",
                         [("dense", "auto"), ("pallas", "grid"),
                          ("fused", "grid")])
def test_samples_grad_matches_f64_fd(golden, grad_impl, pallas_impl):
    """from_samples chain-rules dW/dC = T* to the coordinates (normalized
    geometry, scale frozen exactly like the fixture's FD reference)."""
    X, Y, L, g, n, reg = _samples_problem(golden)
    layer = _layer(L, g, n, reg, grad_impl, pallas_impl,
                   grad_refine=REFINE_SAMPLES, normalize_cost=True)
    f = jax.jit(jax.value_and_grad(
        lambda X_, Y_: layer.from_samples(X_, Y_), argnums=(0, 1)))
    val, (gX, gY) = f(jnp.asarray(X), jnp.asarray(Y))
    assert float(val) == pytest.approx(golden["samples"]["value_f64"],
                                       abs=5e-6)
    gX, gY = np.asarray(gX), np.asarray(gY)
    ginf = max(np.abs(gX).max(), np.abs(gY).max())
    assert ginf > 0
    for i, k, fd in golden["samples"]["fd_x_probes"]:
        assert abs(gX[i, k] - fd) <= 2e-4 * ginf, ("x", i, k, gX[i, k], fd)
    for j, k, fd in golden["samples"]["fd_y_probes"]:
        assert abs(gY[j, k] - fd) <= 2e-4 * ginf, ("y", j, k, gY[j, k], fd)


def test_samples_backends_agree(golden):
    """Factorized (pallas) and materialized (dense) sample routes compute
    the same value and the same coordinate gradients."""
    X, Y, L, g, n, reg = _samples_problem(golden)
    out = {}
    for grad_impl, pallas_impl in (("dense", "auto"), ("pallas", "grid")):
        layer = _layer(L, g, n, reg, grad_impl, pallas_impl,
                       grad_refine=REFINE_SAMPLES, normalize_cost=True)
        f = jax.value_and_grad(
            lambda X_, Y_: layer.from_samples(X_, Y_), argnums=(0, 1))
        out[grad_impl] = f(jnp.asarray(X), jnp.asarray(Y))
    v_d, (gx_d, gy_d) = out["dense"]
    v_p, (gx_p, gy_p) = out["pallas"]
    assert float(v_d) == pytest.approx(float(v_p), abs=1e-6)
    np.testing.assert_allclose(gx_d, gx_p, atol=1e-5)
    np.testing.assert_allclose(gy_d, gy_p, atol=1e-5)


def test_backward_pass_adds_no_solver_calls(golden):
    """O(1) solves per training step: value_and_grad = ONE forward solve,
    the backward pass is closed-form plan recovery."""
    C, L, g, n, reg = _dense_problem(golden)
    layer = _layer(L, g, n, reg, "screened", "auto")
    diff.reset_solve_count()
    jax.value_and_grad(layer)(jnp.asarray(C))   # eager: fwd rule runs once
    assert diff.solve_count() == 1


# -- stochastic minibatch solver ---------------------------------------------

def test_stochastic_converges_to_lbfgs_objective(golden):
    """The minibatch dual-ascent solver reaches the exact solver's
    objective on the golden problem (fixed seed, tolerance 1e-3)."""
    C, L, g, n, reg = _dense_problem(golden)
    spec = G.GroupSpec(num_groups=L, group_size=g, sizes=(g,) * L, m=L * g)
    a = np.full(L * g, 1.0 / (L * g), np.float32)
    b = np.full(n, 1.0 / n, np.float32)
    prob = ot.Problem.from_padded(C, a, b, spec, reg)

    exact = ot.compile(prob, ot.ExecutionPlan(grad_impl="dense",
                                              **PLAN_KW)).solve()
    plan = ot.ExecutionPlan(solver="stochastic", sgd_epochs=200,
                            sgd_batch_blocks=2, sgd_block_cols=4,
                            sgd_step_size=0.5, sgd_decay=0.02)
    sol1 = ot.compile(prob, plan).solve()
    assert abs(float(sol1.value) - float(exact.value)) <= 1e-3
    # deterministic given the seed: a rerun is bitwise identical
    sol2 = ot.compile(prob, plan).solve()
    assert float(sol1.value) == float(sol2.value)
    # a different seed takes a different path to the same neighborhood
    sol3 = ot.compile(prob, ot.ExecutionPlan(
        solver="stochastic", sgd_epochs=200, sgd_batch_blocks=2,
        sgd_block_cols=4, sgd_step_size=0.5, sgd_decay=0.02,
        sgd_seed=1)).solve()
    assert float(sol3.value) != float(sol1.value)
    assert abs(float(sol3.value) - float(exact.value)) <= 1e-3


def test_stochastic_layer_gradients_still_danskin(golden):
    """solver='stochastic' slots under the same custom_vjp: gradients are
    the plan recovered from ITS duals (row sums ~ a at convergence)."""
    C, L, g, n, reg = _dense_problem(golden)
    plan = ot.ExecutionPlan(solver="stochastic", sgd_epochs=200,
                            sgd_batch_blocks=2, sgd_block_cols=4,
                            sgd_step_size=0.5, sgd_decay=0.02)
    # the stochastic duals start farther from the optimum than L-BFGS's
    # (objective gap ~1e-4), so the polish loop needs more steps to reach
    # the same dual residual before the FD gate applies
    layer = diff.OTLayer(L, g, n, reg, plan=plan, grad_refine=4000)
    val, grad = jax.value_and_grad(layer)(jnp.asarray(C))
    grad = np.asarray(grad)
    assert grad.min() >= 0
    np.testing.assert_allclose(grad.sum(1), np.full(L * g, 1.0 / (L * g)),
                               atol=2e-4)
    for i, j, fd in golden["dense"]["fd_probes"]:
        assert abs(grad[i, j] - fd) <= 1e-4 * np.abs(grad).max()


def test_stochastic_rejects_stream_and_mesh(golden):
    C, L, g, n, reg = _dense_problem(golden)
    spec = G.GroupSpec(num_groups=L, group_size=g, sizes=(g,) * L, m=L * g)
    a = np.full(L * g, 1.0 / (L * g), np.float32)
    b = np.full(n, 1.0 / n, np.float32)
    prob = ot.Problem.from_padded(C, a, b, spec, reg)
    ex = ot.compile(prob, ot.ExecutionPlan(solver="stochastic"))
    with pytest.raises(ValueError, match="stream"):
        ex.stream([prob])
