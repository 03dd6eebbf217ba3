"""OT serving engine: bucketing, slot recycling, convergence to solo values."""
import numpy as np
import pytest

from repro import ot
from repro.core import cpu_baseline
from repro.core.lbfgs import LbfgsOptions
from repro.core.ot import solve_groupsparse_ot, squared_euclidean_cost
from repro.core.regularizers import ElasticNetGroupReg, GroupSparseReg, L2Reg
from repro.core.solver import (
    SolveOptions,
    dispatch_count,
    reset_dispatch_count,
)
from repro.serving.ot_engine import OTRequest, OTServingEngine

# reference solves go through the deprecated solve_groupsparse_ot shim ON
# PURPOSE (engine results are compared against the legacy solo path)
pytestmark = pytest.mark.filterwarnings(
    "ignore:solve_groupsparse_ot:DeprecationWarning"
)

OPTS = SolveOptions(grad_impl="screened", lbfgs=LbfgsOptions(max_iters=150))


def _make_request(rng, rid, L, g, n):
    m = L * g
    labels = np.repeat(np.arange(L), g)
    Xs = rng.normal(size=(m, 2)) + labels[:, None] * 3.0
    Xt = rng.normal(size=(n, 2)) + rng.integers(0, L, n)[:, None] * 3.0
    C = squared_euclidean_cost(Xs, Xt).astype(np.float32)
    C /= C.max()
    return OTRequest(rid=rid, C=C, labels=labels), (Xs, labels, Xt)


def _f64_plan(problem, reg):
    """The problem's plan at the f64 optimum, in its original row order."""
    p = problem.padded()
    ref = cpu_baseline.fast_solve(p.C, p.a, p.b, p.spec, reg, gtol=1e-10)
    L, g = p.spec.num_groups, p.spec.group_size
    F = ref.alpha[:, None] + ref.beta[None, :] - p.C.astype(np.float64)
    Fp = np.maximum(F, 0.0)
    Z = np.sqrt(np.sum(Fp.reshape(L, g, -1) ** 2, axis=1))
    _, s = cpu_baseline._psi_terms(Z, reg.tau_vec(L, np.float64)[:, None],
                                   reg.gamma)
    T_pad = np.repeat(s, g, axis=0) * Fp / reg.gamma
    real = p.perm >= 0
    T = np.zeros((p.spec.m, T_pad.shape[1]))
    T[p.perm[real]] = T_pad[real]
    return T


def test_mixed_shape_stream_converges_to_solo_values():
    """Mixed-shape requests stream through bucketing; every request ends up
    at its solo-solve objective (and plan) despite row/column padding and
    batch-mates at different convergence stages."""
    rng = np.random.default_rng(0)
    shapes = [(4, 6, 30), (4, 6, 35), (5, 8, 50), (4, 6, 28), (5, 8, 40)]
    reqs, raws = [], []
    for rid, (L, g, n) in enumerate(shapes):
        req, raw = _make_request(rng, rid, L, g, n)
        reqs.append(req)
        raws.append(raw)

    engine = OTServingEngine(
        GroupSparseReg.from_rho(1.0, 0.6), OPTS, max_batch=2, n_quant=64
    )
    done = engine.run(reqs)
    assert sorted(r.rid for r in done) == list(range(len(shapes)))
    # two distinct (L, g_pad) geometries -> two buckets
    assert len(engine.buckets) == 2

    for req, (Xs, labels, Xt) in zip(reqs, raws):
        assert req.done and req.converged
        sol = solve_groupsparse_ot(
            Xs, labels, Xt, gamma=1.0, rho=0.6, opts=OPTS, pad_to=8
        )
        np.testing.assert_allclose(req.value, sol.value, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(
            req.plan, sol.plan, rtol=1e-3, atol=2e-4
        )
        # marginals of the served plan match the request's (uniform) ones
        m, n = req.C.shape
        np.testing.assert_allclose(req.plan.sum(1), np.full(m, 1 / m), atol=5e-4)
        np.testing.assert_allclose(req.plan.sum(0), np.full(n, 1 / n), atol=5e-4)


def test_more_requests_than_slots_recycles():
    """5 same-bucket requests through 2 slots: all finish, in <= 1 bucket."""
    rng = np.random.default_rng(1)
    reqs = [_make_request(rng, rid, 4, 6, 32)[0] for rid in range(5)]
    engine = OTServingEngine(
        GroupSparseReg.from_rho(1.0, 0.6), OPTS, max_batch=2
    )
    done = engine.run(reqs)
    assert sorted(r.rid for r in done) == [0, 1, 2, 3, 4]
    assert len(engine.buckets) == 1
    assert all(r.converged for r in done)


def test_admission_preserves_inflight_neighbor():
    """Admitting into a bucket mid-solve must not perturb the neighbor:
    its final value equals a run without the late arrival."""
    rng = np.random.default_rng(2)
    r0, _ = _make_request(rng, 0, 4, 6, 30)
    r1, _ = _make_request(rng, 1, 4, 6, 31)
    reg = GroupSparseReg.from_rho(1.0, 0.6)

    # reference: r0 alone
    e0 = OTServingEngine(reg, OPTS, max_batch=2)
    ref = {r.rid: r.value for r in e0.run([OTRequest(r0.rid, r0.C, r0.labels)])}

    # r0 starts, r1 arrives after two ticks into the same bucket
    engine = OTServingEngine(reg, OPTS, max_batch=2)
    assert engine.try_admit(OTRequest(r0.rid, r0.C, r0.labels))
    finished = []
    finished += engine.tick()
    finished += engine.tick()
    assert engine.try_admit(OTRequest(r1.rid, r1.C, r1.labels))
    while len(finished) < 2:
        finished += engine.tick()
    vals = {r.rid: r.value for r in finished}
    assert vals[0] == pytest.approx(ref[0], abs=0.0)  # bitwise-preserved


def test_no_head_of_line_blocking_across_buckets():
    """A full bucket at the queue head must not starve other buckets:
    the lone bucket-B request finishes while surplus bucket-A requests are
    still waiting for slots."""
    rng = np.random.default_rng(4)
    reqs_a = [_make_request(rng, rid, 4, 6, 32)[0] for rid in range(3)]
    req_b, _ = _make_request(rng, 99, 5, 8, 32)
    engine = OTServingEngine(
        GroupSparseReg.from_rho(1.0, 0.6), OPTS, max_batch=1
    )
    done = engine.run(reqs_a + [req_b])
    assert sorted(r.rid for r in done) == [0, 1, 2, 99]
    # with max_batch=1 and 3 A-requests ahead of it, B can only have been
    # served concurrently if admission skipped over the blocked A queue
    assert req_b.done and req_b.converged


def test_mixed_regularizer_streams_do_not_share_buckets():
    """Requests with identical padded geometry but different regularizers
    must land in different buckets (the compiled program and the screening
    thresholds specialize per regularizer), and every retired request must
    match a solo solve with ITS regularizer."""
    rng = np.random.default_rng(5)
    regs = {
        0: None,                                            # engine default
        1: L2Reg(gamma=0.4),
        2: ElasticNetGroupReg(gamma=0.4, mu_weights=(0.0, 0.5, 1.0, 1.5)),
        3: None,                                            # shares bucket w/ 0
    }
    reqs, raws = [], []
    for rid, reg in regs.items():
        req, raw = _make_request(rng, rid, 4, 6, 30 + rid)  # same bucket geom
        req.reg = reg
        reqs.append(req)
        raws.append(raw)

    default = GroupSparseReg.from_rho(1.0, 0.6)
    engine = OTServingEngine(default, OPTS, max_batch=4, n_quant=64)
    done = engine.run(reqs)
    assert sorted(r.rid for r in done) == [0, 1, 2, 3]
    # one geometry, three regularizers -> exactly three buckets
    assert len(engine.buckets) == 3
    kinds = sorted(type(key[3]).kind for key in engine.buckets)
    assert kinds == ["elastic_net", "group_sparse", "l2"]

    for req, (Xs, labels, Xt) in zip(reqs, raws):
        assert req.done and req.converged
        reg = req.reg if req.reg is not None else default
        sol = solve_groupsparse_ot(Xs, labels, Xt, reg=reg, opts=OPTS, pad_to=8)
        np.testing.assert_allclose(req.value, sol.value, rtol=1e-5, atol=1e-6)
        # Bucket isolation: a solo solve at the bucket's column width runs
        # the same padded operands through the same program, so the plans
        # are bitwise equal.
        (width,) = [key[2] for key in engine.buckets if key[3] == reg]
        problem = ot.Problem(reg=reg, C=req.C, labels=labels, pad_to=8)
        same_width = ot.Executor(problem.group_spec(), width, reg,
                                 ot.ExecutionPlan.from_solve_options(OPTS))
        solo = same_width.solve(problem)
        assert req.value == solo.value
        np.testing.assert_array_equal(req.plan, solo.plan)
        # The plan itself, against the f64 optimum.  The unpadded solo is
        # no referee for it: it is another f32 L-BFGS run that stops
        # elsewhere within the same tolerance (up to 1.3e-4 from the f64
        # plan, 2.2e-4 from the engine's).  Engine-to-f64 readings on the
        # CPU: 1.04e-4 (group_sparse), 8.9e-6 (l2), 2.04e-4 (elastic_net).
        np.testing.assert_allclose(req.plan, _f64_plan(problem, reg),
                                   rtol=0, atol=3e-4)

    # a malformed per-group regularizer is rejected at admission, BEFORE
    # any slot/bucket mutation — it must not poison the engine
    bad = reqs[0]
    malformed = OTRequest(
        rid=9, C=bad.C, labels=bad.labels,
        reg=ElasticNetGroupReg(gamma=1.0, mu_weights=(0.1, 0.2)),  # 2 != 4
    )
    with pytest.raises(ValueError, match="group"):
        engine.try_admit(malformed)
    assert len(engine.buckets) == 3                       # no new bucket
    assert all(not b.occupied() for b in engine.buckets.values())
    assert engine.tick() == []                            # engine still healthy


def test_retired_plan_matches_solo_solve_per_regularizer():
    """A request retired from a mixed-convergence bucket gets the same plan
    (bitwise value) as the same problem solved alone with the same
    regularizer — for the non-default kinds too."""
    rng = np.random.default_rng(6)
    for reg in (
        L2Reg(gamma=0.4),
        ElasticNetGroupReg(gamma=0.4, mu_weights=(0.0, 0.5, 1.0, 1.5)),
    ):
        r0, _ = _make_request(rng, 0, 4, 6, 30)
        r1, _ = _make_request(rng, 1, 4, 6, 31)
        r0.reg = r1.reg = reg

        # reference: r0 alone in its own engine
        e0 = OTServingEngine(GroupSparseReg.from_rho(1.0, 0.6), OPTS, max_batch=2)
        solo = OTRequest(r0.rid, r0.C, r0.labels, reg=reg)
        ref = {r.rid: (r.value, r.plan) for r in e0.run([solo])}

        # r0 + a late-arriving bucket-mate
        engine = OTServingEngine(GroupSparseReg.from_rho(1.0, 0.6), OPTS, max_batch=2)
        assert engine.try_admit(OTRequest(r0.rid, r0.C, r0.labels, reg=reg))
        finished = []
        finished += engine.tick()
        assert engine.try_admit(OTRequest(r1.rid, r1.C, r1.labels, reg=reg))
        while len(finished) < 2:
            finished += engine.tick()
        vals = {r.rid: (r.value, r.plan) for r in finished}
        assert vals[0][0] == pytest.approx(ref[0][0], abs=0.0), type(reg).kind
        np.testing.assert_array_equal(vals[0][1], ref[0][1])


@pytest.mark.parametrize("n,aligned", [(30, False), (100, True)])
def test_pallas_bucket_pads_in_program_when_tile_aligned(n, aligned):
    """A pallas bucket whose width is a whole number of kernel tiles keeps
    no padded copy of its cost (the round program pads it for free); a
    ragged one keeps the copy.  Either way the served request equals a
    solo solve at the bucket's width, bit for bit."""
    opts = SolveOptions(grad_impl="pallas", lbfgs=LbfgsOptions(max_iters=150))
    reg = GroupSparseReg.from_rho(1.0, 0.6)
    req, (_, labels, _) = _make_request(np.random.default_rng(7), 0, 4, 6, n)
    engine = OTServingEngine(reg, opts, max_batch=2)
    assert engine.try_admit(req)
    (bucket,) = engine.buckets.values()
    done = []
    while not done:
        done += engine.tick()
        assert (bucket._padded is None) == aligned
    width = bucket.key[2]
    assert (width % 128 == 0) == aligned

    problem = ot.Problem(reg=reg, C=req.C, labels=labels, pad_to=8)
    solo = ot.Executor(problem.group_spec(), width, reg,
                       ot.ExecutionPlan.from_solve_options(opts)).solve(problem)
    assert done[0].value == solo.value
    np.testing.assert_array_equal(done[0].plan, solo.plan)


def test_engine_dispatch_efficiency():
    """B requests in one bucket tick with ONE launch per round, not B."""
    rng = np.random.default_rng(3)
    reqs = [_make_request(rng, rid, 4, 6, 32)[0] for rid in range(4)]
    engine = OTServingEngine(
        GroupSparseReg.from_rho(1.0, 0.6), OPTS, max_batch=4
    )
    for r in reqs:
        assert engine.try_admit(r)
    reset_dispatch_count()
    engine.tick()
    # one fused batch_round for the whole bucket
    assert dispatch_count() == 1
