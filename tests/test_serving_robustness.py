"""Serving robustness: SLOs, shedding, quarantine, and chaos invariants.

The contract under test (ISSUE: SLO-aware serving under failure): with
faults injected through ``repro.utils.faults``, the engine never crashes
or hangs, every request reaches EXACTLY ONE terminal status, and the
plans of retired-DONE requests are bitwise identical to a no-fault run of
the same healthy requests.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.core.lbfgs import LbfgsOptions
from repro.core.regularizers import GroupSparseReg
from repro.core.solver import SolveOptions
from repro.ot.problem import Problem, SubmitOptions
from repro.serving.ot_engine import OTRequest, OTServingEngine
from repro.serving.policy import (
    PendingQueue,
    RequestStatus,
    ServingPolicy,
    TERMINAL_STATUSES,
)
from repro.serving.traffic import TrafficSpec, drive, make_trace
from repro.utils.faults import REGISTRY, FaultSpec, injected

OPTS = SolveOptions(grad_impl="screened", lbfgs=LbfgsOptions(max_iters=150))
REG = GroupSparseReg.from_rho(1.0, 0.6)
SRC = str(Path(__file__).resolve().parents[1] / "src")


def _run_on_host_devices(code: str, devices: int, timeout: int = 600):
    """Run ``code`` in a child whose CPU backend has ``devices`` devices."""
    env = dict(
        os.environ,
        XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
        PYTHONPATH=SRC,
        JAX_PLATFORMS="cpu",
    )
    return subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        env=env, capture_output=True, text=True, timeout=timeout,
    )


@pytest.fixture(autouse=True)
def _clean_registry():
    """No test may leak faults into its neighbours."""
    REGISTRY.reset()
    yield
    REGISTRY.reset()


def _request(rng, rid, L=4, g=6, n=30, **kw):
    m = L * g
    labels = np.repeat(np.arange(L), g)
    C = rng.random((m, n)).astype(np.float32)
    return OTRequest(rid=rid, C=C, labels=labels, **kw)


def _problem(rng, L=4, g=6, n=30, submit=None):
    m = L * g
    labels = np.repeat(np.arange(L), g)
    return Problem(reg=REG, C=rng.random((m, n)), labels=labels, pad_to=8,
                   submit=submit)


# -- lifecycle & SLO plumbing --------------------------------------------------

def test_submit_none_when_full_then_succeeds_after_tick():
    """submit() returns None while the bucket is full; the same problem is
    admitted once a slot frees up (the documented retry contract)."""
    rng = np.random.default_rng(0)
    engine = OTServingEngine(REG, OPTS, max_batch=1)
    p0, p1 = _problem(rng), _problem(rng)
    r0 = engine.submit(p0)
    assert r0 is not None and r0.status is RequestStatus.RUNNING
    assert engine.submit(p1) is None          # one slot, already taken
    finished = []
    while not finished:
        finished += engine.tick()
    assert finished[0].rid == r0.rid and finished[0].status is RequestStatus.DONE
    r1 = engine.submit(p1)                    # slot recycled: admits now
    assert r1 is not None and r1.status is RequestStatus.RUNNING
    while engine._in_flight():
        finished += engine.tick()
    assert {r.status for r in finished} == {RequestStatus.DONE}


def test_submit_options_thread_through_problem():
    """Problem.submit carries SLOs into the engine request; explicit
    keywords override; the policy default fills the rest."""
    rng = np.random.default_rng(1)
    engine = OTServingEngine(
        REG, OPTS, policy=ServingPolicy(default_deadline=99, default_priority=1)
    )
    p = _problem(rng, submit=SubmitOptions(deadline=7, priority=3))
    req, _ = engine.enqueue(p)
    assert (req.deadline, req.priority) == (7, 3)
    req2, _ = engine.enqueue(_problem(rng), deadline=5)
    assert (req2.deadline, req2.priority) == (5, 1)   # kwarg + policy default
    # round-trips through the declarative config wire too
    p3 = Problem.from_config(p.config())
    assert p3.submit == SubmitOptions(deadline=7, priority=3)


def test_problem_rejects_nonfinite_inputs():
    """Satellite: non-finite costs/marginals fail Problem validation with a
    clear error, and a poisoned raw request FAILS at admission without
    touching any bucket."""
    rng = np.random.default_rng(2)
    m, n = 24, 30
    labels = np.repeat(np.arange(4), 6)
    C = rng.random((m, n))
    C_bad = C.copy()
    C_bad[3, 4] = np.nan
    with pytest.raises(ValueError, match="non-finite"):
        Problem(reg=REG, C=C_bad, labels=labels)
    a_bad = np.full(m, 1.0 / m)
    a_bad[0] = np.inf
    with pytest.raises(ValueError, match="non-finite"):
        Problem(reg=REG, C=C, labels=labels, a=a_bad)

    engine = OTServingEngine(REG, OPTS)
    req = OTRequest(rid=0, C=C_bad, labels=labels)
    req, shed = engine.enqueue(req)
    assert shed == [req]
    assert req.status is RequestStatus.FAILED
    assert "rejected at admission" in req.error
    assert not engine.buckets                 # engine untouched


def test_deadline_expires_mid_flight():
    """A deadline-carrying request that cannot finish in time is retired
    DEADLINE_EXCEEDED mid-flight; its slot is recycled cleanly."""
    rng = np.random.default_rng(3)
    slow = SolveOptions(grad_impl="screened", lbfgs=LbfgsOptions(max_iters=3))
    engine = OTServingEngine(REG, slow, max_batch=2)
    done = engine.run([_request(rng, 0, n=40, deadline=2),
                       _request(rng, 1, n=41)])
    by_rid = {r.rid: r for r in done}
    assert by_rid[0].status is RequestStatus.DEADLINE_EXCEEDED
    assert "mid-flight" in by_rid[0].error
    assert by_rid[0].ticks_in_flight == 2
    assert by_rid[1].status is RequestStatus.DONE   # neighbour unaffected
    assert engine.stats()["status"]["DEADLINE_EXCEEDED"] == 1


def test_priority_shedding_at_double_capacity():
    """At 2x queue capacity the LOWEST-priority requests are shed (ties:
    youngest first) and every high-priority request survives."""
    rng = np.random.default_rng(4)
    engine = OTServingEngine(REG, OPTS, policy=ServingPolicy(max_pending=4))
    shed_all = []
    reqs = []
    for i in range(8):                        # 2x capacity, alternating prio
        req, shed = engine.enqueue(_request(rng, i, priority=i % 2))
        reqs.append(req)
        shed_all += shed
    assert len(shed_all) == 4
    assert all(r.status is RequestStatus.SHED for r in shed_all)
    assert all(r.priority == 0 for r in shed_all)          # low prio only
    survivors = list(engine.pending)
    assert all(r.priority == 1 for r in survivors)
    assert [r.rid for r in survivors] == [1, 3, 5, 7]      # FIFO within class
    # shed + queued partition the submissions: nothing lost, nothing twice
    assert {r.rid for r in shed_all} | {r.rid for r in survivors} == set(range(8))


def test_geometry_over_limits_is_shed_not_queued():
    """A request that can NEVER fit the engine's limits is shed at
    submission (enqueue) or rejected loudly (submit), not left pending."""
    rng = np.random.default_rng(5)
    engine = OTServingEngine(
        REG, OPTS, policy=ServingPolicy(max_groups=3)
    )
    req, shed = engine.enqueue(_request(rng, 0, L=4))
    assert shed == [req] and req.status is RequestStatus.SHED
    assert "exceeds engine limits" in req.error
    with pytest.raises(ValueError, match="exceeds engine limits"):
        engine.submit(_problem(rng, L=4))
    assert len(engine.pending) == 0


# -- quarantine & fallback -----------------------------------------------------

def test_failed_slot_keeps_done_neighbour_bitwise():
    """A quarantined slot (injected NaN, no usable fallback) must retire
    FAILED while its bucket neighbour's value AND plan stay bitwise equal
    to a no-fault run of the same healthy request."""
    rng = np.random.default_rng(6)
    C0 = rng.random((24, 30)).astype(np.float32)
    labels = np.repeat(np.arange(4), 6)
    policy = ServingPolicy(fallback_ladder=("restart",), max_attempts=2)

    ref_engine = OTServingEngine(REG, OPTS, max_batch=2, policy=policy)
    ref = ref_engine.run([OTRequest(rid=0, C=C0, labels=labels)])[0]
    assert ref.status is RequestStatus.DONE

    engine = OTServingEngine(REG, OPTS, max_batch=2, policy=policy)
    with injected(FaultSpec("nan_cost", rids={1})):
        done = engine.run([
            OTRequest(rid=0, C=C0, labels=labels),
            _request(rng, 1),
        ])
    by_rid = {r.rid: r for r in done}
    assert by_rid[1].status is RequestStatus.FAILED
    assert by_rid[1].attempts == 2            # initial + one in-slot restart
    assert "ladder exhausted" in by_rid[1].error
    assert by_rid[0].status is RequestStatus.DONE
    assert by_rid[0].value == ref.value       # bitwise
    np.testing.assert_array_equal(by_rid[0].plan, ref.plan)


def test_fallback_ladder_recovers_poisoned_slot():
    """With the full ladder, a NaN-poisoned slot walks restart -> dense and
    retires DONE via the dense fallback (the slot copy was poisoned, the
    validated payload is healthy), with attempts accounted."""
    rng = np.random.default_rng(7)
    engine = OTServingEngine(REG, OPTS, max_batch=2)
    with injected(FaultSpec("nan_cost", rids={0})):
        done = engine.run([_request(rng, 0)])
    (req,) = done
    assert req.status is RequestStatus.DONE
    assert req.route == "dense"
    assert req.attempts == 3                  # slot + restart + dense
    assert "recovered via dense fallback" in req.error
    assert np.all(np.isfinite(req.plan)) and np.isfinite(req.value)
    assert engine.stats()["retry_attempts"] == 2
    # sanity: the recovered value matches a clean engine solve of the same C
    clean = OTServingEngine(REG, OPTS, max_batch=2)
    ref = clean.run([OTRequest(rid=0, C=req.C, labels=req.labels)])[0]
    assert req.value == pytest.approx(ref.value, rel=1e-4)


def test_forced_lbfgs_failure_routes_to_cpu_rung():
    """A persistently failing device solve (forced L-BFGS failure + a
    ladder without the dense rung) lands on the CPU baseline and still
    returns a finite plan."""
    rng = np.random.default_rng(8)
    policy = ServingPolicy(fallback_ladder=("cpu",), max_attempts=2)
    engine = OTServingEngine(REG, OPTS, max_batch=1, policy=policy)
    with injected(FaultSpec("lbfgs_fail", rids={0})):
        done = engine.run([_request(rng, 0)])
    (req,) = done
    assert req.status is RequestStatus.DONE
    assert req.route == "cpu"
    assert np.all(np.isfinite(req.plan)) and np.isfinite(req.value)


# -- stall guards & hygiene ----------------------------------------------------

def test_run_stall_guard_sheds_unadmittable_work():
    """Satellite regression: with admission permanently failing, run() must
    terminate (shedding the queue) instead of looping forever."""
    rng = np.random.default_rng(9)
    engine = OTServingEngine(REG, OPTS, policy=ServingPolicy(stall_passes=2))
    with injected(FaultSpec("admit_fail")):   # unlimited budget
        done = engine.run([_request(rng, 0), _request(rng, 1)])
    assert len(done) == 2
    assert all(r.status is RequestStatus.SHED for r in done)
    assert all("stall guard" in r.error for r in done)
    assert engine.stats()["in_flight"] == 0


def test_run_safety_valve_fails_frozen_bucket():
    """A bucket frozen by a persistent slow fault cannot hang run(): the
    in-flight request is force-failed once the safety valve trips."""
    rng = np.random.default_rng(10)
    opts = SolveOptions(grad_impl="screened", max_rounds=5,
                        lbfgs=LbfgsOptions(max_iters=150))
    engine = OTServingEngine(REG, opts, policy=ServingPolicy(stall_passes=2))
    with injected(FaultSpec("slow_bucket")):  # every tick, forever
        done = engine.run([_request(rng, 0)])
    (req,) = done
    assert req.status is RequestStatus.FAILED
    assert "stall guard" in req.error


def test_slow_bucket_lets_deadlines_expire():
    """A slow bucket makes requests age without progress; deadline-carrying
    requests expire instead of hanging."""
    rng = np.random.default_rng(11)
    engine = OTServingEngine(REG, OPTS, max_batch=2)
    with injected(FaultSpec("slow_bucket")):
        done = engine.run([_request(rng, 0, deadline=3)])
    (req,) = done
    assert req.status is RequestStatus.DEADLINE_EXCEEDED
    assert req.ticks_in_flight == 3


def test_idle_buckets_are_evicted():
    """Buckets with no occupants are evicted after the policy's idle
    window, bounding the bucket dict under shifting traffic mixes."""
    rng = np.random.default_rng(12)
    engine = OTServingEngine(
        REG, OPTS, policy=ServingPolicy(idle_evict_after=2)
    )
    engine.run([_request(rng, 0)])
    assert len(engine.buckets) == 1           # still warm right after run()
    for _ in range(3):
        engine.tick()
    assert len(engine.buckets) == 0
    assert engine.stats()["evictions"] == 1
    # the engine still serves after eviction (programs re-attach from the
    # process-wide jit cache)
    done = engine.run([_request(rng, 1)])
    assert done[0].status is RequestStatus.DONE


def test_pending_queue_unit_behavior():
    """PendingQueue ordering + overflow shed rules, in isolation."""

    class R:
        def __init__(self, rid, priority, tick):
            self.rid, self.priority, self.submitted_tick = rid, priority, tick

    q = PendingQueue(3)
    assert q.push(R(0, 0, 0)) == []
    assert q.push(R(1, 2, 1)) == []
    assert q.push(R(2, 1, 2)) == []
    assert [r.rid for r in q] == [1, 2, 0]    # priority desc, FIFO in class
    shed = q.push(R(3, 0, 3))                 # overflow: lowest prio, youngest
    assert [r.rid for r in shed] == [3]
    shed = q.push(R(4, 3, 4))
    assert [r.rid for r in shed] == [0]       # now rid 0 is the victim
    assert [r.rid for r in q.drain()] == [4, 1, 2]
    assert len(q) == 0


def test_failed_slot_keeps_done_neighbour_bitwise_sharded():
    """The quarantine bitwise guarantee must hold across a device mesh too
    (slots on other devices are frozen through the same masked merges).

    Runs in a child process with 4 forced host devices: the device count
    must be set before jax initializes (same pattern as test_sharded.py).
    """
    code = """
        import numpy as np, jax
        from repro.core.distributed import make_batch_mesh
        from repro.core.lbfgs import LbfgsOptions
        from repro.core.regularizers import GroupSparseReg
        from repro.core.solver import SolveOptions
        from repro.serving.ot_engine import OTRequest, OTServingEngine
        from repro.serving.policy import RequestStatus, ServingPolicy
        from repro.utils.faults import FaultSpec, injected

        assert jax.device_count() == 4, jax.device_count()
        OPTS = SolveOptions(grad_impl="screened",
                            lbfgs=LbfgsOptions(max_iters=150))
        REG = GroupSparseReg.from_rho(1.0, 0.6)
        rng = np.random.default_rng(15)
        C0 = rng.random((24, 30)).astype(np.float32)
        C1 = rng.random((24, 30)).astype(np.float32)
        labels = np.repeat(np.arange(4), 6)
        policy = ServingPolicy(fallback_ladder=("restart",), max_attempts=2)

        ref_engine = OTServingEngine(REG, OPTS, max_batch=1,
                                     mesh=make_batch_mesh(), policy=policy)
        ref = ref_engine.run([OTRequest(rid=0, C=C0, labels=labels)])[0]

        engine = OTServingEngine(REG, OPTS, max_batch=1,
                                 mesh=make_batch_mesh(), policy=policy)
        with injected(FaultSpec("nan_cost", rids={1})):
            done = engine.run([
                OTRequest(rid=0, C=C0, labels=labels),
                OTRequest(rid=1, C=C1, labels=labels),
            ])
        by_rid = {r.rid: r for r in done}
        assert by_rid[1].status is RequestStatus.FAILED
        assert by_rid[0].status is RequestStatus.DONE
        assert by_rid[0].value == ref.value       # bitwise across the mesh
        np.testing.assert_array_equal(by_rid[0].plan, ref.plan)
        print("OK")
    """
    r = _run_on_host_devices(code, devices=4)
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-4000:]


# -- chaos: everything at once -------------------------------------------------

def test_chaos_traffic_all_requests_terminal_exactly_once():
    """The headline invariant: seeded overload traffic + every fault kind
    at once; the engine neither crashes nor hangs, and each request ends
    in exactly one terminal status."""
    spec = TrafficSpec(
        num_requests=12, arrival_rate=4.0, seed=13,
        shapes=((12, 20, 3), (16, 24, 4)),
        deadline=6, deadline_fraction=0.5, priorities=(0, 1, 2),
    )
    trace = make_trace(spec)
    engine = OTServingEngine(
        REG, OPTS, max_batch=2,
        policy=ServingPolicy(max_pending=4, max_attempts=2,
                             fallback_ladder=("restart", "dense")),
    )
    with injected(
        FaultSpec("nan_cost", count=2),
        FaultSpec("lbfgs_fail", count=2, after_tick=1),
        FaultSpec("admit_fail", count=2),
        FaultSpec("slow_bucket", count=2, after_tick=2),
    ):
        done = drive(engine, trace, max_ticks=500)
    assert len(done) == spec.num_requests
    assert sorted(r.rid for r in done) == list(range(spec.num_requests))
    assert all(r.status in TERMINAL_STATUSES for r in done)
    stats = engine.stats()
    assert stats["pending"] == 0 and stats["in_flight"] == 0
    assert sum(stats["status"].values()) == spec.num_requests
    # every DONE result is finite and shaped for the caller
    for r in done:
        if r.status is RequestStatus.DONE:
            assert np.isfinite(r.value) and np.all(np.isfinite(r.plan))
            assert r.plan.shape == r.C.shape


def test_traffic_trace_is_deterministic():
    """Same spec -> identical trace (arrivals, payload bits, SLOs)."""
    spec = TrafficSpec(num_requests=6, arrival_rate=2.0, seed=21,
                       deadline=5, deadline_fraction=0.5, priorities=(0, 3))
    t1, t2 = make_trace(spec), make_trace(spec)
    assert [t for t, _ in t1] == [t for t, _ in t2]
    assert [t for t, _ in t1] == sorted(t for t, _ in t1)
    for (_, a), (_, b) in zip(t1, t2):
        np.testing.assert_array_equal(a.C, b.C)
        np.testing.assert_array_equal(a.labels, b.labels)
        assert (a.deadline, a.priority) == (b.deadline, b.priority)


def test_traffic_poisson_arrivals_deterministic_same_payloads():
    """Satellite: arrivals='poisson' — seeded exponential gaps give a
    reproducible bursty schedule, the payload stream is bit-identical to
    deterministic mode, and the mean rate is honored."""
    det = TrafficSpec(num_requests=64, arrival_rate=2.0, seed=21,
                      deadline=5, deadline_fraction=0.5, priorities=(0, 3))
    poi = dataclasses.replace(det, arrivals="poisson")
    tp1, tp2 = make_trace(poi), make_trace(poi)
    # deterministic given the seed, ticks sorted
    assert [t for t, _ in tp1] == [t for t, _ in tp2]
    assert [t for t, _ in tp1] == sorted(t for t, _ in tp1)
    # a different seed gives a different schedule; same seed+rate matches the
    # configured mean rate within a loose statistical band
    tp3 = make_trace(dataclasses.replace(poi, seed=22))
    assert [t for t, _ in tp3] != [t for t, _ in tp1]
    span = max(t for t, _ in tp1) + 1
    assert 0.5 * poi.num_requests / poi.arrival_rate <= span \
        <= 2.0 * poi.num_requests / poi.arrival_rate
    # payloads are untouched by the arrival mode
    td = make_trace(det)
    assert [t for t, _ in td] != [t for t, _ in tp1]  # schedules do differ
    for (_, a), (_, b) in zip(td, tp1):
        np.testing.assert_array_equal(a.C, b.C)
        np.testing.assert_array_equal(a.labels, b.labels)
        assert (a.deadline, a.priority) == (b.deadline, b.priority)
    # knob is validated and round-trips through config()
    assert poi.config()["arrivals"] == "poisson"
    with pytest.raises(ValueError, match="arrivals"):
        TrafficSpec(arrivals="uniform")


def test_traffic_poisson_drives_engine_to_terminal():
    """Poisson bursts still drain: every request reaches a terminal
    status exactly once under the same engine invariants."""
    spec = TrafficSpec(num_requests=10, arrival_rate=3.0, seed=7,
                       arrivals="poisson", priorities=(0, 1))
    engine = OTServingEngine(REG, OPTS, max_batch=2,
                             policy=ServingPolicy(max_pending=4))
    done = drive(engine, make_trace(spec), max_ticks=500)
    assert sorted(r.rid for r in done) == list(range(spec.num_requests))
    assert all(r.status in TERMINAL_STATUSES for r in done)


# -- facade observability ------------------------------------------------------

def test_executor_stats_and_stream_status():
    """Satellite: Executor.stats() reports per-terminal-status counts (the
    serving vocabulary), stream diagnostics carry per-problem status, and
    describe() ends with the health line."""
    import repro.ot as ot

    rng = np.random.default_rng(14)
    problems = [_problem(rng, n=31), _problem(rng, n=30)]
    ex = ot.compile(problems[0], ot.ExecutionPlan(grad_impl="screened"))
    ex.solve(problems[0])
    last = None
    for info in ex.stream(problems):
        assert set(info["status"]) <= {"RUNNING", "DONE", "FAILED"}
        last = info
    assert last["status"] == ["DONE", "DONE"]
    stats = ex.stats()
    assert stats["status"]["DONE"] == 3       # 1 solo + 2 streamed
    assert stats["status"]["FAILED"] == 0
    assert set(stats["status"]) == {s.value for s in TERMINAL_STATUSES}
    assert stats["retry_attempts"] == 0
    assert "health:" in ex.describe()
