"""Drive the solver's main path once on a TPU and check what comes out.

Run from the repository root on a machine with a TPU:

    python chip_smoke.py             # one chip: phases (a)-(c)
    python chip_smoke.py --chips 4   # four chips: the sharded phase only

Phases on one chip (every Pallas kernel runs compiled, never interpreted):

(a) paper scale: |L| = 1280 classes of g = 10 samples, m = n = 12,800,
    group-sparse gamma = 0.1, rho = 0.8, built with ``repro.ot.Problem`` and
    solved through ``ExecutionPlan`` -> ``Executor`` with grad_impl
    'pallas' and 'fused'; both dual objectives must equal the unscreened
    'dense' backend's (Theorem 2) to ``PAPER_RTOL``.  A reduced problem
    (|L| = 128, n = 1,280) on the 'pallas' backend must match the f64
    reference ``cpu_baseline.fast_solve`` to ``REF_RTOL``.
(b) batch: ``Executor.solve_many`` on 4 reduced problems issues ONE program
    launch and matches the solo solves to ``BATCH_RTOL``.
(c) serving: ``OTServingEngine`` answers 8 requests, 4 of the reduced shape
    and 4 at the paper's size, each shape filling one bucket of the default
    ``max_batch = 4`` slots; every one must retire DONE through its batch
    slot (``route == 'slot'``) and match the f64 reference (reduced,
    ``REF_RTOL``) or the chip's 'dense' solve (paper size, ``PAPER_RTOL``).

With ``--chips 4``: ``solve_many`` under ``ExecutionPlan(devices='all')``
on 8 reduced problems over a 4-chip batch mesh, checked to really span the
four chips, against the same batch on one chip (``BATCH_RTOL``).

Lines before the last are informational (wall times include compilation).
The last line is one JSON object: ``{"ok": true, "device": {...}}``.  Any
failed check, or a run without a TPU, exits non-zero before that line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

# Theorem 2: the screened backends solve the same dual as the dense one;
# left over is f32 rounding along slightly different L-BFGS trajectories.
PAPER_RTOL = 1e-5
# f32 device solve vs the f64 scipy reference (the repo's differential
# tests use the same bound, tests/test_regularizers.py).
REF_RTOL = 2e-5
# batched vs solo, sharded vs one chip: the same per-problem program; the
# contract is bitwise, a stated tolerance covers XLA fusion differences.
BATCH_RTOL = 1e-6

PAPER = dict(L=1280, g=10, gamma=0.1, rho=0.8)
REDUCED = dict(L=128, g=10, gamma=0.1, rho=0.8)


def info(msg: str) -> None:
    print(f"[info] {msg}", flush=True)


class SmokeFailure(RuntimeError):
    """A check of the smoke failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    info(f"PASS {what}")


def rel(x: float, y: float) -> float:
    return abs(x - y) / max(abs(y), 1e-30)


def domain_problem(L: int, g: int, gamma: float, rho: float, seed: int = 0):
    """The paper's synthetic domain-adaptation problem, in cost mode.

    Same construction as ``benchmarks/bench_ot_scale.measure_screening``:
    normalized squared-Euclidean costs between the two domains of
    ``make_domain_pair``, uniform marginals, group-sparse regularizer.
    """
    from repro import ot
    from repro.core.ot import squared_euclidean_cost
    from repro.core.regularizers import GroupSparseReg
    from repro.data.pipeline import DomainPairConfig, make_domain_pair

    Xs, ys, Xt, _ = make_domain_pair(
        DomainPairConfig(num_classes=L, samples_per_class=g, seed=seed)
    )
    C = squared_euclidean_cost(Xs, Xt).astype(np.float32)
    C /= C.max()
    return ot.Problem(reg=GroupSparseReg.from_rho(gamma, rho), C=C, labels=ys)


def timed_solve(problem, plan, label: str):
    from repro import ot

    ex = ot.compile(problem, plan)
    t0 = time.perf_counter()
    sol = ex.solve(problem)
    wall = time.perf_counter() - t0
    info(f"{label}: value={sol.value!r} iterations={sol.iterations} "
         f"rounds={sol.rounds} converged={sol.converged} "
         f"wall_s={wall:.1f} (compile included)")
    check(np.isfinite(sol.value), f"{label}: finite objective")
    check(sol.converged, f"{label}: converged")
    return sol


def reference_value(problem) -> float:
    from repro.core import cpu_baseline

    pa = problem.padded()
    ref = cpu_baseline.fast_solve(
        pa.C.astype(np.float64), pa.a.astype(np.float64),
        pa.b.astype(np.float64), pa.spec, problem.reg,
    )
    return float(ref.value)


def phase_paper(scale=PAPER, reduced=REDUCED) -> None:
    """(a) the paper-scale solve on three backends + the f64 check."""
    from repro import ot

    problem = domain_problem(**scale)
    info(f"paper problem: L={scale['L']} g={scale['g']} "
         f"m=n={problem.num_target}")
    vals = {}
    for impl in ("dense", "pallas", "fused"):
        sol = timed_solve(problem, ot.ExecutionPlan(grad_impl=impl),
                          f"paper {impl}")
        vals[impl] = sol.value
        del sol
    for impl in ("pallas", "fused"):
        r = rel(vals[impl], vals["dense"])
        check(r <= PAPER_RTOL,
              f"paper {impl} objective within rtol {PAPER_RTOL} of dense "
              f"(rel diff {r:.3e})")

    small = domain_problem(**reduced)
    sol = timed_solve(small, ot.ExecutionPlan(grad_impl="pallas"),
                      "reduced pallas")
    ref = reference_value(small)
    r = rel(sol.value, ref)
    info(f"reduced f64 reference value={ref!r}")
    check(r <= REF_RTOL,
          f"reduced pallas within rtol {REF_RTOL} of f64 fast_solve "
          f"(rel diff {r:.3e})")


def phase_batch(reduced=REDUCED, B: int = 4) -> None:
    """(b) one launch for a batch of reduced problems, equal to solo."""
    from repro import ot

    problems = [domain_problem(**reduced, seed=s) for s in range(B)]
    plan = ot.ExecutionPlan(grad_impl="pallas", batching="batched")
    ex = ot.compile(problems[0], plan)
    t0 = time.perf_counter()
    sols = ex.solve_many(problems)
    info(f"batch of {B}: wall_s={time.perf_counter() - t0:.1f} "
         f"(compile included) launches={ex.stats()['launches']}")
    check(ex.stats()["launches"] == 1, f"batch of {B}: one program launch")
    solo = ot.compile(problems[0], ot.ExecutionPlan(grad_impl="pallas"))
    worst, bitwise = 0.0, True
    for p, s in zip(problems, sols):
        v = solo.solve(p).value
        worst = max(worst, rel(s.value, v))
        bitwise &= s.value == v
    info(f"batch vs solo: bitwise={bitwise} max rel diff={worst:.3e}")
    check(all(s.converged for s in sols), "batch: every problem converged")
    check(worst <= BATCH_RTOL, f"batch matches solo within rtol {BATCH_RTOL}")


def phase_serving(shapes=(REDUCED, PAPER), per_shape: int = 4):
    """(c) the serving engine answers mixed-shape requests from its slots.

    ``per_shape`` requests of each shape fill one bucket of the engine's
    default ``max_batch`` slots.  Reduced-shape answers are checked against
    the f64 reference; larger ones, which the host's f64 solver would take
    too long for, against the unscreened 'dense' backend on the chip
    (Theorem 2, ``PAPER_RTOL``).
    """
    from repro import ot
    from repro.core.solver import SolveOptions
    from repro.serving.ot_engine import OTServingEngine
    from repro.serving.policy import RequestStatus

    problems = [domain_problem(**shape, seed=s)
                for shape in shapes for s in range(per_shape)]
    engine = OTServingEngine(problems[0].reg, SolveOptions(grad_impl="pallas"))
    check(engine.max_batch == per_shape,
          f"serving: {per_shape} requests per shape fill a bucket")
    t0 = time.perf_counter()
    done = engine.run(problems)
    info(f"serving: {len(done)} requests in {engine.stats()['ticks']} ticks, "
         f"wall_s={time.perf_counter() - t0:.1f} (compile included)")
    info("serving buckets (L, g_pad, n_pad): "
         f"{sorted(key[:3] for key in engine.buckets)}")
    del engine                          # frees its slots' device buffers
    check(len(done) == len(problems), "serving: every request retired")
    for req in done:
        check(req.status is RequestStatus.DONE and req.route == "slot",
              f"serving: request {req.rid} DONE via slot "
              f"(status={req.status.value}, route={req.route})")
    for req in sorted(done, key=lambda r: r.rid):
        problem = problems[req.rid]
        m, n = problem.C.shape
        if shapes[req.rid // per_shape] is REDUCED:
            ref, what, rtol = reference_value(problem), "f64 fast_solve", REF_RTOL
        else:
            dense = ot.compile(problem, ot.ExecutionPlan(grad_impl="dense"))
            ref, what, rtol = dense.solve(problem).value, "dense", PAPER_RTOL
        r = rel(req.value, ref)
        check(req.converged and r <= rtol,
              f"serving: request {req.rid} ({m}x{n}) converged, within rtol "
              f"{rtol} of {what} (rel diff {r:.3e})")


def phase_sharded(reduced=REDUCED, B: int = 8, chips: int = 4) -> None:
    """--chips 4: sharded solve_many over a 4-chip mesh vs one chip."""
    import jax

    from repro import ot
    from repro.core.distributed import make_batch_mesh

    check(jax.device_count() >= chips, f"{chips} devices visible")
    problems = [domain_problem(**reduced, seed=s) for s in range(B)]
    mesh = make_batch_mesh(chips)
    ex = ot.compile(problems[0], ot.ExecutionPlan(grad_impl="pallas",
                                                  devices="all"), mesh=mesh)
    launched = []
    launch = ex._launch

    def spy(fn, *args):
        out = launch(fn, *args)
        launched.append((args, out))
        return out

    ex._launch = spy
    t0 = time.perf_counter()
    sharded = ex.solve_many(problems)
    info(f"sharded over {mesh.size} chips: wall_s="
         f"{time.perf_counter() - t0:.1f} (compile included)")
    check(len(launched) == 1, "sharded: one program launch")
    (C, *_), (lb, *_) = launched[0]
    for name, arr in (("cost operand", C), ("dual iterate", lb.x)):
        devs = {s.device for s in arr.addressable_shards}
        rows = {s.data.shape[0] for s in arr.addressable_shards}
        info(f"sharded {name}: shape={arr.shape} on {len(devs)} devices, "
             f"{rows} problems per shard")
        check(len(devs) == chips and rows == {B // chips},
              f"sharded {name} spans {chips} chips, {B // chips} per chip")

    one = ot.compile(problems[0], ot.ExecutionPlan(grad_impl="pallas",
                                                   batching="batched"))
    t0 = time.perf_counter()
    single = one.solve_many(problems)
    info(f"same batch on one chip: wall_s={time.perf_counter() - t0:.1f}")
    worst = max(rel(s.value, u.value) for s, u in zip(sharded, single))
    bitwise = all(s.value == u.value for s, u in zip(sharded, single))
    info(f"sharded vs one chip: bitwise={bitwise} max rel diff={worst:.3e}")
    check(all(s.converged for s in sharded), "sharded: every problem converged")
    check(worst <= BATCH_RTOL,
          f"sharded matches one chip within rtol {BATCH_RTOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded phase over four chips")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke.py must run from a checkout of the repository "
              f"(no src/repro next to {__file__})", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"device: {json.dumps(device)}", flush=True)
    if device["platform"] != "tpu":
        print("no TPU: refusing to run the smoke on "
              f"{device['platform']!r}", file=sys.stderr)
        return 1

    from repro.kernels import gradpsi, ops
    from repro.utils.compile_cache import enable_compile_cache

    info(f"compile cache: {enable_compile_cache()}")
    check(ops.default_interpret() is False, "Pallas kernels run compiled")
    if args.chips == 4:
        phases = [("sharded", phase_sharded)]
    else:
        phases = [("paper", phase_paper), ("batch", phase_batch),
                  ("serving", phase_serving)]
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            phase()
        except Exception as e:  # report which phase failed, then exit 1
            traceback.print_exc()
            print(f"phase {name} FAILED: {type(e).__name__}: {e}",
                  file=sys.stderr, flush=True)
            return 1
        info(f"phase {name} done in {time.perf_counter() - t0:.1f}s")
    info(f"Pallas kernels traced: {sorted(gradpsi.launch_counts())}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
