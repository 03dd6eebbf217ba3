"""Tutorial: group-sparse OT for domain adaptation — solo, batched, sharded.

A narrated, runnable walkthrough of the whole stack on the paper's task
(unsupervised domain adaptation): source samples are labeled, target
samples are not, and the group-sparse transport plan moves class-coherent
mass so each target point can be labeled by the class that sends it the
most mass.  Everything runs through the ``repro.ot`` façade — one
declarative Problem, one compiled Executor — climbing the three
execution tiers:

  1. SOLO     one problem, one program        (Executor.solve)
  2. BATCHED  B problems, ONE program         (Executor.solve_many)
  3. SHARDED  B problems over all devices     (Executor.solve_many + mesh)

and verifies at each step that the answer is *bitwise* the same — the
batch axis and the device mesh are performance structure, never numerics.

Run:  PYTHONPATH=src python examples/domain_adaptation.py [--classes 5]

On a CPU-only machine we force 4 virtual host devices (before jax
initializes) so stage 3 genuinely shards; on a real multi-device host the
flag is unnecessary and left untouched.  Docs: docs/architecture.md for
the map of the layers used here.
"""
import argparse
import os
import sys
import time
from pathlib import Path

# stage 3 wants >1 device; the host-platform override must be set before
# jax is imported (harmless if XLA_FLAGS is already configured)
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np
import jax
import jax.numpy as jnp

import repro.ot as ot
from repro.core import sinkhorn_log, squared_euclidean_cost
from repro.core.regularizers import GroupSparseReg
from repro.data.pipeline import DomainPairConfig, make_domain_pair


def predict_from_plan(T: np.ndarray, y_src: np.ndarray, L: int) -> np.ndarray:
    """Target label = class with max incoming transported mass."""
    mass = np.zeros((L, T.shape[1]))
    for lbl in range(L):
        mass[lbl] = T[y_src == lbl].sum(axis=0)
    return mass.argmax(axis=0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--classes", type=int, default=5)
    ap.add_argument("--per-class", type=int, default=10)
    ap.add_argument("--dim", type=int, default=8)
    ap.add_argument("--domains", type=int, default=8,
                    help="target domains for the batched/sharded stages")
    args = ap.parse_args()
    L = args.classes

    # ----------------------------------------------------------------- setup
    # One labeled source domain and `--domains` unlabeled target domains
    # (independent draws of the same shifted-cluster generator) — the
    # serving scenario: many concurrent adaptation problems, same geometry.
    print("=" * 72)
    print("SETUP: synthetic unsupervised domain adaptation")
    print("=" * 72)
    Xs, ys, Xt0, yt0 = make_domain_pair(
        DomainPairConfig(num_classes=L, samples_per_class=args.per_class,
                         dim=args.dim, shift=3.0, seed=0)
    )
    targets = [(Xt0, yt0)]
    for s in range(1, args.domains):
        targets.append(make_domain_pair(
            DomainPairConfig(num_classes=L, samples_per_class=args.per_class,
                             dim=args.dim, shift=3.0, seed=s)
        )[2:])
    m, n = len(ys), len(targets[0][0])

    # ONE declarative problem per target domain; the regularizer, group
    # layout and execution policy live in the Problem / ExecutionPlan
    reg = GroupSparseReg.from_rho(1.0, 0.6)
    problems = [
        ot.Problem.from_samples(Xs, ys, Xt, reg=reg, pad_to=8)
        for Xt, _ in targets
    ]
    plan = ot.ExecutionPlan(grad_impl="screened", max_iters=150)
    ex = ot.compile(problems[0], plan)
    print(f"source: {m} samples, {L} classes; "
          f"targets: {len(targets)} domains x {n} samples")
    print(ex.describe())

    # ------------------------------------------------------------ 1. solo
    # One problem end to end, plus the entropic baseline for accuracy
    # context: group structure is what transports class-coherent mass.
    print()
    print("=" * 72)
    print("STAGE 1 — SOLO: one problem, one program")
    print("=" * 72)
    t0 = time.perf_counter()
    sol = ex.solve(problems[0])
    t_solo = time.perf_counter() - t0
    acc_gs = float((predict_from_plan(sol.plan, ys, L) == yt0).mean())

    C0 = squared_euclidean_cost(Xs, Xt0)
    C0 /= C0.max()
    sk = sinkhorn_log(jnp.asarray(C0, jnp.float32), jnp.full((m,), 1 / m),
                      jnp.full((n,), 1 / n), eps=0.01)
    acc_sk = float((predict_from_plan(np.asarray(sk.plan), ys, L) == yt0).mean())
    print(f"group-sparse OT:  accuracy {acc_gs:.1%}  "
          f"value {sol.value:.6f}  ({t_solo:.2f}s incl. jit)")
    print(f"entropic OT:      accuracy {acc_sk:.1%}  (no group structure)")

    # ---------------------------------------------------------- 2. batched
    # All target domains at once: solve_many stacks every problem behind a
    # leading B axis and the whole batch advances in ONE jitted program
    # (masked per-problem convergence — no recompiles, no Python loop).
    print()
    print("=" * 72)
    print(f"STAGE 2 — BATCHED: {len(targets)} problems, ONE program")
    print("=" * 72)
    launches_before = ex.stats()["launches"]
    t0 = time.perf_counter()
    sols = ex.solve_many(problems)
    t_batch = time.perf_counter() - t0
    print(f"solved {len(sols)} problems in {t_batch:.2f}s (incl. jit) with "
          f"{ex.stats()['launches'] - launches_before} program launch(es)")
    print(f"per-problem rounds: {[s.rounds for s in sols]}")
    # the batch axis is invisible to numerics: problem 0 solved inside the
    # batch equals the solo solve of stage 1 bit for bit
    assert sols[0].value == sol.value, "batched != solo ?!"
    print("bitwise check: batched problem 0 == solo solve        OK")

    # ---------------------------------------------------------- 3. sharded
    # Same batch, problem axis split over every local device: attach a
    # mesh (ExecutionPlan(devices='all')) and solve_many dispatches to the
    # shard_map program — each device runs the stage-2 solver on its slice
    # (its own screening state, its own compact tile schedules), no
    # collectives inside a round.  Still one program launch.
    print()
    print("=" * 72)
    print(f"STAGE 3 — SHARDED: {len(targets)} problems over "
          f"{jax.local_device_count()} devices")
    print("=" * 72)
    exs = ot.compile(problems[0], ot.ExecutionPlan(
        grad_impl="screened", max_iters=150, devices="all"
    ))
    mesh = exs.mesh
    t0 = time.perf_counter()
    sols_sh = exs.solve_many(problems)
    t_shard = time.perf_counter() - t0
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} -> "
          f"{mesh.size} x {len(targets) // mesh.size} problems/device, "
          f"{exs.stats()['launches']} launch(es), {t_shard:.2f}s (incl. jit)")
    # the mesh is invisible too: every problem bitwise-equals stage 2
    same = all(
        bool(jnp.all(a.result.lbfgs_state.x == b.result.lbfgs_state.x))
        for a, b in zip(sols_sh, sols)
    )
    assert same, "sharded != batched ?!"
    print("bitwise check: sharded == batched (all problems)      OK")

    # label all target domains — Solution.plan is already un-padded back
    # to the caller's row order, so prediction is a one-liner per domain
    accs = [
        float((predict_from_plan(s.plan, ys, L) == yt).mean())
        for s, (_, yt) in zip(sols_sh, targets)
    ]
    print(f"target-domain accuracies: "
          f"{', '.join(f'{a:.1%}' for a in accs)}")
    print()
    print("Next: stream mixed-shape problems through the serving engine "
          "(docs/serving.md) — it runs stage 3 continuously.")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
