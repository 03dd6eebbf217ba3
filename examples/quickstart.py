"""Quickstart: the ``repro.ot`` façade in five minutes.

Declare a Problem, compile an Executor, and solve the paper's synthetic
domain-adaptation task — solo, as a fused batch, and as a round-step
stream — showing the Theorem-2 equality (dense == screened, bitwise) and
the structured (group-sparse) transportation plan along the way.

Run:  PYTHONPATH=src python examples/quickstart.py

This example is executed in CI (smoke step), so the headline API shown
here can never silently rot.
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import repro.ot as ot
from repro.core import GroupSparseReg
from repro.data.pipeline import DomainPairConfig, make_domain_pair


def main():
    # the paper's synthetic: L classes at (l*5, -5) [source] / (l*5, +5) [target]
    Xs, ys, Xt, _ = make_domain_pair(
        DomainPairConfig(num_classes=8, samples_per_class=10, seed=0)
    )
    reg = GroupSparseReg.from_rho(1.0, 0.6)

    print("=== 1. Declare a problem, solve it (screened backend) ===")
    problem = ot.Problem.from_samples(Xs, ys, Xt, reg=reg)
    sol = ot.solve(problem)
    print(f"dual objective        : {sol.value:.6f}")
    print(f"transport cost <T,C>  : {sol.distance:.6f}")
    print(f"group sparsity        : {sol.group_sparsity:.1%} of (class,target) "
          "blocks are exactly zero")
    print(f"L-BFGS iterations     : {sol.iterations} "
          f"(skipped blocks: {sol.stats['zero']})")

    print("\n=== 2. Theorem 2: the dense (unscreened) backend matches ===")
    sol_dense = ot.solve(problem, ot.ExecutionPlan(grad_impl="dense"))
    print(f"dense objective       : {sol_dense.value:.6f}")
    print(f"identical             : {sol.value == sol_dense.value}")

    print("\n=== 3. Materialization-free geometry: no (m, n) cost in HBM ===")
    # sample-mode problems can skip the dense cost entirely: the Pallas
    # kernels rebuild each cost tile from the samples via
    # |x|^2 + |y|^2 - 2<x, y>  (docs/geometry.md).  The route is bitwise-
    # equal to the dense route run on the SAME factorized-recipe cost —
    # problem.materialized() — for a fixed backend.
    plan_otf = ot.ExecutionPlan(grad_impl="pallas", geometry="on_the_fly")
    sol_otf = ot.solve(problem, plan_otf)
    sol_mat = ot.solve(
        problem.materialized(), ot.ExecutionPlan(grad_impl="pallas", geometry="dense")
    )
    assert sol_otf.value == sol_mat.value, "on-the-fly != materialized-dense ?!"
    geom = ot.SquaredL2Geometry.from_samples(
        Xs, ys, Xt, problem.group_spec(), normalize_cost=True
    )
    dense_bytes = geom.rows * geom.cols * 4
    print(f"on-the-fly objective  : {sol_otf.value:.6f} "
          f"(== dense route on problem.materialized(), bitwise)")
    print(f"cost operand bytes    : dense {dense_bytes:,} -> "
          f"factorized {geom.hbm_bytes():,}")

    print("\n=== 4. A reusable executor: B problems, ONE fused program ===")
    problems = [problem] + [
        ot.Problem.from_samples(
            Xs, ys,
            make_domain_pair(
                DomainPairConfig(num_classes=8, samples_per_class=10, seed=s)
            )[2],
            reg=reg,
        )
        for s in range(1, 4)
    ]
    ex = ot.compile(problem)
    sols = ex.solve_many(problems)
    assert sols[0].value == sol.value, "batched != solo ?!"
    print(f"solved {len(sols)} problems in {ex.stats()['launches']} launch(es); "
          "problem 0 == solo solve, bitwise")
    print(f"objectives            : {[round(s.value, 6) for s in sols]}")

    print("\n=== 5. Round-step streaming (the serving engine's tick) ===")
    stream = ot.compile(problem).stream(problems)
    for info in stream:
        print(f"round {info['round']:2d}: {info['alive']} problem(s) still solving")
    assert [s.value for s in stream.solutions()] == [s.value for s in sols]
    print("stream result == fused batch, bitwise")

    print("\n=== 6. Diagnostics ===")
    print(ex.describe(sols[0]))


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
