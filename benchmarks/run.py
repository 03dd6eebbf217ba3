"""Benchmark harness entry point: one function per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--full | --smoke]

Prints ``name,metric,derived`` CSV rows per experiment and writes JSON
artifacts next to the repo root (stable key order + schema_version via
benchmarks.bench_io, so the CI regression gate diffs cleanly).  --full
restores the paper's grids (slow on one CPU core); default grids are
trimmed but cover every figure's qualitative claim; --smoke runs tiny
shapes in seconds (CI sanity — no JSON artifacts are written, so the
committed perf-trajectory files are never clobbered by a smoke run).
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, no JSON artifacts (CI sanity)")
    ap.add_argument("--skip", nargs="*", default=[],
                    choices=["synthetic", "gradcount", "objective", "kernels",
                             "sharded", "geometry"])
    args = ap.parse_args()
    if args.full and args.smoke:
        ap.error("--full and --smoke are mutually exclusive")
    smoke = args.smoke

    print("name,metric,derived")

    if "synthetic" not in args.skip:
        from benchmarks import bench_synthetic

        rows = bench_synthetic.main(
            full=args.full, smoke=smoke,
            out=None if smoke else "bench_synthetic.json",
        )
        for r in rows:
            print(f"fig2_{r['sweep']}{r['value']},{r['fast_s']},gain={r['gain']}x")

    if "gradcount" not in args.skip:
        from benchmarks import bench_gradcount

        rows = bench_gradcount.main(
            smoke=smoke, out=None if smoke else "bench_gradcount.json"
        )
        for r in rows:
            if r["fig"] == "6":
                print(f"fig6_rho{r['rho']},{r['ours_blocks']},"
                      f"computed_frac={r['computed_frac']}")
            else:
                print(f"figD_gamma{r['gamma']},{r['fast_with_lower_s']},"
                      f"gain={r['gain_with_lower']}x")

    if "objective" not in args.skip:
        from benchmarks import bench_objective

        rows = bench_objective.main(
            full=args.full, smoke=smoke,
            out=None if smoke else "bench_objective.json",
        )
        for r in rows:
            print(f"table1_L{r['classes']},{r['ours']:.6e},match={r['match']}")

    if "kernels" not in args.skip:
        from benchmarks import bench_kernels

        if smoke:
            rows = bench_kernels.main(
                L=8, g=8, n=256, out=None, densities=(1.0, 0.25), batch=2
            )
        else:
            rows = bench_kernels.main(out="BENCH_kernels.json")
        for r in rows:
            impl = r["impl"]
            if "pallas_compact" in impl:
                c = impl["pallas_compact"]
                d = impl["xla_dense"]
                speedup = round(d["c_bytes"] / max(c["c_bytes"], 1), 2)
                print(f"kernel_gradpsi_d{r['density']},{c['grid_steps']},"
                      f"modeled_tpu_speedup={speedup}x")
            else:
                c = impl["pallas_compact_batched"]
                print(f"kernel_gradpsi_{r['density']},{c['grid_steps']},"
                      f"live={r['live_tiles']}/{r['total_tiles']}")

    if "geometry" not in args.skip:
        from benchmarks import bench_geometry

        rows = bench_geometry.main(
            smoke=smoke, out=None if smoke else "BENCH_geometry.json"
        )
        for r in rows:
            ob = r["operand_bytes"]
            save = round(ob["dense"] / max(ob["factorized"], 1), 1)
            print(f"geometry_n{r['n']}_d{r['density']},{r['grid_steps']},"
                  f"operand_save={save}x")

    if "sharded" not in args.skip:
        from benchmarks import bench_sharded

        rows = bench_sharded.main(
            smoke=smoke, out=None if smoke else "BENCH_sharded.json"
        )
        for r in rows:
            c = r["counters"]
            print(f"sharded_{r['workload']}_{r['grad_impl']},"
                  f"{c['rounds_total']},"
                  f"bitwise_mismatches={c['bitwise_mismatches']}")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
