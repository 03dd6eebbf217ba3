"""Readings of a cell's correctness check over many seeds, in one process.

    python3 bench/control.py --workload <cell> --seeds 11 12 13 --seconds 3 [--precision bf16]

Runs the cell as ``bench/run.py`` does, once per seed, and prints one JSON
line per seed with the numbers that the check compares.  With
``--precision bf16`` the plain reference, computed in bfloat16, takes the
system's place in the window: the control of the check, which has to come
out not correct.  Without it, the system runs as the configuration states.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run as bench_run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--precision", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(bench_run.ROOT / "src"))
    cell = bench_run.load_cell(args.workload)
    bench_run.init_jax()
    for seed in args.seeds:
        r = bench_run.Run(cell, seed, args.seconds, False, precision=args.precision,
                          t_start=time.perf_counter())
        try:
            res = bench_run.execute(r)
        except bench_run.NoAccelerator as e:
            print(f"[bench] {e}", file=sys.stderr)
            return 3
        print(json.dumps({
            "workload": args.workload, "seed": seed, "precision": r.precision,
            "correct": res["correct"], "checks": res["checks"],
            "metrics": res["metrics"], "facts": res["facts"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
