"""Tiny cells for the benchmark's own tests: the real drivers and checks at
sizes the CPU runs in seconds."""
from __future__ import annotations

import copy
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as bench_run  # noqa: E402

SIZE = {"num_classes": 16, "num_target": 160}


def cell(name: str, **traffic) -> dict:
    """A cell of ``BENCHMARK.json`` cut to a CPU-sized problem, with the
    cell's own limits."""
    c = copy.deepcopy(bench_run.load_cell(name))
    c["config_data"].update(SIZE)
    c["traffic_data"].update(traffic)
    return c


def execute(c: dict, seed: int = 1, seconds: float = 0.5, trace: bool = False,
            precision=None) -> dict:
    r = bench_run.Run(c, seed, seconds, trace, precision=precision, require_tpu=False)
    return bench_run.execute(r)
