"""Record the small trace that ``test_traces.py`` reads (run on a TPU).

    python3 bench/tests/record_trace.py

Two solves of a tiny problem (8 classes of 10 samples, 100 iterations)
through the system's ``sinkhorn_log``, traced as ``bench/run.py`` traces a
window, and beside the trace the solves and iterations it holds.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE.parent)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import data  # noqa: E402
import traces  # noqa: E402


def main() -> None:
    from repro.core import sinkhorn_log

    (C,) = data.costs(5, L=8, g=10, dim=2, shift=5.0, count=1)
    a = jnp.full((80,), 1.0 / 80, jnp.float32)

    def solve():
        res = sinkhorn_log(C, a, a, eps=0.01, max_iters=100, tol=1e-8)
        jax.block_until_ready(res.plan)
        return res

    solve()
    out = HERE / "data" / "trace"
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(out), profiler_options=opts)
    iters = 0
    with jax.profiler.TraceAnnotation(traces.WINDOW_SPAN):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.solve"):
                iters += int(solve().n_iters)
    jax.profiler.stop_trace()
    path = Path(traces.find_xplane(str(out)))
    target = HERE / "data" / "sinkhorn.xplane.pb"
    shutil.move(str(path), target)
    shutil.rmtree(out, ignore_errors=True)
    facts = {"solves": 2, "iters": iters, "device_kind": jax.devices()[0].device_kind}
    (HERE / "data" / "sinkhorn.json").write_text(json.dumps(facts, indent=1) + "\n")
    print(json.dumps(facts))


if __name__ == "__main__":
    main()
