"""Run one cell of the benchmark on the accelerator and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell is an entry of ``workloads`` in
``BENCHMARK.json``; everything else is found by name:

* ``bench/configs/<config>.json``   the deployment: sizes, regulariser, backend;
* ``bench/traffic/<traffic>.json``  the traffic mix, whose ``driver`` names
  ``bench/drivers/<driver>.py``, the general generator of that kind of load;
* ``bench/workloads/<cell>.json``   the limits of the cell's correctness check;
* ``bench/metrics/<metric>.py``     one reader per per-layer metric.

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the same
window.  The last line of standard output is one JSON object; the numbers
that decided ``correct`` are the last lines of standard error too.  Without
a TPU, or with fewer chips than the cell asks for, the run exits 3 and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = BENCH / ".out"


class NoAccelerator(RuntimeError):
    """The machine lacks the chips the cell asks for."""


def load_cell(name: str, bench_json: Path = ROOT / "BENCHMARK.json") -> dict:
    """The cell's entry, configuration, traffic, limits and metric lists."""
    spec = json.loads(bench_json.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = dict(cells[name])

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    cell["config_data"] = json.loads((BENCH / "configs" / f"{cell['config']}.json").read_text())
    cell["traffic_data"] = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    cell["cell_data"] = json.loads((BENCH / "workloads" / f"{name}.json").read_text())
    cell["end_to_end"] = [m for m in spec["end_to_end"] if applies(m)]
    cell["per_layer"] = [m for m in spec["per_layer"] if applies(m)]
    return cell


class Run:
    """One run of one cell: its inputs, clock, window, checks and facts.

    A driver builds its inputs, calls :meth:`setup_done`, drives the
    system under test inside :meth:`window`, then reports what it measured
    through :attr:`metrics`, :attr:`facts` and :meth:`check`.
    """

    def __init__(self, cell: dict, seed: int, seconds: float, trace: bool,
                 precision: str | None = None, require_tpu: bool = True,
                 t_start: float | None = None):
        self.cell = cell
        self.name = cell["name"]
        self.config = cell["config_data"]
        self.traffic = cell["traffic_data"]
        self.limits = cell["cell_data"]["limits"]
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        # the precision the program computes in: the configuration's, or a
        # lower one for the control of the correctness check
        self.precision = precision or self.config["precision"]
        self.require_tpu = require_tpu
        self.t_start = T_START if t_start is None else t_start
        self.setup_s = None
        self.window_s = None
        self.metrics: dict = {}        # end-to-end, filled by the driver
        self.facts: dict = {}          # what the per-layer readers read
        self.checks: dict = {}         # name -> (value, limit)
        self.attempted = 0
        self.failed = 0
        self.trace_dir = OUT / f"trace-{self.name}-{self.seed}"
        self.devices = None

    # -- set-up ---------------------------------------------------------------
    def devices_or_fail(self):
        """The chips of this cell; :class:`NoAccelerator` when they are not there."""
        import jax

        devs = jax.devices()
        need = int(self.cell["chips"])
        if self.require_tpu and (devs[0].platform != "tpu" or len(devs) < need):
            raise NoAccelerator(
                f"cell {self.name} needs {need} TPU chip(s); JAX sees "
                f"{len(devs)} {devs[0].platform} device(s)"
            )
        self.devices = devs[:need]
        return self.devices

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - self.t_start

    def span(self, name: str):
        """A host span in the trace (nothing when not tracing)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def window(self):
        """The measured window; traced with ``--trace 1``."""
        import jax

        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
        w = Window(self.seconds)
        try:
            with self.span("bench.window"):
                yield w
        finally:
            w.close()
            self.window_s = w.elapsed
            if self.trace:
                jax.profiler.stop_trace()

    def check(self, name: str, value: float, limit: float) -> None:
        """One number that decides ``correct``: passes when ``value <= limit``."""
        self.checks[name] = (float(value), float(limit))

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            math.isfinite(v) and v <= lim for v, lim in self.checks.values()
        )

    def memory_peak_bytes(self) -> int:
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        return max(peaks) if peaks else 0


class Window:
    """Closed-loop window clock: ``over()`` once ``seconds`` have passed."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.t0 = time.perf_counter()
        self.t1 = None

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def over(self) -> bool:
        return self.now() >= self.seconds

    def close(self) -> None:
        if self.t1 is None:
            self.t1 = time.perf_counter()

    @property
    def elapsed(self) -> float:
        return (self.t1 if self.t1 is not None else time.perf_counter()) - self.t0


def reader(name: str):
    """The module ``bench/metrics/<name>.py`` (names hold dots)."""
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", BENCH / "metrics" / f"{name}.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def per_layer(run: Run, reduced) -> dict:
    """Each per-layer metric of the cell, from its reader; silent ones left out."""
    out = {}
    for m in run.cell["per_layer"]:
        value = reader(m["name"]).read(run, reduced)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def execute(run: Run) -> dict:
    """Drive the cell and return its result line (a dict)."""
    run.devices_or_fail()
    driver = importlib.import_module(f"drivers.{run.traffic['driver']}")
    driver.run(run)
    dev = run.devices[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(run.devices),
        "memory_peak_bytes": run.facts.get("memory_peak_bytes", 0),
    }
    result = {"correct": run.correct, "attempted": run.attempted, "failed": run.failed}
    if run.trace:
        import traces

        reduced = traces.reduce(traces.find_xplane(str(run.trace_dir)),
                                chips=len(run.devices))
        shutil.rmtree(run.trace_dir, ignore_errors=True)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["metrics"] = per_layer(run, reduced)
        result["device"] = device
        result["breakdown"] = reduced.breakdown
    else:
        metrics = {"setup_s": {"value": run.setup_s, "unit": "s"}}
        units = {m["name"]: m["unit"] for m in run.cell["end_to_end"]}
        for name, value in run.metrics.items():
            if name in units:
                metrics[name] = {"value": float(value), "unit": units[name]}
        result["metrics"] = metrics
        result["device"] = device
    result["facts"] = {k: v for k, v in run.facts.items() if isinstance(v, (int, float, str))}
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in run.checks.items()}
    return result


def init_jax() -> None:
    """The persistent compile cache at the checkout's fixed path, caching
    every program however fast it compiled: each run is a new process, and
    set-up should find all of them."""
    import jax

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    cell = load_cell(args.workload)
    init_jax()
    run = Run(cell, args.seed, args.seconds, bool(args.trace))
    try:
        result = execute(run)
    except NoAccelerator as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"[check] {name} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(f"[check] correct = {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
