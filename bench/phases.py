"""Device time of each phase of the Sinkhorn solve, per iteration.

The system's ``sinkhorn_log`` runs its loop's updates under named scopes
(``sinkhorn.f_update``, ``sinkhorn.g_update``, ``sinkhorn.marginal_err``)
and its final plan under ``sinkhorn.plan``.  They reach the compiled
program as each instruction's ``metadata={op_name=...}``, and a profiler
trace names each device operation by its instruction, so the map from
instruction to phase is read from the compiled program's text, never from
a list of fusion names.  Instructions that XLA adds, such as the copies
that memory-space assignment inserts, carry no metadata; those inside a
``while`` loop are the phase :data:`LOOP`, the others :data:`OUTSIDE`.

After the window of a traced run, the readers of the phases
(``bench/metrics/*_us_per_iter.entropic.py``):

* compile the solver again at the cell's shapes, with JAX's in-memory
  caches cleared and the persistent compilation cache off for that compile
  (the cache key ignores the scopes, so an entry written by a program
  without them would come back without metadata), and read the map from
  its text;
* trace one more solve on each of the cell's costs, called as the window
  calls it, which now runs that executable: the window's program, compiled
  from the same module by the same compiler, under the instruction names
  of the map.  Each instruction's device seconds in that trace
  (:func:`instruction_seconds`), over the iterations those solves ran,
  give the phases; the window's own trace is reduced and deleted before
  any reader runs.

A program whose text holds no ``sinkhorn.`` scope gives no map, and the
readers then report nothing.  What this module builds and runs does not
count as a build after set-up (:data:`own_work`).
"""
from __future__ import annotations

import contextlib
import functools
import re
import shutil
import time
from pathlib import Path
from typing import Dict, Optional, Tuple

import numpy as np

import traces

SCOPE = re.compile(r"sinkhorn\.(f_update|g_update|marginal_err|plan)\b")
LOOP = "loop"            # inside a while loop, without scope: XLA's copies
OUTSIDE = "outside"      # outside every loop, without scope
OUT = Path(__file__).resolve().parent / ".out"

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([^\s(]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%([^\s=]+) = (.*)$")
_LOOP_PARTS = re.compile(r"(?:condition|body)=%([^\s,)}]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')

# perf_counter intervals in which this module compiled or ran the solver
own_work: list = []


def result_shape(text: str) -> str:
    """``%copy-done = f32[80]{0:T(128)S(1)} copy-done(..)`` ->
    ``f32[80]{0:T(128)S(1)}``; a tuple result whole, parentheses included."""
    rest = text.split(" = ", 1)[1] if " = " in text else ""
    if not rest.startswith("("):
        return rest.split(" ", 1)[0]
    depth = 0
    for i, ch in enumerate(rest):
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if depth == 0:
            return rest[:i + 1]
    return rest


def instruction_seconds(path: str, chips: Optional[int] = None) -> Dict[str, Tuple[float, str]]:
    """Device seconds per leaf HLO instruction in the trace's window (as
    ``traces.load`` finds it), mean over chips, with the instruction's result
    shape: ``{"fusion.7": (seconds, "f32[3200]{0}")}``.

    Keyed by the instruction's name with its number, which is unique within
    one program; instructions of two programs that share a name add up under
    it, with the shape of the first.  Summed by ``traces.base_name`` these
    are ``traces.leaf_seconds``."""
    from jax.profiler import ProfileData

    lo, hi = traces.load(path, chips).window
    planes = sorted((int(p.name.rsplit(":", 1)[1]), p) for p in ProfileData.from_file(path).planes
                    if traces.DEVICE_PLANE.match(p.name))[:chips]
    tot: Dict[str, Tuple[float, str]] = {}
    for _, plane in planes:
        d = traces._read([ln for ln in plane.lines if ln.name == traces.OPS_LINE], lambda n: n)
        clipped = np.clip(np.minimum(d.end, hi) - np.maximum(d.start, lo), 0.0, None)
        sums = np.bincount(d.code, weights=clipped, minlength=len(d.names))
        for text, v in zip(d.names, sums):
            name = traces.op_name(text)
            if traces.base_name(name) in traces.CONTAINERS or v <= 0:
                continue
            sec, shape = tot.get(name, (0.0, result_shape(text)))
            tot[name] = (sec + float(v) * 1e-9, shape)
    n = max(len(planes), 1)
    return {k: (sec / n, shape) for k, (sec, shape) in tot.items()}


def instruction_phases(hlo_text: str) -> Optional[Dict[str, Tuple[str, str]]]:
    """``{instruction: (phase, result shape)}`` of a compiled module's text;
    ``None`` when no instruction carries a ``sinkhorn.`` scope.

    The phase is the last ``sinkhorn.<phase>`` scope in the instruction's
    ``op_name``; without one, :data:`LOOP` for an instruction of a while
    loop's body or condition, else :data:`OUTSIDE`."""
    where: Dict[str, str] = {}
    lines: Dict[str, str] = {}
    loops: set = set()
    comp = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            continue
        ins = _INSTRUCTION.match(line)
        if not ins or comp is None:
            continue
        name, rest = ins.groups()
        where[name], lines[name] = comp, rest
        if re.search(r"\bwhile\(", rest):
            loops.update(_LOOP_PARTS.findall(rest))
    out = {}
    for name, rest in lines.items():
        meta = _OP_NAME.search(rest)
        scopes = SCOPE.findall(meta.group(1)) if meta else []
        phase = scopes[-1] if scopes else (LOOP if where[name] in loops else OUTSIDE)
        out[name] = (phase, result_shape(f"%{name} = {rest}"))
    if all(p in (LOOP, OUTSIDE) for p, _ in out.values()):
        return None
    return out


def seconds_by_phase(instructions: Dict[str, Tuple[float, str]],
                     phases: Dict[str, Tuple[str, str]]) -> Optional[Dict[str, float]]:
    """Device seconds per phase; ``None`` when the trace holds no instruction,
    or one that is not the map's (another program ran, or the map is not of
    this program)."""
    if not instructions:
        return None
    out: Dict[str, float] = {}
    for name, (sec, shape) in instructions.items():
        got = phases.get(name)
        if got is None or got[1] != shape:
            return None
        out[got[0]] = out.get(got[0], 0.0) + sec
    return out


@contextlib.contextmanager
def _no_persistent_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@functools.lru_cache(maxsize=None)
def _measure(L: int, g: int, n: int, dim: int, eps: float, max_iters: int, tol: float,
             shift: float, pool: int, seed: int, chips: int):
    """``(instructions, phase map, iterations)`` of one solve on each cost
    of the pool (:func:`instruction_seconds`, :func:`instruction_phases`);
    ``None`` when the compiled solver names no phase."""
    import jax
    import jax.numpy as jnp

    import data
    from repro.core import sinkhorn

    m = L * g
    f32 = jnp.float32
    t0 = time.perf_counter()
    try:
        with _no_persistent_cache():
            # without the in-memory caches too, or the window's executable
            # comes back, as loaded from the persistent cache
            jax.clear_caches()
            text = sinkhorn.sinkhorn_log.lower(
                jax.ShapeDtypeStruct((m, n), f32), jax.ShapeDtypeStruct((m,), f32),
                jax.ShapeDtypeStruct((n,), f32), eps=eps, max_iters=max_iters, tol=tol,
            ).compile().as_text()
        phases = instruction_phases(text)
        if phases is None:
            return None
        # the window's inputs and call: the same program as the window's, now
        # the executable just compiled
        costs = data.costs(data.device_seed(seed), L=L, g=g, dim=dim, shift=shift, count=pool)
        a = jnp.full((m,), 1.0 / m, f32)
        b = jnp.full((n,), 1.0 / n, f32)
        out = OUT / f"phases-{seed}"
        shutil.rmtree(out, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(out), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(traces.WINDOW_SPAN):
                res = [sinkhorn.sinkhorn_log(C, a, b, eps=eps, max_iters=max_iters, tol=tol)
                       for C in costs]
                jax.block_until_ready(res)
        finally:
            jax.profiler.stop_trace()
        iters = sum(int(r.n_iters) for r in res)
        instructions = instruction_seconds(traces.find_xplane(str(out)), chips)
        shutil.rmtree(out, ignore_errors=True)
        return instructions, phases, iters
    finally:
        own_work.append((t0, time.perf_counter()))


def us_per_iter(run, phase: str) -> Optional[float]:
    """Device microseconds a Sinkhorn iteration of the cell spends in
    ``phase``; ``None`` when there is nothing to attribute."""
    cfg, tr = run.config, run.traffic
    got = _measure(int(cfg["num_classes"]), int(cfg["samples_per_class"]),
                   int(cfg["num_target"]), int(cfg["dim"]), float(cfg["eps"]),
                   int(cfg["max_iters"]), float(cfg["tol"]), float(tr["shift"]),
                   int(tr["pool"]), run.seed, len(run.devices))
    if got is None:
        return None
    instructions, phases, iters = got
    per = seconds_by_phase(instructions, phases)
    if per is None or not iters:
        return None
    return 1e6 * per.get(phase, 0.0) / iters
