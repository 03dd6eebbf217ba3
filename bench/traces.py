"""Reduce a JAX profiler trace (``.xplane.pb``) to device metrics.

What a TPU trace holds (JAX 0.9, one v5e): a plane ``/device:TPU:<i>`` per
chip whose line ``XLA Ops`` has one event per HLO operation that ran, named
by its HLO text (``%fusion.12 = f32[3200]{0} fusion(...)``),
and a plane ``/host:CPU`` whose lines hold the host's spans, the
benchmark's own ``bench.*`` annotations among them, on the same clock.

Control-flow operations (``while``, ``cond``) are events that contain the
operations of their bodies, so busy time is the union of the intervals,
and a sum over operations counts only the leaves.  A window can hold
millions of events, so everything past the reading of the file is NumPy.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
CONTAINERS = frozenset({"while", "cond", "conditional", "call"})


def op_name(text: str) -> str:
    """``%fusion.12 = f32[..] fusion(..)`` -> ``fusion.12``."""
    head = text.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def base_name(name: str) -> str:
    """``reduce_fusion.5`` -> ``reduce_fusion``."""
    return re.sub(r"\.\d+$", "", name)


@dataclass
class Spans:
    """Named intervals (ns): ``names[code[i]]`` ran from ``start[i]`` to ``end[i]``."""

    names: List[str]
    code: np.ndarray
    start: np.ndarray
    end: np.ndarray

    @staticmethod
    def of(items: Sequence[Tuple[str, float, float]]) -> "Spans":
        ids: Dict[str, int] = {}
        code = [ids.setdefault(n, len(ids)) for n, _, _ in items]
        return Spans(list(ids), np.asarray(code, np.int64),
                     np.asarray([s for _, s, _ in items], np.float64),
                     np.asarray([e for _, _, e in items], np.float64))


@dataclass
class Trace:
    """The parts of one trace that the metrics read (times in ns)."""

    devices: List[Spans]            # operations per chip, by base name
    host: Spans                     # every host span
    window: Tuple[float, float]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _read(lines, rename) -> Spans:
    ids: Dict[str, int] = {}
    code, start, dur = [], [], []
    for line in lines:
        for ev in line.events:
            name = ev.name
            k = ids.get(name)
            if k is None:
                k = ids[name] = len(ids)
            code.append(k)
            start.append(ev.start_ns)
            dur.append(ev.duration_ns)
    raw = list(ids)
    # several raw names can share a base name: renumber by base name
    base: Dict[str, int] = {}
    remap = np.array([base.setdefault(rename(n), len(base)) for n in raw] or [0], np.int64)
    s = np.asarray(start, np.float64)
    return Spans(list(base), remap[np.asarray(code, np.int64)] if code else np.zeros(0, np.int64),
                 s, s + np.asarray(dur, np.float64))


def load(path: str, chips: Optional[int] = None) -> Trace:
    """Read the device operations, host spans and the window of a trace.

    ``chips`` keeps the planes of the first chips only (those the run
    computes on, when it holds more)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = sorted((int(p.name.rsplit(":", 1)[1]), p) for p in pd.planes
                    if DEVICE_PLANE.match(p.name))
    devices = [_read([ln for ln in plane.lines if ln.name == OPS_LINE],
                     lambda n: base_name(op_name(n)))
               for _, plane in planes[:chips]]
    host = Spans([], np.zeros(0, np.int64), np.zeros(0), np.zeros(0))
    for plane in pd.planes:
        if plane.name == HOST_PLANE:
            host = _read(list(plane.lines), lambda n: n)
    if WINDOW_SPAN in host.names:
        k = host.names.index(WINDOW_SPAN)
        sel = host.code == k
        window = (float(host.start[sel].min()), float(host.end[sel].max()))
    elif any(d.start.size for d in devices):
        window = (min(float(d.start.min()) for d in devices if d.start.size),
                  max(float(d.end.max()) for d in devices if d.end.size))
    else:
        window = (0.0, 0.0)
    return Trace(devices, host, window)


def merged(start: np.ndarray, end: np.ndarray, lo: float, hi: float):
    """Union of intervals clipped to ``[lo, hi]``: sorted disjoint ``(starts, ends)``."""
    s, e = np.clip(start, lo, hi), np.clip(end, lo, hi)
    keep = e > s
    s, e = s[keep], e[keep]
    if not s.size:
        return s, e
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    reach = np.maximum.accumulate(e)
    new = np.concatenate([[True], s[1:] > reach[:-1]])
    first = np.flatnonzero(new)
    last = np.concatenate([first[1:] - 1, [s.size - 1]])
    return s[first], reach[last]


def busy_s(tr: Trace) -> float:
    """Seconds in the window in which some operation ran, mean over chips."""
    if not tr.devices:
        return 0.0
    lo, hi = tr.window
    per = []
    for d in tr.devices:
        s, e = merged(d.start, d.end, lo, hi)
        per.append(float(np.sum(e - s)))
    return sum(per) / len(per) * 1e-9


def idle_share_pct(tr: Trace) -> Optional[float]:
    """100 (1 - busy / window); ``None`` for an empty window."""
    if tr.window_s <= 0 or not tr.devices:
        return None
    return 100.0 * (1.0 - busy_s(tr) / tr.window_s)


def leaf_seconds(tr: Trace) -> Dict[str, float]:
    """Device seconds per leaf operation (containers left out), mean over chips."""
    lo, hi = tr.window
    tot: Dict[str, float] = {}
    for d in tr.devices:
        clipped = np.clip(np.minimum(d.end, hi) - np.maximum(d.start, lo), 0.0, None)
        sums = np.bincount(d.code, weights=clipped, minlength=len(d.names))
        for name, v in zip(d.names, sums):
            if name not in CONTAINERS and v > 0:
                tot[name] = tot.get(name, 0.0) + float(v) * 1e-9
    n = max(len(tr.devices), 1)
    return {k: v / n for k, v in tot.items()}


def idle_gaps(tr: Trace, attributed: int = 2000) -> Dict[str, float]:
    """Idle device seconds in the window, by what the host was doing.

    Each of the ``attributed`` longest gaps between busy intervals (chip 0)
    goes to the innermost host span that covers its midpoint, other than
    the window itself; the rest are summed as ``short gaps``."""
    if not tr.devices:
        return {}
    lo, hi = tr.window
    d = tr.devices[0]
    bs, be = merged(d.start, d.end, lo, hi)
    gs = np.concatenate([[lo], be])
    ge = np.concatenate([bs, [hi]])
    length = ge - gs
    keep = length > 0
    gs, ge, length = gs[keep], ge[keep], length[keep]
    order = np.argsort(-length, kind="stable")
    h = tr.host
    skip = h.names.index(WINDOW_SPAN) if WINDOW_SPAN in h.names else -1
    use = h.code != skip
    hc, hs, he = h.code[use], h.start[use], h.end[use]
    dur = he - hs
    out: Dict[str, float] = {}
    for i in order[:attributed]:
        mid = 0.5 * (gs[i] + ge[i])
        cover = np.where((hs <= mid) & (he >= mid), dur, np.inf)
        k = int(np.argmin(cover)) if cover.size else -1
        label = h.names[hc[k]] if k >= 0 and np.isfinite(cover[k]) else "no host span"
        out[label] = out.get(label, 0.0) + float(length[i]) * 1e-9
    rest = float(np.sum(length[order[attributed:]]))
    if rest:
        out["short gaps"] = rest * 1e-9
    return out


def top(d: Dict[str, float], k: int = 10) -> List[list]:
    """The ``k`` largest entries as ``[[name, seconds], ...]``."""
    return [[name, sec] for name, sec in sorted(d.items(), key=lambda kv: -kv[1])[:k]]


@dataclass
class Reduced:
    """What the per-layer readers and the result line take from a trace."""

    busy_s: float
    window_s: float
    idle_share_pct: Optional[float]
    breakdown: dict = field(default_factory=dict)


def reduce(path: str, chips: Optional[int] = None) -> Reduced:
    tr = load(path, chips)
    return Reduced(
        busy_s=busy_s(tr),
        window_s=tr.window_s,
        idle_share_pct=idle_share_pct(tr),
        breakdown={"device_ops": top(leaf_seconds(tr)), "idle_gaps": top(idle_gaps(tr))},
    )
