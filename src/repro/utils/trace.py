"""Compile and cache counters: every program JAX builds in this process.

Importing this module registers one recorder with ``jax.monitoring``; it
changes no JAX setting.  Each event JAX reports while it builds a program
becomes a :class:`Build` record: tracing to a jaxpr, lowering to MLIR, the
backend compile (or the load from the persistent cache, which happens
inside it), a persistent-cache hit or miss, and the seconds spent reading
the cache.  Records are stamped with ``time.perf_counter()`` when JAX
reports them, the clock that entry points time their set-up with, so a
build can be placed before or after any moment they took on that clock.

The cost is one list append per event: about a hundred while a process
builds a solver's program (each jitted ``jnp`` function it calls is traced
too), and none when a built program runs.

A solver that picks a route while it traces records it with
:func:`record_route` (``sinkhorn_log``: the VMEM-resident kernel or the XLA
loop); :func:`routes` lists them.  JAX traces before it looks the program
up in the persistent cache, so every process that builds the solver
records its route, also where the compiled program comes from the cache.
"""
from __future__ import annotations

import threading
import time
from typing import List, NamedTuple, Tuple

from jax import monitoring

# JAX's event names (jax/_src/dispatch.py, compiler.py) -> the record's event
_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}
_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hit",
    "/jax/compilation_cache/cache_misses": "cache_miss",
}


class Build(NamedTuple):
    """One event of building a program.

    ``seconds`` is the event's duration (0 for a cache hit or miss) and
    ``stamp`` the ``perf_counter`` reading when JAX reported it, at the
    event's end.  ``fun_name`` is JAX's name for the program: the
    function's name when tracing (``sinkhorn_log``), the module's after
    (``jit(sinkhorn_log)``).
    """

    fun_name: str
    event: str
    seconds: float
    stamp: float


class _Pending(threading.local):
    """Cache hits, misses and retrievals are reported without a name, from
    inside the backend compile that reports the name when it ends: they
    wait here, per thread, for it."""

    def __init__(self):
        self.records = []


_records: List[Build] = []
_pending = _Pending()


def _on_duration(event: str, duration: float, **kwargs) -> None:
    kind = _DURATIONS.get(event)
    if kind is None:
        return
    stamp = time.perf_counter()
    if kind == "cache_retrieval":
        _pending.records.append((kind, float(duration), stamp))
        return
    name = str(kwargs.get("fun_name", ""))
    if kind == "compile":
        _records.extend(Build(name, k, s, t) for k, s, t in _pending.records)
        _pending.records.clear()
    _records.append(Build(name, kind, float(duration), stamp))


def _on_event(event: str, **kwargs) -> None:
    kind = _EVENTS.get(event)
    if kind is not None:
        _pending.records.append((kind, 0.0, time.perf_counter()))


monitoring.register_event_duration_secs_listener(_on_duration)
monitoring.register_event_listener(_on_event)


def builds() -> List[Build]:
    """Every record so far, in the order JAX reported them."""
    return list(_records)


class Route(NamedTuple):
    """The route one trace of a solver took: ``fun_name`` is the solver's
    name (``sinkhorn_log``), ``route`` the one it took (``"resident"`` or
    ``"xla"`` for ``sinkhorn_log``), ``shape`` the shape it was traced at."""

    fun_name: str
    route: str
    shape: Tuple[int, ...]


_routes: List[Route] = []


def record_route(fun_name: str, route: str, shape) -> None:
    """Record the route of one trace; called by the solver while it traces,
    so each program it builds records it once, also where the compiled
    program then comes from the persistent cache."""
    _routes.append(Route(fun_name, route, tuple(int(d) for d in shape)))


def routes() -> List[Route]:
    """Every route recorded so far, in the order the traces took them."""
    return list(_routes)
