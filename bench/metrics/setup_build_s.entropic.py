"""Seconds of set-up spent building programs: the union of the intervals
in which JAX traced, lowered, compiled or loaded from the persistent cache
a program, as the system's recorder (``repro.utils.trace``) reported them
before set-up ended.  Nothing where the program has no recorder."""
import numpy as np

import traces


def read(run, reduced):
    try:
        from repro.utils import trace
    except ImportError:
        return None
    end = run.t_start + run.setup_s
    spans = [(b.stamp - b.seconds, b.stamp) for b in trace.builds()
             if b.event in ("trace", "lower", "compile") and b.stamp <= end]
    if not spans:
        return None
    s, e = traces.merged(np.array([a for a, _ in spans]), np.array([b for _, b in spans]),
                         run.t_start, end)
    return float(np.sum(e - s))
