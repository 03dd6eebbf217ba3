"""Programs of ``sinkhorn_log`` built (compiled or loaded from the cache)
after set-up ended, as the system's recorder (``repro.utils.trace``)
reported them; what the phase readers compile and run after the window
(``phases.own_work``) does not count.  Should be 0: set-up builds every
shape the window runs.  Nothing where the program has no recorder."""
import phases


def read(run, reduced):
    try:
        from repro.utils import trace
    except ImportError:
        return None
    end = run.t_start + run.setup_s
    return sum(1 for b in trace.builds()
               if b.event == "compile" and b.fun_name == "jit(sinkhorn_log)" and b.stamp > end
               and not any(t0 <= b.stamp <= t1 for t0, t1 in phases.own_work))
