"""Device microseconds of one Sinkhorn iteration: the seconds in which an
operation ran on the device in the traced window (``traces.busy_s``) over
the iterations that the window's solves ran."""


def read(run, reduced):
    iters = run.facts.get("iters", 0)
    if not iters or reduced.busy_s <= 0:
        return None
    return 1e6 * reduced.busy_s / iters
