"""Device milliseconds of one loss step: the seconds in which an operation
ran on the device in the traced window (``traces.busy_s``) over the steps
the window completed."""


def read(run, reduced):
    steps = run.facts.get("steps", 0)
    if not steps or reduced.busy_s <= 0:
        return None
    return 1e3 * reduced.busy_s / steps
