"""Share of the traced window in which no operation ran on the device, in %.

Read from the profiler trace: 100 (1 - busy / window), busy being the union
of the device's operation intervals (``traces.idle_share_pct``)."""


def read(run, reduced):
    return reduced.idle_share_pct
