"""Device microseconds a Sinkhorn iteration spends in the g column update (``sinkhorn.g_update``): the device
seconds of the instructions whose ``op_name`` holds that scope, traced
over one solve on each of the cell's costs after the window, over the
iterations those solves ran (``phases.us_per_iter``)."""
import phases


def read(run, reduced):
    return phases.us_per_iter(run, "g_update")
