"""Device microseconds a Sinkhorn iteration spends in the loop's
instructions that carry no scope: the copies XLA adds to the loop body
(the cost moved to another memory space in every iteration, and the
potentials' copies), traced as ``phases.us_per_iter`` says."""
import phases


def read(run, reduced):
    return phases.us_per_iter(run, phases.LOOP)
