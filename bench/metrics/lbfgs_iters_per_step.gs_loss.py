"""L-BFGS iterations of the solve in one loss step: the ``iter`` of the
solver state that the layer's own program (``repro.core.solver._solve_jit``)
returns on each pool cost after the window, averaged over the costs."""


def read(run, reduced):
    return run.facts.get("lbfgs_iters")
