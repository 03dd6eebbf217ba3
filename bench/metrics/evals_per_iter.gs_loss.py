"""Oracle evaluations (value and gradient of the dual) per L-BFGS iteration,
line search included: the solver state's ``n_evals`` over its ``iter``, from
the same solves as ``lbfgs_iters_per_step.gs_loss``."""


def read(run, reduced):
    iters = run.facts.get("lbfgs_iters")
    if not iters:
        return None
    return run.facts["evals"] / iters
