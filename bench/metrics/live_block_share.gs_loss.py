"""Share of the class blocks (one source class by one target column) that
screening leaves live, in %: the blocks with verdict check or active at the
rounds' snapshots (the solver's ``stats``), over all ``L n`` blocks of each
round, from the same solves as ``lbfgs_iters_per_step.gs_loss``.  Lower
means screening skips more of the gradient."""


def read(run, reduced):
    rounds = run.facts.get("rounds")
    if not rounds:
        return None
    cfg = run.config
    return 100.0 * run.facts["live_blocks"] / (rounds * cfg["num_classes"] * cfg["num_target"])
