"""Sinkhorn iterations a solve ran: the ``n_iters`` that the system's
``sinkhorn_log`` returns, summed over the window's solves and divided by
them."""


def read(run, reduced):
    solves = run.facts.get("solves", 0)
    if not solves:
        return None
    return run.facts["iters"] / solves
