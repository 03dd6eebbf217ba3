"""Entropic OT, the paper's baseline: a closed loop of Sinkhorn solves.

Each solve is the system's ``repro.core.sinkhorn_log`` on a cost that lives
on the device: one client that keeps ``ahead`` solves in flight and waits
for the oldest plan (``block_until_ready``) before it asks for the next,
as a job that solves a stream of problems does, so that a stall of the
host shorter than those solves leaves the chip busy.  The costs are a pool
of ``pool`` distinct draws from the seed, cycled, so no solve repeats the
previous solve's cost.

End to end: ``entropic_solve_s``, the window's seconds over its solves.
When the window's time is up nothing more is sent, every solve in flight
is waited for, and the clock is read after that wait: every solve sent
counts, over all of that time.  Checked once the window has closed: the
plans of ``sample`` solves drawn from the seed, each against the plain
reference (``bench/reference.py``) on the same cost: the plan's L1 gap, and
how far the plan is from its marginals.
"""
from __future__ import annotations

from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

import data
import reference


def _solver(cfg: dict, precision: str):
    """``cost -> (plan, iterations)``: the system's Sinkhorn in the
    configuration's precision; for the control of the check, the reference
    put in its place and computed in ``precision``."""
    if precision == cfg["precision"]:
        from repro.core import sinkhorn

        def solve(C, a, b):
            res = sinkhorn.sinkhorn_log(C, a, b, eps=cfg["eps"], max_iters=cfg["max_iters"],
                                        tol=cfg["tol"])
            return res.plan, res.n_iters
    else:
        dtype = {"bf16": jnp.bfloat16}[precision]

        def solve(C, a, b):
            res = reference.sinkhorn(C, a, b, cfg["eps"], cfg["tol"],
                                     max_iters=cfg["max_iters"], dtype=dtype)
            return res.plan, res.iters
    return solve


def run(r) -> None:
    cfg, tr = r.config, r.traffic
    L, g, n = cfg["num_classes"], cfg["samples_per_class"], cfg["num_target"]
    m, K, S, ahead = L * g, int(tr["pool"]), int(tr["sample"]), int(tr["ahead"])
    pool = data.costs(data.device_seed(r.seed), L=L, g=g, dim=cfg["dim"],
                      shift=float(tr["shift"]), count=K)
    a = jnp.full((m,), 1.0 / m, jnp.float32)
    b = jnp.full((n,), 1.0 / n, jnp.float32)
    solve = _solver(cfg, r.precision)
    jax.block_until_ready(solve(pool[0], a, b))        # compiles (or loads) the program
    r.setup_done()

    # a reservoir of S solves, drawn from the seed, to check after the window
    rng = np.random.default_rng([r.seed, 1])
    sample, iters, flight = [], [], deque()
    with r.window() as w:
        i = 0
        while not w.over():
            with r.span("bench.dispatch"):
                plan, it = solve(pool[i % K], a, b)
            flight.append(plan)
            iters.append(it)
            if len(sample) < S:
                sample.append((i, plan))
            else:
                j = int(rng.integers(0, i + 1))
                if j < S:
                    sample[j] = (i, plan)
            i += 1
            if len(flight) >= ahead:
                with r.span("bench.wait"):
                    jax.block_until_ready(flight.popleft())
        with r.span("bench.drain"):
            jax.block_until_ready(list(flight))
    del plan, flight
    solves = i
    r.attempted = solves
    r.metrics["entropic_solve_s"] = r.window_s / solves
    r.facts["memory_peak_bytes"] = r.memory_peak_bytes()
    r.facts["solves"] = solves
    r.facts["iters"] = int(jnp.sum(jnp.stack(iters)))

    # the reference, on the cost of each sampled solve
    plan_l1, marg, ref_marg, nonfinite, refs = 0.0, 0.0, 0.0, 0, {}
    for i, plan in sorted(sample, key=lambda s: s[0]):
        k = i % K
        if k not in refs:
            refs[k] = reference.sinkhorn(pool[k], a, b, cfg["eps"], cfg["tol"],
                                         max_iters=cfg["max_iters"]).plan
            ref_marg = max(ref_marg, float(reference.marginal_l1(refs[k], a, b)))
        nonfinite += int(not jnp.all(jnp.isfinite(plan)))
        plan_l1 = max(plan_l1, float(jnp.sum(jnp.abs(plan - refs[k]))))
        marg = max(marg, float(reference.marginal_l1(plan, a, b)))
    r.failed = nonfinite
    # what the reference reaches on the same costs
    r.facts["ref_marginal_l1"] = ref_marg
    r.check("plan_l1", plan_l1, r.limits["plan_l1"])
    r.check("marginal_l1", marg, r.limits["marginal_l1"])
