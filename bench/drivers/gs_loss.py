"""Group-sparse OT as a loss: a closed loop of loss steps.

A loss step is one ``jax.value_and_grad`` of the system's
``repro.ot.OTLayer`` with respect to the cost: the group-sparse OT value
(Ida et al., AAAI 2023) and its gradient, which is the optimal plan
(Danskin).  The layer's forward pass runs the screened L-BFGS solve of
Algorithm 1 with the configuration's ``ExecutionPlan``.  The client is that of
``drivers/entropic.py``: the same costs from the seed (``data.costs``, a pool of
``pool`` cycled), ``ahead`` steps in flight, the oldest waited for with
``block_until_ready`` before the next is sent.

End to end: ``gs_loss_step_s``, the window's seconds over its steps.  When
the window's time is up nothing more is sent, every step in flight is
waited for, and the clock is read after that wait.

Checked once the window has closed, on ``sample`` steps drawn from the
seed, against the plain reference's float64 optimum
(``reference_gs.optimum``) on the same cost, in float64 on the host:
``value_gap`` (relative gap of the value), ``plan_l1`` (sum of
``|T - T_ref|``; plans have mass 1), ``marginal_rel`` (the largest
``|T 1 - a| / a`` or ``|T^T 1 - b| / b``) and ``empty_marginals`` (target
columns with less than half their mass), and ``nonfinite`` (a value or a
plan with a NaN or an infinity).  A number is compared where the
cell's file gives it a limit, and reported as a fact otherwise; beside
each, as a fact, what the reference itself reads.

With ``--trace 1`` the window is followed by one more solve of each pool
cost with the layer's own program (``repro.core.solver._solve_jit``), whose
state gives the solver's counts to the readers.  ``check_s`` is the time
the check took.
"""
from __future__ import annotations

import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

import data
import reference_gs


def layer(cfg: dict, precision: str):
    """The system's OT layer as the configuration states it, computing in
    ``precision`` (``'bf16'``, the system's own lower-precision cost, is
    the control of the check)."""
    from repro.core.regularizers import GroupSparseReg
    from repro.ot import ExecutionPlan, OTLayer

    return OTLayer(
        num_groups=cfg["num_classes"], group_size=cfg["samples_per_class"],
        num_target=cfg["num_target"],
        reg=GroupSparseReg.from_rho(cfg["gamma"], cfg["rho"]),
        plan=ExecutionPlan(**dict(cfg["plan"], precision=precision)),
    )


def marginal_rel(T, a, b) -> float:
    """The largest relative miss of a row or column sum of ``T``."""
    return float(max(np.max(np.abs(T.sum(axis=1) - a) / a),
                     np.max(np.abs(T.sum(axis=0) - b) / b)))


def compare(value, T, ref: reference_gs.RefSolution, a, b) -> dict:
    """The numbers of the check for one step against the reference, all in
    float64 on the host."""
    value, T = np.float64(value), np.asarray(T, np.float64)
    return {
        "value_gap": float(np.abs(value - ref.value) / np.abs(ref.value)),
        "plan_l1": float(np.sum(np.abs(T - ref.plan))),
        "marginal_rel": marginal_rel(T, a, b),
        "empty_marginals": float(np.sum(T.sum(axis=0) < 0.5 * b)),
        "nonfinite": float(not (np.isfinite(value) and np.all(np.isfinite(T)))),
    }


def solver_counts(lay, C, a, b) -> dict:
    """One solve of ``C`` with the layer's own program: L-BFGS iterations,
    oracle evaluations, rounds, and the class blocks that screening left
    live (verdict check or active) at the rounds' snapshots, summed over
    the rounds."""
    from repro.core import solver as slv

    prob, spec = lay.dual_problem(), lay.spec()
    row_mask = jnp.asarray(spec.row_mask().reshape(-1))
    sqrt_g = jnp.asarray(spec.sqrt_sizes(), jnp.float32)
    lb, _, rounds, stats = slv._solve_jit(C, a, b, row_mask, sqrt_g, prob,
                                          lay.plan.solve_options())
    _, check, active = (int(v) for v in stats)
    return {"lbfgs_iters": int(lb.iter), "evals": int(lb.n_evals),
            "rounds": int(rounds), "live_blocks": check + active}


def run(r) -> None:
    cfg, tr = r.config, r.traffic
    L, g, n = cfg["num_classes"], cfg["samples_per_class"], cfg["num_target"]
    m, K, S, ahead = L * g, int(tr["pool"]), int(tr["sample"]), int(tr["ahead"])
    pool = data.costs(data.device_seed(r.seed), L=L, g=g, dim=cfg["dim"],
                      shift=float(tr["shift"]), count=K)
    a = jnp.full((m,), 1.0 / m, jnp.float32)
    b = jnp.full((n,), 1.0 / n, jnp.float32)
    lay = layer(cfg, r.precision)
    step = jax.jit(jax.value_and_grad(lay))
    jax.block_until_ready(step(pool[0]))               # compiles (or loads) the program
    r.setup_done()

    # a reservoir of S steps, drawn from the seed, to check after the window
    rng = np.random.default_rng([r.seed, 1])
    sample, flight = [], deque()
    with r.window() as w:
        i = 0
        while not w.over():
            with r.span("bench.dispatch"):
                out = step(pool[i % K])
            flight.append(out)
            if len(sample) < S:
                sample.append((i, out))
            else:
                j = int(rng.integers(0, i + 1))
                if j < S:
                    sample[j] = (i, out)
            i += 1
            if len(flight) >= ahead:
                with r.span("bench.wait"):
                    jax.block_until_ready(flight.popleft())
        with r.span("bench.drain"):
            jax.block_until_ready(list(flight))
    del out, flight
    steps = i
    r.attempted = steps
    r.metrics["gs_loss_step_s"] = r.window_s / steps
    r.facts["memory_peak_bytes"] = r.memory_peak_bytes()
    r.facts["steps"] = steps
    if r.trace:
        counts = [solver_counts(lay, C, a, b) for C in pool]
        for key in counts[0]:
            r.facts[key] = sum(c[key] for c in counts) / K
        # the window ran cost i % K in its step i: (steps, counts) per cost
        r.facts["window_solves"] = [(steps // K + (k < steps % K), c)
                                    for k, c in enumerate(counts)]

    # the reference, on the cost of each sampled step
    t0 = time.perf_counter()
    reg = reference_gs.from_rho(L, g, cfg["gamma"], cfg["rho"])
    a64, b64 = np.full(m, 1.0 / m), np.full(n, 1.0 / n)
    readings, refs = {}, {}
    for i, (value, T) in sorted(sample, key=lambda s: s[0]):
        k = i % K
        if k not in refs:
            refs[k] = reference_gs.optimum(pool[k], a, b, reg=reg,
                                           max_iters=cfg["reference"]["max_iters"])
            # how far the reference itself lies from its optimality test
            r.facts["ref_residual"] = max(r.facts.get("ref_residual", 0.0),
                                          float(refs[k].residual))
            r.facts["ref_iters"] = max(r.facts.get("ref_iters", 0), int(refs[k].iters))
        for key, v in compare(value, T, refs[k], a64, b64).items():
            readings.setdefault(key, []).append(v)
    r.facts["check_s"] = time.perf_counter() - t0
    r.failed = int(sum(readings["nonfinite"]))
    for key, vs in readings.items():
        v = float(np.max(vs))              # the worst step; a NaN stays a NaN
        if key in r.limits:
            r.check(key, v, r.limits[key])
        else:
            r.facts[key] = v
