"""Algorithm 1 of the paper: screened L-BFGS for the group-sparse OT dual.

Outer loop (rounds): run the solver for ``r`` iterations with the current
screen state frozen  ->  refresh the active set N from lower bounds
(Definition 3)  ->  take new snapshots (Definition 1/2)  ->  repeat until the
solver converges.

The gradient oracle inside a round evaluates, per Algorithm 2:
  * ACTIVE entries (in N): exact gradient, no bound check,
  * other entries: Eq. 6 upper bound; ZERO-certified blocks are skipped
    (exact zeros), the rest computed exactly.

``grad_impl`` selects the execution backend:
  'dense'     original (unscreened) method — the paper's "origin",
  'screened'  screening with masked XLA ops (accounting-exact reference),
  'pallas'    the block-masked Pallas kernels from repro.kernels
              (two launches per evaluation: screen, then gradient),
  'fused'     the single-launch mega-kernel — verdicts computed
              in-register inside the gradient grid step (DESIGN.md §10).

By Theorem 2 all backends return identical objective values and iterates
(screening only ever zeroes provably-zero entries); tests assert this.

Batching: the dual is separable over problems, so B same-shape problems
solve in ONE jitted program — every array carries a leading B axis, the
L-BFGS segment masks per-problem convergence (``core.lbfgs``), and the
screening state is per-problem.  :func:`solve_dual` is the B = 1 slice of
:func:`solve_batch`; because both run the identical batched op sequence,
a problem solved solo and the same problem solved inside a batch produce
bitwise-identical iterates (asserted by tests/test_solve_batch.py).  The
round-step API (:func:`init_batch_state` / :func:`batch_round`) exposes
one fused round per call for the OT serving engine
(``repro.serving.ot_engine``), which retires converged problems and
recycles their slots between rounds.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import screening
from repro.core.dual import (
    DualProblem,
    dual_value_and_grad,
    plan_from_duals,
    snapshot_norms,
)
from repro.core.groups import GroupSpec
from repro.core.lbfgs import (
    LbfgsOptions,
    LbfgsState,
    init_state_batched,
    run_segment_batched,
    where_state,
)
from repro.core.regularizers import Regularizer


@dataclasses.dataclass(frozen=True)
class SolveOptions:
    """Static solver configuration (jitted programs specialize on it).

    Parameters
    ----------
    snapshot_every : int
        ``r`` in Algorithm 1 — L-BFGS iterations per screening round.
    max_rounds : int
        Cap on the number of rounds (``s_r``).
    grad_impl : {'dense', 'screened', 'pallas', 'fused'}
        Gradient oracle backend: the paper's unscreened origin, the
        masked-XLA screened reference, the two-launch Pallas pipeline
        (screen kernel -> gradient kernel), or the fused single-launch
        mega-kernel (verdicts computed in-register, DESIGN.md §10).
    pallas_impl : {'grid', 'compact', 'auto'}
        Kernel grid mode for ``grad_impl='pallas'`` (see kernels/ops.py).
        For ``grad_impl='fused'``: 'grid' is the fused dense grid,
        'compact' the two-launch reference, 'auto' a runtime switch on the
        snapshot-point live-tile density.
    tight_active_refresh : bool
        Beyond-paper tighter active-set refresh (off for paper fidelity).
    precision : {'f32', 'bf16'}
        Cost-operand storage precision for the pallas/fused backends:
        'bf16' stores the prepared cost (or factorized sample blocks) in
        bfloat16 while every kernel still upcasts on load and accumulates
        T/psi in f32.  Screening snapshots are taken against the SAME
        bf16-rounded cost, so the Eq. 6 bounds stay exactly safe w.r.t.
        the cost the gradient actually sees (docs/geometry.md numerics
        policy).  Rejected for the dense/screened reference backends.
    lbfgs : LbfgsOptions
        Inner optimizer configuration.
    """

    snapshot_every: int = 10          # r in Algorithm 1
    max_rounds: int = 200             # cap on s_r
    grad_impl: str = "screened"       # 'dense' | 'screened' | 'pallas' | 'fused'
    pallas_impl: str = "auto"         # 'grid' | 'compact' | 'auto': kernel
    #   grid mode for grad_impl='pallas'/'fused' (see kernels/ops.py docstring)
    tight_active_refresh: bool = False  # beyond-paper: refresh N *after* the
    #   snapshot update (Delta = 0 => lower bound k~ - o~, strictly tighter
    #   than Eq. 7 evaluated pre-update; N stays a performance hint so
    #   exactness is unaffected).  Off by default for paper fidelity.
    precision: str = "f32"            # 'f32' | 'bf16' cost-operand storage
    #   (pallas/fused only; accumulation is always f32)
    lbfgs: LbfgsOptions = dataclasses.field(default_factory=LbfgsOptions)


# host->device program launches issued through this module's public entry
# points (one per jitted call).  The batched solver's whole point is that a
# B-problem solve is ONE launch instead of B; tests assert the ratio here.
_DISPATCHES = {"count": 0}


def dispatch_count() -> int:
    """Number of jitted-program launches since :func:`reset_dispatch_count`."""
    return _DISPATCHES["count"]


def reset_dispatch_count() -> None:
    """Zero the launch counter (tests / benchmarks bracket work with it)."""
    _DISPATCHES["count"] = 0


def _launch(fn, *args):
    _DISPATCHES["count"] += 1
    return fn(*args)


class OTResult:
    """Solution container (host-side convenience wrapper).

    Attributes
    ----------
    alpha : jnp.ndarray
        ``(m_pad,)`` optimal source duals (padded layout).
    beta : jnp.ndarray
        ``(n,)`` optimal target duals.
    value : jnp.ndarray
        Scalar dual objective at the solution (maximization sign).
    lbfgs_state : LbfgsState
        Final optimizer state (iterates, history, convergence flags).
    screen_state : ScreenState
        Final screening snapshots + active set.
    rounds : int
        Algorithm-1 rounds run.
    stats : dict
        Accumulated screening verdict counts ``{'zero','check','active'}``.
    """

    def __init__(self, alpha, beta, value, state, screen_state, rounds, stats):
        self.alpha = alpha
        self.beta = beta
        self.value = value
        self.lbfgs_state = state
        self.screen_state = screen_state
        self.rounds = rounds
        self.stats = stats

    @property
    def iterations(self):
        """Total L-BFGS iterations taken."""
        return int(self.lbfgs_state.iter)

    @property
    def n_evals(self):
        """Total value_and_grad oracle evaluations."""
        return int(self.lbfgs_state.n_evals)

    @property
    def converged(self):
        """Whether the dual solve converged (vs. failed / hit caps)."""
        return bool(self.lbfgs_state.converged)


class BatchOTResult:
    """Batched solution container: B independent problems, one solve.

    ``result[i]`` materializes the i-th problem as a solo :class:`OTResult`
    (leaf slicing only; no recomputation).
    """

    def __init__(self, alpha, beta, values, lb, scr, rounds, stats):
        self.alpha = alpha              # (B, m_pad)
        self.beta = beta                # (B, n)
        self.values = values            # (B,)
        self.lbfgs_state = lb           # batched leaves
        self.screen_state = scr         # batched leaves
        self.rounds = rounds            # (B,) int
        self.stats = stats              # (B, 3) int [zero, check, active]

    def __len__(self):
        return int(self.alpha.shape[0])

    @property
    def converged(self):
        """``(B,)`` bool — per-problem convergence flags."""
        return self.lbfgs_state.converged

    def __getitem__(self, i: int) -> OTResult:
        sl = lambda t: jax.tree_util.tree_map(lambda v: v[i], t)
        stats = {
            "zero": int(self.stats[i, 0]),
            "check": int(self.stats[i, 1]),
            "active": int(self.stats[i, 2]),
        }
        return OTResult(
            self.alpha[i], self.beta[i], self.values[i],
            sl(self.lbfgs_state), sl(self.screen_state),
            int(self.rounds[i]), stats,
        )


class BatchSolveState(NamedTuple):
    """Device-side state of a batch of solves between rounds."""

    lb: LbfgsState                  # batched L-BFGS state
    scr: screening.ScreenState      # batched screening state
    rounds: jnp.ndarray             # (B,) int32 rounds each problem ran
    stats: jnp.ndarray              # (B, 3) int32 [zero, check, active]


def _split(x: jnp.ndarray, m_pad: int):
    return x[..., :m_pad], x[..., m_pad:]


def make_value_and_grad(
    C: jnp.ndarray,
    a: jnp.ndarray,
    b: jnp.ndarray,
    prob: DualProblem,
    sqrt_g: jnp.ndarray,
    grad_impl: str,
    screen_state: Optional[screening.ScreenState],
    padded=None,                       # kernels.ops.PaddedProblem (pallas)
    pallas_impl: str = "auto",
):
    """Build the (negated, minimized) value_and_grad oracle for L-BFGS.

    Single-problem variant (x is (m_pad + n,)): used by the distributed
    driver and the roofline lowering.  The solver's own loop uses
    :func:`make_value_and_grad_batched`.
    """
    m_pad = prob.m_pad

    if grad_impl == "dense":
        _reject_factorized(C, grad_impl)

        def vag(x):
            alpha, beta = _split(x, m_pad)
            v, (ga, gb) = dual_value_and_grad(alpha, beta, C, a, b, prob)
            return -v, -jnp.concatenate([ga, gb])

        return vag

    if grad_impl == "screened":
        assert screen_state is not None
        _reject_factorized(C, grad_impl)

        def vag(x):
            alpha, beta = _split(x, m_pad)
            verdict = screening.verdicts(
                screen_state, alpha, beta, sqrt_g, prob.tau_vec()
            )
            zero_mask = verdict == screening.ZERO
            v, (ga, gb) = dual_value_and_grad(
                alpha, beta, C, a, b, prob, zero_mask=zero_mask
            )
            return -v, -jnp.concatenate([ga, gb])

        return vag

    if grad_impl in ("pallas", "fused"):
        assert screen_state is not None
        from repro.kernels import ops as kops

        pp = padded
        if pp is None:
            pp = (
                kops.prepare_factorized_problem(C, prob)
                if _is_factorized(C)
                else kops.prepare_padded_problem(C, prob)
            )
        pstate = kops.pad_screen_state(screen_state, sqrt_g, pp)

        if grad_impl == "fused":
            # single-launch oracle: verdicts computed in-register inside the
            # gradient grid step (DESIGN.md §10); no standalone screen pass.
            def vag(x):
                alpha, beta = _split(x, m_pad)
                v, ga, gb = kops.dual_value_and_grad_fused(
                    alpha, beta, a, b, pstate, pp, prob, impl=pallas_impl
                )
                return -v, -jnp.concatenate([ga, gb])

            return vag

        grad_fn = (
            kops.dual_value_and_grad_factorized
            if isinstance(pp, kops.FactorizedProblem)
            else kops.dual_value_and_grad_padded
        )

        def vag(x):
            alpha, beta = _split(x, m_pad)
            flags = kops.screen_tile_flags(
                pstate, alpha, beta, pp, prob.tau_vec()
            )
            v, ga, gb = grad_fn(
                alpha, beta, a, b, flags, pp, prob, impl=pallas_impl
            )
            return -v, -jnp.concatenate([ga, gb])

        return vag

    raise ValueError(f"unknown grad_impl: {grad_impl}")


def make_value_and_grad_batched(
    C: jnp.ndarray,                    # (B, m_pad, n)
    a: jnp.ndarray,                    # (B, m_pad)
    b: jnp.ndarray,                    # (B, n)
    prob: DualProblem,
    sqrt_g: jnp.ndarray,               # (L,) shared or (B, L) per problem
    grad_impl: str,
    screen_state: Optional[screening.ScreenState],   # batched leaves
    padded=None,                       # kernels.ops.PaddedProblem (B, ...) Cp
    pallas_impl: str = "auto",
):
    """Batched oracle: x (B, m_pad + n) -> ((B,) value, (B, d) grad).

    For the pallas impl the batched screening state is padded to the kernel
    grid HERE — once per snapshot round — so each evaluation only computes
    the O(B (L + n)) delta norms, runs the vmapped screening kernel for
    per-problem tile flags, and feeds them straight to the batched gradient
    kernel (one dynamic grid over the batch's concatenated active tiles in
    compact mode).
    """
    m_pad = prob.m_pad

    if grad_impl == "dense":
        _reject_factorized(C, grad_impl)

        def vag(x):
            alpha, beta = _split(x, m_pad)
            v, (ga, gb) = dual_value_and_grad(alpha, beta, C, a, b, prob)
            return -v, -jnp.concatenate([ga, gb], axis=-1)

        return vag

    if grad_impl == "screened":
        assert screen_state is not None
        _reject_factorized(C, grad_impl)

        def vag(x):
            alpha, beta = _split(x, m_pad)
            verdict = screening.verdicts(
                screen_state, alpha, beta, sqrt_g, prob.tau_vec()
            )
            zero_mask = verdict == screening.ZERO
            v, (ga, gb) = dual_value_and_grad(
                alpha, beta, C, a, b, prob, zero_mask=zero_mask
            )
            return -v, -jnp.concatenate([ga, gb], axis=-1)

        return vag

    if grad_impl in ("pallas", "fused"):
        assert screen_state is not None
        from repro.kernels import ops as kops

        B = C.shape[0]
        pp = padded
        if pp is None:
            pp = (
                kops.prepare_factorized_problem(C, prob)
                if _is_factorized(C)
                else kops.prepare_padded_problem_batched(C, prob)
            )
        sqb = jnp.broadcast_to(sqrt_g, (B, prob.num_groups))
        pstate = kops.pad_screen_state_batched(screen_state, sqb, pp)

        if grad_impl == "fused":
            def vag(x):
                alpha, beta = _split(x, m_pad)
                v, ga, gb = kops.dual_value_and_grad_fused_batched(
                    alpha, beta, a, b, pstate, pp, prob, impl=pallas_impl
                )
                return -v, -jnp.concatenate([ga, gb], axis=-1)

            return vag

        grad_fn = (
            kops.dual_value_and_grad_factorized_batched
            if isinstance(pp, kops.FactorizedProblem)
            else kops.dual_value_and_grad_padded_batched
        )

        def vag(x):
            alpha, beta = _split(x, m_pad)
            flags = kops.screen_tile_flags_batched(
                pstate, alpha, beta, pp, prob.tau_vec()
            )
            v, ga, gb = grad_fn(
                alpha, beta, a, b, flags, pp, prob, impl=pallas_impl
            )
            return -v, -jnp.concatenate([ga, gb], axis=-1)

        return vag

    raise ValueError(f"unknown grad_impl: {grad_impl}")


def _is_factorized(C) -> bool:
    """True when the cost operand is a materialization-free FactorizedCost."""
    from repro.kernels import ops as kops

    return isinstance(C, kops.FactorizedCost)


def _reject_factorized(C, grad_impl: str) -> None:
    """Trace-time guard: only the pallas backend lowers factorized costs.

    The facade's executor materializes the cost (chunked) before routing a
    factorized geometry to the dense/screened reference backends, so this
    is reached only by callers bypassing the executor.
    """
    if _is_factorized(C):
        raise TypeError(
            f"grad_impl='{grad_impl}' cannot consume a FactorizedCost; use "
            "grad_impl='pallas' or materialize the geometry first "
            "(SquaredL2Geometry.materialize)."
        )


def _snapshot_norms_any(alpha, beta, C, prob, row_mask, padded,
                        precision="f32"):
    """Eq. 6 snapshot norms for either cost representation.

    Dense costs use the closed-form ``dual.snapshot_norms``; factorized
    costs run the materialization-free Pallas snapshot kernel against the
    prepared :class:`~repro.kernels.ops.FactorizedProblem` (building one on
    the fly if the caller had no pallas preparation).

    ``precision='bf16'`` rounds the dense cost through bfloat16 first so
    the snapshot bounds describe EXACTLY the cost the kernels integrate
    (``_prepare_padded`` stored ``Cp`` in bf16) — screening correctness is
    then exact with respect to the rounded problem, not approximate with
    respect to the f32 one.  The factorized route is consistent for free:
    the snapshot kernel reads the same (possibly bf16) prepared leaves.
    """
    if _is_factorized(C):
        from repro.kernels import ops as kops

        fp = padded
        if fp is None:
            fp = kops.prepare_factorized_problem(C, prob)
        return kops.snapshot_norms_factorized(alpha, beta, fp, prob, row_mask)
    if precision == "bf16":
        C = C.astype(jnp.bfloat16).astype(C.dtype)
    return snapshot_norms(alpha, beta, C, prob, row_mask)


def _prepare_padded(C, prob, opts):
    """One-time padded-problem preparation for the pallas/fused backends.

    The padded copy of C (the largest array in the problem) is made once
    per solve / per engine round, outside the L-BFGS evaluation loop.
    Factorized costs get a tile-padded :class:`FactorizedProblem` instead
    — no (m, n) array is ever built.

    ``opts.precision == 'bf16'`` downcasts the prepared cost operands
    (``Cp`` or the factorized ``x/x_sq/y/y_sq`` blocks) to bfloat16 HERE,
    once, so every downstream consumer — snapshot norms, screening bounds,
    and the gradient kernels — sees the SAME rounded cost.  Kernels upcast
    on load and accumulate T/psi in f32 (docs/api.md "precision").
    """
    if opts.grad_impl not in ("pallas", "fused"):
        if opts.precision != "f32":
            raise ValueError(
                "precision='bf16' requires grad_impl='pallas' or 'fused' "
                f"(got grad_impl={opts.grad_impl!r}); the dense/screened "
                "reference backends are f32-only."
            )
        return None
    from repro.kernels import ops as kops

    if _is_factorized(C):
        fp = kops.prepare_factorized_problem(C, prob)
        if opts.precision == "bf16":
            fp = dataclasses.replace(
                fp,
                x=fp.x.astype(jnp.bfloat16),
                x_sq=fp.x_sq.astype(jnp.bfloat16),
                y=fp.y.astype(jnp.bfloat16),
                y_sq=fp.y_sq.astype(jnp.bfloat16),
            )
        return fp
    pp = kops.prepare_padded_problem_batched(C, prob)
    if opts.precision == "bf16":
        pp = dataclasses.replace(pp, Cp=pp.Cp.astype(jnp.bfloat16))
    return pp


def _init_batch_state(C, a, b, row_mask, sqrt_g, prob, opts, padded):
    """Initial BatchSolveState: valid snapshots + first oracle evaluation."""
    B = C.shape[0]
    m_pad, n, L = prob.m_pad, prob.n, prob.num_groups
    x0 = jnp.zeros((B, m_pad + n), C.dtype)

    screen0 = screening.init_state(m_pad, n, L, C.dtype, batch_shape=(B,))
    # valid snapshots at the init point (alpha = beta = 0)
    z0, k0, o0 = _snapshot_norms_any(
        jnp.zeros((B, m_pad), C.dtype), jnp.zeros((B, n), C.dtype),
        C, prob, row_mask, padded, opts.precision,
    )
    screen0 = screening.take_snapshot(
        screen0, x0[..., :m_pad], x0[..., m_pad:], z0, k0, o0
    )

    vag0 = make_value_and_grad_batched(
        C, a, b, prob, sqrt_g, opts.grad_impl, screen0,
        padded=padded, pallas_impl=opts.pallas_impl,
    )
    lb0 = init_state_batched(x0, vag0, opts.lbfgs)
    return BatchSolveState(
        lb=lb0,
        scr=screen0,
        rounds=jnp.zeros((B,), jnp.int32),
        stats=jnp.zeros((B, 3), jnp.int32),
    )


def _round_body(state, C, a, b, row_mask, sqrt_g, prob, opts, padded):
    """One Algorithm-1 round over the whole batch, frozen problems masked.

    A problem alive at round start runs the full round (segment + screening
    refresh + snapshot), even if it converges mid-segment — exactly the
    rounds a solo solve of that problem would run.  Problems finished
    before the round keep their state bit-for-bit.
    """
    lb, scr, rounds, stats = state
    m_pad = prob.m_pad
    alive = jnp.logical_and(~lb.converged, ~lb.failed)      # (B,)

    vag = make_value_and_grad_batched(
        C, a, b, prob, sqrt_g, opts.grad_impl, scr,
        padded=padded, pallas_impl=opts.pallas_impl,
    )
    lb = run_segment_batched(vag, lb, opts.snapshot_every, opts.lbfgs)

    alpha, beta = _split(lb.x, m_pad)

    if opts.grad_impl != "dense":
        if not opts.tight_active_refresh:
            # paper order: refresh N w.r.t. OLD snapshots (Eq. 7), then
            # take the new snapshot (Algorithm 1 lines 6-15).
            scr_new = screening.refresh_active(
                scr, alpha, beta, sqrt_g, prob.tau_vec()
            )
            z, k, o = _snapshot_norms_any(alpha, beta, C, prob, row_mask,
                                          padded, opts.precision)
            scr_new = screening.take_snapshot(scr_new, alpha, beta, z, k, o)
        else:
            # beyond-paper: snapshot first => Delta = 0 => lower bound
            # becomes k~ - o~ exactly (Theorem 4's fixed point), tighter N.
            z, k, o = _snapshot_norms_any(alpha, beta, C, prob, row_mask,
                                          padded, opts.precision)
            scr_new = screening.take_snapshot(scr, alpha, beta, z, k, o)
            scr_new = screening.refresh_active(
                scr_new, alpha, beta, sqrt_g, prob.tau_vec()
            )
        verdict = screening.verdicts(
            scr_new, alpha, beta, sqrt_g, prob.tau_vec()
        )
        delta = jnp.stack(
            [
                jnp.sum(verdict == screening.ZERO, axis=(-2, -1)),
                jnp.sum(verdict == screening.CHECK, axis=(-2, -1)),
                jnp.sum(verdict == screening.ACTIVE, axis=(-2, -1)),
            ],
            axis=-1,
        ).astype(jnp.int32)
        scr = where_state(alive, scr_new, scr)
        stats = stats + jnp.where(alive[:, None], delta, 0)

    rounds = rounds + alive.astype(jnp.int32)
    return BatchSolveState(lb=lb, scr=scr, rounds=rounds, stats=stats)


def _solve_batch_impl(C, a, b, row_mask, sqrt_g, prob, opts):
    padded = _prepare_padded(C, prob, opts)
    st0 = _init_batch_state(C, a, b, row_mask, sqrt_g, prob, opts, padded)

    def cond(carry):
        st, rnd = carry
        alive = jnp.logical_and(~st.lb.converged, ~st.lb.failed)
        return jnp.logical_and(rnd < opts.max_rounds, jnp.any(alive))

    def body(carry):
        st, rnd = carry
        st = _round_body(st, C, a, b, row_mask, sqrt_g, prob, opts, padded)
        return (st, rnd + 1)

    st, _ = jax.lax.while_loop(cond, body, (st0, jnp.zeros((), jnp.int32)))
    return st.lb, st.scr, st.rounds, st.stats


@functools.partial(jax.jit, static_argnames=("prob", "opts"))
def _solve_batch_jit(C, a, b, row_mask, sqrt_g, prob, opts):
    """One program: solve B same-shape problems to convergence."""
    return _solve_batch_impl(C, a, b, row_mask, sqrt_g, prob, opts)


@functools.partial(jax.jit, static_argnames=("prob", "opts"))
def _solve_jit(
    C: jnp.ndarray,
    a: jnp.ndarray,
    b: jnp.ndarray,
    row_mask: jnp.ndarray,
    sqrt_g: jnp.ndarray,
    prob: DualProblem,
    opts: SolveOptions,
):
    """Single-problem entry point: the B = 1 slice of the batched solver.

    Kept for the distributed driver (GSPMD shards the unbatched operands)
    and any caller wanting unbatched outputs; returns (lb, scr, rounds,
    stats) with unbatched leaves and a scalar round count.
    """
    C1 = jax.tree_util.tree_map(lambda v: v[None], C)
    lb, scr, rounds, stats = _solve_batch_impl(
        C1, a[None], b[None], row_mask, sqrt_g, prob, opts
    )
    one = lambda t: jax.tree_util.tree_map(lambda v: v[0], t)
    return one(lb), one(scr), rounds[0], stats[0]


@functools.partial(jax.jit, static_argnames=("prob", "opts"))
def init_batch_state(C, a, b, row_mask, sqrt_g, prob, opts, padded=None):
    """Jitted initial state for the round-step API (one launch).

    ``row_mask`` / ``sqrt_g`` may be shared ((m_pad,) / (L,)) or per-problem
    ((B, m_pad) / (B, L)) — the serving engine packs problems with
    different true group sizes into one bucket.  ``padded`` may carry a
    pre-built batched PaddedProblem (pallas backend) so long-lived callers
    like the serving engine don't re-pad C per call.
    """
    if padded is None:
        padded = _prepare_padded(C, prob, opts)
    return _init_batch_state(C, a, b, row_mask, sqrt_g, prob, opts, padded)


@functools.partial(jax.jit, static_argnames=("prob", "opts"))
def batch_round(state, C, a, b, row_mask, sqrt_g, prob, opts, padded=None):
    """Jitted single round over the batch (one launch per engine tick).

    ``padded`` as in :func:`init_batch_state` — the engine passes its
    cached copy so the (largest-array) re-pad doesn't run every tick, or
    None when padding would not change the cost.
    """
    if padded is None:
        padded = _prepare_padded(C, prob, opts)
    return _round_body(state, C, a, b, row_mask, sqrt_g, prob, opts, padded)


def _solve_solo(C, a, b, spec, reg, opts, launch) -> OTResult:
    """Shared solo-solve body: operand construction, launch, packing.

    ``launch`` is the launcher wrapper — the module-level :func:`_launch`
    for :func:`solve_dual`, or an ``Executor._launch`` bound method so the
    façade counts the program against its own stats.  Keeping ONE copy of
    this op sequence is what makes ``Executor.solve`` bitwise-identical
    to ``solve_dual`` by construction.
    """
    prob = DualProblem(
        num_groups=spec.num_groups,
        group_size=spec.group_size,
        n=int(C.shape[1]),
        reg=reg,
    )
    row_mask = jnp.asarray(spec.row_mask().reshape(-1))
    sqrt_g = jnp.asarray(spec.sqrt_sizes(), C.dtype)

    lb, scr, rounds, stats = launch(
        _solve_jit, C, a, b, row_mask, sqrt_g, prob, opts
    )
    alpha, beta = _split(lb.x, prob.m_pad)
    stats_dict = {
        "zero": int(stats[0]),
        "check": int(stats[1]),
        "active": int(stats[2]),
    }
    return OTResult(alpha, beta, -lb.f, lb, scr, int(rounds), stats_dict)


def solve_dual(
    C: jnp.ndarray,
    a: jnp.ndarray,
    b: jnp.ndarray,
    spec: GroupSpec,
    reg: Regularizer,
    opts: SolveOptions = SolveOptions(),
) -> OTResult:
    """Solve the group-sparse OT dual on padded inputs (one problem).

    The B = 1 slice of :func:`solve_batch` — identical op sequence, so a
    problem solved solo matches the same problem inside any batch bitwise.

    Parameters
    ----------
    C : jnp.ndarray
        ``(m_pad, n)`` float32 padded cost matrix (see
        :func:`repro.core.groups.pad_cost_matrix`).
    a : jnp.ndarray
        ``(m_pad,)`` padded source marginal (zero mass on padded rows).
    b : jnp.ndarray
        ``(n,)`` target marginal.
    spec : GroupSpec
        Group layout of the padded rows.
    reg : Regularizer
        Regularizer (group-sparse, pure-l2, or elastic-net; see
        :mod:`repro.core.regularizers`).
    opts : SolveOptions, optional
        Backend and schedule configuration.

    Returns
    -------
    OTResult
        Optimal duals, objective, final solver/screening state, stats.
    """
    return _solve_solo(C, a, b, spec, reg, opts, _launch)


def solve_batch(
    C: jnp.ndarray,
    a: jnp.ndarray,
    b: jnp.ndarray,
    spec: GroupSpec,
    reg: Regularizer,
    opts: SolveOptions = SolveOptions(),
) -> BatchOTResult:
    """Solve B same-shape group-sparse OT problems in ONE jitted program.

    All problems share the group layout ``spec`` and regularizer ``reg``
    (the static geometry the program is compiled for); marginals and
    costs vary freely.  Per problem the result is bitwise-identical to
    :func:`solve_dual` on the same inputs: the batch axis only adds a
    leading dim to every op, and converged problems freeze via masking
    rather than early exit.  For the multi-device variant see
    :func:`repro.core.sharded.solve_batch_sharded`.

    Parameters
    ----------
    C : jnp.ndarray
        ``(B, m_pad, n)`` float32 padded cost matrices.
    a : jnp.ndarray
        ``(B, m_pad)`` padded source marginals.
    b : jnp.ndarray
        ``(B, n)`` target marginals.
    spec : GroupSpec
        Shared group layout.
    reg : Regularizer
        Regularizer (any :class:`~repro.core.regularizers.Regularizer`).
    opts : SolveOptions, optional
        Backend and schedule configuration.

    Returns
    -------
    BatchOTResult
        Batched result; ``result[i]`` views problem i as an OTResult.

    .. deprecated:: use :meth:`repro.ot.Executor.solve_many` — this shim
       delegates there and emits a ``DeprecationWarning``.
    """
    import warnings

    warnings.warn(
        "solve_batch() is deprecated; use repro.ot "
        "(compile(...).solve_many) instead",
        DeprecationWarning, stacklevel=2,
    )
    assert C.ndim == 3, f"solve_batch expects (B, m_pad, n) costs, got {C.shape}"
    from repro.ot.executor import Executor
    from repro.ot.plan import ExecutionPlan

    ex = Executor(spec, int(C.shape[2]), reg, ExecutionPlan.from_solve_options(opts))
    lb, scr, rounds, stats = ex._solve_padded_batch(C, a, b)
    alpha, beta = _split(lb.x, ex._prob.m_pad)
    return BatchOTResult(alpha, beta, -lb.f, lb, scr, rounds, stats)


def recover_plan(result: OTResult, C: jnp.ndarray, spec: GroupSpec, reg: Regularizer):
    """Primal plan T* = grad psi(alpha* + beta_j* 1 - c_j) (padded rows incl.)."""
    prob = DualProblem(spec.num_groups, spec.group_size, int(C.shape[1]), reg)
    return plan_from_duals(result.alpha, result.beta, C, prob)


def recover_plan_batch(
    result: BatchOTResult, C: jnp.ndarray, spec: GroupSpec, reg: Regularizer
):
    """Batched primal plans (B, m_pad, n) from a :class:`BatchOTResult`."""
    prob = DualProblem(spec.num_groups, spec.group_size, int(C.shape[2]), reg)
    return plan_from_duals(result.alpha, result.beta, C, prob)


def describe(
    spec: GroupSpec,
    n: int,
    reg: Regularizer,
    opts: SolveOptions = SolveOptions(),
    result=None,
) -> str:
    """One diagnostic block: padded geometry, tile counts, live density.

    Docs examples and bug reports print this so everyone looks at the
    same numbers (see also the compact ``repr`` of :class:`GroupSpec` and
    ``ScreenState``).

    Parameters
    ----------
    spec : GroupSpec
        Group layout of the (padded) problem.
    n : int
        Number of target columns.
    reg : Regularizer
        Regularizer (any :class:`~repro.core.regularizers.Regularizer`).
    opts : SolveOptions, optional
        Shown so reports pin down the backend that ran.
    result : OTResult or BatchOTResult, optional
        When given, appends convergence and screening-verdict totals —
        the live-density line is the fraction of gradient blocks the
        screened oracle actually computed over the whole solve.

    Returns
    -------
    str
        A multi-line human-readable report.
    """
    from repro.kernels.gradpsi import DEFAULT_TILE_N, resolve_tile_l

    prob = DualProblem(spec.num_groups, spec.group_size, int(n), reg)
    tile_l = resolve_tile_l(
        prob.num_groups, prob.group_size, DEFAULT_TILE_N, 4
    )
    L_pad, n_pad = prob.tile_padded_shape(tile_l, DEFAULT_TILE_N)
    lt, nt = L_pad // tile_l, n_pad // DEFAULT_TILE_N
    lines = [
        f"problem:  {spec!r}",
        f"dual:     m_pad={prob.m_pad} n={prob.n} "
        f"(x dim {prob.m_pad + prob.n}), reg={reg!r} "
        f"(kind={type(reg).kind}, tau_max={reg.tau_max:g})",
        f"tiles:    ({tile_l} groups x {DEFAULT_TILE_N} cols) grid "
        f"{lt} x {nt} = {lt * nt} tiles "
        f"(L padded {prob.num_groups}->{L_pad}, n padded {prob.n}->{n_pad})",
        f"backend:  grad_impl={opts.grad_impl} pallas_impl={opts.pallas_impl} "
        f"precision={opts.precision} snapshot_every={opts.snapshot_every}",
    ]
    if result is not None:
        if isinstance(result.stats, dict):
            zero = result.stats["zero"]
            check = result.stats["check"]
            act = result.stats["active"]
            conv, rounds = result.converged, result.rounds
        else:
            import numpy as _np

            s = _np.asarray(result.stats)
            zero, check, act = (int(v) for v in s.sum(axis=0))
            conv = bool(jnp.all(result.converged))
            rounds = int(jnp.sum(result.rounds))
        total = max(zero + check + act, 1)
        lines += [
            f"solve:    rounds={rounds} converged={conv}",
            f"verdicts: zero={zero} check={check} active={act} "
            f"-> live density {(check + act) / total:.1%}",
        ]
    return "\n".join(lines)
