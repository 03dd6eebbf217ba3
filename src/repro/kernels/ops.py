"""Jit'd public wrappers around the Pallas kernels.

Handles: interpret-mode selection (CPU backend -> interpret=True; TPU ->
compiled Mosaic; any other backend is an error), padding of L and n up to
tile multiples, and assembling kernel partials into the (value,
grad_alpha, grad_beta) triple the solver consumes.  Padded tiles are marked skipped in the flag matrix, so they cost
nothing and contribute exact zeros.

The hot path is structured around two prepared states (DESIGN.md §4):

  * :class:`PaddedProblem` — the tile-padded cost matrix plus geometry,
    built ONCE per solve by :func:`prepare_padded_problem` (previously every
    gradient evaluation re-padded and copied C, the largest array in the
    problem).
  * :class:`PaddedScreenState` — tile-padded screening snapshots, built once
    per snapshot round by :func:`pad_screen_state`; per evaluation only the
    O(L + n) delta-norm vectors are computed and fed to the fused screening
    kernel, which hands tile flags straight to the gradient kernel without
    materializing the (L, n) verdict matrix in HBM.

Gradient execution mode (``impl``):
  'grid'     dense (L_tiles, N_tiles) grid, skipped tiles elide DMA/compute,
  'compact'  dynamic grid over the compacted surviving-tile list,
  'auto'     runtime switch on surviving-tile density
             (<= COMPACT_DENSITY_THRESHOLD -> compact).

Batched entry points (``*_batched``) mirror the solo ones with a leading
problem axis B (same-shape problems): one prepared (B, ...) cost matrix,
per-problem screening snapshots, fused per-problem flag grids (the screen
kernel vmaps over B), and a gradient dispatch whose compact mode runs ONE
dynamic grid over the whole batch's concatenated surviving tiles.  These
feed ``core.solver.solve_batch`` and the OT serving engine.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import screening
from repro.core.dual import DualProblem
from repro.core.screening import ScreenState
from repro.kernels.gradpsi import (
    COMPACT_DENSITY_THRESHOLD,
    DEFAULT_TILE_N,
    build_batch_tile_schedule,
    build_tile_schedule,
    gradpsi_pallas,
    gradpsi_pallas_batched,
    gradpsi_pallas_compact,
    gradpsi_pallas_compact_batched,
    resolve_tile_l,
    resolve_tile_l_factorized,
    tau_row,
)
from repro.kernels.screen import screen_pallas


def default_interpret() -> bool:
    """Pallas interpret mode on the CPU backend, compiled Mosaic on a TPU.

    Any other backend raises: the kernels are Mosaic TPU kernels, and
    silently interpreting them elsewhere would hide that the device path
    never ran.
    """
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels need a TPU (compiled) or the CPU (interpreted); "
        f"the default JAX backend is {backend!r}"
    )


def _pad_tau(tau, L: int, tile_l: int) -> jnp.ndarray:
    """Normalize ``tau`` (scalar or per-group ``(L,)``) to (L_pad,) fp32.

    Padded groups get tau = 0; together with their all-zero snapshots
    (zbar = 0 <= 0) they still always certify ZERO, so tile padding keeps
    costing nothing for every regularizer — including pure-l2, whose real
    groups also carry tau = 0.
    """
    return _pad_axis(tau_row(tau, L), 0, tile_l, 0.0)


def _pad_axis(x: jnp.ndarray, axis: int, mult: int, value=0.0):
    """Pad ``axis`` (negative axes OK — batched callers pad trailing dims)."""
    size = x.shape[axis]
    target = -(-size // mult) * mult
    if target == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(x, pads, constant_values=value)


def _meta():
    return dataclasses.field(metadata=dict(static=True))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PaddedProblem:
    """One-time tile-padded problem geometry + the padded cost matrix.

    ``Cp`` is (L_pad * g, n_pad) with +PAD_COST in the padded area, so
    f = alpha + beta - c < 0 there and padded entries contribute exact
    zeros even inside partially-real tiles.
    """

    Cp: jnp.ndarray
    L: int = _meta()
    g: int = _meta()
    n: int = _meta()
    L_pad: int = _meta()
    n_pad: int = _meta()
    tile_l: int = _meta()
    tile_n: int = _meta()

    @property
    def grid(self) -> Tuple[int, int]:
        """``(L_tiles, N_tiles)`` — the kernel grid / flag-matrix shape."""
        return (self.L_pad // self.tile_l, self.n_pad // self.tile_n)

    @property
    def num_tiles(self) -> int:
        """Total tiles in the dense grid (per problem)."""
        lt, nt = self.grid
        return lt * nt


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PaddedScreenState:
    """Tile-padded screening snapshots (fixed within a snapshot round).

    Padded rows/columns carry z~ = k~ = o~ = 0 and sqrt_g = 0, so their
    upper bound is 0 <= tau (ZERO) and their lower bound never certifies
    ACTIVE — padded-only tiles always flag as skipped.
    """

    z: jnp.ndarray              # (L_pad, n_pad)
    k: jnp.ndarray              # (L_pad, n_pad)
    o: jnp.ndarray              # (L_pad, n_pad)
    act: jnp.ndarray            # (L_pad, n_pad) int8
    sqrt_g: jnp.ndarray         # (L_pad,)
    alpha_snap: jnp.ndarray     # (m_pad,)  unpadded snapshot point
    beta_snap: jnp.ndarray      # (n,)


def prepare_padded_problem(
    C: jnp.ndarray,
    prob: DualProblem,
    tile_l: int = 0,
    tile_n: int = DEFAULT_TILE_N,
) -> PaddedProblem:
    """Pad C to tile multiples ONCE (owned by solve_dual, reused per eval)."""
    from repro.core.groups import PAD_COST

    L, g, n = prob.num_groups, prob.group_size, prob.n
    if tile_l == 0:
        tile_l = resolve_tile_l(L, g, tile_n, jnp.dtype(C.dtype).itemsize)
    L_pad, n_pad = prob.tile_padded_shape(tile_l, tile_n)
    Cp = _pad_axis(
        _pad_axis(C.reshape(L, g, n), 2, tile_n, PAD_COST), 0, tile_l, PAD_COST
    )
    return PaddedProblem(
        Cp=Cp.reshape(L_pad * g, n_pad),
        L=L, g=g, n=n, L_pad=L_pad, n_pad=n_pad,
        tile_l=tile_l, tile_n=tile_n,
    )


def pad_tile_inputs(
    alpha: jnp.ndarray, beta: jnp.ndarray, pp: PaddedProblem
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pad the per-eval dual variables to the kernel grid of ``pp``.

    Batch-polymorphic (alpha (..., m_pad), beta (..., n)).  The single
    definition of the kernel input layout — shared by
    :func:`dual_value_and_grad_padded`, its batched variant, and the
    benchmarks.
    """
    lead = alpha.shape[:-1]
    alphap = _pad_axis(
        alpha.reshape(lead + (pp.L, pp.g)), -2, pp.tile_l, 0.0
    ).reshape(lead + (-1,))
    betap = _pad_axis(beta, -1, pp.tile_n, 0.0)
    return alphap, betap


def pad_screen_state(
    state: ScreenState, sqrt_g: jnp.ndarray, pp: PaddedProblem
) -> PaddedScreenState:
    """Pad the (L, n) snapshots to the kernel grid once per snapshot round."""
    pad2 = lambda x: _pad_axis(
        _pad_axis(x, 1, pp.tile_n, 0.0), 0, pp.tile_l, 0.0
    )
    return PaddedScreenState(
        z=pad2(state.z_snap),
        k=pad2(state.k_snap),
        o=pad2(state.o_snap),
        act=pad2(state.active.astype(jnp.int8)),
        sqrt_g=_pad_axis(sqrt_g, 0, pp.tile_l, 0.0),
        alpha_snap=state.alpha_snap,
        beta_snap=state.beta_snap,
    )


def screen_tile_flags(
    pstate: PaddedScreenState,
    alpha: jnp.ndarray,
    beta: jnp.ndarray,
    pp: PaddedProblem,
    tau,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Per-eval fused screening -> (L_tiles, N_tiles) skip flags.

    Computes the O(L + n) delta norms in jnp, then one Pallas pass over the
    padded bound matrices; the verdict matrix never reaches HBM.  ``tau``
    is a scalar or per-group ``(L,)`` threshold (see
    :meth:`repro.core.regularizers.Regularizer.tau_vec`).
    """
    if interpret is None:
        interpret = default_interpret()
    L = pp.L
    da_plus, da_full, da_neg = screening.grouped_norms(
        alpha - pstate.alpha_snap, L
    )
    db = beta - pstate.beta_snap
    padL = lambda x: _pad_axis(x, 0, pp.tile_l, 0.0)
    padN = lambda x: _pad_axis(x, 0, pp.tile_n, 0.0)
    _, flags = screen_pallas(
        pstate.z, pstate.k, pstate.o, pstate.act,
        padL(da_plus), padL(da_full), padL(da_neg), padN(db), pstate.sqrt_g,
        tau=_pad_tau(tau, L, pp.tile_l), tile_l=pp.tile_l, tile_n=pp.tile_n,
        interpret=interpret, emit_verdict=False,
    )
    return flags


@functools.partial(
    jax.jit, static_argnames=("prob", "impl", "interpret")
)
def dual_value_and_grad_padded(
    alpha: jnp.ndarray,
    beta: jnp.ndarray,
    a: jnp.ndarray,
    b: jnp.ndarray,
    flags: jnp.ndarray,             # (L_tiles, N_tiles) int32 skip flags
    pp: PaddedProblem,
    prob: DualProblem,
    impl: str = "auto",
    interpret: bool | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Screened Pallas evaluation against a prepared (pre-padded) problem.

    Identical to ``repro.core.dual.dual_value_and_grad`` with the screened
    mask (Theorem 2: masked entries are provably zero); this is the
    ``grad_impl='pallas'`` oracle of the solver.

    Parameters
    ----------
    alpha : jnp.ndarray
        ``(m_pad,)`` float32 source duals (unpadded kernel-input layout;
        tile padding happens here via :func:`pad_tile_inputs`).
    beta : jnp.ndarray
        ``(n,)`` float32 target duals.
    a, b : jnp.ndarray
        ``(m_pad,)`` / ``(n,)`` marginals.
    flags : jnp.ndarray
        ``(L_tiles, N_tiles)`` int32 tile skip flags (0 = certified-zero
        tile) from :func:`screen_tile_flags`.
    pp : PaddedProblem
        Prepared geometry + padded cost from :func:`prepare_padded_problem`.
    prob : DualProblem
        Static problem description (static jit arg).
    impl : {'grid', 'compact', 'auto'}
        Dense grid, compacted dynamic grid, or runtime density switch.
    interpret : bool, optional
        Pallas interpret mode; defaults to "not on a real TPU".

    Returns
    -------
    tuple of jnp.ndarray
        ``(value, grad_alpha, grad_beta)`` — scalar, ``(m_pad,)``,
        ``(n,)`` — for the MAXIMIZATION dual.
    """
    if interpret is None:
        interpret = default_interpret()
    L, g = pp.L, pp.g
    assert flags.shape == pp.grid, (flags.shape, pp.grid)

    alphap, betap = pad_tile_inputs(alpha, beta, pp)
    kw = dict(
        num_groups=pp.L_pad, group_size=g,
        tau=_pad_tau(prob.tau_vec(), pp.L, pp.tile_l), gamma=prob.reg.gamma,
        tile_l=pp.tile_l, tile_n=pp.tile_n, interpret=interpret,
    )

    def run_grid(flags):
        rowsum, colsum, psi = gradpsi_pallas(alphap, betap, pp.Cp, flags, **kw)
        return rowsum, colsum, psi, jnp.int32(pp.num_tiles)

    def run_compact(flags):
        sched, nact = build_tile_schedule(flags)
        return gradpsi_pallas_compact(alphap, betap, pp.Cp, sched, nact, **kw)

    if impl == "grid":
        rowsum, colsum, psi, _ = run_grid(flags)
    elif impl == "compact":
        rowsum, colsum, psi, _ = run_compact(flags)
    elif impl == "auto":
        live = jnp.sum(flags != 0)
        use_compact = live <= COMPACT_DENSITY_THRESHOLD * pp.num_tiles
        rowsum, colsum, psi, _ = jax.lax.cond(
            use_compact, run_compact, run_grid, flags
        )
    else:
        raise ValueError(f"unknown pallas impl: {impl}")

    rowsum = rowsum.reshape(pp.L_pad, g)[:L].reshape(-1)
    colsum = colsum[: pp.n]
    value = alpha @ a + beta @ b - psi
    return value, a - rowsum, b - colsum


# -- batched entry points (leading problem axis B) ----------------------------

def prepare_padded_problem_batched(
    C: jnp.ndarray,                    # (B, m_pad, n)
    prob: DualProblem,
    tile_l: int = 0,
    tile_n: int = DEFAULT_TILE_N,
) -> PaddedProblem:
    """Pad a batch of cost matrices to tile multiples once per solve.

    Returns a :class:`PaddedProblem` whose ``Cp`` is (B, L_pad * g, n_pad);
    the static geometry fields are shared by every problem in the batch.
    """
    from repro.core.groups import PAD_COST

    L, g, n = prob.num_groups, prob.group_size, prob.n
    B = C.shape[0]
    if tile_l == 0:
        tile_l = resolve_tile_l(L, g, tile_n, jnp.dtype(C.dtype).itemsize)
    L_pad, n_pad = prob.tile_padded_shape(tile_l, tile_n)
    Cp = _pad_axis(
        _pad_axis(C.reshape(B, L, g, n), -1, tile_n, PAD_COST),
        -3, tile_l, PAD_COST,
    )
    return PaddedProblem(
        Cp=Cp.reshape(B, L_pad * g, n_pad),
        L=L, g=g, n=n, L_pad=L_pad, n_pad=n_pad,
        tile_l=tile_l, tile_n=tile_n,
    )


def is_tile_aligned(prob: DualProblem, dtype,
                    tile_n: int = DEFAULT_TILE_N) -> bool:
    """True when the kernels' tile padding leaves a cost of ``prob`` as is.

    :func:`prepare_padded_problem_batched` then returns a copy of ``C``
    with the same shape and values, which a caller need not keep.
    """
    tile_l = resolve_tile_l(prob.num_groups, prob.group_size, tile_n,
                            jnp.dtype(dtype).itemsize)
    return prob.tile_padded_shape(tile_l, tile_n) == (prob.num_groups, prob.n)


def pad_screen_state_batched(
    state: ScreenState, sqrt_g: jnp.ndarray, pp: PaddedProblem
) -> PaddedScreenState:
    """Pad batched (B, L, n) snapshots to the kernel grid once per round.

    ``sqrt_g`` is (B, L) — per problem, because the serving engine packs
    problems with different true group sizes into one bucket.
    """
    pad2 = lambda x: _pad_axis(
        _pad_axis(x, -1, pp.tile_n, 0.0), -2, pp.tile_l, 0.0
    )
    return PaddedScreenState(
        z=pad2(state.z_snap),
        k=pad2(state.k_snap),
        o=pad2(state.o_snap),
        act=pad2(state.active.astype(jnp.int8)),
        sqrt_g=_pad_axis(sqrt_g, -1, pp.tile_l, 0.0),
        alpha_snap=state.alpha_snap,
        beta_snap=state.beta_snap,
    )


def screen_tile_flags_batched(
    pstate: PaddedScreenState,
    alpha: jnp.ndarray,                # (B, m_pad)
    beta: jnp.ndarray,                 # (B, n)
    pp: PaddedProblem,
    tau,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Per-eval fused screening for a batch -> (B, L_tiles, N_tiles) flags.

    The O(B (L + n)) delta norms run in jnp; the screening kernel vmaps
    over the problem axis (screening state never couples problems), so the
    per-problem verdict matrices still never reach HBM.  ``tau`` (scalar
    or per-group ``(L,)``) is shared by every problem in the batch — a
    bucket packs one regularizer.
    """
    if interpret is None:
        interpret = default_interpret()
    L = pp.L
    tau_p = _pad_tau(tau, L, pp.tile_l)
    da_plus, da_full, da_neg = screening.grouped_norms(
        alpha - pstate.alpha_snap, L
    )
    db = beta - pstate.beta_snap
    padL = lambda x: _pad_axis(x, -1, pp.tile_l, 0.0)
    padN = lambda x: _pad_axis(x, -1, pp.tile_n, 0.0)

    def one(z, k, o, act, dap, daf, dan, dbv, sg):
        _, flags = screen_pallas(
            z, k, o, act, dap, daf, dan, dbv, sg,
            tau=tau_p, tile_l=pp.tile_l, tile_n=pp.tile_n,
            interpret=interpret, emit_verdict=False,
        )
        return flags

    return jax.vmap(one)(
        pstate.z, pstate.k, pstate.o, pstate.act,
        padL(da_plus), padL(da_full), padL(da_neg), padN(db), pstate.sqrt_g,
    )


@functools.partial(
    jax.jit, static_argnames=("prob", "impl", "interpret")
)
def dual_value_and_grad_padded_batched(
    alpha: jnp.ndarray,                # (B, m_pad)
    beta: jnp.ndarray,                 # (B, n)
    a: jnp.ndarray,                    # (B, m_pad)
    b: jnp.ndarray,                    # (B, n)
    flags: jnp.ndarray,                # (B, L_tiles, N_tiles) int32
    pp: PaddedProblem,
    prob: DualProblem,
    impl: str = "auto",
    interpret: bool | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Screened Pallas evaluation of B problems against a prepared batch.

    Per problem identical (bitwise) to the solo padded path.  'compact'
    (and 'auto' below the density threshold) runs ONE dynamic grid over
    the concatenated surviving tiles of the whole batch, so grid steps
    scale with the batch's total live tiles.  Under ``shard_map`` each
    shard calls this on its local problems and builds its own schedule
    (see ``repro.core.sharded``).

    Parameters
    ----------
    alpha, beta : jnp.ndarray
        ``(B, m_pad)`` / ``(B, n)`` float32 duals.
    a, b : jnp.ndarray
        ``(B, m_pad)`` / ``(B, n)`` marginals.
    flags : jnp.ndarray
        ``(B, L_tiles, N_tiles)`` int32 per-problem tile skip flags from
        :func:`screen_tile_flags_batched`.
    pp : PaddedProblem
        Prepared batch geometry (``Cp`` is ``(B, L_pad*g, n_pad)``).
    prob : DualProblem
        Static problem description.
    impl : {'grid', 'compact', 'auto'}
        Gradient grid mode (both modes are bitwise-equal; 'auto' switches
        on the batch-wide live-tile fraction).
    interpret : bool, optional
        Pallas interpret mode; defaults to "not on a real TPU".

    Returns
    -------
    tuple of jnp.ndarray
        ``(value (B,), grad_alpha (B, m_pad), grad_beta (B, n))`` for the
        MAXIMIZATION dual.
    """
    if interpret is None:
        interpret = default_interpret()
    B = alpha.shape[0]
    L, g = pp.L, pp.g
    assert flags.shape == (B,) + pp.grid, (flags.shape, (B,) + pp.grid)

    alphap, betap = pad_tile_inputs(alpha, beta, pp)
    kw = dict(
        num_groups=pp.L_pad, group_size=g,
        tau=_pad_tau(prob.tau_vec(), pp.L, pp.tile_l), gamma=prob.reg.gamma,
        tile_l=pp.tile_l, tile_n=pp.tile_n, interpret=interpret,
    )

    def run_grid(flags):
        return gradpsi_pallas_batched(alphap, betap, pp.Cp, flags, **kw)

    def run_compact(flags):
        sched, nact = build_batch_tile_schedule(flags)
        rowsum, colsum, psi, _ = gradpsi_pallas_compact_batched(
            alphap, betap, pp.Cp, sched, nact, **kw
        )
        return rowsum, colsum, psi

    if impl == "grid":
        rowsum, colsum, psi = run_grid(flags)
    elif impl == "compact":
        rowsum, colsum, psi = run_compact(flags)
    elif impl == "auto":
        live = jnp.sum(flags != 0)
        use_compact = live <= COMPACT_DENSITY_THRESHOLD * B * pp.num_tiles
        rowsum, colsum, psi = jax.lax.cond(
            use_compact, run_compact, run_grid, flags
        )
    else:
        raise ValueError(f"unknown pallas impl: {impl}")

    rowsum = rowsum.reshape(B, pp.L_pad, g)[:, :L].reshape(B, -1)
    colsum = colsum[:, : pp.n]
    value = (
        jnp.sum(alpha * a, axis=-1) + jnp.sum(beta * b, axis=-1) - psi
    )
    return value, a - rowsum, b - colsum


@functools.partial(
    jax.jit,
    static_argnames=("prob", "tile_l", "tile_n", "interpret", "impl"),
)
def dual_value_and_grad(
    alpha: jnp.ndarray,
    beta: jnp.ndarray,
    C: jnp.ndarray,
    a: jnp.ndarray,
    b: jnp.ndarray,
    verdict: jnp.ndarray,           # (L, n) int32 from screening.verdicts
    prob: DualProblem,
    tile_l: int = 0,
    tile_n: int = DEFAULT_TILE_N,
    interpret: bool | None = None,
    impl: str = "auto",
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Block-masked Pallas evaluation from a raw verdict matrix.

    Convenience wrapper (tests, one-shot evaluations): pads C per call.  The
    solver's hot loop uses :func:`prepare_padded_problem` +
    :func:`dual_value_and_grad_padded` instead.
    """
    pp = prepare_padded_problem(C, prob, tile_l=tile_l, tile_n=tile_n)
    flags = screening.tile_flags(verdict, pp.tile_l, pp.tile_n)
    return dual_value_and_grad_padded(
        alpha, beta, a, b, flags, pp, prob, impl=impl, interpret=interpret
    )


@functools.partial(
    jax.jit, static_argnames=("tile_l", "tile_n", "interpret")
)
def screen_verdicts(
    z_snap, k_snap, o_snap, active, da_plus, da_full, da_neg, db, sqrt_g,
    tau,
    tile_l: int = 8,
    tile_n: int = 128,
    interpret: bool | None = None,
):
    """Pallas screening pass; pads (L, n) to tile multiples transparently.

    ``tau`` is a scalar or per-group ``(L,)`` threshold vector.
    """
    if interpret is None:
        interpret = default_interpret()
    L, n = z_snap.shape
    pad2 = lambda x: _pad_axis(_pad_axis(x, 1, tile_n, 0.0), 0, tile_l, 0.0)
    padL = lambda x: _pad_axis(x, 0, tile_l, 0.0)
    padN = lambda x: _pad_axis(x, 0, tile_n, 0.0)
    v, flags = screen_pallas(
        pad2(z_snap), pad2(k_snap),
        # padded k/o rows are zero => zlow <= 0 <= tau => never ACTIVE
        pad2(o_snap), pad2(active.astype(jnp.int8)),
        padL(da_plus), padL(da_full), padL(da_neg), padN(db), padL(sqrt_g),
        tau=_pad_tau(tau, L, tile_l), tile_l=tile_l, tile_n=tile_n,
        interpret=interpret,
    )
    return v[:L, :n], flags


# -- factorized (materialization-free) entry points ----------------------------
#
# The on-the-fly squared-l2 route (docs/geometry.md): the cost operand is a
# FactorizedCost pytree of scaled sample blocks + squared norms instead of a
# dense (m_pad, n) array.  The wrappers below mirror the padded dense ones
# one-for-one; the kernels rebuild each cost tile in VMEM via
# gradpsi.factorized_cost_tile, so HBM holds O((m + n) d) operand bytes
# instead of O(m n).


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FactorizedCost:
    """Squared-l2 cost in factorized form — a drop-in cost-matrix pytree.

    Leaves are the scaled source/target samples and squared norms produced
    by :class:`repro.ot.geometry.SquaredL2Geometry` (normalization and
    PAD_COST sentinels are pre-folded into the stored values, so kernels
    need no extra scale or mask operands for the gradient).  Batched callers
    carry a leading problem axis on every leaf, which is what lets the
    sharded path's pytree-prefix specs and ``C[None]``-style lifts treat
    this exactly like a dense cost array.
    """

    x: jnp.ndarray      # (..., m_pad, d) fp32 scaled source samples
    x_sq: jnp.ndarray   # (..., m_pad)    fp32 scaled |x|^2 (+PAD_COST rows)
    y: jnp.ndarray      # (..., n, d)     fp32 scaled target samples
    y_sq: jnp.ndarray   # (..., n)        fp32 scaled |y|^2 (+PAD_COST cols)

    @property
    def shape(self) -> Tuple[int, ...]:
        """Shape of the equivalent dense cost array ``(..., m_pad, n)``."""
        return self.x.shape[:-1] + (self.y.shape[-2],)

    @property
    def dtype(self):
        """Dtype of the equivalent dense cost array."""
        return self.x.dtype

    @property
    def d(self) -> int:
        """Feature dimension of the sample blocks."""
        return self.x.shape[-1]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FactorizedProblem:
    """One-time tile-padded factorized problem (the on-the-fly PaddedProblem).

    Carries the same static geometry fields as :class:`PaddedProblem` (so
    :func:`pad_tile_inputs`, :func:`pad_screen_state_batched` and
    :func:`screen_tile_flags_batched` work unchanged) but the cost operand
    is the tile-padded sample factorization: padded rows are zero samples
    with ``x_sq = PAD_COST``, padded columns zero samples with
    ``y_sq = PAD_COST`` — every padded cost entry is >= PAD_COST, so
    f < 0 there and padded entries contribute exact zeros.
    """

    x: jnp.ndarray      # (..., L_pad*g, d)
    x_sq: jnp.ndarray   # (..., L_pad*g)
    y: jnp.ndarray      # (..., n_pad, d)
    y_sq: jnp.ndarray   # (..., n_pad)
    L: int = _meta()
    g: int = _meta()
    n: int = _meta()
    d: int = _meta()
    L_pad: int = _meta()
    n_pad: int = _meta()
    tile_l: int = _meta()
    tile_n: int = _meta()

    @property
    def grid(self) -> Tuple[int, int]:
        """``(L_tiles, N_tiles)`` — the kernel grid / flag-matrix shape."""
        return (self.L_pad // self.tile_l, self.n_pad // self.tile_n)

    @property
    def num_tiles(self) -> int:
        """Total tiles in the dense grid (per problem)."""
        lt, nt = self.grid
        return lt * nt


def prepare_factorized_problem(
    fc: FactorizedCost,
    prob: DualProblem,
    tile_l: int = 0,
    tile_n: int = DEFAULT_TILE_N,
) -> FactorizedProblem:
    """Tile-pad a factorized cost ONCE per solve (batch-polymorphic).

    The factorized analog of :func:`prepare_padded_problem` /
    :func:`prepare_padded_problem_batched`: leading batch axes on the
    ``fc`` leaves pass straight through.  TILE_L is resolved with the
    d-aware VMEM model (the kernels hold a (TILE_L, g, TILE_N, d)
    intermediate).
    """
    from repro.core.groups import PAD_COST

    L, g, n = prob.num_groups, prob.group_size, prob.n
    d = fc.d
    if tile_l == 0:
        tile_l = resolve_tile_l_factorized(
            L, g, tile_n, d, jnp.dtype(fc.dtype).itemsize
        )
    L_pad, n_pad = prob.tile_padded_shape(tile_l, tile_n)
    lead = fc.x.shape[:-2]
    x = _pad_axis(
        fc.x.reshape(lead + (L, g, d)), -3, tile_l, 0.0
    ).reshape(lead + (L_pad * g, d))
    x_sq = _pad_axis(
        fc.x_sq.reshape(lead + (L, g)), -2, tile_l, PAD_COST
    ).reshape(lead + (L_pad * g,))
    y = _pad_axis(fc.y, -2, tile_n, 0.0)
    y_sq = _pad_axis(fc.y_sq, -1, tile_n, PAD_COST)
    return FactorizedProblem(
        x=x, x_sq=x_sq, y=y, y_sq=y_sq,
        L=L, g=g, n=n, d=d, L_pad=L_pad, n_pad=n_pad,
        tile_l=tile_l, tile_n=tile_n,
    )


@functools.partial(
    jax.jit, static_argnames=("prob", "impl", "interpret")
)
def dual_value_and_grad_factorized(
    alpha: jnp.ndarray,
    beta: jnp.ndarray,
    a: jnp.ndarray,
    b: jnp.ndarray,
    flags: jnp.ndarray,             # (L_tiles, N_tiles) int32 skip flags
    fp: FactorizedProblem,
    prob: DualProblem,
    impl: str = "auto",
    interpret: bool | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Screened materialization-free evaluation (solo).

    Drop-in for :func:`dual_value_and_grad_padded` with the prepared dense
    cost replaced by a :class:`FactorizedProblem`; bitwise-equal to the
    dense path on a cost materialized with the same factorized recipe.
    """
    if interpret is None:
        interpret = default_interpret()
    from repro.kernels.gradpsi import (
        gradpsi_fact_pallas,
        gradpsi_fact_pallas_compact,
    )

    L, g = fp.L, fp.g
    assert flags.shape == fp.grid, (flags.shape, fp.grid)

    alphap, betap = pad_tile_inputs(alpha, beta, fp)
    kw = dict(
        num_groups=fp.L_pad, group_size=g,
        tau=_pad_tau(prob.tau_vec(), fp.L, fp.tile_l), gamma=prob.reg.gamma,
        tile_l=fp.tile_l, tile_n=fp.tile_n, interpret=interpret,
    )

    def run_grid(flags):
        rowsum, colsum, psi = gradpsi_fact_pallas(
            alphap, betap, fp.x, fp.x_sq, fp.y, fp.y_sq, flags, **kw
        )
        return rowsum, colsum, psi, jnp.int32(fp.num_tiles)

    def run_compact(flags):
        sched, nact = build_tile_schedule(flags)
        return gradpsi_fact_pallas_compact(
            alphap, betap, fp.x, fp.x_sq, fp.y, fp.y_sq, sched, nact, **kw
        )

    if impl == "grid":
        rowsum, colsum, psi, _ = run_grid(flags)
    elif impl == "compact":
        rowsum, colsum, psi, _ = run_compact(flags)
    elif impl == "auto":
        live = jnp.sum(flags != 0)
        use_compact = live <= COMPACT_DENSITY_THRESHOLD * fp.num_tiles
        rowsum, colsum, psi, _ = jax.lax.cond(
            use_compact, run_compact, run_grid, flags
        )
    else:
        raise ValueError(f"unknown pallas impl: {impl}")

    rowsum = rowsum.reshape(fp.L_pad, g)[:L].reshape(-1)
    colsum = colsum[: fp.n]
    value = alpha @ a + beta @ b - psi
    return value, a - rowsum, b - colsum


@functools.partial(
    jax.jit, static_argnames=("prob", "impl", "interpret")
)
def dual_value_and_grad_factorized_batched(
    alpha: jnp.ndarray,                # (B, m_pad)
    beta: jnp.ndarray,                 # (B, n)
    a: jnp.ndarray,                    # (B, m_pad)
    b: jnp.ndarray,                    # (B, n)
    flags: jnp.ndarray,                # (B, L_tiles, N_tiles) int32
    fp: FactorizedProblem,
    prob: DualProblem,
    impl: str = "auto",
    interpret: bool | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Screened materialization-free evaluation of B problems.

    Drop-in for :func:`dual_value_and_grad_padded_batched`; per problem
    bitwise-equal to the solo factorized path.
    """
    if interpret is None:
        interpret = default_interpret()
    from repro.kernels.gradpsi import (
        gradpsi_fact_pallas_batched,
        gradpsi_fact_pallas_compact_batched,
    )

    B = alpha.shape[0]
    L, g = fp.L, fp.g
    assert flags.shape == (B,) + fp.grid, (flags.shape, (B,) + fp.grid)

    alphap, betap = pad_tile_inputs(alpha, beta, fp)
    kw = dict(
        num_groups=fp.L_pad, group_size=g,
        tau=_pad_tau(prob.tau_vec(), fp.L, fp.tile_l), gamma=prob.reg.gamma,
        tile_l=fp.tile_l, tile_n=fp.tile_n, interpret=interpret,
    )

    def run_grid(flags):
        return gradpsi_fact_pallas_batched(
            alphap, betap, fp.x, fp.x_sq, fp.y, fp.y_sq, flags, **kw
        )

    def run_compact(flags):
        sched, nact = build_batch_tile_schedule(flags)
        rowsum, colsum, psi, _ = gradpsi_fact_pallas_compact_batched(
            alphap, betap, fp.x, fp.x_sq, fp.y, fp.y_sq, sched, nact, **kw
        )
        return rowsum, colsum, psi

    if impl == "grid":
        rowsum, colsum, psi = run_grid(flags)
    elif impl == "compact":
        rowsum, colsum, psi = run_compact(flags)
    elif impl == "auto":
        live = jnp.sum(flags != 0)
        use_compact = live <= COMPACT_DENSITY_THRESHOLD * B * fp.num_tiles
        rowsum, colsum, psi = jax.lax.cond(
            use_compact, run_compact, run_grid, flags
        )
    else:
        raise ValueError(f"unknown pallas impl: {impl}")

    rowsum = rowsum.reshape(B, fp.L_pad, g)[:, :L].reshape(B, -1)
    colsum = colsum[:, : fp.n]
    value = (
        jnp.sum(alpha * a, axis=-1) + jnp.sum(beta * b, axis=-1) - psi
    )
    return value, a - rowsum, b - colsum


def snapshot_norms_factorized(
    alpha: jnp.ndarray,
    beta: jnp.ndarray,
    fp: FactorizedProblem,
    prob: DualProblem,
    row_mask: jnp.ndarray,
    interpret: bool | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Materialization-free Eq. 6 snapshot norms (z~, k~, o~ each (..., L, n)).

    Drop-in for :func:`repro.core.dual.snapshot_norms` on the on-the-fly
    route: one Pallas pass rebuilds cost tiles from the sample blocks and
    reduces the three per-group norms in VMEM.  Batch-polymorphic — batched
    callers vmap the solo kernel (the existing screen-kernel idiom), and a
    shared ``(m_pad,)`` row mask broadcasts across the batch.
    """
    if interpret is None:
        interpret = default_interpret()
    from repro.kernels.screen import snapshot_norms_fact_pallas

    L, g, n = fp.L, fp.g, fp.n
    alphap, betap = pad_tile_inputs(alpha, beta, fp)
    mask = row_mask.reshape(row_mask.shape[:-1] + (L, g)).astype(jnp.int8)
    maskp = _pad_axis(mask, -2, fp.tile_l, 0)
    maskp = maskp.reshape(maskp.shape[:-2] + (-1,))

    def one(al, be, xv, xs, yv, ys, mk):
        z, k, o = snapshot_norms_fact_pallas(
            al, be, xv, xs, yv, ys, mk,
            num_groups=fp.L_pad, group_size=g,
            tile_l=fp.tile_l, tile_n=fp.tile_n, interpret=interpret,
        )
        return z[:L, :n], k[:L, :n], o[:L, :n]

    if alpha.ndim == 1:
        return one(alphap, betap, fp.x, fp.x_sq, fp.y, fp.y_sq, maskp)

    B = alphap.shape[0]
    maskb = jnp.broadcast_to(maskp, (B,) + maskp.shape[-1:])
    return jax.vmap(one)(
        alphap, betap, fp.x, fp.x_sq, fp.y, fp.y_sq, maskb
    )


# -- fused screen+gradient entry points (DESIGN.md §10) -------------------------
#
# The steady-state oracle of grad_impl='fused': ONE Pallas launch per L-BFGS
# evaluation computes the screening verdict in-register and the screened
# gradient in the same grid step.  The wrappers below mirror the two-launch
# padded/factorized entry points one-for-one and dispatch on the prepared
# problem's cost representation, so the solver needs a single fused branch.


def snapshot_live_tiles(pstate: PaddedScreenState, pp, tau) -> jnp.ndarray:
    """Live-tile count at the snapshot point (deltas = 0) — no kernel launch.

    At the snapshot point the Eq. 6 upper bound is exactly z~, so a tile is
    live iff any entry is ACTIVE or has ``z~ > tau``.  This is the fused
    route's 'auto' heuristic input: computed once per round from the padded
    snapshots with plain XLA ops, it amortizes to nothing over the round's
    evaluations, unlike the per-eval screen launch it replaces.  Counts the
    TOTAL over a leading batch axis when ``pstate`` is batched.
    """
    tau_p = _pad_tau(tau, pp.L, pp.tile_l)
    nz = jnp.logical_or(pstate.act != 0, pstate.z > tau_p[:, None])
    lt, nt = pp.grid
    lead = nz.shape[:-2]
    tiles = nz.reshape(lead + (lt, pp.tile_l, nt, pp.tile_n))
    return jnp.sum(jnp.any(tiles, axis=(-3, -1)))


@functools.partial(
    jax.jit, static_argnames=("prob", "impl", "interpret")
)
def dual_value_and_grad_fused(
    alpha: jnp.ndarray,
    beta: jnp.ndarray,
    a: jnp.ndarray,
    b: jnp.ndarray,
    pstate: PaddedScreenState,
    pp,
    prob: DualProblem,
    impl: str = "auto",
    interpret: bool | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused screened evaluation: verdicts + gradient in ONE Pallas launch.

    The ``grad_impl='fused'`` oracle (solo).  Consumes the padded screening
    snapshots directly instead of a precomputed flag matrix; the kernel
    computes the per-tile verdicts in-register (DESIGN.md §10).  ``pp`` may
    be a :class:`PaddedProblem` (dense cost) or :class:`FactorizedProblem`
    (on-the-fly cost) — the fused kernel layout is chosen accordingly.

    ``impl`` maps to fused execution modes:

    - ``'grid'``: the fused dense grid — one launch, every tile steps.
    - ``'compact'``: the two-launch reference (standalone screen pass +
      compacted gradient grid).  There is no fused compact mode — a compact
      schedule needs flags before launch, which is exactly the screen pass
      fused mode removes.
    - ``'auto'``: runtime :func:`jax.lax.cond` between the two on the
      snapshot-point live-tile density (:func:`snapshot_live_tiles`) —
      fused when dense, two-launch compact under heavy screening.  Both
      branches are bitwise-equal, so the switch never changes iterates.

    Returns ``(value, grad_alpha (m_pad,), grad_beta (n,))`` for the
    MAXIMIZATION dual — bitwise-identical to the two-launch
    :func:`dual_value_and_grad_padded` / :func:`dual_value_and_grad_factorized`
    oracle on the same inputs.
    """
    if interpret is None:
        interpret = default_interpret()
    from repro.kernels.gradpsi import (
        gradpsi_fact_pallas_compact,
        gradpsi_fused_fact_pallas,
        gradpsi_fused_pallas,
        gradpsi_pallas_compact,
    )

    L, g = pp.L, pp.g
    factorized = isinstance(pp, FactorizedProblem)
    cost_ops = (pp.x, pp.x_sq, pp.y, pp.y_sq) if factorized else (pp.Cp,)
    fused_fn = gradpsi_fused_fact_pallas if factorized else gradpsi_fused_pallas
    compact_fn = (
        gradpsi_fact_pallas_compact if factorized else gradpsi_pallas_compact
    )

    alphap, betap = pad_tile_inputs(alpha, beta, pp)
    tau_p = _pad_tau(prob.tau_vec(), L, pp.tile_l)
    kw = dict(
        num_groups=pp.L_pad, group_size=g, tau=tau_p, gamma=prob.reg.gamma,
        tile_l=pp.tile_l, tile_n=pp.tile_n, interpret=interpret,
    )

    da_plus, da_full, da_neg = screening.grouped_norms(
        alpha - pstate.alpha_snap, L
    )
    db = beta - pstate.beta_snap
    padL = lambda v: _pad_axis(v, 0, pp.tile_l, 0.0)
    padN = lambda v: _pad_axis(v, 0, pp.tile_n, 0.0)
    dap, daf, dan, dbp = padL(da_plus), padL(da_full), padL(da_neg), padN(db)

    def run_fused(_):
        rowsum, colsum, psi, _flags = fused_fn(
            alphap, betap, *cost_ops,
            pstate.z, pstate.k, pstate.o, pstate.act,
            dap, daf, dan, dbp, pstate.sqrt_g, **kw,
        )
        return rowsum, colsum, psi

    def run_two_launch(_):
        _, flags = screen_pallas(
            pstate.z, pstate.k, pstate.o, pstate.act,
            dap, daf, dan, dbp, pstate.sqrt_g,
            tau=tau_p, tile_l=pp.tile_l, tile_n=pp.tile_n,
            interpret=interpret, emit_verdict=False,
        )
        sched, nact = build_tile_schedule(flags)
        rowsum, colsum, psi, _ = compact_fn(
            alphap, betap, *cost_ops, sched, nact, **kw
        )
        return rowsum, colsum, psi

    if impl == "grid":
        rowsum, colsum, psi = run_fused(None)
    elif impl == "compact":
        rowsum, colsum, psi = run_two_launch(None)
    elif impl == "auto":
        live0 = snapshot_live_tiles(pstate, pp, prob.tau_vec())
        use_compact = live0 <= COMPACT_DENSITY_THRESHOLD * pp.num_tiles
        rowsum, colsum, psi = jax.lax.cond(
            use_compact, run_two_launch, run_fused, 0
        )
    else:
        raise ValueError(f"unknown pallas impl: {impl}")

    rowsum = rowsum.reshape(pp.L_pad, g)[:L].reshape(-1)
    colsum = colsum[: pp.n]
    value = alpha @ a + beta @ b - psi
    return value, a - rowsum, b - colsum


@functools.partial(
    jax.jit, static_argnames=("prob", "impl", "interpret")
)
def dual_value_and_grad_fused_batched(
    alpha: jnp.ndarray,                # (B, m_pad)
    beta: jnp.ndarray,                 # (B, n)
    a: jnp.ndarray,                    # (B, m_pad)
    b: jnp.ndarray,                    # (B, n)
    pstate: PaddedScreenState,         # batched leaves
    pp,
    prob: DualProblem,
    impl: str = "auto",
    interpret: bool | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused screened evaluation of B problems: ONE launch per eval.

    Batched :func:`dual_value_and_grad_fused` — the fused kernel runs a
    (B, Lt, Nt) grid; the ``'compact'``/low-density-``'auto'`` reference
    branch vmaps the standalone screen kernel and runs one dynamic grid
    over the batch's concatenated surviving tiles, exactly like the
    two-launch batched oracle.  Per problem bitwise-identical to the solo
    fused path.  Returns ``(value (B,), grad_alpha (B, m_pad), grad_beta
    (B, n))``.
    """
    if interpret is None:
        interpret = default_interpret()
    from repro.kernels.gradpsi import (
        gradpsi_fact_pallas_compact_batched,
        gradpsi_fused_fact_pallas_batched,
        gradpsi_fused_pallas_batched,
        gradpsi_pallas_compact_batched,
    )

    B = alpha.shape[0]
    L, g = pp.L, pp.g
    factorized = isinstance(pp, FactorizedProblem)
    cost_ops = (pp.x, pp.x_sq, pp.y, pp.y_sq) if factorized else (pp.Cp,)
    fused_fn = (
        gradpsi_fused_fact_pallas_batched
        if factorized
        else gradpsi_fused_pallas_batched
    )
    compact_fn = (
        gradpsi_fact_pallas_compact_batched
        if factorized
        else gradpsi_pallas_compact_batched
    )

    alphap, betap = pad_tile_inputs(alpha, beta, pp)
    tau_p = _pad_tau(prob.tau_vec(), L, pp.tile_l)
    kw = dict(
        num_groups=pp.L_pad, group_size=g, tau=tau_p, gamma=prob.reg.gamma,
        tile_l=pp.tile_l, tile_n=pp.tile_n, interpret=interpret,
    )

    da_plus, da_full, da_neg = screening.grouped_norms(
        alpha - pstate.alpha_snap, L
    )
    db = beta - pstate.beta_snap
    padL = lambda v: _pad_axis(v, -1, pp.tile_l, 0.0)
    padN = lambda v: _pad_axis(v, -1, pp.tile_n, 0.0)
    dap, daf, dan, dbp = padL(da_plus), padL(da_full), padL(da_neg), padN(db)

    def run_fused(_):
        rowsum, colsum, psi, _flags = fused_fn(
            alphap, betap, *cost_ops,
            pstate.z, pstate.k, pstate.o, pstate.act,
            dap, daf, dan, dbp, pstate.sqrt_g, **kw,
        )
        return rowsum, colsum, psi

    def run_two_launch(_):
        def one(z, k, o, act, dp, df, dn, dbv, sg):
            _, fl = screen_pallas(
                z, k, o, act, dp, df, dn, dbv, sg,
                tau=tau_p, tile_l=pp.tile_l, tile_n=pp.tile_n,
                interpret=interpret, emit_verdict=False,
            )
            return fl

        flags = jax.vmap(one)(
            pstate.z, pstate.k, pstate.o, pstate.act,
            dap, daf, dan, dbp, pstate.sqrt_g,
        )
        sched, nact = build_batch_tile_schedule(flags)
        rowsum, colsum, psi, _ = compact_fn(
            alphap, betap, *cost_ops, sched, nact, **kw
        )
        return rowsum, colsum, psi

    if impl == "grid":
        rowsum, colsum, psi = run_fused(None)
    elif impl == "compact":
        rowsum, colsum, psi = run_two_launch(None)
    elif impl == "auto":
        live0 = snapshot_live_tiles(pstate, pp, prob.tau_vec())
        use_compact = live0 <= COMPACT_DENSITY_THRESHOLD * B * pp.num_tiles
        rowsum, colsum, psi = jax.lax.cond(
            use_compact, run_two_launch, run_fused, 0
        )
    else:
        raise ValueError(f"unknown pallas impl: {impl}")

    rowsum = rowsum.reshape(B, pp.L_pad, g)[:, :L].reshape(B, -1)
    colsum = colsum[:, : pp.n]
    value = (
        jnp.sum(alpha * a, axis=-1) + jnp.sum(beta * b, axis=-1) - psi
    )
    return value, a - rowsum, b - colsum
