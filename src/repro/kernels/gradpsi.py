"""Pallas TPU kernels: fused, screened dual gradient for group-sparse OT.

This is the paper's Algorithm 2 adapted to the TPU memory hierarchy (see
DESIGN.md §2).  One kernel instance owns a (TILE_L groups x g rows) x TILE_N
columns tile and fuses the whole gradient pipeline in VMEM:

    F = alpha + beta_j - c          (VPU broadcast add)
    Z = ||[F_group]_+||_2           (relu + per-group reduction)
    s = [1 - tau/Z]_+               (soft threshold, Eq. 5)
    T = s * [F]_+ / gamma           (the gradient block / plan block)
    psi contribution                (closed form in Z)

Screening enters through per-tile skip flags (int32, 0 = every (l, j) in the
tile is certified-zero by the Eq. 6 upper bound).  Two execution modes share
the math (DESIGN.md §3):

``gradpsi_pallas`` — dense grid (L_tiles, N_tiles).  Skipped tiles run no
  compute (``@pl.when``) and remap their C-tile index to (l, 0, 0), so
  consecutive skipped steps request the same block and Mosaic's revisit
  elision drops the HBM->VMEM DMA.  FLOPs and HBM traffic scale with
  surviving tiles, but the *grid itself* still issues one step per tile.

``gradpsi_pallas_compact`` — compacted grid.  :func:`build_tile_schedule`
  packs the coordinates of surviving tiles into a scalar-prefetched list
  (on-device cumsum + scatter) and the kernel runs a *dynamic* 1-D grid of
  exactly ``max(num_active, 1)`` steps, so grid steps — not just FLOPs and
  DMAs — are proportional to surviving tiles.  Each step writes its partial
  results into a per-step slot; a masked scatter-add outside the kernel
  assembles them (unvisited slots hold garbage and are dropped, never read).

Outputs are partials assembled by ops.py:
  T_rowsum (m_pad,), T_colsum (n,), psi_total scalar — callers form
  value = alpha@a + beta@b - psi, grad_alpha = a - rowsum, grad_beta = b -
  colsum.  The compact kernel additionally returns the grid-step count
  actually issued (the scaling contract asserted by tests).

Batched variants (``solve_batch`` / the OT serving engine) extend both
modes with a leading problem axis B over same-shape problems:

``gradpsi_pallas_batched`` — dense grid (B, L_tiles, N_tiles) with a
  (B, L_tiles, N_tiles) flag matrix; per-(b, l, j) skip/DMA-remap exactly
  as in the solo kernel.

``gradpsi_pallas_compact_batched`` — ONE dynamic grid over the
  concatenated active list of the whole batch: :func:`build_batch_tile_schedule`
  compacts the (B, Lt, Nt) flags into a scalar-prefetched (3, B*T) list of
  (b, l, j) coordinates, so total grid steps equal the batch's total
  surviving tiles.  A heavily-screened problem contributes almost no steps
  instead of padding the batch to its worst member.

Fused screen+gradient mega-kernels (``gradpsi_fused_*``, DESIGN.md §10)
collapse the steady-state oracle's two launches into one: the per-tile
screening verdict (paper Eq. 6/7 — the same :func:`_verdict_tile` math the
standalone screen kernel runs) is computed IN-REGISTER at the top of every
grid step from the snapshot-bound tiles, a tile whose bound test fails
writes zeros without its F/T working set ever leaving VMEM, and the
verdict's per-tile OR lands in a flag output that replaces the standalone
screen launch.  All four operand layouts are covered
(``gradpsi_fused_pallas[_batched]`` dense, ``gradpsi_fused_fact_pallas
[_batched]`` factorized).  The tradeoff: BlockSpec index maps cannot see
in-kernel verdicts, so the fused dense grid cannot remap a skipped tile's
cost (or sample-block) DMA onto a resident block the way the two-launch
grid does — skipped tiles still pay their cost-tile HBM read.  Fused wins
when live density is high or launch overhead dominates; the two-launch
compact path wins under heavy screening.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.screening import ZERO, CHECK, ACTIVE

DEFAULT_TILE_N = 128
VMEM_BUDGET_BYTES = 8 * 1024 * 1024  # one grid step's full working set

# Mosaic's automatic pipelining keeps the NEXT step's blocks in flight while
# the current step computes; granting the compact kernels two working sets of
# VMEM is what lets that double-buffering actually happen for their dynamic
# (scalar-prefetched) schedules instead of serializing DMA behind compute.
COMPACT_PIPELINE_BUFFERS = 2


# Mosaic parameters of the compact kernels: VMEM for two working sets.
_COMPACT_PARAMS = pltpu.CompilerParams(
    vmem_limit_bytes=COMPACT_PIPELINE_BUFFERS * VMEM_BUDGET_BYTES,
)

# Above this fraction of live tiles the dense grid wins: compaction pays an
# O(T) schedule build plus per-step partial-output traffic, while the dense
# grid's only overhead for a skipped tile is an empty (DMA-elided) grid step.
# See DESIGN.md §3 for the model behind the 0.5 crossover.
COMPACT_DENSITY_THRESHOLD = 0.5

# -- trace-time launch accounting ----------------------------------------------
# Each jitted wrapper below bumps its counter ONCE PER FRESH TRACE, so after
# ``jax.clear_caches()`` one solver evaluation records exactly the set of
# pallas_call launches its oracle issues per eval (2 for the two-launch
# screen+grad path, 1 for the fused path).  bench_kernels.py gates on this.

_LAUNCHES: dict = {}


def _record_launch(name: str) -> None:
    """Bump the trace-time launch counter for one Pallas kernel wrapper."""
    _LAUNCHES[name] = _LAUNCHES.get(name, 0) + 1


def launch_counts() -> dict:
    """Snapshot of {kernel wrapper name: traces since last reset}."""
    return dict(_LAUNCHES)


def reset_launch_counts() -> None:
    """Zero the trace-time launch counters (pair with ``jax.clear_caches()``)."""
    _LAUNCHES.clear()


def tile_working_set_bytes(tile_l: int, g: int, tile_n: int, d=None,
                           dtype_bytes: int = 4) -> int:
    """Explicit per-route VMEM bytes held by ONE grid step at TILE_L=tile_l.

    The single byte model shared by :func:`pick_tile_l` (dense route,
    ``d=None``) and :func:`pick_tile_l_factorized` (on-the-fly route,
    ``d`` = sample dimension), pinned by a unit test so the accounting
    cannot silently drift from the kernels:

    - F and T intermediates of :func:`_gradpsi_tile`, always f32;
    - the cost operand: a dense ``(TILE_L, g, TILE_N)`` tile in the cost
      dtype, or — factorized — the f32 product intermediate of
      :func:`factorized_cost_tile` plus the ``(x, x_sq, y, y_sq)`` blocks
      in the sample dtype;
    - dual rows/cols and the tau row;
    - the ga/gb/psi output blocks;
    - the fused route's screening operands (z/k/o f32 tiles, int8 active
      tile, three delta-norm rows + sqrt_g row, db column, flag cell) —
      budgeted unconditionally so fused and two-launch kernels agree on
      tiling and screening flag grids stay interchangeable.
    """
    ft = 2 * tile_l * g * tile_n * 4
    if d is None:
        cost = tile_l * g * tile_n * dtype_bytes
    else:
        cost = (tile_l * g * tile_n * d * 4
                + (tile_l * g + tile_n) * (d + 1) * dtype_bytes)
    duals = (tile_l * g + tile_n + tile_l) * 4
    outputs = (tile_l * g + tile_n + 1) * 4
    screen = (3 * tile_l * tile_n * 4 + tile_l * tile_n
              + (4 * tile_l + tile_n) * 4 + 4)
    return ft + cost + duals + outputs + screen


def pick_tile_l(g: int, tile_n: int, dtype_bytes: int = 4) -> int:
    """Largest TILE_L (power of two, <=8) whose working set fits VMEM."""
    for cand in (8, 4, 2, 1):
        if tile_working_set_bytes(cand, g, tile_n,
                                  dtype_bytes=dtype_bytes) <= VMEM_BUDGET_BYTES:
            return cand
    return 1


def resolve_tile_l(L: int, g: int, tile_n: int, dtype_bytes: int = 4) -> int:
    """VMEM-fitting TILE_L, halved until it divides L (minimizes padding).

    Shared by ops.py and the solver so the screening flag grid and the
    gradient grid always agree on tiling.
    """
    t = pick_tile_l(g, tile_n, dtype_bytes)
    t = min(t, L)
    while t > 1 and L % t:
        t //= 2
    return max(t, 1)


def _gradpsi_tile(alpha, beta, c, tau, *, gamma: float):
    """Shared per-tile math: returns (T (TL, g, TN), psi_sum scalar).

    ``tau`` is the per-group threshold column (TL, 1) — uniform for the
    paper's group-sparse Psi, zero for pure-l2 (nonnegativity skipping),
    mixed for elastic-net group weights (see core.regularizers).
    """
    f = alpha[:, :, None] + beta[None, None, :] - c
    fp = jnp.maximum(f, 0.0)
    zsq = jnp.sum(fp * fp, axis=1)                   # (TL, TN)
    z = jnp.sqrt(zsq)
    on = z > tau                                     # tau (TL, 1)
    zs = jnp.where(on, z, 1.0)
    s = jnp.where(on, 1.0 - tau / zs, 0.0)           # (TL, TN)
    t = s[:, None, :] * fp * (1.0 / gamma)           # (TL, g, TN)
    # psi closed form (regularizers.psi_from_z)
    mu_s_z = (tau / gamma) * s * zs                  # mu_l*s*z, tau_l=mu_l*gamma
    psi = jnp.where(on, s * zs * zs / gamma * (1.0 - 0.5 * s) - mu_s_z, 0.0)
    return t, jnp.sum(psi)


def tau_row(tau, L: int) -> jnp.ndarray:
    """Normalize ``tau`` (scalar or per-group ``(L,)``) to an (L,) fp32 row.

    The single definition of the kernel-facing threshold layout — shared
    by the gradient kernels here, the screening kernel, ops.py's padding,
    and the ref.py oracles, so the normalization cannot drift between the
    kernels and the oracles the parity tests compare against.
    """
    return jnp.broadcast_to(jnp.asarray(tau, jnp.float32), (L,))


# -- Mosaic block layouts --------------------------------------------------------
# Mosaic accepts a block only if its last two dims are multiples of (8, 128)
# or span the whole array, and a rank-1 block only if it is a multiple of 128
# or the whole array.  Everything indexed by group tile is therefore handed
# to the kernels as (..., L_tiles, tile_l, k): the tile axis is squeezed by
# the BlockSpec (``None``) and the (tile_l, k) block spans the trailing dims
# for any tile_l.  Per-group rows become (tile_l, 1) columns, and per-tile
# partial outputs get a unit second-minor axis, e.g. (L_tiles, 1, n).


def group_tiles(x: jnp.ndarray, tile_l: int) -> jnp.ndarray:
    """(..., L, k) -> (..., L_tiles, tile_l, k), one kernel block per tile."""
    return x.reshape(x.shape[:-2] + (-1, tile_l, x.shape[-1]))


def group_rows(x: jnp.ndarray, tile_l: int) -> jnp.ndarray:
    """Per-group row (..., L) -> (..., L_tiles, tile_l, 1) column blocks."""
    return group_tiles(x[..., None], tile_l)


def _verdict_tile(z, k, o, act, da_plus, da_full, da_neg, db, sqrt_g, tau):
    """Per-tile screening verdicts (paper Eq. 6/7) from loaded VMEM arrays.

    THE single definition of the verdict math: the standalone screen kernel
    (screen.py) and the fused ``gradpsi_fused_*`` kernels both call it on
    identically-blocked operands, which is what makes the fused route's tile
    flags bitwise-equal to the two-launch route's.  ``z``/``k``/``o`` are
    (TL, TN) f32 bound tiles, ``act`` an int8 (TL, TN) persistent-set tile,
    ``da_plus``/``da_full``/``da_neg``/``sqrt_g``/``tau`` (TL, 1) per-group
    columns and ``db`` a (TN,) row; returns (TL, TN) int32 verdicts.
    """
    db_r = db[None, :]                                # (1, TN)

    zbar = z + da_plus + sqrt_g * jnp.maximum(db_r, 0.0)
    zlow = (
        k
        - da_full
        - sqrt_g * jnp.abs(db_r)
        - o
        - da_neg
        - sqrt_g * jnp.maximum(-db_r, 0.0)
    )
    active = act != 0
    v = jnp.where(zbar <= tau, ZERO, CHECK)
    v = jnp.where(active, ACTIVE, v)
    # lower bound can also certify non-zero outside N within this eval
    v = jnp.where(jnp.logical_and(v == CHECK, zlow > tau), ACTIVE, v)
    return v.astype(jnp.int32)


def _dense_kernel(flags_ref, alpha_ref, beta_ref, c_ref, tau_ref,
                  ga_ref, gb_ref, psi_ref, *, gamma: float):
    l = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init_ga():
        ga_ref[...] = jnp.zeros_like(ga_ref)

    @pl.when(jnp.logical_and(l == 0, j == 0))
    def _init_psi():
        psi_ref[...] = jnp.zeros_like(psi_ref)

    gb_ref[...] = jnp.zeros_like(gb_ref)

    flag = flags_ref[l, j]

    @pl.when(flag != 0)
    def _compute():
        alpha = alpha_ref[...].astype(jnp.float32)       # (TL, g)
        beta = beta_ref[0].astype(jnp.float32)           # (TN,)
        c = c_ref[...].astype(jnp.float32)               # (TL, g, TN)
        tau = tau_ref[...].astype(jnp.float32)           # (TL, 1)
        t, psi = _gradpsi_tile(alpha, beta, c, tau, gamma=gamma)
        psi_ref[...] += jnp.reshape(psi, (1, 1))
        ga_ref[...] += jnp.sum(t, axis=2)                # (TL, g)
        gb_ref[...] = jnp.sum(t, axis=(0, 1))[None, :]   # (1, TN)


@functools.partial(
    jax.jit,
    static_argnames=("num_groups", "group_size", "gamma",
                     "tile_l", "tile_n", "interpret"),
)
def gradpsi_pallas(
    alpha: jnp.ndarray,        # (m_pad,) fp32
    beta: jnp.ndarray,         # (n,) fp32
    C: jnp.ndarray,            # (m_pad, n) fp32 or bf16
    flags: jnp.ndarray,        # (L_tiles, N_tiles) int32 tile skip flags
    *,
    num_groups: int,
    group_size: int,
    tau,
    gamma: float,
    tile_l: int = 0,
    tile_n: int = DEFAULT_TILE_N,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Dense-grid kernel: returns (T_rowsum (m_pad,), T_colsum (n,), psi).

    n and L must be padded to tile multiples (ops.py handles padding).
    ``tau`` is a scalar or a per-group ``(L,)`` threshold vector (the
    regularizer subsystem's per-group screening thresholds); it is a
    kernel *operand*, loaded one (tile_l,) row per tile.
    """
    _record_launch("gradpsi_pallas")
    L, g = num_groups, group_size
    n = beta.shape[0]
    tau_g = tau_row(tau, L)
    if tile_l == 0:
        tile_l = pick_tile_l(g, tile_n, jnp.dtype(C.dtype).itemsize)
    assert L % tile_l == 0 and n % tile_n == 0, (L, tile_l, n, tile_n)
    grid = (L // tile_l, n // tile_n)
    assert flags.shape == grid, (flags.shape, grid)

    alpha_g = alpha.reshape(grid[0], tile_l, g)
    C3 = C.reshape(L, g, n)

    def c_index(l, j, flags_ref):
        # remap skipped tiles to (l, 0, 0): consecutive skipped steps request
        # the same block => the DMA is elided (revisit optimization).
        active = flags_ref[l, j] != 0
        return (l, 0, jnp.where(active, j, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, tile_l, g), lambda l, j, f: (l, 0, 0)),
            pl.BlockSpec((1, tile_n), lambda l, j, f: (0, j)),
            pl.BlockSpec((tile_l, g, tile_n), c_index),
            pl.BlockSpec((None, tile_l, 1), lambda l, j, f: (l, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, tile_l, g), lambda l, j, f: (l, 0, 0)),
            pl.BlockSpec((None, 1, tile_n), lambda l, j, f: (l, 0, j)),
            pl.BlockSpec((1, 1), lambda l, j, f: (0, 0)),
        ],
    )

    ga_part, gb_part, psi = pl.pallas_call(
        functools.partial(_dense_kernel, gamma=float(gamma)),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((grid[0], tile_l, g), jnp.float32),
            jax.ShapeDtypeStruct((grid[0], 1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(flags, alpha_g, beta[None, :], C3, group_rows(tau_g, tile_l))

    return ga_part.reshape(-1), jnp.sum(gb_part[:, 0], axis=0), psi[0, 0]


def build_tile_schedule(flags: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Compact the (L_tiles, N_tiles) flag matrix into an active-tile list.

    Returns ``(sched (2, T) int32, num_active () int32)`` where
    ``sched[:, s] = (l, j)`` of the s-th surviving tile in row-major order
    and entries past ``num_active`` repeat the last surviving coordinate
    (so the pipeline's block lookahead lands on an already-resident tile).
    All on-device: one cumsum + one scatter, O(T) with T = L_tiles * N_tiles.
    """
    Lt, Nt = flags.shape
    T = Lt * Nt
    flat = flags.reshape(-1) != 0
    num_active = jnp.sum(flat).astype(jnp.int32)
    pos = jnp.cumsum(flat).astype(jnp.int32) - 1      # rank among survivors
    idx = jnp.arange(T, dtype=jnp.int32)
    dest = jnp.where(flat, pos, T)                    # dead tiles -> dropped
    order = jnp.zeros((T,), jnp.int32).at[dest].set(idx, mode="drop")
    last = jnp.where(num_active > 0, order[jnp.maximum(num_active - 1, 0)], 0)
    order = jnp.where(idx < num_active, order, last)
    sched = jnp.stack([order // Nt, order % Nt])
    return sched, num_active


def _compact_kernel(sched_ref, nact_ref, alpha_ref, beta_ref, c_ref, tau_ref,
                    ga_ref, gb_ref, psi_ref, steps_ref,
                    *, gamma: float):
    s = pl.program_id(0)

    @pl.when(s == 0)
    def _init_steps():
        steps_ref[...] = jnp.zeros_like(steps_ref)

    steps_ref[...] += 1

    alpha = alpha_ref[...].astype(jnp.float32)           # (TL, g)
    beta = beta_ref[0].astype(jnp.float32)               # (TN,)
    c = c_ref[...].astype(jnp.float32)                   # (TL, g, TN)
    tau = tau_ref[...].astype(jnp.float32)               # (TL, 1)
    t, psi = _gradpsi_tile(alpha, beta, c, tau, gamma=gamma)
    # per-step slots: every visited block is written exactly once, so no
    # cross-step accumulation state and no uninitialized revisits.
    ga_ref[...] = jnp.sum(t, axis=2)[None]               # (1, TL, g)
    gb_ref[...] = jnp.sum(t, axis=(0, 1))[None, :]       # (1, TN)
    psi_ref[...] = jnp.reshape(psi, (1, 1))


@functools.partial(
    jax.jit,
    static_argnames=("num_groups", "group_size", "gamma",
                     "tile_l", "tile_n", "interpret"),
)
def gradpsi_pallas_compact(
    alpha: jnp.ndarray,        # (m_pad,) fp32
    beta: jnp.ndarray,         # (n,) fp32
    C: jnp.ndarray,            # (m_pad, n) fp32 or bf16
    sched: jnp.ndarray,        # (2, T) int32 from build_tile_schedule
    num_active: jnp.ndarray,   # () int32 surviving-tile count
    *,
    num_groups: int,
    group_size: int,
    tau,
    gamma: float,
    tile_l: int = 0,
    tile_n: int = DEFAULT_TILE_N,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Compacted-grid kernel: grid steps scale with surviving tiles.

    Returns (T_rowsum (m_pad,), T_colsum (n,), psi, steps_issued ()).
    With ``num_active == 0`` one sentinel step runs (a grid cannot be empty)
    and its outputs are masked to exact zeros.  ``tau`` is a scalar or a
    per-group ``(L,)`` threshold vector, gathered per scheduled tile.
    """
    _record_launch("gradpsi_pallas_compact")
    L, g = num_groups, group_size
    n = beta.shape[0]
    tau_g = tau_row(tau, L)
    if tile_l == 0:
        tile_l = pick_tile_l(g, tile_n, jnp.dtype(C.dtype).itemsize)
    assert L % tile_l == 0 and n % tile_n == 0, (L, tile_l, n, tile_n)
    Lt, Nt = L // tile_l, n // tile_n
    T = Lt * Nt
    assert sched.shape == (2, T), (sched.shape, (2, T))

    alpha_g = alpha.reshape(Lt, tile_l, g)
    C3 = C.reshape(L, g, n)
    num_active = num_active.astype(jnp.int32)
    nact = num_active.reshape(1)
    num_steps = jnp.maximum(num_active, 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_steps,),
        in_specs=[
            pl.BlockSpec((None, tile_l, g),
                         lambda s, sc, na: (sc[0, s], 0, 0)),
            pl.BlockSpec((1, tile_n), lambda s, sc, na: (0, sc[1, s])),
            pl.BlockSpec((tile_l, g, tile_n),
                         lambda s, sc, na: (sc[0, s], 0, sc[1, s])),
            pl.BlockSpec((None, tile_l, 1),
                         lambda s, sc, na: (sc[0, s], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, tile_l, g), lambda s, sc, na: (s, 0, 0)),
            pl.BlockSpec((None, 1, tile_n), lambda s, sc, na: (s, 0, 0)),
            pl.BlockSpec((None, 1, 1), lambda s, sc, na: (s, 0, 0)),
            pl.BlockSpec((1, 1), lambda s, sc, na: (0, 0)),
        ],
    )

    ga_steps, gb_steps, psi_steps, steps = pl.pallas_call(
        functools.partial(_compact_kernel, gamma=float(gamma)),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((T, tile_l, g), jnp.float32),
            jax.ShapeDtypeStruct((T, 1, tile_n), jnp.float32),
            jax.ShapeDtypeStruct((T, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        compiler_params=_COMPACT_PARAMS,
        interpret=interpret,
    )(sched, nact, alpha_g, beta[None, :], C3, group_rows(tau_g, tile_l))
    gb_steps, psi_steps = gb_steps[:, 0], psi_steps[:, 0]

    # assemble: slots past num_active were never visited (garbage) — route
    # them to an out-of-range segment so the scatter drops them.
    idx = jnp.arange(T, dtype=jnp.int32)
    valid = idx < num_active
    seg_l = jnp.where(valid, sched[0], Lt)
    seg_n = jnp.where(valid, sched[1], Nt)
    ga = jnp.zeros((Lt, tile_l, g), jnp.float32).at[seg_l].add(
        ga_steps, mode="drop"
    )
    gb = jnp.zeros((Nt, tile_n), jnp.float32).at[seg_n].add(
        gb_steps, mode="drop"
    )
    psi = jnp.sum(jnp.where(valid[:, None], psi_steps, 0.0))
    return ga.reshape(-1), gb.reshape(-1), psi, steps[0, 0]


# -- batched variants (leading problem axis B) --------------------------------

def _dense_kernel_batched(flags_ref, alpha_ref, beta_ref, c_ref, tau_ref,
                          ga_ref, gb_ref, psi_ref, *, gamma: float):
    bi = pl.program_id(0)
    l = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init_ga():
        ga_ref[...] = jnp.zeros_like(ga_ref)

    @pl.when(jnp.logical_and(l == 0, j == 0))
    def _init_psi():
        psi_ref[...] = jnp.zeros_like(psi_ref)

    gb_ref[...] = jnp.zeros_like(gb_ref)

    flag = flags_ref[bi, l, j]

    @pl.when(flag != 0)
    def _compute():
        alpha = alpha_ref[0].astype(jnp.float32)         # (TL, g)
        beta = beta_ref[0].astype(jnp.float32)           # (TN,)
        c = c_ref[0].astype(jnp.float32)                 # (TL, g, TN)
        tau = tau_ref[...].astype(jnp.float32)           # (TL, 1)
        t, psi = _gradpsi_tile(alpha, beta, c, tau, gamma=gamma)
        psi_ref[...] += jnp.reshape(psi, (1, 1, 1))
        ga_ref[...] += jnp.sum(t, axis=2)[None]          # (1, TL, g)
        gb_ref[...] = jnp.sum(t, axis=(0, 1))[None, None, :]  # (1, 1, TN)


@functools.partial(
    jax.jit,
    static_argnames=("num_groups", "group_size", "gamma",
                     "tile_l", "tile_n", "interpret"),
)
def gradpsi_pallas_batched(
    alpha: jnp.ndarray,        # (B, m_pad) fp32
    beta: jnp.ndarray,         # (B, n) fp32
    C: jnp.ndarray,            # (B, m_pad, n) fp32 or bf16
    flags: jnp.ndarray,        # (B, L_tiles, N_tiles) int32 tile skip flags
    *,
    num_groups: int,
    group_size: int,
    tau,
    gamma: float,
    tile_l: int = 0,
    tile_n: int = DEFAULT_TILE_N,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Dense-grid kernel over B problems: grid (B, L_tiles, N_tiles).

    Returns (T_rowsum (B, m_pad), T_colsum (B, n), psi (B,)).  Semantics
    per problem are identical to :func:`gradpsi_pallas`.  ``tau`` (scalar
    or per-group ``(L,)``) is shared by the whole batch — a bucket packs
    problems with one regularizer, so thresholds are batch-static.
    """
    _record_launch("gradpsi_pallas_batched")
    L, g = num_groups, group_size
    B, n = beta.shape
    tau_g = tau_row(tau, L)
    if tile_l == 0:
        tile_l = pick_tile_l(g, tile_n, jnp.dtype(C.dtype).itemsize)
    assert L % tile_l == 0 and n % tile_n == 0, (L, tile_l, n, tile_n)
    grid = (B, L // tile_l, n // tile_n)
    assert flags.shape == grid, (flags.shape, grid)

    alpha_g = alpha.reshape(B, grid[1], tile_l, g)
    C4 = C.reshape(B, L, g, n)

    def c_index(b, l, j, flags_ref):
        # remap skipped tiles to column 0: consecutive skipped steps request
        # the same block => the DMA is elided (revisit optimization).
        active = flags_ref[b, l, j] != 0
        return (b, l, 0, jnp.where(active, j, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, None, tile_l, g),
                         lambda b, l, j, f: (b, l, 0, 0)),
            pl.BlockSpec((None, 1, tile_n), lambda b, l, j, f: (b, 0, j)),
            pl.BlockSpec((1, tile_l, g, tile_n), c_index),
            pl.BlockSpec((None, tile_l, 1), lambda b, l, j, f: (l, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, None, tile_l, g),
                         lambda b, l, j, f: (b, l, 0, 0)),
            pl.BlockSpec((1, None, 1, tile_n),
                         lambda b, l, j, f: (b, l, 0, j)),
            pl.BlockSpec((1, 1, 1), lambda b, l, j, f: (b, 0, 0)),
        ],
    )

    ga_part, gb_part, psi = pl.pallas_call(
        functools.partial(_dense_kernel_batched, gamma=float(gamma)),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, grid[1], tile_l, g), jnp.float32),
            jax.ShapeDtypeStruct((B, grid[1], 1, n), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(flags, alpha_g, beta[:, None, :], C4, group_rows(tau_g, tile_l))

    return (
        ga_part.reshape(B, -1),
        jnp.sum(gb_part[:, :, 0], axis=1),
        psi[:, 0, 0],
    )


def build_batch_tile_schedule(
    flags: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Compact (B, Lt, Nt) flags into one concatenated active-tile list.

    Returns ``(sched (3, B*T) int32, num_active () int32)`` where
    ``sched[:, s] = (b, l, j)`` of the s-th surviving tile in
    (problem-major, then row-major) order and ``num_active`` is the TOTAL
    surviving count across the batch.  Entries past ``num_active`` repeat
    the last surviving coordinate (pipeline lookahead lands on a resident
    block).  Because the list concatenates per-problem schedules, a
    heavily-screened problem contributes few steps — the batch never pads
    to its worst member.
    """
    B, Lt, Nt = flags.shape
    T = Lt * Nt
    BT = B * T
    flat = flags.reshape(-1) != 0
    num_active = jnp.sum(flat).astype(jnp.int32)
    pos = jnp.cumsum(flat).astype(jnp.int32) - 1      # rank among survivors
    idx = jnp.arange(BT, dtype=jnp.int32)
    dest = jnp.where(flat, pos, BT)                   # dead tiles -> dropped
    order = jnp.zeros((BT,), jnp.int32).at[dest].set(idx, mode="drop")
    last = jnp.where(num_active > 0, order[jnp.maximum(num_active - 1, 0)], 0)
    order = jnp.where(idx < num_active, order, last)
    sched = jnp.stack([order // T, (order % T) // Nt, order % Nt])
    return sched, num_active


def _compact_kernel_batched(sched_ref, nact_ref, alpha_ref, beta_ref, c_ref,
                            tau_ref, ga_ref, gb_ref, psi_ref, steps_ref,
                            *, gamma: float):
    s = pl.program_id(0)

    @pl.when(s == 0)
    def _init_steps():
        steps_ref[...] = jnp.zeros_like(steps_ref)

    steps_ref[...] += 1

    alpha = alpha_ref[0].astype(jnp.float32)             # (TL, g)
    beta = beta_ref[0].astype(jnp.float32)               # (TN,)
    c = c_ref[0].astype(jnp.float32)                     # (TL, g, TN)
    tau = tau_ref[...].astype(jnp.float32)               # (TL, 1)
    t, psi = _gradpsi_tile(alpha, beta, c, tau, gamma=gamma)
    # per-step slots: every visited block is written exactly once, so no
    # cross-step accumulation state and no uninitialized revisits.
    ga_ref[...] = jnp.sum(t, axis=2)[None]               # (1, TL, g)
    gb_ref[...] = jnp.sum(t, axis=(0, 1))[None, :]       # (1, TN)
    psi_ref[...] = jnp.reshape(psi, (1, 1))


@functools.partial(
    jax.jit,
    static_argnames=("num_groups", "group_size", "gamma",
                     "tile_l", "tile_n", "interpret"),
)
def gradpsi_pallas_compact_batched(
    alpha: jnp.ndarray,        # (B, m_pad) fp32
    beta: jnp.ndarray,         # (B, n) fp32
    C: jnp.ndarray,            # (B, m_pad, n) fp32 or bf16
    sched: jnp.ndarray,        # (3, B*T) int32 from build_batch_tile_schedule
    num_active: jnp.ndarray,   # () int32 TOTAL surviving-tile count
    *,
    num_groups: int,
    group_size: int,
    tau,
    gamma: float,
    tile_l: int = 0,
    tile_n: int = DEFAULT_TILE_N,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Compacted-grid kernel over B problems: ONE dynamic grid of exactly
    ``max(num_active, 1)`` steps covering the whole batch's surviving tiles.

    Returns (T_rowsum (B, m_pad), T_colsum (B, n), psi (B,), steps ()).
    With ``num_active == 0`` one sentinel step runs (a grid cannot be
    empty) and its outputs are masked to exact zeros.  ``tau`` (scalar or
    per-group ``(L,)``) is shared batch-wide, gathered per scheduled tile.
    """
    _record_launch("gradpsi_pallas_compact_batched")
    L, g = num_groups, group_size
    B, n = beta.shape
    tau_g = tau_row(tau, L)
    if tile_l == 0:
        tile_l = pick_tile_l(g, tile_n, jnp.dtype(C.dtype).itemsize)
    assert L % tile_l == 0 and n % tile_n == 0, (L, tile_l, n, tile_n)
    Lt, Nt = L // tile_l, n // tile_n
    BT = B * Lt * Nt
    assert sched.shape == (3, BT), (sched.shape, (3, BT))

    alpha_g = alpha.reshape(B, Lt, tile_l, g)
    C4 = C.reshape(B, L, g, n)
    num_active = num_active.astype(jnp.int32)
    nact = num_active.reshape(1)
    num_steps = jnp.maximum(num_active, 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_steps,),
        in_specs=[
            pl.BlockSpec((1, None, tile_l, g),
                         lambda s, sc, na: (sc[0, s], sc[1, s], 0, 0)),
            pl.BlockSpec((None, 1, tile_n),
                         lambda s, sc, na: (sc[0, s], 0, sc[2, s])),
            pl.BlockSpec((1, tile_l, g, tile_n),
                         lambda s, sc, na: (sc[0, s], sc[1, s], 0, sc[2, s])),
            pl.BlockSpec((None, tile_l, 1),
                         lambda s, sc, na: (sc[1, s], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, tile_l, g), lambda s, sc, na: (s, 0, 0)),
            pl.BlockSpec((None, 1, tile_n), lambda s, sc, na: (s, 0, 0)),
            pl.BlockSpec((None, 1, 1), lambda s, sc, na: (s, 0, 0)),
            pl.BlockSpec((1, 1), lambda s, sc, na: (0, 0)),
        ],
    )

    ga_steps, gb_steps, psi_steps, steps = pl.pallas_call(
        functools.partial(_compact_kernel_batched, gamma=float(gamma)),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((BT, tile_l, g), jnp.float32),
            jax.ShapeDtypeStruct((BT, 1, tile_n), jnp.float32),
            jax.ShapeDtypeStruct((BT, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        compiler_params=_COMPACT_PARAMS,
        interpret=interpret,
    )(sched, nact, alpha_g, beta[:, None, :], C4, group_rows(tau_g, tile_l))
    gb_steps, psi_steps = gb_steps[:, 0], psi_steps[:, 0]

    # assemble: slots past num_active were never visited (garbage) — route
    # them to an out-of-range segment so the scatter drops them.  Segments
    # are flattened (b, l) / (b, j) / (b,) ids; each problem's steps stay in
    # schedule order, so per-problem accumulation order is batch-invariant.
    idx = jnp.arange(BT, dtype=jnp.int32)
    valid = idx < num_active
    seg_ga = jnp.where(valid, sched[0] * Lt + sched[1], B * Lt)
    seg_gb = jnp.where(valid, sched[0] * Nt + sched[2], B * Nt)
    seg_psi = jnp.where(valid, sched[0], B)
    ga = jnp.zeros((B * Lt, tile_l, g), jnp.float32).at[seg_ga].add(
        ga_steps, mode="drop"
    )
    gb = jnp.zeros((B * Nt, tile_n), jnp.float32).at[seg_gb].add(
        gb_steps, mode="drop"
    )
    psi = jnp.zeros((B,), jnp.float32).at[seg_psi].add(
        psi_steps[:, 0], mode="drop"
    )
    return ga.reshape(B, -1), gb.reshape(B, -1), psi, steps[0, 0]


# -- materialization-free (factorized squared-l2) variants ---------------------
#
# Instead of a dense (m_pad, n) C operand, these kernels take the raw sample
# blocks and precomputed squared norms of a SquaredL2Geometry (docs/geometry.md)
# and rebuild each cost tile in VMEM via the factorization
#     c[i, j] = max(|x_i|^2 + |y_j|^2 - 2 <x_i, y_j>, 0)
# so HBM traffic per tile is O((tile_l*g + tile_n) * d) instead of
# O(tile_l*g*tile_n).  `factorized_cost_tile` below is THE single definition of
# the recipe: geometry.py materializes with the same function, which is what
# makes the on-the-fly route bitwise-equal to the materialized-dense route.


def factorized_cost_tile(x, x_sq, y, y_sq):
    """On-the-fly squared-l2 cost tile: ``max(x2 + y2 - 2<x,y>, 0)``.

    ``x`` is ``(..., R, d)`` with matching ``x_sq (..., R)``; ``y`` is
    ``(TN, d)`` with ``y_sq (TN,)``; returns ``(..., R, TN)``.  The inner
    product is an elementwise product reduced over ``d`` (NOT a matmul), so
    every output element sees the identical f32 operation sequence no matter
    how the caller tiles or chunks — the bitwise contract between the Pallas
    kernels and :meth:`repro.ot.geometry.SquaredL2Geometry.materialize`.
    The leading axes are broadcast, never flattened: Mosaic cannot fold a
    (TILE_L, g) block with g off the sublane tiling into one row axis.
    """
    xy = jnp.sum(x[..., :, None, :] * y, axis=-1)
    return jnp.maximum(x_sq[..., None] + y_sq - 2.0 * xy, 0.0)


def pick_tile_l_factorized(g: int, tile_n: int, d: int,
                           dtype_bytes: int = 4) -> int:
    """Largest TILE_L (power of two, <=8) whose factorized tile fits VMEM.

    Same explicit byte model as :func:`pick_tile_l`
    (:func:`tile_working_set_bytes` with ``d`` set): the working set swaps
    the dense cost tile for the ``(TILE_L, g, TILE_N, d)`` product
    intermediate of :func:`factorized_cost_tile` plus its sample blocks.
    """
    for cand in (8, 4, 2, 1):
        if tile_working_set_bytes(cand, g, tile_n, d=d,
                                  dtype_bytes=dtype_bytes) <= VMEM_BUDGET_BYTES:
            return cand
    return 1


def resolve_tile_l_factorized(L: int, g: int, tile_n: int, d: int,
                              dtype_bytes: int = 4) -> int:
    """VMEM-fitting factorized TILE_L, halved until it divides L."""
    t = pick_tile_l_factorized(g, tile_n, d, dtype_bytes)
    t = min(t, L)
    while t > 1 and L % t:
        t //= 2
    return max(t, 1)


def _dense_kernel_fact(flags_ref, alpha_ref, beta_ref, x_ref, xsq_ref,
                       y_ref, ysq_ref, tau_ref,
                       ga_ref, gb_ref, psi_ref, *, gamma: float):
    l = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init_ga_f():
        ga_ref[...] = jnp.zeros_like(ga_ref)

    @pl.when(jnp.logical_and(l == 0, j == 0))
    def _init_psi_f():
        psi_ref[...] = jnp.zeros_like(psi_ref)

    gb_ref[...] = jnp.zeros_like(gb_ref)

    flag = flags_ref[l, j]

    @pl.when(flag != 0)
    def _compute_f():
        alpha = alpha_ref[...].astype(jnp.float32)       # (TL, g)
        beta = beta_ref[0].astype(jnp.float32)           # (TN,)
        c = factorized_cost_tile(
            x_ref[...].astype(jnp.float32),              # (TL, g, d)
            xsq_ref[...].astype(jnp.float32),            # (TL, g)
            y_ref[...].astype(jnp.float32),              # (TN, d)
            ysq_ref[0].astype(jnp.float32),              # (TN,)
        )
        tau = tau_ref[...].astype(jnp.float32)           # (TL, 1)
        t, psi = _gradpsi_tile(alpha, beta, c, tau, gamma=gamma)
        psi_ref[...] += jnp.reshape(psi, (1, 1))
        ga_ref[...] += jnp.sum(t, axis=2)                # (TL, g)
        gb_ref[...] = jnp.sum(t, axis=(0, 1))[None, :]   # (1, TN)


@functools.partial(
    jax.jit,
    static_argnames=("num_groups", "group_size", "gamma",
                     "tile_l", "tile_n", "interpret"),
)
def gradpsi_fact_pallas(
    alpha: jnp.ndarray,        # (m_pad,) fp32
    beta: jnp.ndarray,         # (n,) fp32
    x: jnp.ndarray,            # (m_pad, d) fp32 scaled source samples
    x_sq: jnp.ndarray,         # (m_pad,) fp32 scaled squared norms
    y: jnp.ndarray,            # (n, d) fp32 scaled target samples
    y_sq: jnp.ndarray,         # (n,) fp32 scaled squared norms
    flags: jnp.ndarray,        # (L_tiles, N_tiles) int32 tile skip flags
    *,
    num_groups: int,
    group_size: int,
    tau,
    gamma: float,
    tile_l: int = 0,
    tile_n: int = DEFAULT_TILE_N,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Dense-grid factorized kernel: cost tiles built in VMEM from samples.

    Same outputs and skip semantics as :func:`gradpsi_pallas`; the C operand
    is replaced by ``(x, x_sq, y, y_sq)`` blocked operands.  Skipped tiles
    remap the column-indexed ``y``/``y_sq`` blocks to column 0 so the DMA is
    elided exactly like the dense kernel's C tile.
    """
    _record_launch("gradpsi_fact_pallas")
    L, g = num_groups, group_size
    n = beta.shape[0]
    d = x.shape[-1]
    tau_g = tau_row(tau, L)
    if tile_l == 0:
        tile_l = pick_tile_l_factorized(g, tile_n, d,
                                        jnp.dtype(x.dtype).itemsize)
    assert L % tile_l == 0 and n % tile_n == 0, (L, tile_l, n, tile_n)
    grid = (L // tile_l, n // tile_n)
    assert flags.shape == grid, (flags.shape, grid)

    alpha_g = alpha.reshape(grid[0], tile_l, g)
    x3 = x.reshape(L, g, d)
    xsq_g = x_sq.reshape(grid[0], tile_l, g)

    def y_index(l, j, flags_ref):
        active = flags_ref[l, j] != 0
        return (jnp.where(active, j, 0), 0)

    def ysq_index(l, j, flags_ref):
        active = flags_ref[l, j] != 0
        return (0, jnp.where(active, j, 0))

    row_g = pl.BlockSpec((None, tile_l, g), lambda l, j, f: (l, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            row_g,
            pl.BlockSpec((1, tile_n), lambda l, j, f: (0, j)),
            pl.BlockSpec((tile_l, g, d), lambda l, j, f: (l, 0, 0)),
            row_g,
            pl.BlockSpec((tile_n, d), y_index),
            pl.BlockSpec((1, tile_n), ysq_index),
            pl.BlockSpec((None, tile_l, 1), lambda l, j, f: (l, 0, 0)),
        ],
        out_specs=[
            row_g,
            pl.BlockSpec((None, 1, tile_n), lambda l, j, f: (l, 0, j)),
            pl.BlockSpec((1, 1), lambda l, j, f: (0, 0)),
        ],
    )

    ga_part, gb_part, psi = pl.pallas_call(
        functools.partial(_dense_kernel_fact, gamma=float(gamma)),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((grid[0], tile_l, g), jnp.float32),
            jax.ShapeDtypeStruct((grid[0], 1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(flags, alpha_g, beta[None, :], x3, xsq_g, y, y_sq[None, :],
      group_rows(tau_g, tile_l))

    return ga_part.reshape(-1), jnp.sum(gb_part[:, 0], axis=0), psi[0, 0]


def _compact_kernel_fact(sched_ref, nact_ref, alpha_ref, beta_ref, x_ref,
                         xsq_ref, y_ref, ysq_ref, tau_ref,
                         ga_ref, gb_ref, psi_ref, steps_ref,
                         *, gamma: float):
    s = pl.program_id(0)

    @pl.when(s == 0)
    def _init_steps_f():
        steps_ref[...] = jnp.zeros_like(steps_ref)

    steps_ref[...] += 1

    alpha = alpha_ref[...].astype(jnp.float32)           # (TL, g)
    beta = beta_ref[0].astype(jnp.float32)               # (TN,)
    c = factorized_cost_tile(
        x_ref[...].astype(jnp.float32),
        xsq_ref[...].astype(jnp.float32),
        y_ref[...].astype(jnp.float32),
        ysq_ref[0].astype(jnp.float32),
    )
    tau = tau_ref[...].astype(jnp.float32)               # (TL, 1)
    t, psi = _gradpsi_tile(alpha, beta, c, tau, gamma=gamma)
    ga_ref[...] = jnp.sum(t, axis=2)[None]               # (1, TL, g)
    gb_ref[...] = jnp.sum(t, axis=(0, 1))[None, :]       # (1, TN)
    psi_ref[...] = jnp.reshape(psi, (1, 1))


@functools.partial(
    jax.jit,
    static_argnames=("num_groups", "group_size", "gamma",
                     "tile_l", "tile_n", "interpret"),
)
def gradpsi_fact_pallas_compact(
    alpha: jnp.ndarray,        # (m_pad,) fp32
    beta: jnp.ndarray,         # (n,) fp32
    x: jnp.ndarray,            # (m_pad, d) fp32
    x_sq: jnp.ndarray,         # (m_pad,) fp32
    y: jnp.ndarray,            # (n, d) fp32
    y_sq: jnp.ndarray,         # (n,) fp32
    sched: jnp.ndarray,        # (2, T) int32 from build_tile_schedule
    num_active: jnp.ndarray,   # () int32 surviving-tile count
    *,
    num_groups: int,
    group_size: int,
    tau,
    gamma: float,
    tile_l: int = 0,
    tile_n: int = DEFAULT_TILE_N,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Compacted-grid factorized kernel: steps scale with surviving tiles.

    Same contract as :func:`gradpsi_pallas_compact` with the C operand
    replaced by ``(x, x_sq, y, y_sq)`` blocked operands.
    """
    _record_launch("gradpsi_fact_pallas_compact")
    L, g = num_groups, group_size
    n = beta.shape[0]
    d = x.shape[-1]
    tau_g = tau_row(tau, L)
    if tile_l == 0:
        tile_l = pick_tile_l_factorized(g, tile_n, d,
                                        jnp.dtype(x.dtype).itemsize)
    assert L % tile_l == 0 and n % tile_n == 0, (L, tile_l, n, tile_n)
    Lt, Nt = L // tile_l, n // tile_n
    T = Lt * Nt
    assert sched.shape == (2, T), (sched.shape, (2, T))

    alpha_g = alpha.reshape(Lt, tile_l, g)
    x3 = x.reshape(L, g, d)
    xsq_g = x_sq.reshape(Lt, tile_l, g)
    num_active = num_active.astype(jnp.int32)
    nact = num_active.reshape(1)
    num_steps = jnp.maximum(num_active, 1)

    row_g = pl.BlockSpec((None, tile_l, g),
                         lambda s, sc, na: (sc[0, s], 0, 0))
    col = pl.BlockSpec((1, tile_n), lambda s, sc, na: (0, sc[1, s]))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_steps,),
        in_specs=[
            row_g,
            col,
            pl.BlockSpec((tile_l, g, d), lambda s, sc, na: (sc[0, s], 0, 0)),
            row_g,
            pl.BlockSpec((tile_n, d), lambda s, sc, na: (sc[1, s], 0)),
            col,
            pl.BlockSpec((None, tile_l, 1),
                         lambda s, sc, na: (sc[0, s], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, tile_l, g), lambda s, sc, na: (s, 0, 0)),
            pl.BlockSpec((None, 1, tile_n), lambda s, sc, na: (s, 0, 0)),
            pl.BlockSpec((None, 1, 1), lambda s, sc, na: (s, 0, 0)),
            pl.BlockSpec((1, 1), lambda s, sc, na: (0, 0)),
        ],
    )

    ga_steps, gb_steps, psi_steps, steps = pl.pallas_call(
        functools.partial(_compact_kernel_fact, gamma=float(gamma)),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((T, tile_l, g), jnp.float32),
            jax.ShapeDtypeStruct((T, 1, tile_n), jnp.float32),
            jax.ShapeDtypeStruct((T, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        compiler_params=_COMPACT_PARAMS,
        interpret=interpret,
    )(sched, nact, alpha_g, beta[None, :], x3, xsq_g, y, y_sq[None, :],
      group_rows(tau_g, tile_l))
    gb_steps, psi_steps = gb_steps[:, 0], psi_steps[:, 0]

    idx = jnp.arange(T, dtype=jnp.int32)
    valid = idx < num_active
    seg_l = jnp.where(valid, sched[0], Lt)
    seg_n = jnp.where(valid, sched[1], Nt)
    ga = jnp.zeros((Lt, tile_l, g), jnp.float32).at[seg_l].add(
        ga_steps, mode="drop"
    )
    gb = jnp.zeros((Nt, tile_n), jnp.float32).at[seg_n].add(
        gb_steps, mode="drop"
    )
    psi = jnp.sum(jnp.where(valid[:, None], psi_steps, 0.0))
    return ga.reshape(-1), gb.reshape(-1), psi, steps[0, 0]


def _dense_kernel_fact_batched(flags_ref, alpha_ref, beta_ref, x_ref, xsq_ref,
                               y_ref, ysq_ref, tau_ref,
                               ga_ref, gb_ref, psi_ref, *, gamma: float):
    bi = pl.program_id(0)
    l = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init_ga_fb():
        ga_ref[...] = jnp.zeros_like(ga_ref)

    @pl.when(jnp.logical_and(l == 0, j == 0))
    def _init_psi_fb():
        psi_ref[...] = jnp.zeros_like(psi_ref)

    gb_ref[...] = jnp.zeros_like(gb_ref)

    flag = flags_ref[bi, l, j]

    @pl.when(flag != 0)
    def _compute_fb():
        alpha = alpha_ref[0].astype(jnp.float32)         # (TL, g)
        beta = beta_ref[0].astype(jnp.float32)           # (TN,)
        c = factorized_cost_tile(
            x_ref[0].astype(jnp.float32),                # (TL, g, d)
            xsq_ref[0].astype(jnp.float32),              # (TL, g)
            y_ref[0].astype(jnp.float32),                # (TN, d)
            ysq_ref[0].astype(jnp.float32),              # (TN,)
        )
        tau = tau_ref[...].astype(jnp.float32)           # (TL, 1)
        t, psi = _gradpsi_tile(alpha, beta, c, tau, gamma=gamma)
        psi_ref[...] += jnp.reshape(psi, (1, 1, 1))
        ga_ref[...] += jnp.sum(t, axis=2)[None]          # (1, TL, g)
        gb_ref[...] = jnp.sum(t, axis=(0, 1))[None, None, :]  # (1, 1, TN)


@functools.partial(
    jax.jit,
    static_argnames=("num_groups", "group_size", "gamma",
                     "tile_l", "tile_n", "interpret"),
)
def gradpsi_fact_pallas_batched(
    alpha: jnp.ndarray,        # (B, m_pad) fp32
    beta: jnp.ndarray,         # (B, n) fp32
    x: jnp.ndarray,            # (B, m_pad, d) fp32
    x_sq: jnp.ndarray,         # (B, m_pad) fp32
    y: jnp.ndarray,            # (B, n, d) fp32
    y_sq: jnp.ndarray,         # (B, n) fp32
    flags: jnp.ndarray,        # (B, L_tiles, N_tiles) int32 tile skip flags
    *,
    num_groups: int,
    group_size: int,
    tau,
    gamma: float,
    tile_l: int = 0,
    tile_n: int = DEFAULT_TILE_N,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Dense-grid factorized kernel over B problems: grid (B, Lt, Nt).

    Per-problem semantics identical to :func:`gradpsi_fact_pallas`.
    """
    _record_launch("gradpsi_fact_pallas_batched")
    L, g = num_groups, group_size
    B, n = beta.shape
    d = x.shape[-1]
    tau_g = tau_row(tau, L)
    if tile_l == 0:
        tile_l = pick_tile_l_factorized(g, tile_n, d,
                                        jnp.dtype(x.dtype).itemsize)
    assert L % tile_l == 0 and n % tile_n == 0, (L, tile_l, n, tile_n)
    grid = (B, L // tile_l, n // tile_n)
    assert flags.shape == grid, (flags.shape, grid)

    alpha_g = alpha.reshape(B, grid[1], tile_l, g)
    x4 = x.reshape(B, L, g, d)
    xsq_g = x_sq.reshape(B, grid[1], tile_l, g)

    def y_index(b, l, j, flags_ref):
        active = flags_ref[b, l, j] != 0
        return (b, jnp.where(active, j, 0), 0)

    def ysq_index(b, l, j, flags_ref):
        active = flags_ref[b, l, j] != 0
        return (b, 0, jnp.where(active, j, 0))

    brow_g = pl.BlockSpec((1, None, tile_l, g),
                          lambda b, l, j, f: (b, l, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            brow_g,
            pl.BlockSpec((None, 1, tile_n), lambda b, l, j, f: (b, 0, j)),
            pl.BlockSpec((1, tile_l, g, d), lambda b, l, j, f: (b, l, 0, 0)),
            brow_g,
            pl.BlockSpec((1, tile_n, d), y_index),
            pl.BlockSpec((None, 1, tile_n), ysq_index),
            pl.BlockSpec((None, tile_l, 1), lambda b, l, j, f: (l, 0, 0)),
        ],
        out_specs=[
            brow_g,
            pl.BlockSpec((1, None, 1, tile_n),
                         lambda b, l, j, f: (b, l, 0, j)),
            pl.BlockSpec((1, 1, 1), lambda b, l, j, f: (b, 0, 0)),
        ],
    )

    ga_part, gb_part, psi = pl.pallas_call(
        functools.partial(_dense_kernel_fact_batched, gamma=float(gamma)),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, grid[1], tile_l, g), jnp.float32),
            jax.ShapeDtypeStruct((B, grid[1], 1, n), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(flags, alpha_g, beta[:, None, :], x4, xsq_g, y, y_sq[:, None, :],
      group_rows(tau_g, tile_l))

    return (
        ga_part.reshape(B, -1),
        jnp.sum(gb_part[:, :, 0], axis=1),
        psi[:, 0, 0],
    )


def _compact_kernel_fact_batched(sched_ref, nact_ref, alpha_ref, beta_ref,
                                 x_ref, xsq_ref, y_ref, ysq_ref, tau_ref,
                                 ga_ref, gb_ref, psi_ref, steps_ref,
                                 *, gamma: float):
    s = pl.program_id(0)

    @pl.when(s == 0)
    def _init_steps_fb():
        steps_ref[...] = jnp.zeros_like(steps_ref)

    steps_ref[...] += 1

    alpha = alpha_ref[0].astype(jnp.float32)             # (TL, g)
    beta = beta_ref[0].astype(jnp.float32)               # (TN,)
    c = factorized_cost_tile(
        x_ref[0].astype(jnp.float32),
        xsq_ref[0].astype(jnp.float32),
        y_ref[0].astype(jnp.float32),
        ysq_ref[0].astype(jnp.float32),
    )
    tau = tau_ref[...].astype(jnp.float32)               # (TL, 1)
    t, psi = _gradpsi_tile(alpha, beta, c, tau, gamma=gamma)
    ga_ref[...] = jnp.sum(t, axis=2)[None]               # (1, TL, g)
    gb_ref[...] = jnp.sum(t, axis=(0, 1))[None, :]       # (1, TN)
    psi_ref[...] = jnp.reshape(psi, (1, 1))


@functools.partial(
    jax.jit,
    static_argnames=("num_groups", "group_size", "gamma",
                     "tile_l", "tile_n", "interpret"),
)
def gradpsi_fact_pallas_compact_batched(
    alpha: jnp.ndarray,        # (B, m_pad) fp32
    beta: jnp.ndarray,         # (B, n) fp32
    x: jnp.ndarray,            # (B, m_pad, d) fp32
    x_sq: jnp.ndarray,         # (B, m_pad) fp32
    y: jnp.ndarray,            # (B, n, d) fp32
    y_sq: jnp.ndarray,         # (B, n) fp32
    sched: jnp.ndarray,        # (3, B*T) int32 from build_batch_tile_schedule
    num_active: jnp.ndarray,   # () int32 TOTAL surviving-tile count
    *,
    num_groups: int,
    group_size: int,
    tau,
    gamma: float,
    tile_l: int = 0,
    tile_n: int = DEFAULT_TILE_N,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Compacted-grid factorized kernel over B problems (one dynamic grid).

    Same contract as :func:`gradpsi_pallas_compact_batched` with the C
    operand replaced by ``(x, x_sq, y, y_sq)`` blocked operands.
    """
    _record_launch("gradpsi_fact_pallas_compact_batched")
    L, g = num_groups, group_size
    B, n = beta.shape
    d = x.shape[-1]
    tau_g = tau_row(tau, L)
    if tile_l == 0:
        tile_l = pick_tile_l_factorized(g, tile_n, d,
                                        jnp.dtype(x.dtype).itemsize)
    assert L % tile_l == 0 and n % tile_n == 0, (L, tile_l, n, tile_n)
    Lt, Nt = L // tile_l, n // tile_n
    BT = B * Lt * Nt
    assert sched.shape == (3, BT), (sched.shape, (3, BT))

    alpha_g = alpha.reshape(B, Lt, tile_l, g)
    x4 = x.reshape(B, L, g, d)
    xsq_g = x_sq.reshape(B, Lt, tile_l, g)
    num_active = num_active.astype(jnp.int32)
    nact = num_active.reshape(1)
    num_steps = jnp.maximum(num_active, 1)

    brow_g = pl.BlockSpec((1, None, tile_l, g),
                          lambda s, sc, na: (sc[0, s], sc[1, s], 0, 0))
    bcol = pl.BlockSpec((None, 1, tile_n),
                        lambda s, sc, na: (sc[0, s], 0, sc[2, s]))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_steps,),
        in_specs=[
            brow_g,
            bcol,
            pl.BlockSpec((1, tile_l, g, d),
                         lambda s, sc, na: (sc[0, s], sc[1, s], 0, 0)),
            brow_g,
            pl.BlockSpec((1, tile_n, d),
                         lambda s, sc, na: (sc[0, s], sc[2, s], 0)),
            bcol,
            pl.BlockSpec((None, tile_l, 1),
                         lambda s, sc, na: (sc[1, s], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, tile_l, g), lambda s, sc, na: (s, 0, 0)),
            pl.BlockSpec((None, 1, tile_n), lambda s, sc, na: (s, 0, 0)),
            pl.BlockSpec((None, 1, 1), lambda s, sc, na: (s, 0, 0)),
            pl.BlockSpec((1, 1), lambda s, sc, na: (0, 0)),
        ],
    )

    ga_steps, gb_steps, psi_steps, steps = pl.pallas_call(
        functools.partial(_compact_kernel_fact_batched, gamma=float(gamma)),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((BT, tile_l, g), jnp.float32),
            jax.ShapeDtypeStruct((BT, 1, tile_n), jnp.float32),
            jax.ShapeDtypeStruct((BT, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        compiler_params=_COMPACT_PARAMS,
        interpret=interpret,
    )(sched, nact, alpha_g, beta[:, None, :], x4, xsq_g, y, y_sq[:, None, :],
      group_rows(tau_g, tile_l))
    gb_steps, psi_steps = gb_steps[:, 0], psi_steps[:, 0]

    idx = jnp.arange(BT, dtype=jnp.int32)
    valid = idx < num_active
    seg_ga = jnp.where(valid, sched[0] * Lt + sched[1], B * Lt)
    seg_gb = jnp.where(valid, sched[0] * Nt + sched[2], B * Nt)
    seg_psi = jnp.where(valid, sched[0], B)
    ga = jnp.zeros((B * Lt, tile_l, g), jnp.float32).at[seg_ga].add(
        ga_steps, mode="drop"
    )
    gb = jnp.zeros((B * Nt, tile_n), jnp.float32).at[seg_gb].add(
        gb_steps, mode="drop"
    )
    psi = jnp.zeros((B,), jnp.float32).at[seg_psi].add(
        psi_steps[:, 0], mode="drop"
    )
    return ga.reshape(B, -1), gb.reshape(B, -1), psi, steps[0, 0]


# -- fused screen+gradient mega-kernels (DESIGN.md §10) ------------------------
#
# One launch per oracle evaluation: the screening verdict is computed
# IN-REGISTER at the top of every grid step (the same _verdict_tile math the
# standalone screen kernel runs on identically-blocked operands), the tile's
# gradient work is gated on the verdict's per-tile OR, and that OR lands in a
# (L_tiles, N_tiles) flag output replacing the standalone screen launch.  The
# screen operands are the padded snapshot tiles (z/k/o/act/sqrt_g, fixed
# within a round) plus the O(L + n) per-eval delta norms; a tile whose bound
# test fails writes zeros without its F/T working set ever leaving VMEM.
# There is deliberately NO fused compact mode: a compact schedule must be
# built from flags that exist before launch, which is exactly the standalone
# screen pass the fused route removes (and a stale snapshot-point schedule
# would be unsafe — snapshot-ZERO tiles can go live as the deltas grow).


def _fused_dense_kernel(alpha_ref, beta_ref, c_ref, tau_ref, z_ref, k_ref,
                        o_ref, act_ref, dap_ref, daf_ref, dan_ref, db_ref,
                        sg_ref, ga_ref, gb_ref, psi_ref, flag_ref,
                        *, gamma: float):
    l = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init_ga_fu():
        ga_ref[...] = jnp.zeros_like(ga_ref)

    @pl.when(jnp.logical_and(l == 0, j == 0))
    def _init_psi_fu():
        psi_ref[...] = jnp.zeros_like(psi_ref)

    gb_ref[...] = jnp.zeros_like(gb_ref)

    tau = tau_ref[...].astype(jnp.float32)               # (TL, 1)
    v = _verdict_tile(
        z_ref[...], k_ref[...], o_ref[...], act_ref[...],
        dap_ref[...], daf_ref[...], dan_ref[...],
        db_ref[0], sg_ref[...], tau,
    )
    flag = jnp.any(v != ZERO).astype(jnp.int32)
    flag_ref[...] = jnp.reshape(flag, (1, 1))

    @pl.when(flag != 0)
    def _compute_fu():
        alpha = alpha_ref[...].astype(jnp.float32)       # (TL, g)
        beta = beta_ref[0].astype(jnp.float32)           # (TN,)
        c = c_ref[...].astype(jnp.float32)               # (TL, g, TN)
        t, psi = _gradpsi_tile(alpha, beta, c, tau, gamma=gamma)
        psi_ref[...] += jnp.reshape(psi, (1, 1))
        ga_ref[...] += jnp.sum(t, axis=2)                # (TL, g)
        gb_ref[...] = jnp.sum(t, axis=(0, 1))[None, :]   # (1, TN)


@functools.partial(
    jax.jit,
    static_argnames=("num_groups", "group_size", "gamma",
                     "tile_l", "tile_n", "interpret"),
)
def gradpsi_fused_pallas(
    alpha: jnp.ndarray,        # (m_pad,) fp32
    beta: jnp.ndarray,         # (n,) fp32
    C: jnp.ndarray,            # (m_pad, n) fp32 or bf16
    z: jnp.ndarray,            # (L, n) fp32 snapshot upper-bound matrix
    k: jnp.ndarray,            # (L, n) fp32 snapshot full-norm matrix
    o: jnp.ndarray,            # (L, n) fp32 snapshot negative-norm matrix
    active: jnp.ndarray,       # (L, n) int8/bool persistent set N
    da_plus: jnp.ndarray,      # (L,)  ||[d_alpha_[l]]_+||
    da_full: jnp.ndarray,      # (L,)  ||d_alpha_[l]||
    da_neg: jnp.ndarray,       # (L,)  ||[d_alpha_[l]]_-||
    db: jnp.ndarray,           # (n,)  d_beta
    sqrt_g: jnp.ndarray,       # (L,)
    *,
    num_groups: int,
    group_size: int,
    tau,
    gamma: float,
    tile_l: int = 0,
    tile_n: int = DEFAULT_TILE_N,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused dense-grid kernel: verdicts + gradient in ONE launch.

    Returns (T_rowsum (m_pad,), T_colsum (n,), psi, flags (Lt, Nt) int32)
    where ``flags`` is bitwise-identical to the standalone screen kernel's
    tile-flag output on the same operands and the gradient triple is
    bitwise-identical to :func:`gradpsi_pallas` fed those flags.  All
    operands must be tile-padded (ops.py handles padding); screen operands
    follow :func:`repro.kernels.screen.screen_pallas`.
    """
    _record_launch("gradpsi_fused_pallas")
    L, g = num_groups, group_size
    n = beta.shape[0]
    tau_g = tau_row(tau, L)
    if tile_l == 0:
        tile_l = pick_tile_l(g, tile_n, jnp.dtype(C.dtype).itemsize)
    assert L % tile_l == 0 and n % tile_n == 0, (L, tile_l, n, tile_n)
    grid = (L // tile_l, n // tile_n)
    assert z.shape == (L, n), (z.shape, (L, n))

    alpha_g = alpha.reshape(grid[0], tile_l, g)
    C3 = C.reshape(L, g, n)
    rows = functools.partial(group_rows, tile_l=tile_l)
    tiles = functools.partial(group_tiles, tile_l=tile_l)

    row = pl.BlockSpec((None, tile_l, 1), lambda l, j: (l, 0, 0))
    row_g = pl.BlockSpec((None, tile_l, g), lambda l, j: (l, 0, 0))
    col = pl.BlockSpec((1, tile_n), lambda l, j: (0, j))
    mat = pl.BlockSpec((None, tile_l, tile_n), lambda l, j: (l, 0, j))

    ga_part, gb_part, psi, flags = pl.pallas_call(
        functools.partial(_fused_dense_kernel, gamma=float(gamma)),
        grid=grid,
        in_specs=[
            row_g,                                                 # alpha
            col,                                                   # beta
            pl.BlockSpec((tile_l, g, tile_n), lambda l, j: (l, 0, j)),  # C
            row,                                                   # tau
            mat, mat, mat, mat,                                    # z k o act
            row, row, row,                                         # da norms
            col,                                                   # db
            row,                                                   # sqrt_g
        ],
        out_specs=[
            row_g,
            pl.BlockSpec((None, 1, tile_n), lambda l, j: (l, 0, j)),
            pl.BlockSpec((1, 1), lambda l, j: (0, 0)),
            pl.BlockSpec((None, None, 1, 1), lambda l, j: (l, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((grid[0], tile_l, g), jnp.float32),
            jax.ShapeDtypeStruct((grid[0], 1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct(grid + (1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(alpha_g, beta[None, :], C3, rows(tau_g), tiles(z), tiles(k), tiles(o),
      tiles(active.astype(jnp.int8)), rows(da_plus), rows(da_full),
      rows(da_neg), db[None, :], rows(sqrt_g))

    return (ga_part.reshape(-1), jnp.sum(gb_part[:, 0], axis=0), psi[0, 0],
            flags[:, :, 0, 0])


def _fused_dense_kernel_batched(alpha_ref, beta_ref, c_ref, tau_ref, z_ref,
                                k_ref, o_ref, act_ref, dap_ref, daf_ref,
                                dan_ref, db_ref, sg_ref,
                                ga_ref, gb_ref, psi_ref, flag_ref,
                                *, gamma: float):
    j = pl.program_id(2)
    lj0 = jnp.logical_and(pl.program_id(1) == 0, j == 0)

    @pl.when(j == 0)
    def _init_ga_fub():
        ga_ref[...] = jnp.zeros_like(ga_ref)

    @pl.when(lj0)
    def _init_psi_fub():
        psi_ref[...] = jnp.zeros_like(psi_ref)

    gb_ref[...] = jnp.zeros_like(gb_ref)

    tau = tau_ref[...].astype(jnp.float32)               # (TL, 1)
    v = _verdict_tile(
        z_ref[0], k_ref[0], o_ref[0], act_ref[0],
        dap_ref[0], daf_ref[0], dan_ref[0],
        db_ref[0], sg_ref[0], tau,
    )
    flag = jnp.any(v != ZERO).astype(jnp.int32)
    flag_ref[...] = jnp.reshape(flag, (1, 1))

    @pl.when(flag != 0)
    def _compute_fub():
        alpha = alpha_ref[0].astype(jnp.float32)         # (TL, g)
        beta = beta_ref[0].astype(jnp.float32)           # (TN,)
        c = c_ref[0].astype(jnp.float32)                 # (TL, g, TN)
        t, psi = _gradpsi_tile(alpha, beta, c, tau, gamma=gamma)
        psi_ref[...] += jnp.reshape(psi, (1, 1, 1))
        ga_ref[...] += jnp.sum(t, axis=2)[None]          # (1, TL, g)
        gb_ref[...] = jnp.sum(t, axis=(0, 1))[None, None, :]  # (1, 1, TN)


@functools.partial(
    jax.jit,
    static_argnames=("num_groups", "group_size", "gamma",
                     "tile_l", "tile_n", "interpret"),
)
def gradpsi_fused_pallas_batched(
    alpha: jnp.ndarray,        # (B, m_pad) fp32
    beta: jnp.ndarray,         # (B, n) fp32
    C: jnp.ndarray,            # (B, m_pad, n) fp32 or bf16
    z: jnp.ndarray,            # (B, L, n) fp32
    k: jnp.ndarray,            # (B, L, n) fp32
    o: jnp.ndarray,            # (B, L, n) fp32
    active: jnp.ndarray,       # (B, L, n) int8/bool
    da_plus: jnp.ndarray,      # (B, L)
    da_full: jnp.ndarray,      # (B, L)
    da_neg: jnp.ndarray,       # (B, L)
    db: jnp.ndarray,           # (B, n)
    sqrt_g: jnp.ndarray,       # (B, L)
    *,
    num_groups: int,
    group_size: int,
    tau,
    gamma: float,
    tile_l: int = 0,
    tile_n: int = DEFAULT_TILE_N,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused dense-grid kernel over B problems: grid (B, Lt, Nt), ONE launch.

    Returns (T_rowsum (B, m_pad), T_colsum (B, n), psi (B,), flags
    (B, Lt, Nt) int32).  Per-problem semantics identical to
    :func:`gradpsi_fused_pallas`; ``tau`` is shared batch-wide.
    """
    _record_launch("gradpsi_fused_pallas_batched")
    L, g = num_groups, group_size
    B, n = beta.shape
    tau_g = tau_row(tau, L)
    if tile_l == 0:
        tile_l = pick_tile_l(g, tile_n, jnp.dtype(C.dtype).itemsize)
    assert L % tile_l == 0 and n % tile_n == 0, (L, tile_l, n, tile_n)
    grid = (B, L // tile_l, n // tile_n)
    assert z.shape == (B, L, n), (z.shape, (B, L, n))

    alpha_g = alpha.reshape(B, grid[1], tile_l, g)
    C4 = C.reshape(B, L, g, n)
    rows = functools.partial(group_rows, tile_l=tile_l)
    tiles = functools.partial(group_tiles, tile_l=tile_l)

    brow = pl.BlockSpec((1, None, tile_l, 1), lambda b, l, j: (b, l, 0, 0))
    brow_g = pl.BlockSpec((1, None, tile_l, g), lambda b, l, j: (b, l, 0, 0))
    bcol = pl.BlockSpec((None, 1, tile_n), lambda b, l, j: (b, 0, j))
    bmat = pl.BlockSpec((1, None, tile_l, tile_n),
                        lambda b, l, j: (b, l, 0, j))

    ga_part, gb_part, psi, flags = pl.pallas_call(
        functools.partial(_fused_dense_kernel_batched, gamma=float(gamma)),
        grid=grid,
        in_specs=[
            brow_g,                                                   # alpha
            bcol,                                                     # beta
            pl.BlockSpec((1, tile_l, g, tile_n),
                         lambda b, l, j: (b, l, 0, j)),               # C
            pl.BlockSpec((None, tile_l, 1), lambda b, l, j: (l, 0, 0)),  # tau
            bmat, bmat, bmat, bmat,                                   # z k o act
            brow, brow, brow,                                         # da norms
            bcol,                                                     # db
            brow,                                                     # sqrt_g
        ],
        out_specs=[
            brow_g,
            pl.BlockSpec((1, None, 1, tile_n), lambda b, l, j: (b, l, 0, j)),
            pl.BlockSpec((1, 1, 1), lambda b, l, j: (b, 0, 0)),
            pl.BlockSpec((None, None, None, 1, 1),
                         lambda b, l, j: (b, l, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, grid[1], tile_l, g), jnp.float32),
            jax.ShapeDtypeStruct((B, grid[1], 1, n), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct(grid + (1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(alpha_g, beta[:, None, :], C4, rows(tau_g), tiles(z), tiles(k),
      tiles(o), tiles(active.astype(jnp.int8)), rows(da_plus),
      rows(da_full), rows(da_neg), db[:, None, :], rows(sqrt_g))

    return (
        ga_part.reshape(B, -1),
        jnp.sum(gb_part[:, :, 0], axis=1),
        psi[:, 0, 0],
        flags[..., 0, 0],
    )


def _fused_fact_kernel(alpha_ref, beta_ref, x_ref, xsq_ref, y_ref, ysq_ref,
                       tau_ref, z_ref, k_ref, o_ref, act_ref, dap_ref,
                       daf_ref, dan_ref, db_ref, sg_ref,
                       ga_ref, gb_ref, psi_ref, flag_ref, *, gamma: float):
    l = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init_ga_ff():
        ga_ref[...] = jnp.zeros_like(ga_ref)

    @pl.when(jnp.logical_and(l == 0, j == 0))
    def _init_psi_ff():
        psi_ref[...] = jnp.zeros_like(psi_ref)

    gb_ref[...] = jnp.zeros_like(gb_ref)

    tau = tau_ref[...].astype(jnp.float32)               # (TL, 1)
    v = _verdict_tile(
        z_ref[...], k_ref[...], o_ref[...], act_ref[...],
        dap_ref[...], daf_ref[...], dan_ref[...],
        db_ref[0], sg_ref[...], tau,
    )
    flag = jnp.any(v != ZERO).astype(jnp.int32)
    flag_ref[...] = jnp.reshape(flag, (1, 1))

    @pl.when(flag != 0)
    def _compute_ff():
        alpha = alpha_ref[...].astype(jnp.float32)       # (TL, g)
        beta = beta_ref[0].astype(jnp.float32)           # (TN,)
        c = factorized_cost_tile(
            x_ref[...].astype(jnp.float32),              # (TL, g, d)
            xsq_ref[...].astype(jnp.float32),            # (TL, g)
            y_ref[...].astype(jnp.float32),              # (TN, d)
            ysq_ref[0].astype(jnp.float32),              # (TN,)
        )
        t, psi = _gradpsi_tile(alpha, beta, c, tau, gamma=gamma)
        psi_ref[...] += jnp.reshape(psi, (1, 1))
        ga_ref[...] += jnp.sum(t, axis=2)                # (TL, g)
        gb_ref[...] = jnp.sum(t, axis=(0, 1))[None, :]   # (1, TN)


@functools.partial(
    jax.jit,
    static_argnames=("num_groups", "group_size", "gamma",
                     "tile_l", "tile_n", "interpret"),
)
def gradpsi_fused_fact_pallas(
    alpha: jnp.ndarray,        # (m_pad,) fp32
    beta: jnp.ndarray,         # (n,) fp32
    x: jnp.ndarray,            # (m_pad, d) fp32/bf16 scaled source samples
    x_sq: jnp.ndarray,         # (m_pad,) fp32/bf16 scaled squared norms
    y: jnp.ndarray,            # (n, d) fp32/bf16 scaled target samples
    y_sq: jnp.ndarray,         # (n,) fp32/bf16 scaled squared norms
    z: jnp.ndarray,            # (L, n) fp32
    k: jnp.ndarray,            # (L, n) fp32
    o: jnp.ndarray,            # (L, n) fp32
    active: jnp.ndarray,       # (L, n) int8/bool
    da_plus: jnp.ndarray,      # (L,)
    da_full: jnp.ndarray,      # (L,)
    da_neg: jnp.ndarray,       # (L,)
    db: jnp.ndarray,           # (n,)
    sqrt_g: jnp.ndarray,       # (L,)
    *,
    num_groups: int,
    group_size: int,
    tau,
    gamma: float,
    tile_l: int = 0,
    tile_n: int = DEFAULT_TILE_N,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused dense-grid factorized kernel: ONE launch, cost tiles in VMEM.

    Same contract as :func:`gradpsi_fused_pallas` with the C operand
    replaced by ``(x, x_sq, y, y_sq)`` blocked operands (the
    :func:`factorized_cost_tile` recipe).
    """
    _record_launch("gradpsi_fused_fact_pallas")
    L, g = num_groups, group_size
    n = beta.shape[0]
    d = x.shape[-1]
    tau_g = tau_row(tau, L)
    if tile_l == 0:
        tile_l = pick_tile_l_factorized(g, tile_n, d,
                                        jnp.dtype(x.dtype).itemsize)
    assert L % tile_l == 0 and n % tile_n == 0, (L, tile_l, n, tile_n)
    grid = (L // tile_l, n // tile_n)
    assert z.shape == (L, n), (z.shape, (L, n))

    alpha_g = alpha.reshape(grid[0], tile_l, g)
    x3 = x.reshape(L, g, d)
    xsq_g = x_sq.reshape(grid[0], tile_l, g)
    rows = functools.partial(group_rows, tile_l=tile_l)
    tiles = functools.partial(group_tiles, tile_l=tile_l)

    row = pl.BlockSpec((None, tile_l, 1), lambda l, j: (l, 0, 0))
    row_g = pl.BlockSpec((None, tile_l, g), lambda l, j: (l, 0, 0))
    col = pl.BlockSpec((1, tile_n), lambda l, j: (0, j))
    mat = pl.BlockSpec((None, tile_l, tile_n), lambda l, j: (l, 0, j))

    ga_part, gb_part, psi, flags = pl.pallas_call(
        functools.partial(_fused_fact_kernel, gamma=float(gamma)),
        grid=grid,
        in_specs=[
            row_g,                                                 # alpha
            col,                                                   # beta
            pl.BlockSpec((tile_l, g, d), lambda l, j: (l, 0, 0)),  # x
            row_g,                                                 # x_sq
            pl.BlockSpec((tile_n, d), lambda l, j: (j, 0)),        # y
            col,                                                   # y_sq
            row,                                                   # tau
            mat, mat, mat, mat,                                    # z k o act
            row, row, row,                                         # da norms
            col,                                                   # db
            row,                                                   # sqrt_g
        ],
        out_specs=[
            row_g,
            pl.BlockSpec((None, 1, tile_n), lambda l, j: (l, 0, j)),
            pl.BlockSpec((1, 1), lambda l, j: (0, 0)),
            pl.BlockSpec((None, None, 1, 1), lambda l, j: (l, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((grid[0], tile_l, g), jnp.float32),
            jax.ShapeDtypeStruct((grid[0], 1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct(grid + (1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(alpha_g, beta[None, :], x3, xsq_g, y, y_sq[None, :], rows(tau_g),
      tiles(z), tiles(k), tiles(o), tiles(active.astype(jnp.int8)),
      rows(da_plus), rows(da_full), rows(da_neg), db[None, :], rows(sqrt_g))

    return (ga_part.reshape(-1), jnp.sum(gb_part[:, 0], axis=0), psi[0, 0],
            flags[:, :, 0, 0])


def _fused_fact_kernel_batched(alpha_ref, beta_ref, x_ref, xsq_ref, y_ref,
                               ysq_ref, tau_ref, z_ref, k_ref, o_ref,
                               act_ref, dap_ref, daf_ref, dan_ref, db_ref,
                               sg_ref, ga_ref, gb_ref, psi_ref, flag_ref,
                               *, gamma: float):
    j = pl.program_id(2)
    lj0 = jnp.logical_and(pl.program_id(1) == 0, j == 0)

    @pl.when(j == 0)
    def _init_ga_ffb():
        ga_ref[...] = jnp.zeros_like(ga_ref)

    @pl.when(lj0)
    def _init_psi_ffb():
        psi_ref[...] = jnp.zeros_like(psi_ref)

    gb_ref[...] = jnp.zeros_like(gb_ref)

    tau = tau_ref[...].astype(jnp.float32)               # (TL, 1)
    v = _verdict_tile(
        z_ref[0], k_ref[0], o_ref[0], act_ref[0],
        dap_ref[0], daf_ref[0], dan_ref[0],
        db_ref[0], sg_ref[0], tau,
    )
    flag = jnp.any(v != ZERO).astype(jnp.int32)
    flag_ref[...] = jnp.reshape(flag, (1, 1))

    @pl.when(flag != 0)
    def _compute_ffb():
        alpha = alpha_ref[0].astype(jnp.float32)         # (TL, g)
        beta = beta_ref[0].astype(jnp.float32)           # (TN,)
        c = factorized_cost_tile(
            x_ref[0].astype(jnp.float32),                # (TL, g, d)
            xsq_ref[0].astype(jnp.float32),              # (TL, g)
            y_ref[0].astype(jnp.float32),                # (TN, d)
            ysq_ref[0].astype(jnp.float32),              # (TN,)
        )
        t, psi = _gradpsi_tile(alpha, beta, c, tau, gamma=gamma)
        psi_ref[...] += jnp.reshape(psi, (1, 1, 1))
        ga_ref[...] += jnp.sum(t, axis=2)[None]          # (1, TL, g)
        gb_ref[...] = jnp.sum(t, axis=(0, 1))[None, None, :]  # (1, 1, TN)


@functools.partial(
    jax.jit,
    static_argnames=("num_groups", "group_size", "gamma",
                     "tile_l", "tile_n", "interpret"),
)
def gradpsi_fused_fact_pallas_batched(
    alpha: jnp.ndarray,        # (B, m_pad) fp32
    beta: jnp.ndarray,         # (B, n) fp32
    x: jnp.ndarray,            # (B, m_pad, d) fp32/bf16
    x_sq: jnp.ndarray,         # (B, m_pad) fp32/bf16
    y: jnp.ndarray,            # (B, n, d) fp32/bf16
    y_sq: jnp.ndarray,         # (B, n) fp32/bf16
    z: jnp.ndarray,            # (B, L, n) fp32
    k: jnp.ndarray,            # (B, L, n) fp32
    o: jnp.ndarray,            # (B, L, n) fp32
    active: jnp.ndarray,       # (B, L, n) int8/bool
    da_plus: jnp.ndarray,      # (B, L)
    da_full: jnp.ndarray,      # (B, L)
    da_neg: jnp.ndarray,       # (B, L)
    db: jnp.ndarray,           # (B, n)
    sqrt_g: jnp.ndarray,       # (B, L)
    *,
    num_groups: int,
    group_size: int,
    tau,
    gamma: float,
    tile_l: int = 0,
    tile_n: int = DEFAULT_TILE_N,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused dense-grid factorized kernel over B problems, ONE launch.

    Per-problem semantics identical to :func:`gradpsi_fused_fact_pallas`.
    """
    _record_launch("gradpsi_fused_fact_pallas_batched")
    L, g = num_groups, group_size
    B, n = beta.shape
    d = x.shape[-1]
    tau_g = tau_row(tau, L)
    if tile_l == 0:
        tile_l = pick_tile_l_factorized(g, tile_n, d,
                                        jnp.dtype(x.dtype).itemsize)
    assert L % tile_l == 0 and n % tile_n == 0, (L, tile_l, n, tile_n)
    grid = (B, L // tile_l, n // tile_n)
    assert z.shape == (B, L, n), (z.shape, (B, L, n))

    alpha_g = alpha.reshape(B, grid[1], tile_l, g)
    x4 = x.reshape(B, L, g, d)
    xsq_g = x_sq.reshape(B, grid[1], tile_l, g)
    rows = functools.partial(group_rows, tile_l=tile_l)
    tiles = functools.partial(group_tiles, tile_l=tile_l)

    brow = pl.BlockSpec((1, None, tile_l, 1), lambda b, l, j: (b, l, 0, 0))
    brow_g = pl.BlockSpec((1, None, tile_l, g), lambda b, l, j: (b, l, 0, 0))
    bcol = pl.BlockSpec((None, 1, tile_n), lambda b, l, j: (b, 0, j))
    bmat = pl.BlockSpec((1, None, tile_l, tile_n),
                        lambda b, l, j: (b, l, 0, j))

    ga_part, gb_part, psi, flags = pl.pallas_call(
        functools.partial(_fused_fact_kernel_batched, gamma=float(gamma)),
        grid=grid,
        in_specs=[
            brow_g,                                                # alpha
            bcol,                                                  # beta
            pl.BlockSpec((1, tile_l, g, d),
                         lambda b, l, j: (b, l, 0, 0)),            # x
            brow_g,                                                # x_sq
            pl.BlockSpec((1, tile_n, d), lambda b, l, j: (b, j, 0)),  # y
            bcol,                                                  # y_sq
            pl.BlockSpec((None, tile_l, 1), lambda b, l, j: (l, 0, 0)),  # tau
            bmat, bmat, bmat, bmat,                                # z k o act
            brow, brow, brow,                                      # da norms
            bcol,                                                  # db
            brow,                                                  # sqrt_g
        ],
        out_specs=[
            brow_g,
            pl.BlockSpec((1, None, 1, tile_n), lambda b, l, j: (b, l, 0, j)),
            pl.BlockSpec((1, 1, 1), lambda b, l, j: (b, 0, 0)),
            pl.BlockSpec((None, None, None, 1, 1),
                         lambda b, l, j: (b, l, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, grid[1], tile_l, g), jnp.float32),
            jax.ShapeDtypeStruct((B, grid[1], 1, n), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct(grid + (1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(alpha_g, beta[:, None, :], x4, xsq_g, y, y_sq[:, None, :],
      rows(tau_g), tiles(z), tiles(k), tiles(o),
      tiles(active.astype(jnp.int8)), rows(da_plus), rows(da_full),
      rows(da_neg), db[:, None, :], rows(sqrt_g))

    return (
        ga_part.reshape(B, -1),
        jnp.sum(gb_part[:, :, 0], axis=1),
        psi[:, 0, 0],
        flags[..., 0, 0],
    )
