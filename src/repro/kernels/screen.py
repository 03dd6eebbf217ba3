"""Pallas TPU kernel: screening bound matrices + verdicts (paper Eq. 6/7).

Rank-1 "outer broadcast" pass over the (L, n) bound matrices:

  z_bar[l, j] = z~[l, j] + ||[d_alpha_[l]]_+|| + sqrt(g_l) * [d_beta_j]_+
  z_low[l, j] = k~ - ||d_alpha_[l]|| - sqrt(g_l)|d_beta_j|
                - o~ - ||[d_alpha_[l]]_-|| - sqrt(g_l)[−d_beta_j]_+

  verdict = ACTIVE where active mask (N),
            ZERO   where z_bar <= tau,
            CHECK  otherwise.

One VPU pass, O(L n) bytes — this is the O(|L|(n+g)) cost of Lemma 3/6
(the per-group delta norms are O(L g) and computed outside in plain jnp).
The kernel also emits the per-tile OR-reduction consumed by gradpsi's skip
flags.  With ``emit_verdict=False`` (the solver's steady-state gradient
path) only the tile flags are written back to HBM: the (L, n) verdict
matrix lives and dies in VMEM and never round-trips between screening and
the gradient kernel.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.screening import ZERO
from repro.kernels.gradpsi import (
    _record_launch,
    _verdict_tile,
    factorized_cost_tile,
    group_rows,
    group_tiles,
    tau_row,
)


def _kernel_full(z_ref, k_ref, o_ref, act_ref, dap_ref, daf_ref, dan_ref,
                 db_ref, sg_ref, tau_ref, verdict_ref, flag_ref):
    # gradpsi._verdict_tile is THE verdict math — the fused kernels call the
    # same function on identically-blocked operands, which is what keeps the
    # standalone and fused flag outputs bitwise-interchangeable.
    v = _verdict_tile(z_ref[...], k_ref[...], o_ref[...], act_ref[...],
                      dap_ref[...], daf_ref[...], dan_ref[...],
                      db_ref[0], sg_ref[...], tau_ref[...])
    verdict_ref[...] = v
    flag_ref[...] = jnp.reshape(
        jnp.any(v != ZERO).astype(jnp.int32), (1, 1))


def _kernel_flags(z_ref, k_ref, o_ref, act_ref, dap_ref, daf_ref, dan_ref,
                  db_ref, sg_ref, tau_ref, flag_ref):
    v = _verdict_tile(z_ref[...], k_ref[...], o_ref[...], act_ref[...],
                      dap_ref[...], daf_ref[...], dan_ref[...],
                      db_ref[0], sg_ref[...], tau_ref[...])
    flag_ref[...] = jnp.reshape(
        jnp.any(v != ZERO).astype(jnp.int32), (1, 1))


@functools.partial(
    jax.jit,
    static_argnames=("tile_l", "tile_n", "interpret", "emit_verdict"),
)
def screen_pallas(
    z_snap: jnp.ndarray,       # (L, n)
    k_snap: jnp.ndarray,       # (L, n)
    o_snap: jnp.ndarray,       # (L, n)
    active: jnp.ndarray,       # (L, n) int8/bool persistent set N
    da_plus: jnp.ndarray,      # (L,)  ||[d_alpha_[l]]_+||
    da_full: jnp.ndarray,      # (L,)  ||d_alpha_[l]||
    da_neg: jnp.ndarray,       # (L,)  ||[d_alpha_[l]]_-||
    db: jnp.ndarray,           # (n,)  d_beta
    sqrt_g: jnp.ndarray,       # (L,)
    *,
    tau,
    tile_l: int = 8,
    tile_n: int = 128,
    interpret: bool = False,
    emit_verdict: bool = True,
) -> Tuple[Optional[jnp.ndarray], jnp.ndarray]:
    """Returns (verdict (L, n) int32 | None, tile_flags (L/tl, n/tn) int32).

    ``tau`` is a scalar or per-group ``(L,)`` threshold vector (the
    regularizer's screening thresholds); it rides as a row operand next to
    ``sqrt_g``.  ``emit_verdict=False`` skips the (L, n) HBM write-back
    entirely; only the tile-flag reduction leaves the chip.
    """
    _record_launch("screen_pallas")
    L, n = z_snap.shape
    assert L % tile_l == 0 and n % tile_n == 0, (L, tile_l, n, tile_n)
    grid = (L // tile_l, n // tile_n)
    tau_g = tau_row(tau, L)
    rows = functools.partial(group_rows, tile_l=tile_l)
    tiles = functools.partial(group_tiles, tile_l=tile_l)

    # block layouts: see "Mosaic block layouts" in gradpsi.py
    row = pl.BlockSpec((None, tile_l, 1), lambda l, j: (l, 0, 0))
    col = pl.BlockSpec((1, tile_n), lambda l, j: (0, j))
    mat = pl.BlockSpec((None, tile_l, tile_n), lambda l, j: (l, 0, j))
    flag = pl.BlockSpec((None, None, 1, 1), lambda l, j: (l, j, 0, 0))
    flag_shape = jax.ShapeDtypeStruct(grid + (1, 1), jnp.int32)

    if emit_verdict:
        kernel = _kernel_full
        out_specs = [mat, flag]
        out_shape = [
            jax.ShapeDtypeStruct((grid[0], tile_l, n), jnp.int32),
            flag_shape,
        ]
    else:
        kernel = _kernel_flags
        out_specs = [flag]
        out_shape = [flag_shape]

    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[mat, mat, mat, mat, row, row, row, col, row, row],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(tiles(z_snap), tiles(k_snap), tiles(o_snap),
      tiles(active.astype(jnp.int8)), rows(da_plus), rows(da_full),
      rows(da_neg), db[None, :], rows(sqrt_g), rows(tau_g))

    flags = outs[-1][:, :, 0, 0]
    if emit_verdict:
        return outs[0].reshape(L, n), flags
    return None, flags


# -- factorized snapshot-norms kernel (materialization-free route) -------------
#
# The dense solver snapshots the Eq. 6 bound matrices via dual.snapshot_norms,
# which reads the full (m_pad, n) C.  On the on-the-fly route there is no C:
# this kernel rebuilds each cost tile from sample blocks (the same
# factorized_cost_tile recipe as the gradient kernels) and reduces the three
# per-group norms in VMEM, so the only (L, n)-sized HBM traffic is the three
# bound matrices themselves — exactly what the dense route also writes.


def _snapshot_kernel_fact(alpha_ref, beta_ref, x_ref, xsq_ref, y_ref, ysq_ref,
                          mask_ref, z_ref, k_ref, o_ref):
    c = factorized_cost_tile(
        x_ref[...].astype(jnp.float32),                  # (TL, g, d)
        xsq_ref[...].astype(jnp.float32),                # (TL, g)
        y_ref[...].astype(jnp.float32),                  # (TN, d)
        ysq_ref[0].astype(jnp.float32),                  # (TN,)
    )
    f = (alpha_ref[...].astype(jnp.float32)[:, :, None]
         + beta_ref[0].astype(jnp.float32)[None, None, :]
         - c)                                            # (TL, g, TN)
    mask = mask_ref[...].astype(jnp.float32)[:, :, None]    # (TL, g, 1)
    fm = jnp.where(mask != 0, f, 0.0)
    z_ref[...] = jnp.sqrt(jnp.sum(jnp.square(jnp.maximum(fm, 0.0)), axis=1))
    k_ref[...] = jnp.sqrt(jnp.sum(jnp.square(fm), axis=1))
    o_ref[...] = jnp.sqrt(jnp.sum(jnp.square(jnp.minimum(fm, 0.0)), axis=1))


@functools.partial(
    jax.jit,
    static_argnames=("num_groups", "group_size",
                     "tile_l", "tile_n", "interpret"),
)
def snapshot_norms_fact_pallas(
    alpha: jnp.ndarray,        # (L_pad*g,) fp32 tile-padded duals
    beta: jnp.ndarray,         # (n_pad,) fp32
    x: jnp.ndarray,            # (L_pad*g, d) fp32 scaled source samples
    x_sq: jnp.ndarray,         # (L_pad*g,) fp32
    y: jnp.ndarray,            # (n_pad, d) fp32 scaled target samples
    y_sq: jnp.ndarray,         # (n_pad,) fp32
    mask: jnp.ndarray,         # (L_pad*g,) int8 real-row mask
    *,
    num_groups: int,           # L_pad (tile-padded group count)
    group_size: int,
    tile_l: int = 8,
    tile_n: int = 128,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Factorized snapshot norms: returns (z, k, o) each (L_pad, n_pad).

    Per-element math replicates :func:`repro.core.dual.snapshot_norms` on a
    cost materialized with :func:`factorized_cost_tile` — F is masked to zero
    on padded group members BEFORE the three reductions, so k~/o~ never see
    the PAD_COST sentinel rows.  Callers slice ``[:L, :n]``.
    """
    _record_launch("snapshot_norms_fact_pallas")
    L, g = num_groups, group_size
    d = x.shape[-1]
    n_pad = beta.shape[0]
    assert L % tile_l == 0 and n_pad % tile_n == 0, (L, tile_l, n_pad, tile_n)
    grid = (L // tile_l, n_pad // tile_n)

    Lt = L // tile_l
    alpha_g = alpha.reshape(Lt, tile_l, g)
    x3 = x.reshape(L, g, d)
    xsq_g = x_sq.reshape(Lt, tile_l, g)
    mask_g = mask.reshape(Lt, tile_l, g).astype(jnp.int8)

    # block layouts: see "Mosaic block layouts" in gradpsi.py
    row_g = pl.BlockSpec((None, tile_l, g), lambda l, j: (l, 0, 0))
    col = pl.BlockSpec((1, tile_n), lambda l, j: (0, j))
    mat = pl.BlockSpec((None, tile_l, tile_n), lambda l, j: (l, 0, j))

    z, k, o = pl.pallas_call(
        _snapshot_kernel_fact,
        grid=grid,
        in_specs=[
            row_g,                                           # alpha
            col,                                             # beta
            pl.BlockSpec((tile_l, g, d), lambda l, j: (l, 0, 0)),  # x
            row_g,                                           # x_sq
            pl.BlockSpec((tile_n, d), lambda l, j: (j, 0)),  # y
            col,                                             # y_sq
            row_g,                                           # mask
        ],
        out_specs=[mat, mat, mat],
        out_shape=[jax.ShapeDtypeStruct((Lt, tile_l, n_pad), jnp.float32)] * 3,
        interpret=interpret,
    )(alpha_g, beta[None, :], x3, xsq_g, y, y_sq[None, :], mask_g)

    return z.reshape(L, n_pad), k.reshape(L, n_pad), o.reshape(L, n_pad)
