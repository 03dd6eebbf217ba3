"""Pallas TPU kernel: the whole log-domain Sinkhorn loop on a VMEM-resident cost.

``core/sinkhorn.sinkhorn_log``'s XLA loop reads the cost C from HBM in
every iteration: XLA carries C through its ``while`` loop and copies it
into VMEM again each time round.  This kernel reads C from HBM once, with
one DMA into a VMEM scratch, and runs every iteration of the loop on that
copy, so the loop itself moves no cost bytes through HBM.

One ``pallas_call`` with no grid.  Each iteration runs the three phases of
the XLA loop's body, in its order and with its formulas, each one sweep of
``tm``-row blocks of the resident C:

    f   row logsumexp of (g - C) / eps, stable by the row max;
    g   column logsumexp of (f - C) / eps, online over the row blocks: a
        running column max and a running sum rescaled to it (an exact
        logsumexp, summed in another order than XLA's);
    err sum_i |exp(logsumexp_j((f_i + g_j - C_ij) / eps)) - a_i|,

and the loop stops as the XLA loop does, on ``it < max_iters and err >
tol`` checked after every iteration.  Everything is float32.

Row quantities (``f``, ``a``, ``log a``) live as ``(m, 1)`` columns, so a
row block's slice needs no relayout; column quantities (``g``, ``log b``)
as ``(1, n)`` rows.  The shapes must lie on the float32 (8, 128) tiling;
:func:`fits` says whether a problem can take this kernel at all.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Rows of C per block of a sweep; a last block takes the rows left over.
# On a v5e at m = n = 3,200 an iteration takes 305.8 / 104.4 / 62.9 / 62.6
# us at 8 / 32 / 128 / 256 rows (PERF.md §6).
TILE_M = 128

# The most VMEM the kernel may ask for (:func:`vmem_bytes`).  A v5e core has
# 128 MiB; the rest is left to Mosaic's own scratch.  A 4,736 x 4,736 cost,
# the largest square under it, compiles and runs on a v5e.
VMEM_BUDGET_BYTES = 100 * 1024 * 1024

_SUBLANES, _LANES = 8, 128
_F32 = 4


def vmem_bytes(m: int, n: int) -> int:
    """VMEM the kernel holds for an ``(m, n)`` float32 cost: the resident C,
    three ``(m, 1)`` columns (f, a, log a) padded to 128 lanes, two ``(1, n)``
    rows (g, log b) padded to 8 sublanes, and one ``(tm, n)`` block of
    temporaries.  Its ``vmem_limit_bytes``: Mosaic refuses the kernel at
    3,200 x 3,200 and at 4,736 x 4,736 with a limit two blocks under it."""
    tm = min(TILE_M, m)
    return _F32 * (m * n + 3 * m * _LANES + 2 * _SUBLANES * n + tm * n)


def fits(m: int, n: int) -> bool:
    """Whether an ``(m, n)`` float32 cost can take the kernel: on the (8, 128)
    tiling, and C with the working set within :data:`VMEM_BUDGET_BYTES`."""
    return (m % _SUBLANES == 0 and n % _LANES == 0
            and vmem_bytes(m, n) <= VMEM_BUDGET_BYTES)


def _lse_rows(x):
    """``jax.scipy.special.logsumexp(x, axis=1)`` of a block, as ``(rows, 1)``."""
    mx = jnp.max(x, axis=1, keepdims=True)
    mx = jnp.where(jnp.isfinite(mx), mx, 0.0)
    return jnp.log(jnp.sum(jnp.exp(x - mx), axis=1, keepdims=True)) + mx


def _kernel(eps_ref, tol_ref, c_hbm, a_ref, loga_ref, logb_ref,
            f_ref, g_ref, it_ref, err_ref, c_ref, sem, *, max_iters, tm):
    copy = pltpu.make_async_copy(c_hbm, c_ref, sem)
    copy.start()
    m = c_ref.shape[0]
    eps = eps_ref[0]
    tol = tol_ref[0]
    f_ref[...] = jnp.zeros_like(f_ref)
    g_ref[...] = jnp.zeros_like(g_ref)
    copy.wait()

    def sweep(block, carry, start=0):
        """``block(rows, carry)`` over the row blocks from row ``start`` on:
        blocks of ``tm`` rows, then one of the rows left over."""
        full, rest = divmod(m - start, tm)

        def step(i, c):
            return block(pl.ds(pl.multiple_of(start + i * tm, _SUBLANES), tm), c)

        carry = jax.lax.fori_loop(0, full, step, carry)
        if rest:
            carry = block(pl.ds(start + full * tm, rest), carry)
        return carry

    def f_block(r, carry):
        mf = (g_ref[...] - c_ref[r, :]) / eps
        f_ref[r, :] = eps * (loga_ref[r, :] - _lse_rows(mf))
        return carry

    def g_block(r, carry):
        mx, s = carry
        mg = (f_ref[r, :] - c_ref[r, :]) / eps
        new = jnp.maximum(mx, jnp.max(mg, axis=0, keepdims=True))
        s = s * jnp.exp(mx - new) + jnp.sum(jnp.exp(mg - new), axis=0, keepdims=True)
        return new, s

    def g_update():
        # the first block starts the running max, so no -inf - -inf arises
        r0 = pl.ds(0, tm)
        mg0 = (f_ref[r0, :] - c_ref[r0, :]) / eps
        mx0 = jnp.max(mg0, axis=0, keepdims=True)
        s0 = jnp.sum(jnp.exp(mg0 - mx0), axis=0, keepdims=True)
        mx, s = sweep(g_block, (mx0, s0), start=tm)
        mx = jnp.where(jnp.isfinite(mx), mx, 0.0)
        g_ref[...] = eps * (logb_ref[...] - (jnp.log(s) + mx))

    def err_block(r, acc):
        logt = (f_ref[r, :] + g_ref[...] - c_ref[r, :]) / eps
        row = jnp.exp(_lse_rows(logt))
        return acc + jnp.sum(jnp.abs(row - a_ref[r, :]), axis=0, keepdims=True)

    def cond(carry):
        it, err = carry
        return jnp.logical_and(it < max_iters, err > tol)

    def body(carry):
        it, _ = carry
        sweep(f_block, 0)
        g_update()
        err = sweep(err_block, jnp.zeros((1, 1), jnp.float32))
        return it + 1, jnp.sum(err)

    it, err = jax.lax.while_loop(cond, body, (jnp.int32(0), jnp.float32(jnp.inf)))
    it_ref[0] = it
    err_ref[0] = err


@functools.partial(jax.jit, static_argnames=("max_iters", "interpret"))
def sinkhorn_resident(C, a, loga, logb, eps, tol, *, max_iters: int,
                      interpret: bool = False):
    """``(f, g, n_iters, err)`` of log-domain Sinkhorn on ``C`` (m, n) float32,
    with C held in VMEM for the whole solve.

    ``a`` (m,) and its log ``loga``, ``logb`` (n,) and the scalars ``eps`` and
    ``tol`` are operands; ``max_iters`` is static.  ``(m, n)`` must satisfy
    :func:`fits`."""
    m, n = C.shape
    assert m % _SUBLANES == 0 and n % _LANES == 0, (m, n)
    f32 = jnp.float32
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    f, g, it, err = pl.pallas_call(
        functools.partial(_kernel, max_iters=max_iters, tm=min(TILE_M, m)),
        out_shape=(jax.ShapeDtypeStruct((m, 1), f32), jax.ShapeDtypeStruct((1, n), f32),
                   jax.ShapeDtypeStruct((1,), jnp.int32), jax.ShapeDtypeStruct((1,), f32)),
        in_specs=[smem, smem, pl.BlockSpec(memory_space=pl.ANY), vmem, vmem, vmem],
        out_specs=(vmem, vmem, smem, smem),
        scratch_shapes=[pltpu.VMEM((m, n), f32), pltpu.SemaphoreType.DMA(())],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_bytes(m, n)),
        interpret=interpret,
        name="sinkhorn_resident",
    )(jnp.reshape(eps, (1,)).astype(f32), jnp.reshape(tol, (1,)).astype(f32), C,
      a.reshape(m, 1), loga.reshape(m, 1), logb.reshape(1, n))
    return f.reshape(m), g.reshape(n), it[0], err[0]
