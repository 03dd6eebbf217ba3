"""Plain reference for entropic discrete OT (Cuturi 2013), in the log domain.

Written from the formulas and nothing else (Peyre & Cuturi, "Computational
Optimal Transport", 2019, Sec. 4.4, Remark 4.23): it imports no module of
the system under test and takes nothing that the system made.  For the
problem ``min <T, C> + eps KL(T | a b^T)`` over plans with marginals
``(a, b)``, one iteration updates the two potentials in turn,

    f_i = eps log a_i - eps LSE_j((g_j - C_ij) / eps),
    g_j = eps log b_j - eps LSE_i((f_i - C_ij) / eps),

and the plan is ``T_ij = exp((f_i + g_j - C_ij) / eps)``.  After each
iteration the columns of T meet ``b``; the iteration stops once the rows'
violation ``sum_i |sum_j T_ij - a_i|`` is at most ``tol``, or after
``max_iters`` iterations.

``dtype`` is the precision of every array and operation: float32 for the
reference, bfloat16 for the control of the check.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp


class RefSolution(NamedTuple):
    plan: jnp.ndarray       # (m, n) float32
    iters: jnp.ndarray      # () iterations run
    row_l1: jnp.ndarray     # () sum_i |T 1 - a|_i after the last iteration


def _lse(M, axis):
    """``log sum exp`` along ``axis``, shifted by the largest entry."""
    top = jnp.max(M, axis=axis, keepdims=True)
    return jnp.squeeze(top, axis) + jnp.log(jnp.sum(jnp.exp(M - top), axis=axis))


@functools.partial(jax.jit, static_argnames=("max_iters", "dtype"))
def sinkhorn(C, a, b, eps, tol, *, max_iters: int, dtype=jnp.float32) -> RefSolution:
    """Log-domain Sinkhorn on ``C (m, n)`` with marginals ``a (m,)``, ``b (n,)``."""
    C = C.astype(dtype)
    eps = jnp.asarray(eps, dtype)
    log_a, log_b = jnp.log(a).astype(dtype), jnp.log(b).astype(dtype)

    def plan(f, g):
        return jnp.exp((f[:, None] + g[None, :] - C) / eps)

    def body(st):
        f, g, it, _ = st
        f = eps * (log_a - _lse((g[None, :] - C) / eps, 1))
        g = eps * (log_b - _lse((f[:, None] - C) / eps, 0))
        rows = jnp.sum(plan(f, g), axis=1).astype(jnp.float32)
        return f, g, it + 1, jnp.sum(jnp.abs(rows - a))

    def cond(st):
        return jnp.logical_and(st[2] < max_iters, st[3] > tol)

    st = (jnp.zeros(a.shape, dtype), jnp.zeros(b.shape, dtype), jnp.int32(0),
          jnp.float32(jnp.inf))
    f, g, it, row_l1 = jax.lax.while_loop(cond, body, st)
    return RefSolution(plan(f, g).astype(jnp.float32), it, row_l1)


def marginal_l1(T, a, b):
    """``sum_i |T 1 - a|_i + sum_j |T^T 1 - b|_j``: how far a plan is from
    its marginals, in the mass it moves (every plan here has mass 1)."""
    return jnp.sum(jnp.abs(jnp.sum(T, axis=1) - a)) + jnp.sum(jnp.abs(jnp.sum(T, axis=0) - b))
