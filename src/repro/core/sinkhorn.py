"""Entropic OT (Cuturi 2013) — the baseline the paper compares against.

Implemented in log-space (stabilized; Schmitzer 2019) because the paper
explicitly notes that the plain Sinkhorn iteration was numerically unstable
across most of their hyperparameter grid.  Pure JAX, jit/shard-friendly.

The loop's phases and the final plan run under named scopes
(``sinkhorn.f_update``, ``sinkhorn.g_update``, ``sinkhorn.marginal_err``,
``sinkhorn.plan``).  They reach the compiled program only as the
``op_name`` metadata of its instructions, so a profile can attribute device
time to them; the persistent-cache key ignores them.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.utils import trace  # noqa: F401  (counts the programs it builds)


class SinkhornResult(NamedTuple):
    f: jnp.ndarray            # (m,) dual potential
    g: jnp.ndarray            # (n,) dual potential
    plan: jnp.ndarray         # (m, n)
    n_iters: jnp.ndarray
    err: jnp.ndarray          # final marginal violation (L1)


@functools.partial(jax.jit, static_argnames=("max_iters",))
def sinkhorn_log(
    C: jnp.ndarray,
    a: jnp.ndarray,
    b: jnp.ndarray,
    eps: float = 1e-2,
    max_iters: int = 2000,
    tol: float = 1e-8,
) -> SinkhornResult:
    """Log-domain Sinkhorn for  min <T,C> + eps * KL(T | a b^T)."""
    loga = jnp.log(jnp.clip(a, 1e-38))
    logb = jnp.log(jnp.clip(b, 1e-38))

    def body(carry):
        f, g, it, err = carry
        # f-update: f_i = -eps logsumexp_j ((g_j - C_ij)/eps) + eps log a_i
        with jax.named_scope("sinkhorn.f_update"):
            Mf = (g[None, :] - C) / eps
            f = eps * (loga - jax.scipy.special.logsumexp(Mf, axis=1))
        with jax.named_scope("sinkhorn.g_update"):
            Mg = (f[:, None] - C) / eps
            g = eps * (logb - jax.scipy.special.logsumexp(Mg, axis=0))
        # marginal error of the implied plan
        with jax.named_scope("sinkhorn.marginal_err"):
            logT = (f[:, None] + g[None, :] - C) / eps
            row = jnp.exp(jax.scipy.special.logsumexp(logT, axis=1))
            err = jnp.sum(jnp.abs(row - a))
        return f, g, it + 1, err

    def cond(carry):
        _, _, it, err = carry
        return jnp.logical_and(it < max_iters, err > tol)

    f0 = jnp.zeros_like(a)
    g0 = jnp.zeros_like(b)
    f, g, it, err = jax.lax.while_loop(
        cond, body, (f0, g0, jnp.zeros((), jnp.int32), jnp.asarray(jnp.inf))
    )
    with jax.named_scope("sinkhorn.plan"):
        plan = jnp.exp((f[:, None] + g[None, :] - C) / eps)
    return SinkhornResult(f=f, g=g, plan=plan, n_iters=it, err=err)
