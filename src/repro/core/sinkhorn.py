"""Entropic OT (Cuturi 2013) — the baseline the paper compares against.

Implemented in log-space (stabilized; Schmitzer 2019) because the paper
explicitly notes that the plain Sinkhorn iteration was numerically unstable
across most of their hyperparameter grid.  Pure JAX, jit/shard-friendly.

The loop runs by one of two routes, chosen at trace time from what the
input is (DESIGN.md §11), with the same algorithm, stopping rule and
result on both:

* ``"resident"``: on a TPU, for a float32 cost that fits the kernel's VMEM
  budget (``kernels/sinkhorn.fits``), the whole loop is one Pallas kernel
  that reads C from HBM once and keeps it in VMEM for every iteration.
* ``"xla"``: everywhere else (the CPU, other dtypes, costs too large for
  VMEM or off the (8, 128) tiling), an XLA ``while`` loop, whose program
  reads C from HBM in every iteration.

``repro.utils.trace.routes()`` records the route of each trace.  The XLA
loop's phases and, on both routes, the final plan run under named scopes
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

from repro.utils import trace


class SinkhornResult(NamedTuple):
    f: jnp.ndarray            # (m,) dual potential
    g: jnp.ndarray            # (n,) dual potential
    plan: jnp.ndarray         # (m, n)
    n_iters: jnp.ndarray
    err: jnp.ndarray          # final marginal violation (L1)


def takes_resident_route(C, a, b) -> bool:
    """Whether ``sinkhorn_log`` on these inputs runs the resident kernel: a
    TPU backend, float32 throughout, and a cost that fits VMEM
    (``kernels/sinkhorn.fits``)."""
    f32 = jnp.dtype(jnp.float32)
    if jax.default_backend() != "tpu" or any(jnp.dtype(x.dtype) != f32 for x in (C, a, b)):
        return False
    # imported here, as in sinkhorn_log: importing Pallas takes over a second,
    # and only the resident route needs it
    from repro.kernels.sinkhorn import fits

    return fits(*C.shape)


def _xla_loop(C, a, b, loga, logb, eps, tol, max_iters):
    """``(f, g, n_iters, err)`` of the XLA ``while`` loop."""
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
    return jax.lax.while_loop(
        cond, body, (f0, g0, jnp.zeros((), jnp.int32), jnp.asarray(jnp.inf))
    )


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

    resident = takes_resident_route(C, a, b)
    trace.record_route("sinkhorn_log", "resident" if resident else "xla", C.shape)
    if resident:
        from repro.kernels.sinkhorn import sinkhorn_resident

        f, g, it, err = sinkhorn_resident(C, a, loga, logb, eps, tol, max_iters=max_iters)
    else:
        f, g, it, err = _xla_loop(C, a, b, loga, logb, eps, tol, max_iters)
    with jax.named_scope("sinkhorn.plan"):
        plan = jnp.exp((f[:, None] + g[None, :] - C) / eps)
    return SinkhornResult(f=f, g=g, plan=plan, n_iters=it, err=err)
