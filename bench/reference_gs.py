"""Plain reference for group-sparse regularised discrete OT, by its dual.

Written from the formulas and nothing else: it imports no module of the
system under test and takes nothing that the system made.  The primal
(Ida et al., AAAI 2023, Eq. 3; Blondel et al., "Smooth and Sparse Optimal
Transport", AISTATS 2018) is

    min_T <T, C> + sum_j Psi(t_j)  over plans with marginals (a, b),
    Psi(t) = gamma ((1 - rho) / 2 ||t||^2 + rho sum_l ||t_[l]||),

where ``t_[l]`` is the part of column ``t`` in the rows of source class
``l``.  With ``gam = gamma (1 - rho)`` and ``tau = gamma rho``, the
conjugate of ``Psi`` on one class block is the squared distance of the
block's positive part to the ball of radius ``tau``,

    psi_l(f) = ([z - tau]_+)^2 / (2 gam),   z = ||[f]_+||,

so the dual (the paper's Eq. 4) is the smooth concave function

    D(alpha, beta) = alpha^T a + beta^T b - sum_{l, j} psi_l(alpha_[l] + beta_j - c_[l]j),

and at its maximum the plan is its gradient's ``psi`` part,
``T_[l]j = [z - tau]_+ / z * [f_[l]j]_+ / gam``, which meets both marginals:
``dD / dalpha = a - T 1``, ``dD / dbeta = b - T^T 1``.

The solver is L-BFGS on ``-D`` (Nocedal and Wright, Alg. 7.4) with a line
search that reads only the slope along the search direction: ``-D`` is
convex, so its slope grows along any line, and a step whose slope lies
between ``C2`` and ``C1`` times the slope at its start meets both Wolfe
conditions without comparing two values of ``D``.  A float32 value of
``D`` rounds at about 1e-8 of its size, which is more than an iteration
near the maximum gains, and a test on values would stop there; the slope
is a sum of marginal residuals and stays exact enough.  It stops once the
largest residual is at most ``gtol``; or once ``PATIENCE`` iterations have
not lowered the least residual seen, which is where the precision's floor
holds it (a dual variable near 1 moves by 1e-7 in float32, and a plan entry
by that over ``gam``, so a column of the paper's size meets its marginal
to about 1% and no closer); or after ``max_iters`` iterations.

``dtype`` is the precision of every array and operation.

The check of a cell takes :func:`optimum`, the float64 optimum, which lies
closer to the true one than any float32 solve can: the float32 solve on
the default device to its floor, then float64 on the host's CPU from that
point.  At the paper's sizes a float64 iteration over all ``L n`` class
blocks takes tens of milliseconds on a CPU and the solve thousands of
them, so the float64 iterations run on a working set: the blocks whose
norm ``z`` lies within ``MARGIN`` of ``tau`` or above it at the float32
point (a block under ``tau`` has no plan and no gradient; on the paper's
costs, which are at most 1, the float64 solve moves a block's norm by
under a tenth of ``MARGIN``).  The answer is then certified on all
blocks: the dense float64 gradient at the point returned has to meet
``F64_GTOL``; where a block outside the set has come alive, the set grows
by every block now within ``MARGIN`` and the solve goes on from there.  Where ``ROUNDS`` sets do not hold the answer (a float32
point far from the optimum, whose set may not even carry the marginals),
the float64 solve runs on every block from the float32 point instead.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

C1, C2 = 1e-4, 0.9          # the slope window of a step, as shares of the start's
MAX_SEARCH = 40             # slope evaluations a line search may make
HISTORY = 10                # L-BFGS pairs kept
PATIENCE = 100              # iterations without a new least residual that end the solve
F64_GTOL = 1e-12            # largest marginal residual of the float64 optimum
F64_MAX_ITERS = 20000       # iterations a float64 solve on a working set may make
MARGIN = 1e-3               # how far under tau a block's norm may lie and join the working set
ROUNDS = 4                  # working sets tried before the solve takes every block
DENSE_MAX_ITERS = 3000      # iterations of that float64 solve on every block


class GroupSparse(NamedTuple):
    L: int                  # source classes
    g: int                  # rows a class (m = L g)
    gam: float              # weight of the squared norm, gamma (1 - rho)
    tau: float              # threshold of a class block's norm, gamma rho


def from_rho(L: int, g: int, gamma: float, rho: float) -> GroupSparse:
    """The paper's parameters ``(gamma, rho)`` of ``Psi``."""
    return GroupSparse(int(L), int(g), float(gamma) * (1.0 - float(rho)),
                       float(gamma) * float(rho))


def _blocks(alpha, beta, C, reg: GroupSparse):
    """``[f]_+`` as ``(L, g, n)`` and the class-block norms ``z`` ``(L, n)``."""
    f = alpha[:, None] + beta[None, :] - C
    fp = jnp.maximum(f, 0).reshape(reg.L, reg.g, -1)
    return fp, jnp.sqrt(jnp.sum(fp * fp, axis=1))


def _plan_and_psi(alpha, beta, C, reg: GroupSparse):
    """The plan as ``(L, g, n)`` and ``sum psi_l``."""
    fp, z = _blocks(alpha, beta, C, reg)
    shrink = jnp.maximum(z - reg.tau, 0)
    scale = jnp.where(z > 0, shrink / jnp.where(z > 0, z, 1), 0) / reg.gam
    return scale[:, None, :] * fp, jnp.sum(shrink * shrink) / (2 * reg.gam)


def dual(alpha, beta, C, a, b, reg: GroupSparse):
    """``D(alpha, beta)`` and its gradient ``(a - T 1, b - T^T 1)``."""
    with jax.default_matmul_precision("highest"):
        T, psi = _plan_and_psi(alpha, beta, C, reg)
        value = alpha @ a + beta @ b - psi
        return value, a - jnp.sum(T, axis=2).reshape(-1), b - jnp.sum(T, axis=(0, 1))


def plan(alpha, beta, C, reg: GroupSparse):
    """The plan of a dual point, ``(m, n)``."""
    return _plan_and_psi(alpha, beta, C, reg)[0].reshape(C.shape)


class RefSolution(NamedTuple):
    alpha: jnp.ndarray      # (m,)
    beta: jnp.ndarray       # (n,)
    value: jnp.ndarray      # () D at the point returned
    plan: jnp.ndarray       # (m, n)
    iters: jnp.ndarray      # () L-BFGS iterations run
    residual: jnp.ndarray   # () largest |marginal residual| at the point returned


def _direction(grad, S, Y, rho, count, k):
    """``-H grad`` by the two-loop recursion over the ``count`` newest pairs
    (slot ``i % HISTORY`` holds pair ``i``)."""
    def newest_first(j, carry):
        q, al = carry
        i = (k - 1 - j) % HISTORY
        use = j < count
        aj = jnp.where(use, rho[i] * (S[i] @ q), 0)
        return q - aj * Y[i], al.at[i].set(aj)

    q, al = jax.lax.fori_loop(0, HISTORY, newest_first,
                              (grad, jnp.zeros((HISTORY,), grad.dtype)))
    i = (k - 1) % HISTORY
    h0 = jnp.where(count > 0, (S[i] @ Y[i]) / jnp.maximum(Y[i] @ Y[i], 1e-30), 1)
    r = h0 * q

    def oldest_first(j, r):
        i = (k - count + j) % HISTORY
        use = j < count
        bj = rho[i] * (Y[i] @ r)
        return r + jnp.where(use, al[i] - bj, 0) * S[i]

    return -jax.lax.fori_loop(0, HISTORY, oldest_first, r)


def _lbfgs(neg, x0, gtol, max_iters: int, patience: int, floor=-jnp.inf):
    """Minimise ``neg`` (value and gradient) from ``x0``, until its largest
    gradient entry is at most ``gtol``, or it has not fallen for ``patience``
    iterations, or after ``max_iters``, or once its value is under
    ``floor``: ``(x, f, g, iters)``."""
    dtype = x0.dtype

    def search(x, d, slope0):
        """A step ``t`` whose slope lies in ``[C2, C1] * slope0``, bracketing
        by doubling and then halving: ``(t, f, grad, found, lo)``, ``lo``
        the longest step found short of the window."""
        def body(c):
            t, lo, hi, _, _, _, it = c
            f, g = neg(x + t * d)
            s = d @ g
            short = s < C2 * slope0       # still steep: the step may grow
            long = s > C1 * slope0        # past the window
            lo = jnp.where(short, t, lo)
            hi = jnp.where(long, t, hi)
            nt = jnp.where(jnp.isinf(hi), 2 * t, 0.5 * (lo + hi))
            done = ~short & ~long
            return jnp.where(done, t, nt), lo, hi, f, g, done, it + 1

        def cond(c):
            return ~c[5] & (c[6] < MAX_SEARCH)

        z = jnp.zeros((), dtype)
        t, lo, _, f, g, done, _ = jax.lax.while_loop(
            cond, body, (jnp.ones((), dtype), z, jnp.full((), jnp.inf, dtype),
                         z, jnp.zeros_like(x), jnp.zeros((), bool), 0))
        return t, f, g, done, lo

    def body(st):
        x, f, g, S, Y, rho, count, k, best, since, it = st
        d = _direction(g, S, Y, rho, count, k)
        slope0 = d @ g
        d = jnp.where(slope0 < 0, d, -g)  # keep a descent direction
        slope0 = d @ g
        t, f1, g1, found, lo = search(x, d, slope0)
        # without a step in the window, fall back to the longest step
        # known to lie short of it, which still descends
        t = jnp.where(found, t, lo)
        moved = t > 0
        f1, g1 = jax.lax.cond(found | ~moved, lambda: (f1, g1), lambda: neg(x + t * d))
        s, y = t * d, g1 - g
        sy = s @ y
        keep = moved & (sy > 1e-10 * jnp.sqrt((s @ s) * (y @ y)))
        i = k % HISTORY
        S = jnp.where(keep, S.at[i].set(s), S)
        Y = jnp.where(keep, Y.at[i].set(y), Y)
        rho = jnp.where(keep, rho.at[i].set(1 / jnp.where(keep, sy, 1)), rho)
        count = jnp.where(keep, jnp.minimum(count + 1, HISTORY), count)
        k = jnp.where(keep, k + 1, k)
        x = jnp.where(moved, x + s, x)
        f = jnp.where(moved, f1, f)
        g = jnp.where(moved, g1, g)
        res = jnp.max(jnp.abs(g))
        since = jnp.where(res < best, 0, since + 1)
        return x, f, g, S, Y, rho, count, k, jnp.minimum(res, best), since, it + 1

    def cond(st):
        f, g, since, it = st[1], st[2], st[-2], st[-1]
        return ((jnp.max(jnp.abs(g)) > gtol) & (it < max_iters) & (since < patience)
                & (f >= floor))

    f0, g0 = neg(x0)
    zH = jnp.zeros((HISTORY, x0.shape[0]), dtype)
    st = (x0, f0, g0, zH, zH, jnp.zeros((HISTORY,), dtype), 0, 0,
          jnp.max(jnp.abs(g0)), 0, 0)
    x, f, g, *_, iters = jax.lax.while_loop(cond, body, st)
    return x, f, g, iters


@functools.partial(jax.jit, static_argnames=("reg", "max_iters", "dtype", "patience"))
def solve(C, a, b, gtol, *, reg: GroupSparse, max_iters: int,
          dtype=jnp.float32, x0=None, patience: int = PATIENCE) -> RefSolution:
    """Maximise ``D`` over ``(alpha, beta)`` from ``x0`` (zero if not
    given), on every block; ``C (m, n)``.  Stops at ``gtol``, at the
    precision's floor (``patience`` iterations without a new least
    residual) or after ``max_iters`` iterations."""
    C, a, b = C.astype(dtype), a.astype(dtype), b.astype(dtype)
    m, n = C.shape
    x0 = jnp.zeros((m + n,), dtype) if x0 is None else x0.astype(dtype)

    def neg(x):                       # -D and its gradient, on x = (alpha, beta)
        v, ga, gb = dual(x[:m], x[m:], C, a, b, reg)
        return -v, -jnp.concatenate([ga, gb])

    x, f, g, iters = _lbfgs(neg, x0, gtol, max_iters, patience)
    alpha, beta = x[:m], x[m:]
    return RefSolution(alpha, beta, -f, plan(alpha, beta, C, reg), iters,
                       jnp.max(jnp.abs(g)))


@functools.partial(jax.jit, static_argnames=("reg", "max_iters"))
def _solve_on_blocks(x0, rows, cols, Cb, a, b, upper, *, reg: GroupSparse,
                     max_iters: int):
    """``D`` maximised from ``x0`` over the class blocks listed: block ``k``
    is rows ``rows[k]`` (``g`` of them, one class) of column ``cols[k]``,
    with costs ``Cb[k]``.  The blocks left out are held to have no plan.
    Stops once that ``D`` passes ``upper``, a bound on the optimum of the
    whole problem: the set does not hold the optimum then."""
    m = a.shape[0]

    def neg(x):
        alpha, beta = x[:m], x[m:]
        f = alpha[rows] + beta[cols][:, None] - Cb
        fp = jnp.maximum(f, 0)
        z = jnp.sqrt(jnp.sum(fp * fp, axis=1))
        shrink = jnp.maximum(z - reg.tau, 0)
        T = (jnp.where(z > 0, shrink / jnp.where(z > 0, z, 1), 0) / reg.gam)[:, None] * fp
        v = alpha @ a + beta @ b - jnp.sum(shrink * shrink) / (2 * reg.gam)
        ga = a - jnp.zeros_like(a).at[rows].add(T)
        gb = b - jnp.zeros_like(b).at[cols].add(jnp.sum(T, axis=1))
        return -v, -jnp.concatenate([ga, gb])

    return _lbfgs(neg, x0, F64_GTOL, max_iters, max_iters, floor=-upper)


@functools.partial(jax.jit, static_argnames=("reg",))
def _dense(x, C, a, b, *, reg: GroupSparse):
    """At ``x``: ``D``, the largest residual, the plan and the block norms."""
    m = a.shape[0]
    alpha, beta = x[:m], x[m:]
    v, ga, gb = dual(alpha, beta, C, a, b, reg)
    res = jnp.maximum(jnp.max(jnp.abs(ga)), jnp.max(jnp.abs(gb)))
    return v, res, plan(alpha, beta, C, reg), _blocks(alpha, beta, C, reg)[1]


def _working_set(blocks, g: int, C):
    """The blocks marked in ``blocks (L, n)`` as ``(rows, cols, Cb)``,
    padded to a multiple of 1,024 blocks (so that costs with sets of about
    one size share a program) by blocks whose cost keeps them without a
    plan."""
    ls, js = np.nonzero(blocks)
    size = -(-len(ls) // 1024) * 1024
    pad = size - len(ls)
    rows = (np.concatenate([ls, np.zeros(pad, ls.dtype)])[:, None] * g
            + np.arange(g)[None, :])
    cols = np.concatenate([js, np.zeros(pad, js.dtype)])
    Cb = np.asarray(C)[rows, cols[:, None]]
    Cb[len(ls):] = 1e30
    return rows, cols, Cb


def optimum(C, a, b, *, reg: GroupSparse, max_iters: int) -> RefSolution:
    """The float64 optimum of ``D``, as numpy arrays on the host, certified
    on every block (module docstring); ``max_iters`` caps the float32 solve.
    ``iters`` counts the float64 iterations."""
    x32 = solve(C, a, b, 0.0, reg=reg, max_iters=max_iters)
    cpu = jax.devices("cpu")[0]
    with jax.enable_x64(True):
        C, a, b, x0 = (jax.device_put(np.asarray(v, np.float64), cpu)
                       for v in (C, a, b, jnp.concatenate([x32.alpha, x32.beta])))
        # weak duality: the primal value of the plan a b^T bounds D from above
        ab = np.asarray(a)[:, None] * np.asarray(b)[None, :]
        upper = float(np.sum(ab * np.asarray(C)) + reg.gam / 2 * np.sum(ab * ab)
                      + reg.tau * np.sum(np.sqrt(np.sum(ab.reshape(reg.L, reg.g, -1) ** 2, axis=1))))
        x, iters, blocks = x0, 0, False
        for round_ in range(ROUNDS + 1):
            v, res, T, z = _dense(x, C, a, b, reg=reg)
            if res <= F64_GTOL or round_ == ROUNDS:
                break
            blocks = blocks | (np.asarray(z) > reg.tau - MARGIN)
            rows, cols, Cb = _working_set(blocks, reg.g, C)
            x, _, _, k = _solve_on_blocks(x, rows, cols, Cb, a, b, upper, reg=reg,
                                          max_iters=F64_MAX_ITERS)
            iters += int(k)
        if not res <= F64_GTOL:
            sol = solve(C, a, b, F64_GTOL, reg=reg, max_iters=DENSE_MAX_ITERS,
                        dtype=jnp.float64, x0=x0, patience=DENSE_MAX_ITERS)
            x = jnp.concatenate([sol.alpha, sol.beta])
            iters += int(sol.iters)
            v, res, T, z = _dense(x, C, a, b, reg=reg)
        m = a.shape[0]
        return RefSolution(*(np.asarray(u) for u in (x[:m], x[m:], v, T)),
                           iters, float(res))
