"""Multi-device sharded batched OT solving: the problem axis over a mesh.

The batched solver (:mod:`repro.core.solver`) advances ``B`` independent
problems in one jitted program; nothing in a round couples batch members
(the dual is separable, screening state is per problem, convergence is
masked per problem).  That makes the batch axis *embarrassingly shardable*:
this module runs ``solve_batch`` / the round-step API under ``shard_map``
with ``B`` split over a 1-D device mesh, and each device executes the
ordinary batched solver on its local slice —

  * per-shard screening state: snapshots and the active set N live with
    their problems, no replication,
  * per-shard compact tile schedules: the dynamic-grid compact kernel
    already runs an independent (b, l, j) list per launch, so each shard
    builds its own list over its local problems and its grid steps scale
    with the shard's surviving tiles,
  * per-problem convergence with masked freezing: a shard whose problems
    all finish simply idles through the masked ops; no cross-device sync
    happens inside a round.

The only cross-device data movement is at round boundaries, when a caller
(the serving engine) reads the ``(B,)`` ``converged`` / ``failed`` flags —
a gather of a few bytes per device, handled by the host read of the
sharded output.

Bitwise contract: a problem solved sharded is bitwise-identical to the
same problem in an unsharded ``solve_batch`` (and hence to its solo
``solve_dual``).  Per-problem math reduces only over trailing axes, and
the two Pallas grid modes produce bitwise-equal outputs, so even the
``impl='auto'`` density switch — which sees shard-local live counts
instead of batch-global ones — cannot break parity.  The same holds for
the fused backend's runtime switch (both of its branches are bitwise
equal too).  Asserted for all ``grad_impl`` backends by
tests/test_sharded.py on 4 forced host devices.

Mesh construction is wired through :func:`repro.core.distributed.make_batch_mesh`
(the 1-D :data:`~repro.core.distributed.BATCH_AXIS` mesh) and
:func:`repro.sharding.partition.batch_solve_rules` (the ``problems``
logical axis), so no caller hand-rolls device lists or axis names.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core import screening
from repro.core import solver as slv
from repro.core.distributed import make_batch_mesh
from repro.core.dual import DualProblem
from repro.core.groups import PAD_COST, GroupSpec
from repro.core.lbfgs import state_pspecs as lbfgs_pspecs
from repro.core.regularizers import Regularizer
from repro.sharding.partition import batch_solve_rules


def problem_pspec(mesh: Mesh) -> PartitionSpec:
    """PartitionSpec sharding a leading problem axis over ``mesh``.

    Derived through the :func:`~repro.sharding.partition.batch_solve_rules`
    table (logical axis ``problems`` -> mesh batch axis), not hard-coded.

    Parameters
    ----------
    mesh : jax.sharding.Mesh
        A mesh containing the batch axis (see
        :func:`repro.core.distributed.make_batch_mesh`).

    Returns
    -------
    jax.sharding.PartitionSpec
        Spec for arrays whose axis 0 is the problem axis; used both as a
        shard_map prefix spec and to build NamedShardings.
    """
    return batch_solve_rules(mesh.axis_names).spec(("problems",))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """NamedSharding placing a ``(B, ...)`` array's axis 0 over the mesh."""
    return NamedSharding(mesh, problem_pspec(mesh))


def device_put_batch(tree, mesh: Mesh):
    """Place every leaf of ``tree`` with its axis 0 sharded over ``mesh``.

    Parameters
    ----------
    tree : pytree of arrays
        Each leaf must have a leading problem axis divisible by the mesh
        size.
    mesh : jax.sharding.Mesh
        The 1-D batch mesh.

    Returns
    -------
    pytree of jax.Array
        Same structure, leaves committed to the mesh devices.
    """
    sh = batch_sharding(mesh)
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), tree)


def state_pspecs(spec) -> slv.BatchSolveState:
    """Flattened shard_map specs for a :class:`~repro.core.solver.BatchSolveState`.

    Composes the per-component flatteners
    (:func:`repro.core.lbfgs.state_pspecs`,
    :func:`repro.core.screening.state_pspecs`) — every leaf of the solver
    state carries the leading problem axis, so the whole state shards with
    one leading-axis spec per leaf.
    """
    return slv.BatchSolveState(
        lb=lbfgs_pspecs(spec),
        scr=screening.state_pspecs(spec),
        rounds=spec,
        stats=spec,
    )


def pad_batch_to_devices(
    C: jnp.ndarray,
    a: jnp.ndarray,
    b: jnp.ndarray,
    row_mask: jnp.ndarray,
    sqrt_g: jnp.ndarray,
    num_devices: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, int]:
    """Pad a ragged batch up to a device-count multiple with dummy problems.

    Dummy problems are the serving engine's empty-slot construction:
    ``PAD_COST`` costs and zero marginals give an identically-zero dual
    gradient, so they converge at initialization and ride along for free
    without perturbing real problems (no cross-problem coupling exists).

    Parameters
    ----------
    C, a, b : jnp.ndarray
        Batched problem arrays ``(B, m_pad, n)`` / ``(B, m_pad)`` / ``(B, n)``.
    row_mask, sqrt_g : jnp.ndarray
        Per-problem ``(B, m_pad)`` bool mask and ``(B, L)`` group norms.
    num_devices : int
        Mesh size the padded batch must divide.

    Returns
    -------
    tuple
        ``(C, a, b, row_mask, sqrt_g, B_orig)`` with the leading axis
        padded to the next multiple of ``num_devices``.
    """
    B = C.shape[0]
    B_pad = -(-B // num_devices) * num_devices
    extra = B_pad - B
    if extra == 0:
        return C, a, b, row_mask, sqrt_g, B
    padB = lambda x, v: jnp.concatenate(
        [x, jnp.full((extra,) + x.shape[1:], v, x.dtype)], axis=0
    )
    from repro.kernels import ops as kops

    if isinstance(C, kops.FactorizedCost):
        # factorized dummy = zero samples + PAD_COST squared norms: every
        # cost entry is >= PAD_COST, same as the dense PAD_COST fill
        C_pad = kops.FactorizedCost(
            x=padB(C.x, 0), x_sq=padB(C.x_sq, PAD_COST),
            y=padB(C.y, 0), y_sq=padB(C.y_sq, PAD_COST),
        )
    else:
        C_pad = padB(C, PAD_COST)
    return (
        C_pad,
        padB(a, 0),
        padB(b, 0),
        padB(row_mask, False),
        padB(sqrt_g, 0),
        B,
    )


@functools.lru_cache(maxsize=64)
def _sharded_programs(mesh: Mesh, prob: DualProblem, opts: slv.SolveOptions):
    """Jitted shard_map'd (solve, init, round) programs for one geometry.

    Cached on ``(mesh, prob, opts)`` — all hashable statics — so long-lived
    callers (the serving engine ticks one of these per round) reuse the
    compiled executable.  ``check_vma=False``: the body is collective-free
    by construction (each shard runs the plain batched solver on its local
    problems), so the replication checker has nothing to verify and would
    reject the interpret-mode Pallas calls on CPU.
    """
    A = problem_pspec(mesh)
    ST = state_pspecs(A)

    def local_solve(C, a, b, rm, sg):
        return slv._solve_batch_impl(C, a, b, rm, sg, prob, opts)

    def local_init(C, a, b, rm, sg, padded):
        return slv._init_batch_state(C, a, b, rm, sg, prob, opts, padded)

    def local_round(state, C, a, b, rm, sg, padded):
        return slv._round_body(state, C, a, b, rm, sg, prob, opts, padded)

    arrs = (A, A, A, A, A)
    # `A` as a pytree-prefix spec covers the PaddedProblem arg (its single
    # leaf Cp carries the leading problem axis; geometry fields are static)
    # and degenerates to "no leaves" when padded is None (non-pallas).
    solve = jax.jit(
        jax.shard_map(
            local_solve, mesh=mesh, in_specs=arrs,
            out_specs=(lbfgs_pspecs(A), screening.state_pspecs(A), A, A),
            check_vma=False,
        )
    )
    init = jax.jit(
        jax.shard_map(
            local_init, mesh=mesh, in_specs=arrs + (A,), out_specs=ST,
            check_vma=False,
        )
    )
    rnd = jax.jit(
        jax.shard_map(
            local_round, mesh=mesh, in_specs=(ST,) + arrs + (A,),
            out_specs=ST, check_vma=False,
        )
    )
    return solve, init, rnd


def prepare_padded_sharded(C: jnp.ndarray, prob: DualProblem, mesh: Mesh,
                           precision: str = "f32"):
    """Build the batched PaddedProblem with its cost matrix mesh-sharded.

    The pallas/fused backends' tile-padded cost copy is the largest array
    in a solve; long-lived callers (engine buckets) build it once and keep
    its ``Cp`` committed shard-wise so a tick never re-pads or re-uploads.

    Parameters
    ----------
    C : jnp.ndarray
        ``(B, m_pad, n)`` batched costs (host or device).
    prob : DualProblem
        Static problem geometry.
    mesh : jax.sharding.Mesh
        The 1-D batch mesh.
    precision : {'f32', 'bf16'}
        Cost-operand storage; 'bf16' downcasts the prepared cost leaves
        exactly as :func:`repro.core.solver._prepare_padded` does, so a
        sharded bf16 solve sees the same rounded cost as an unsharded one.

    Returns
    -------
    repro.kernels.ops.PaddedProblem or repro.kernels.ops.FactorizedProblem
        Dense costs yield a PaddedProblem with ``Cp`` of shape
        ``(B, L_pad * g, n_pad)`` sharded over axis 0; factorized costs a
        FactorizedProblem whose four sample/norm leaves are sharded the
        same way (every leaf carries the leading problem axis).
    """
    import dataclasses

    from repro.kernels import ops as kops

    if isinstance(C, kops.FactorizedCost):
        pp = kops.prepare_factorized_problem(C, prob)
        if precision == "bf16":
            pp = dataclasses.replace(
                pp,
                x=pp.x.astype(jnp.bfloat16),
                x_sq=pp.x_sq.astype(jnp.bfloat16),
                y=pp.y.astype(jnp.bfloat16),
                y_sq=pp.y_sq.astype(jnp.bfloat16),
            )
    else:
        pp = kops.prepare_padded_problem_batched(jnp.asarray(C), prob)
        if precision == "bf16":
            pp = dataclasses.replace(pp, Cp=pp.Cp.astype(jnp.bfloat16))
    return device_put_batch(pp, mesh)


def init_batch_state_sharded(
    C, a, b, row_mask, sqrt_g, prob: DualProblem, opts: slv.SolveOptions,
    mesh: Mesh, padded=None,
):
    """Sharded counterpart of :func:`repro.core.solver.init_batch_state`.

    One program launch; every input/output leaf has its problem axis over
    ``mesh``.  ``row_mask`` / ``sqrt_g`` must be per-problem ``(B, ...)``
    here (shared forms cannot shard over the problem axis).

    Parameters
    ----------
    C, a, b : jnp.ndarray
        ``(B, m_pad, n)`` / ``(B, m_pad)`` / ``(B, n)``, ``B`` divisible by
        the mesh size.
    row_mask, sqrt_g : jnp.ndarray
        ``(B, m_pad)`` bool / ``(B, L)`` float32.
    prob, opts :
        Static solve description (hashable dataclasses).
    mesh : jax.sharding.Mesh
        1-D batch mesh from :func:`~repro.core.distributed.make_batch_mesh`.
    padded : PaddedProblem, optional
        Pre-built sharded padded problem (pallas backend); see
        :func:`prepare_padded_sharded`.

    Returns
    -------
    repro.core.solver.BatchSolveState
        Sharded initial state (valid snapshots + first oracle evaluation).
    """
    if padded is None and opts.grad_impl in ("pallas", "fused"):
        padded = prepare_padded_sharded(C, prob, mesh,
                                        precision=opts.precision)
    _, init, _ = _sharded_programs(mesh, prob, opts)
    return init(C, a, b, row_mask, sqrt_g, padded)


def batch_round_sharded(
    state, C, a, b, row_mask, sqrt_g, prob: DualProblem,
    opts: slv.SolveOptions, mesh: Mesh, padded=None,
):
    """Sharded counterpart of :func:`repro.core.solver.batch_round`.

    One fused Algorithm-1 round for the whole sharded batch in a single
    launch: each device runs the L-BFGS segment + screening refresh +
    snapshot for its local problems, frozen problems masked.  No
    collective appears inside the round; the caller reads the sharded
    ``converged`` flags afterwards (the round-boundary gather).

    Parameters are as in :func:`init_batch_state_sharded`, with ``state``
    the sharded :class:`~repro.core.solver.BatchSolveState` to advance.

    Returns
    -------
    repro.core.solver.BatchSolveState
        The advanced sharded state.
    """
    if padded is None and opts.grad_impl in ("pallas", "fused"):
        padded = prepare_padded_sharded(C, prob, mesh,
                                        precision=opts.precision)
    _, _, rnd = _sharded_programs(mesh, prob, opts)
    return rnd(state, C, a, b, row_mask, sqrt_g, padded)


def solve_batch_sharded(
    C: jnp.ndarray,
    a: jnp.ndarray,
    b: jnp.ndarray,
    spec: GroupSpec,
    reg: Regularizer,
    opts: slv.SolveOptions = slv.SolveOptions(),
    mesh: Optional[Mesh] = None,
) -> slv.BatchOTResult:
    """Solve B same-shape problems with the batch sharded across devices.

    The multi-device form of :func:`repro.core.solver.solve_batch`: one
    jitted ``shard_map`` program runs every problem to convergence, the
    problem axis split over a 1-D device mesh.  Per problem the result is
    bitwise-identical to the unsharded batched solve (and hence to
    :func:`~repro.core.solver.solve_dual`); see the module docstring for
    why the sharding cannot perturb the trajectory.

    Parameters
    ----------
    C : jnp.ndarray
        ``(B, m_pad, n)`` padded cost matrices, float32.
    a : jnp.ndarray
        ``(B, m_pad)`` padded source marginals.
    b : jnp.ndarray
        ``(B, n)`` target marginals.
    spec : GroupSpec
        Shared group layout (static geometry the program compiles for).
    reg : Regularizer
        Regularizer parameters.
    opts : SolveOptions, optional
        Any ``grad_impl`` backend
        ('dense' | 'screened' | 'pallas' | 'fused').
    mesh : jax.sharding.Mesh, optional
        1-D batch mesh; defaults to
        :func:`~repro.core.distributed.make_batch_mesh` over every local
        device.  ``B`` not divisible by the mesh size is padded with dummy
        problems (zero gradient, converged at init) and un-padded on
        return.

    Returns
    -------
    repro.core.solver.BatchOTResult
        Result container whose leaves remain device-sharded; indexing
        (``result[i]``) and the host conversions gather transparently.

    .. deprecated:: use :meth:`repro.ot.Executor.solve_many` with a mesh
       (``ExecutionPlan(devices='all')`` or ``compile(..., mesh=mesh)``)
       — this shim delegates there and emits a ``DeprecationWarning``.
    """
    import warnings

    warnings.warn(
        "solve_batch_sharded() is deprecated; use repro.ot "
        "(compile(..., ExecutionPlan(devices='all')).solve_many) instead",
        DeprecationWarning, stacklevel=2,
    )
    assert C.ndim == 3, f"expected (B, m_pad, n) costs, got {C.shape}"
    if mesh is None:
        mesh = make_batch_mesh()
    from repro.ot.executor import Executor
    from repro.ot.plan import ExecutionPlan

    ex = Executor(
        spec, int(C.shape[2]), reg, ExecutionPlan.from_solve_options(opts),
        mesh=mesh,
    )
    lb, scr, rounds, stats = ex._solve_padded_batch_sharded(C, a, b)
    alpha, beta = slv._split(lb.x, ex._prob.m_pad)
    return slv.BatchOTResult(alpha, beta, -lb.f, lb, scr, rounds, stats)


def _clear_program_cache() -> None:
    """Drop cached sharded executables (tests that rebuild meshes)."""
    _sharded_programs.cache_clear()


# number of local devices a default mesh would span — convenience for
# callers sizing batches/buckets without building a mesh first
def default_device_count() -> int:
    """``jax.local_device_count()`` (the default 1-D mesh size)."""
    return jax.local_device_count()
