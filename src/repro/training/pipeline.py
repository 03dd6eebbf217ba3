"""Pipeline parallelism over the "pod" axis (GPipe-style, shard_map).

For pod-scale deployments where cross-pod FSDP all-gathers are too
expensive (see EXPERIMENTS.md §Roofline: cross-pod wire is the dominant
term for the largest archs), the alternative is to place CONSECUTIVE layer
blocks on different pods and stream microbatches through them:

  * each pod holds 1/P of the layer stack (no cross-pod param movement),
  * activations hop pod->pod once per microbatch per boundary
    (collective_permute — exactly the neighbour link),
  * the schedule is GPipe: P + M - 1 ticks for M microbatches, bubble
    fraction (P-1)/(M+P-1).

This module implements the schedule as a shard_map'd lax.scan: at tick t,
stage s computes microbatch (t - s) if 0 <= t - s < M, then ppermutes its
output to stage s+1.  Stages are data-parallel inside the pod as usual.

Cross-pod wire per step = 2 * M * microbatch_bytes * (P-1) (fwd + bwd) —
for jamba train_4k: 2 * 32 * (8 tok-rows x 4096 x 8192 x 2B) ~= 6.9e10 B
vs the FSDP baseline's 3.9e12: the roofline motivation for PP at this
scale.  The full-framework integration point is `stage_fn`; the unit tests
drive it with real transformer blocks at toy sizes and assert equality
with the sequential model.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def gpipe_forward(
    stage_fn: Callable,        # (stage_params, x) -> x
    stage_params,              # pytree with leading [P] stage axis (sharded)
    x_microbatches: jnp.ndarray,  # (M, mb, ...) microbatched input
    mesh: Mesh,
    axis: str = "pod",
):
    """Run the GPipe forward schedule under shard_map over ``axis``.

    Returns the final-stage outputs, microbatch order preserved.
    Correctness contract: equals sequentially applying all P stages
    (tests/test_pipeline.py).
    """
    Pn = mesh.shape[axis]
    M = x_microbatches.shape[0]

    def staged(params_local, x_mb):
        # params_local: this stage's params (leading axis stripped to size 1)
        params_local = jax.tree_util.tree_map(lambda p: p[0], params_local)
        stage = jax.lax.axis_index(axis)

        mb_shape = x_mb.shape[1:]
        ticks = M + Pn - 1

        def tick(carry, t):
            buf_in, outputs = carry
            # stage 0 injects microbatch t from its local copy; others use
            # what arrived over the link last tick
            mb_idx = t - stage
            active = jnp.logical_and(mb_idx >= 0, mb_idx < M)
            feed = jnp.where(
                stage == 0,
                x_mb[jnp.clip(t, 0, M - 1)],
                buf_in,
            )
            y = stage_fn(params_local, feed)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # last stage records its finished microbatch
            outputs = jnp.where(
                jnp.logical_and(stage == Pn - 1, active),
                outputs.at[jnp.clip(mb_idx, 0, M - 1)].set(y),
                outputs,
            )
            # ship to the next stage (ring; last->first carries garbage,
            # ignored because stage 0 always injects fresh input)
            buf_next = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % Pn) for i in range(Pn)]
            )
            return (buf_next, outputs), None

        buf0 = jnp.zeros(mb_shape, x_mb.dtype)
        outs0 = jnp.zeros((M,) + mb_shape, x_mb.dtype)
        (_, outputs), _ = jax.lax.scan(
            tick, (buf0, outs0), jnp.arange(ticks)
        )
        # every stage holds `outputs`, but only the last stage's is real;
        # broadcast it (tiny at toy scale; on real pods the consumer IS the
        # last stage, so this psum is test-convenience only)
        src = (outputs == 0).all().astype(outputs.dtype)  # unused marker
        del src
        outputs = jax.lax.psum(
            jnp.where(stage == Pn - 1, outputs, jnp.zeros_like(outputs)), axis
        )
        return outputs

    spec_params = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(
        staged,
        mesh=mesh,
        in_specs=(spec_params, P()),      # stages sharded; input replicated
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, x_microbatches)


def bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    return (num_stages - 1) / (num_microbatches + num_stages - 1)
