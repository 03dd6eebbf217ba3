"""Inputs of every cell, made from the run's seed.

The generator is the paper's synthetic domain-adaptation pair (Ida et al.,
AAAI 2023, Sec. 5; the system's ``data.pipeline.make_domain_pair``, copied
here so that a later change to the system cannot move the yardstick):
class l of the source has its mean at ``(l * shift, -shift)`` and of the
target at ``(l * shift, +shift)``, with unit Gaussian noise, and the cost
is the squared Euclidean distance divided by its largest entry.  ``shift``
sets how far apart the classes lie, and so how sparse the plan is.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def device_seed(seed: int) -> int:
    """A 31-bit key for ``jax.random`` from a seed of any size."""
    return int(np.random.default_rng(seed).integers(0, 2**31 - 1))


def _means(L, g, shift, dim, sign):
    labels = jnp.repeat(jnp.arange(L, dtype=jnp.float32), g)
    cols = [labels * shift, jnp.full((L * g,), sign * shift, jnp.float32)]
    cols += [jnp.zeros((L * g,), jnp.float32)] * (dim - 2)
    return jnp.stack(cols, axis=1)


def _cost(xs, xt):
    """Squared Euclidean distances, coordinate by coordinate (no matmul,
    so no reduced-precision pass), divided by their maximum."""
    C = sum((xs[:, d, None] - xt[None, :, d]) ** 2 for d in range(xs.shape[1]))
    return C / jnp.max(C)


@functools.partial(jax.jit, static_argnames=("L", "g", "dim", "shift", "count"))
def costs(seed, *, L, g, dim, shift, count):
    """``count`` costs ``(L g, L g)`` float32, made on the device, rows and
    columns sorted by class."""
    out = []
    for key in jax.random.split(jax.random.key(seed), count):
        ks, kt = jax.random.split(key)
        xs = jax.random.normal(ks, (L * g, dim), jnp.float32) + _means(L, g, shift, dim, -1.0)
        xt = jax.random.normal(kt, (L * g, dim), jnp.float32) + _means(L, g, shift, dim, 1.0)
        out.append(_cost(xs, xt))
    return tuple(out)
