"""Operands drawn on the device from the seed, in one program per set-up."""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """The run's key; any whole seed, also past 32 bits."""
    return jax.random.key(int(seed) % (1 << 63))


@functools.partial(jax.jit, static_argnums=1)
def normal_f64(key: jax.Array, shapes: Sequence[Tuple[int, ...]]):
    """One float64 array per shape, about normal(0, 1): z1 + z2 * 2^-24 from
    two float32 normals, so each value carries some 48 significant bits.
    (``jax.random.normal`` needs a 64-bit bitcast for float64, which XLA:TPU
    does not have.)"""
    keys = jax.random.split(key, 2 * len(shapes))
    out = []
    for i, shape in enumerate(shapes):
        z1 = jax.random.normal(keys[2 * i], shape, jnp.float32)
        z2 = jax.random.normal(keys[2 * i + 1], shape, jnp.float32)
        out.append(z1.astype(jnp.float64) + z2.astype(jnp.float64) * 2.0 ** -24)
    return tuple(out)
