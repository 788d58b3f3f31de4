"""Weights from ``--seed``: He-scaled normals, made on the device.

Each layer's weights are normal with standard deviation
``sqrt(2 / fan_in)``, ``fan_in`` being the product of all axes but the
last, the scaling that keeps activations of order one through a deep ReLU
chain. All layers come out of one jitted call, in float32, the type the
configuration serves them in.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, stream: int):
    """A key for ``stream`` of ``seed``; any seed that fits 64 bits."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 32), stream)


def he_normal(shapes: Sequence[Tuple[int, ...]], seed: int) -> List[jax.Array]:
    shapes = [tuple(s) for s in shapes]
    scales = [float(np.sqrt(2.0 / np.prod(s[:-1]))) for s in shapes]

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(shapes))
        return [jax.random.normal(k, s, jnp.float32) * c
                for k, s, c in zip(keys, shapes, scales)]

    return make(seed_key(seed, 0))
