"""The plain float32 reference of a workload's forward pass, image → logits.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, one layer at a time, with no
kernels, blocks or batching tricks: a convolution is
``lax.conv_general_dilated``, a max pool ``lax.reduce_window``, the global
average pool a ``reduce_window`` sum over the whole map divided by its
size, and an FC layer a ``dot`` after a row-major (height, width, channel)
flatten. A layer reads the previous layer's output or the one its
``input_from`` names; a residual join adds the output its
``residual_from`` names to the convolution's before the activation.
It follows the layer specs and shares no code with the executor, which
the tests compare with it.

``precision="bfloat16"`` rounds each product's operands to bfloat16
first, one bfloat16 pass: the lower precision that a comparison with the
float32 reference has to be tight enough to refuse.
"""
from __future__ import annotations

from typing import Mapping, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.mapping import ConvSpec

PRECISIONS = ("highest", "bfloat16")


def _operand(x, precision: str):
    if precision == "bfloat16":
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x


def _activate(layer, y):
    return jax.nn.relu(y) if layer.activation == "relu" else y


def _conv(layer: ConvSpec, x, w, shortcut, precision: str):
    p = layer.padding
    y = jax.lax.conv_general_dilated(
        _operand(x, precision), _operand(w, precision),
        (layer.stride, layer.stride), ((p, p), (p, p)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    if shortcut is not None:
        y = y + shortcut
    y = _activate(layer, y)
    if layer.pool_k > 0:
        k, s, q = layer.pool_k, layer.pool_stride, layer.pool_padding
        y = jax.lax.reduce_window(y, -jnp.inf, jax.lax.max, (1, k, k, 1),
                                  (1, s, s, 1),
                                  ((0, 0), (q, q), (q, q), (0, 0)))
    if layer.avg_pool:
        _, h, w_, _ = y.shape
        y = jax.lax.reduce_window(y, 0.0, jax.lax.add, (1, h, w_, 1),
                                  (1, 1, 1, 1), "VALID") / (h * w_)
    return y


def forward(layers, weights: Union[Mapping[str, np.ndarray], Sequence],
            images, precision: str = "highest") -> np.ndarray:
    """Float32 logits of ``images`` ``(B, H, W, C)`` through ``layers``
    (a ``Workload`` or a sequence of layer specs), with ``weights`` keyed
    by layer name or aligned with the layers."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    layers = tuple(layers)
    if isinstance(weights, Mapping):
        weights = [weights[l.name] for l in layers]
    outs = {}
    x = jnp.asarray(images, jnp.float32)
    with jax.default_matmul_precision("highest"):
        for layer, w in zip(layers, weights):
            w = jnp.asarray(w, jnp.float32)
            src = getattr(layer, "input_from", None)
            h = outs[src] if src is not None else x
            if isinstance(layer, ConvSpec):
                res = layer.residual_from
                x = _conv(layer, h, w, outs[res] if res else None, precision)
            else:
                h = h.reshape(h.shape[0], -1)
                x = _activate(layer, jnp.dot(_operand(h, precision),
                                             _operand(w, precision)))
            outs[layer.name] = x
    return np.asarray(x)
