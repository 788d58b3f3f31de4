"""The plain reference of a residual configuration's network, in
``jax.numpy``.

It imports nothing of the program: the layers come from the configuration
file (``residual_net.py``) and the weights from the benchmark's own
generator. A convolution is ``lax.conv_general_dilated``, a max pool and
the global average pool ``lax.reduce_window``, an FC layer a ``dot``. A
layer reads the output its ``reads`` names, or the previous one; a join
adds its shortcut's output to the convolution's before the ReLU; the
projections and the logits have no activation.

``precision`` is as in ``bench/reference.py``: ``"highest"``, float32
products, the configuration's own precision; ``"high"``, three bfloat16
passes written out, the control that has to come out as not correct.
"""
from __future__ import annotations

from functools import partial
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import HIGHEST, PRECISIONS, _product
from bench.residual_net import Layer


def _layer(layer: Layer, x, w, shortcut, precision: str):
    if layer.kind == "fc":
        x = x.reshape(x.shape[0], -1)       # row-major (h, w, c) flatten
        y = _product(partial(jnp.dot, precision=HIGHEST), x, w, precision)
        return jax.nn.relu(y) if layer.relu else y
    p = layer.padding

    def op(a, b):
        return jax.lax.conv_general_dilated(
            a, b, (layer.stride, layer.stride), ((p, p), (p, p)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)

    y = _product(op, x, w, precision)
    if shortcut is not None:
        y = y + shortcut
    if layer.relu:
        y = jax.nn.relu(y)
    if layer.pool is not None:
        k, s, q = layer.pool
        y = jax.lax.reduce_window(y, -jnp.inf, jax.lax.max, (1, k, k, 1),
                                  (1, s, s, 1),
                                  ((0, 0), (q, q), (q, q), (0, 0)))
    if layer.average:
        _, h, w_, _ = y.shape
        y = jax.lax.reduce_window(y, 0.0, jax.lax.add, (1, h, w_, 1),
                                  (1, 1, 1, 1), "VALID") / (h * w_)
    return y


def forward(net: Sequence[Layer], precision: str = "highest"):
    """``f(x, ws)``: float32 images ``(B, H, W, C)`` and one weight array
    per layer to float32 logits ``(B, classes)``."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    net = tuple(net)

    def f(x, ws):
        outs = {}
        for layer, w in zip(net, ws):
            h = outs[layer.reads] if layer.reads is not None else x
            x = _layer(layer, h, w, outs.get(layer.shortcut), precision)
            outs[layer.name] = x
        return x

    return f


def logits(net: Sequence[Layer], ws: List, batches: Sequence[np.ndarray],
           precision: str = "highest") -> List[np.ndarray]:
    """The reference's logits for each batch, one batch at a time."""
    f = jax.jit(forward(net, precision))
    ws = [jnp.asarray(w) for w in ws]
    return [np.asarray(f(jnp.asarray(b), ws)) for b in batches]
