"""The plain reference of a configuration's network, in ``jax.numpy``.

It imports nothing of the program: the layer shapes come from the
configuration file (``network.py``) and the weights from the benchmark's
own generator. A convolution is ``lax.conv_general_dilated``, a pool
``lax.reduce_window`` and a fully connected layer a ``dot``, each followed
by the ReLU that the configuration states.

``precision`` is how each product is computed:

* ``"highest"``: float32 operands at ``Precision.HIGHEST``, the
  configuration's own precision (float32 products on a TPU);
* ``"high"``: the next precision below, three bfloat16 passes. Each
  operand is split into a bfloat16 high part and a bfloat16 low part, and
  the product is ``hi*hi + hi*lo + lo*hi``, each pass exact in float32.
  This is what ``Precision.HIGH`` does on a TPU, written out so that it
  reads the same on any backend. It is the control: put in the program's
  place, it has to come out as not correct.
"""
from __future__ import annotations

from functools import partial
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.network import Layer

PRECISIONS = ("highest", "high")
HIGHEST = jax.lax.Precision.HIGHEST


def _bf16(x):
    # rounds to bfloat16 and stays float32; unlike a round trip through
    # astype, XLA may not elide it to keep excess precision
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _split(x):
    hi = _bf16(x)
    return hi, _bf16(x - hi)


def _product(op, a, b, precision: str):
    if precision == "highest":
        return op(a, b)
    a_hi, a_lo = _split(a)
    b_hi, b_lo = _split(b)
    return op(a_hi, b_lo) + op(a_lo, b_hi) + op(a_hi, b_hi)


def _layer(layer: Layer, x, w, precision: str):
    if layer.kind == "conv":
        p = layer.padding

        def op(a, b):
            return jax.lax.conv_general_dilated(
                a, b, (layer.stride, layer.stride), ((p, p), (p, p)),
                dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)

        y = jax.nn.relu(_product(op, x, w, precision))
        if layer.pool is not None:
            k, s = layer.pool
            y = jax.lax.reduce_window(y, -jnp.inf, jax.lax.max,
                                      (1, k, k, 1), (1, s, s, 1), "VALID")
        return y
    if x.ndim > 2:
        x = x.reshape(x.shape[0], -1)       # row-major (h, w, c) flatten
    return jax.nn.relu(_product(partial(jnp.dot, precision=HIGHEST),
                                x, w, precision))


def forward(net: Sequence[Layer], precision: str = "highest"):
    """``f(x, ws)``: float32 images ``(B, H, W, C)`` and one weight array
    per layer to float32 logits ``(B, classes)``."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    net = tuple(net)

    def f(x, ws):
        for layer, w in zip(net, ws):
            x = _layer(layer, x, w, precision)
        return x

    return f


def logits(net: Sequence[Layer], ws: List, batches: Sequence[np.ndarray],
           precision: str = "highest") -> List[np.ndarray]:
    """The reference's logits for each batch, one batch at a time, so that
    a batch's activations are the most it holds on the device."""
    f = jax.jit(forward(net, precision))
    ws = [jnp.asarray(w) for w in ws]
    return [np.asarray(f(jnp.asarray(b), ws)) for b in batches]
