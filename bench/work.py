"""Operations and bytes a network's layers need, from their unpadded shapes.

For one layer and one call of ``batch`` images:

* FLOPs = 2 * MAC * batch;
* bytes = batch * (input + output) + weights, each at the configuration's
  dtype. The output is counted after the layer's fused pool, and the
  weights once a call: the least traffic any implementation can have.

A layer's roofline time is ``max(FLOPs / peak FLOP/s, bytes / peak B/s)``.
Both counts depend on the network alone, never on how the program pads,
tiles or fuses it, so a faster implementation reads against the same work
and a share of the roofline cannot pass 100% unless the time is wrong.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from typing import List

import numpy as np

from bench.network import BENCH, Layer, layers


@dataclass(frozen=True)
class LayerWork:
    flops: float          # per call
    bytes: float          # per call
    seconds: float        # roofline time per call
    bound: str            # "compute" or "memory"


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json; known: {sorted(table)}")
    return table[device_kind]


def layer_bytes(layer: Layer, batch: int, itemsize: int) -> float:
    h, w = layer.out_hw
    ifm = layer.h_in * layer.w_in * layer.c_in
    ofm = h * w * layer.c_out
    return float(itemsize * (batch * (ifm + ofm)
                             + int(np.prod(layer.weight_shape))))


def network_work(cfg: dict, batch: int, peak: dict) -> List[LayerWork]:
    itemsize = np.dtype(cfg["dtype"]).itemsize
    flops_peak = peak["flops_per_s"]["bfloat16"]
    bw = peak["hbm_bytes_per_s"]
    out = []
    for layer in layers(cfg):
        flops = 2.0 * layer.macs * batch
        nbytes = layer_bytes(layer, batch, itemsize)
        tc, tm = flops / flops_peak, nbytes / bw
        out.append(LayerWork(flops, nbytes, max(tc, tm),
                             "compute" if tc >= tm else "memory"))
    return out


def macs_per_image(cfg: dict) -> int:
    return sum(layer.macs for layer in layers(cfg))


def roofline_seconds(cfg: dict, batch: int, peak: dict) -> float:
    """The least time one call of ``batch`` images can take on the chip."""
    return sum(w.seconds for w in network_work(cfg, batch, peak))
