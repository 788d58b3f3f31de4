"""A residual configuration's network as plain layer shapes, and its work.

The benchmark's own walk of ``bench/configs/<name>.json`` for a network
of bottleneck blocks (``"path": "executor_residual"``); it reads nothing
of the program. ``layers(cfg)`` gives, in the program's order and under
its names: the stem (a convolution with a max pool), then for each block
of each stage ``a`` (1x1), ``b`` (3x3, the stage's stride in its first
block), in a stage's first block ``proj`` (the 1x1 projection of the
block's input, no activation), and ``c`` (1x1, the join: ``relu(conv +
shortcut)``); the global average pool after the last ``c``, and the
classifier.

Work counts follow ``work.py``: FLOPs = 2 * MAC * batch; bytes = batch *
(input + output after the pool) + weights once a call, at the
configuration's dtype, plus for each join the shortcut it reads, batch *
its output before the pool. Both depend on the network alone.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np

from bench import weights as weights_mod
from bench.work import LayerWork


@dataclass(frozen=True)
class Layer:
    name: str
    kind: str                     # "conv" or "fc"
    c_in: int
    c_out: int
    h_in: int = 1
    w_in: int = 1
    k: int = 1
    stride: int = 1
    padding: int = 0
    reads: Optional[str] = None   # the layer whose output it reads; None
    #                               for the previous layer's, or the images
    shortcut: Optional[str] = None  # the join's: the output added
    relu: bool = True
    pool: Optional[Tuple[int, int, int]] = None  # max pool: kernel, stride,
    #                                              padding
    average: bool = False         # global average pool after it

    @property
    def h_out(self) -> int:
        return (self.h_in + 2 * self.padding - self.k) // self.stride + 1

    @property
    def w_out(self) -> int:
        return (self.w_in + 2 * self.padding - self.k) // self.stride + 1

    @property
    def out_hw(self) -> Tuple[int, int]:
        """Height and width of what the layer hands on, after its pools."""
        if self.kind == "fc" or self.average:
            return 1, 1
        h, w = self.h_out, self.w_out
        if self.pool is not None:
            k, s, p = self.pool
            h, w = (h + 2 * p - k) // s + 1, (w + 2 * p - k) // s + 1
        return h, w

    @property
    def weight_shape(self) -> Tuple[int, ...]:
        if self.kind == "conv":
            return (self.k, self.k, self.c_in, self.c_out)
        return (self.c_in, self.c_out)

    @property
    def macs(self) -> int:
        """Multiply-accumulates for one image."""
        if self.kind == "conv":
            return (self.h_out * self.w_out * self.k * self.k
                    * self.c_in * self.c_out)
        return self.c_in * self.c_out

    @property
    def joins(self) -> bool:
        return self.shortcut is not None


def input_shape(cfg: dict) -> Tuple[int, int, int]:
    i = cfg["input"]
    return (i["height"], i["width"], i["channels"])


def layers(cfg: dict) -> List[Layer]:
    h, w, c = input_shape(cfg)
    if cfg["pool"] != "global_average" or cfg["flatten"] != "hwc" or h != w:
        raise ValueError(f"{cfg['name']}: only square input, a global "
                         "average pool and an (h, w, c) flatten are walked")
    prefix = cfg["layer_prefix"]
    st = cfg["stem"]
    stem = Layer(f"{prefix}.stem", "conv", c, st["channels"], h, h,
                 st["kernel"], st["stride"], st["padding"],
                 pool=(st["pool_kernel"], st["pool_stride"],
                       st["pool_padding"]))
    out: List[Layer] = [stem]
    x, c, (h, _) = stem.name, stem.c_out, stem.out_hw
    for i, stage in enumerate(cfg["stages"], 1):
        width, wide = stage["width"], stage["width"] * cfg["expansion"]
        for b in range(stage["blocks"]):
            s = stage["stride"] if b == 0 else 1
            name = f"{prefix}.s{i}b{b}"
            out += [Layer(f"{name}.a", "conv", c, width, h, h, reads=x),
                    Layer(f"{name}.b", "conv", width, width, h, h, 3, s, 1)]
            shortcut = x
            if b == 0:
                shortcut = f"{name}.proj"
                out.append(Layer(shortcut, "conv", c, wide, h, h, 1, s,
                                 reads=x, relu=False))
            ho = out[-1].h_out
            out.append(Layer(f"{name}.c", "conv", width, wide, ho, ho,
                             reads=f"{name}.b", shortcut=shortcut))
            x, c, h = f"{name}.c", wide, ho
    out[-1] = Layer(**{**out[-1].__dict__, "average": True})
    relu_last = cfg["activation_on_logits"]
    for j, v in enumerate(cfg["classifier"]):
        last = j == len(cfg["classifier"]) - 1
        out.append(Layer(f"{prefix}.fc" if last else f"{prefix}.fc{j}",
                         "fc", c, int(v), relu=relu_last or not last))
        c = int(v)
    return out


def weights(cfg: dict, net: Sequence[Layer], seed: int) -> list:
    """He-normal weights from the seed (``bench/weights.py``), the last
    convolution of each block's branch, the join, scaled by the
    configuration's ``assumed.branch_scale``; on the device."""
    ws = weights_mod.he_normal([l.weight_shape for l in net], seed)
    scale = cfg["assumed"]["branch_scale"]
    return [w * scale if l.joins else w for l, w in zip(net, ws)]


def layer_bytes(layer: Layer, batch: int, itemsize: int) -> float:
    h, w = layer.out_hw
    ifm = layer.h_in * layer.w_in * layer.c_in
    ofm = h * w * layer.c_out
    shortcut = layer.h_out * layer.w_out * layer.c_out if layer.joins else 0
    return float(itemsize * (batch * (ifm + ofm + shortcut)
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


def roofline_seconds(cfg: dict, batch: int, peak: dict,
                     only_joins: bool = False) -> float:
    """The least time one call of ``batch`` images can take on the chip:
    of every layer, or of the joins alone."""
    return sum(w.seconds for l, w in zip(layers(cfg),
                                         network_work(cfg, batch, peak))
               if l.joins or not only_joins)
