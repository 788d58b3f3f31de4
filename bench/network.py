"""A configuration file's network as plain layer shapes.

The benchmark reads its own copy of each network from
``bench/configs/<name>.json`` and never from the program, so the work
counts (``work.py``) and the reference (``reference.py``) stay the same
whatever the program does. ``layers(cfg)`` walks the VGG-style list: an
integer is a convolution to that many channels, ``"M"`` a max pool fused
into the convolution before it, and ``classifier`` the fully connected
widths after a flatten.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import List, Optional, Tuple

BENCH = Path(__file__).resolve().parent


@dataclass(frozen=True)
class Layer:
    kind: str                     # "conv" or "fc"
    c_in: int
    c_out: int
    h_in: int = 1
    w_in: int = 1
    k: int = 1
    stride: int = 1
    padding: int = 0
    pool: Optional[Tuple[int, int]] = None   # (kernel, stride)

    @property
    def h_out(self) -> int:
        return (self.h_in + 2 * self.padding - self.k) // self.stride + 1

    @property
    def w_out(self) -> int:
        return (self.w_in + 2 * self.padding - self.k) // self.stride + 1

    @property
    def out_hw(self) -> Tuple[int, int]:
        """Height and width of what the layer hands on, after its pool."""
        h, w = self.h_out, self.w_out
        if self.pool is not None:
            pk, ps = self.pool
            h, w = (h - pk) // ps + 1, (w - pk) // ps + 1
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


def load_config(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def input_shape(cfg: dict) -> Tuple[int, int, int]:
    i = cfg["input"]
    return (i["height"], i["width"], i["channels"])


def layers(cfg: dict) -> List[Layer]:
    h, w, c = input_shape(cfg)
    conv = cfg["conv"]
    out: List[Layer] = []
    for v in cfg["layers"]:
        if v == "M":
            prev = out[-1]
            out[-1] = Layer(**{**prev.__dict__,
                               "pool": (conv["pool_kernel"], conv["pool_stride"])})
            h, w = out[-1].out_hw
            continue
        out.append(Layer("conv", c, int(v), h, w, conv["kernel"],
                         conv["stride"], conv["padding"]))
        h, w = out[-1].out_hw
        c = int(v)
    width = h * w * c
    for v in cfg["classifier"]:
        out.append(Layer("fc", width, int(v)))
        width = int(v)
    return out
