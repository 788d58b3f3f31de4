"""Traffic mixes: one data file each, read by one generator.

``bench/traffic/<mix>.json`` gives the loop and its inputs:

* ``loop``: ``"closed"``: one caller sends its next call when the last
  one's answer is on the host;
* ``callers``: how many such callers (1);
* ``batch``: inputs per call;
* ``distinct_batches``: how many different batches are made and cycled;
* ``input_dtype`` and ``distribution``: what each input element is.

Every seed gets the same sizes and the same number of batches; only their
values change with the seed.
"""
from __future__ import annotations

import json
from typing import List, Tuple

import numpy as np

from bench.network import BENCH

LOOPS = ("closed",)
DISTRIBUTIONS = ("standard_normal",)


def load(name: str) -> dict:
    mix = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
    if mix["loop"] not in LOOPS or mix["callers"] != 1:
        raise ValueError(f"traffic {name!r}: only a closed loop with one "
                         f"caller is generated, not {mix['loop']!r} with "
                         f"{mix['callers']}")
    if mix["distribution"] not in DISTRIBUTIONS:
        raise ValueError(f"traffic {name!r}: unknown distribution "
                         f"{mix['distribution']!r}")
    return mix


def batches(mix: dict, input_shape: Tuple[int, ...], seed: int) -> List[np.ndarray]:
    """The mix's distinct input batches, made from ``seed``."""
    rng = np.random.default_rng([seed, 1])
    shape = (mix["batch"],) + tuple(input_shape)
    return [rng.standard_normal(shape, dtype=np.dtype(mix["input_dtype"]))
            for _ in range(mix["distinct_batches"])]
