"""The executor path: the whole-program executor, image to logits.

The system under test is ``ProgramExecutor.run(images)`` with
``backend="jax"`` and ``interpret=False``: the network compiled by
``compile_program``, every layer lowered onto the Pallas ``com_matmul``
kernel, the whole chain one jitted program. A timed call is the user's
whole call: the host conversion of the images, their upload, the chain,
and the logits back on the host.

``setup`` makes the weights and the inputs from the seed, builds the
executor and warms its one shape up. After the window ``close`` frees the
program's state, and ``check`` compares the logits of every timed call
with the plain reference (``bench/reference.py``) on the same inputs and
weights.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List, Sequence

import numpy as np

from bench import network, reference, traffic, weights

WARMUP_CALLS = 2


def max_rel_err(got: np.ndarray, ref: np.ndarray) -> float:
    """``max |got - ref| / max |ref|``; NaN where shapes differ or a value
    is not finite, so that no limit passes it."""
    if got.shape != ref.shape or not np.isfinite(got).all():
        return float("nan")
    return float(np.abs(got.astype(np.float64) - ref).max()
                 / np.abs(ref).max())


class ExecutorCell:
    """One configuration under one traffic mix, set up and warm."""

    def __init__(self, cfg: dict, mix: dict, seed: int, *, interpret: bool):
        from repro.core.executor import ProgramExecutor
        from repro.core.program import compile_program
        from repro.sweep.registry import resolve_network

        self.cfg, self.mix = cfg, mix
        self.phases: Dict[str, float] = {}      # set-up seconds by step
        t = time.perf_counter()
        self.net = network.layers(cfg)
        program = compile_program(resolve_network(cfg["network"]))
        got = _program_weight_shapes(program)
        want = [layer.weight_shape for layer in self.net]
        if got != want:
            raise ValueError(f"{cfg['name']}: the program's layers {got} "
                             f"are not the configuration's {want}")
        t = self._phase("compile_program", t)
        dev_ws = weights.he_normal(want, seed)
        # the benchmark's own copy: the reference reads it after the
        # program's state is freed
        self.weights = [np.asarray(w) for w in dev_ws]
        del dev_ws
        t = self._phase("weights", t)
        self.batches = traffic.batches(mix, network.input_shape(cfg), seed)
        self.images_per_call = mix["batch"]
        t = self._phase("inputs", t)
        self.executor = ProgramExecutor(program, self.weights, backend="jax",
                                        interpret=interpret)
        t = self._phase("executor", t)
        for i in range(WARMUP_CALLS):
            self.call(i)
            t = self._phase(f"warmup_call_{i}", t)

    def _phase(self, name: str, since: float) -> float:
        now = time.perf_counter()
        self.phases[name] = now - since
        return now

    def call(self, i: int) -> np.ndarray:
        """One timed call on distinct batch ``i mod n``: the logits, on
        the host."""
        return self.executor.run(self.batches[i % len(self.batches)]).outputs

    def close(self) -> None:
        """Free the program's state: its weights and compiled chain."""
        self.executor = None
        gc.collect()

    def check(self, outputs: Sequence[np.ndarray],
              limit: float) -> Dict[str, object]:
        """Compare every timed call's logits with the reference's logits of
        its batch."""
        refs = reference.logits(self.net, self.weights, self.batches)
        errs = [max_rel_err(o, refs[i % len(refs)])
                for i, o in enumerate(outputs)]
        return _verdict(errs, limit)

    def control(self, limit: float) -> Dict[str, object]:
        """The reference at the next precision below, in the program's
        place, judged as a run would judge the program."""
        refs = reference.logits(self.net, self.weights, self.batches)
        lower = reference.logits(self.net, self.weights, self.batches, "high")
        return _verdict([max_rel_err(o, r) for o, r in zip(lower, refs)],
                        limit)


def _verdict(errs: List[float], limit: float) -> Dict[str, object]:
    worst = max(errs, key=lambda e: np.inf if np.isnan(e) else e)
    failed = sum(1 for e in errs if not e <= limit)
    return {"attempted": len(errs), "failed": failed,
            "correct": bool(errs) and failed == 0,
            "checks": {"max_rel_err": {"value": worst, "limit": limit}}}


def _program_weight_shapes(program) -> List[tuple]:
    from repro.core.mapping import ConvSpec

    return [(l.k, l.k, l.c_in, l.c_out) if isinstance(l, ConvSpec)
            else (l.c_in, l.c_out) for l in program.workload.layers]


def setup(cfg: dict, mix: dict, seed: int, *, interpret: bool = False):
    return ExecutorCell(cfg, mix, seed, interpret=interpret)
