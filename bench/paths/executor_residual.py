"""The executor path on a residual network: image to logits, joins and all.

The same system under test and the same contract as ``paths/executor.py``
(``call``, ``close``, ``check``, ``control``, ``phases``):
``ProgramExecutor.run(images)`` with ``backend="jax"`` and
``interpret=False``, the network compiled by ``compile_program``, every
layer one Pallas ``com_matmul`` kernel, a join's shortcut added in its
kernel's epilogue. Here the layers come from the benchmark's walk of a
residual configuration (``bench/residual_net.py``), its weights have the
configuration's branch scale, and the check compares with the benchmark's
residual reference (``bench/reference_residual.py``). A program that does
not know the network fails at set-up, before any weight is made.
"""
from __future__ import annotations

import time
from typing import Dict, Sequence

import numpy as np

from bench import reference_residual, residual_net, traffic
from bench.paths.executor import (
    WARMUP_CALLS,
    ExecutorCell,
    _verdict,
    max_rel_err,
)


class ResidualCell(ExecutorCell):
    """One residual configuration under one traffic mix, set up and
    warm."""

    def __init__(self, cfg: dict, mix: dict, seed: int, *, interpret: bool):
        from repro.core.executor import ProgramExecutor
        from repro.core.program import compile_program
        from repro.sweep.registry import resolve_network

        self.cfg, self.mix = cfg, mix
        self.phases: Dict[str, float] = {}      # set-up seconds by step
        t = time.perf_counter()
        self.net = residual_net.layers(cfg)
        program = compile_program(resolve_network(cfg["network"]))
        got = [(l.name, (l.k, l.k, l.c_in, l.c_out) if hasattr(l, "k")
                else (l.c_in, l.c_out)) for l in program.workload.layers]
        want = [(l.name, l.weight_shape) for l in self.net]
        if got != want:
            raise ValueError(f"{cfg['name']}: the program's layers {got} "
                             f"are not the configuration's {want}")
        t = self._phase("compile_program", t)
        dev_ws = residual_net.weights(cfg, self.net, seed)
        # the benchmark's own copy: the reference reads it after the
        # program's state is freed
        self.weights = [np.asarray(w) for w in dev_ws]
        del dev_ws
        t = self._phase("weights", t)
        self.batches = traffic.batches(mix, residual_net.input_shape(cfg),
                                       seed)
        self.images_per_call = mix["batch"]
        t = self._phase("inputs", t)
        self.executor = ProgramExecutor(program, self.weights, backend="jax",
                                        interpret=interpret)
        t = self._phase("executor", t)
        for i in range(WARMUP_CALLS):
            self.call(i)
            t = self._phase(f"warmup_call_{i}", t)

    def check(self, outputs: Sequence[np.ndarray],
              limit: float) -> Dict[str, object]:
        """Compare every timed call's logits with the reference's logits of
        its batch."""
        refs = reference_residual.logits(self.net, self.weights, self.batches)
        errs = [max_rel_err(o, refs[i % len(refs)])
                for i, o in enumerate(outputs)]
        return _verdict(errs, limit)

    def control(self, limit: float) -> Dict[str, object]:
        """The reference at the next precision below, in the program's
        place, judged as a run would judge the program."""
        refs = reference_residual.logits(self.net, self.weights, self.batches)
        lower = reference_residual.logits(self.net, self.weights,
                                          self.batches, "high")
        return _verdict([max_rel_err(o, r) for o, r in zip(lower, refs)],
                        limit)


def setup(cfg: dict, mix: dict, seed: int, *, interpret: bool = False):
    return ResidualCell(cfg, mix, seed, interpret=interpret)
