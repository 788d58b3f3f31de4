"""Collective strategy selection + accounting helpers.

``matmul_strategy`` lets layers swap their row-parallel reduction between:
  * "psum"      — GSPMD baseline: local matmul + all-reduce (the paper's
                   "conventional NoC" strawman: global-buffer reduction),
  * "com"       — Domino COM ring reduce-scatter (core/com.py),
  * "com_bidir" — both ICI directions (dual-router analogue).

``wire_bytes`` gives the per-device ICI bytes of each strategy for the
napkin math used in EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.com import com_matmul_local, com_matmul_local_bidir


def wire_bytes(strategy: str, out_bytes: int, n: int) -> float:
    """Per-device ICI traffic to produce a (replicated|sharded) output of
    ``out_bytes`` from n partial sums."""
    if n <= 1:
        return 0.0
    if strategy == "psum":          # all-reduce, ring: 2(n-1)/n * bytes
        return 2 * (n - 1) / n * out_bytes
    if strategy in ("com", "com_bidir"):  # reduce-scatter: (n-1)/n * bytes
        return (n - 1) / n * out_bytes
    raise ValueError(strategy)


def matmul_strategy(mesh: Mesh, strategy: str, axis: str = "model"):
    """Returns mm(x, w) with x (..., K/axis-sharded), w (K, N) row-sharded.

    psum: output replicated over ``axis``; com: output N-sharded over
    ``axis`` (output-stationary — consumer must accept the sharded layout,
    which is exactly what sequence-parallel consumers want).
    """

    def mm_psum_local(x_l, w_l):
        return jax.lax.psum(x_l @ w_l, axis)

    local = {
        "psum": mm_psum_local,
        "com": lambda x_l, w_l: com_matmul_local(x_l, w_l, axis),
        "com_bidir": lambda x_l, w_l: com_matmul_local_bidir(x_l, w_l, axis),
    }[strategy]

    def mm(x, w):
        ndim = x.ndim
        x_spec = P(*([None] * (ndim - 1) + [axis]))
        out_spec = P() if strategy == "psum" else P(*([None] * (ndim - 1) + [axis]))
        if strategy == "psum":
            out_spec = P(*([None] * ndim))
        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(x_spec, P(axis, None)), out_specs=out_spec, check_vma=False,
        )(x, w)

    return mm
