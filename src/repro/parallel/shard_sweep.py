"""Mesh-sharded sweep evaluation: the scenario axis over a ``("data",)`` mesh.

The batched sweep engine (``repro.sweep.engine``) evaluates every Tab. IV
column as elementwise closed forms over stacked per-scenario arrays —
exactly the shape data parallelism wants. This module registers the
``"jax-sharded"`` backend: the same jitted column kernel the ``"jax"``
backend runs (``repro.sweep.backend_jax``), wrapped in ``shard_map`` so the
flat scenario axis is partitioned across a 1-D ``("data",)`` device mesh
(``repro.launch.mesh.make_data_mesh``). Inputs are placed with a
``NamedSharding`` on the leading axis (``repro.parallel.sharding
.leading_axis_sharding``) so the executable starts from device-local
shards; every device evaluates its scenario slice and the columns
concatenate back on the host.

Composition and contracts:

* **Chunking composes.** ``run_sweep(grid, backend="jax-sharded",
  chunk_size=...)`` hands the backend gathered ``(chunk,)`` batches; each
  chunk is sharded across the mesh in turn, so 1e8-scenario grids stream
  through bounded per-device memory (chunk/n_devices scenarios resident
  per device).
* **Bitwise parity.** The column math is elementwise — no reductions — so
  sharding only changes *where* each scenario is evaluated, not *how*:
  results are bitwise-identical to the unsharded ``"jax"`` backend, and
  identical across 1/2/8-device meshes (asserted by
  ``tests/_shard_checks.py`` under forced host devices).
* **Single-device fallback.** On a 1-device mesh (or when only one device
  is visible) the backend delegates to the plain jitted flat kernel on
  the flattened batch — no ``shard_map`` overhead, bitwise the same
  results as any multi-device mesh.

The scenario axis is padded (edge-replicated) up to a multiple of the mesh
size before sharding and the pad rows are sliced off after — grids need not
divide the device count.

Importing this module registers the backend::

    run_sweep(grid, backend="jax-sharded")
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache
from typing import Dict

import numpy as np

from repro.sweep.engine import (
    COLUMNS,
    ScenarioBatch,
    SweepBackend,
    register_backend,
)


def _pad_to_multiple(a: np.ndarray, multiple: int) -> np.ndarray:
    """Edge-pad the leading axis up to a multiple (pad rows are evaluated
    and discarded — edge values keep them numerically benign)."""
    pad = (-a.shape[0]) % multiple
    if pad == 0:
        return a
    return np.concatenate([a, np.repeat(a[-1:], pad, axis=0)])


@lru_cache(maxsize=8)
def _sharded_columns_kernel(mesh):
    """The flat column kernel wrapped in ``shard_map`` over ``mesh`` and
    jitted — cached per mesh (jit re-specializes per chunk shape)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.sweep.backend_jax import _column_exprs

    def kernel(chips, bits, e_mac, tpc, summary, fdm, step, eff):
        cols = _column_exprs(chips, bits, e_mac, tpc, summary, fdm, step, eff)
        return {c: jnp.broadcast_to(v, chips.shape) for c, v in cols.items()}

    sharded = jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(P("data"), P("data"), P("data"), P("data"), P("data"),
                  P(), P(), P()),
        out_specs=P("data"), check_vma=False,
    )
    return jax.jit(sharded)


def sharded_jax_backend(batch: ScenarioBatch,
                        mesh=None) -> Dict[str, np.ndarray]:
    """Evaluate a :class:`ScenarioBatch` with the scenario axis sharded
    across a ``("data",)`` mesh (default: all visible devices).

    Full-grid batches are flattened to per-scenario gathers first (the
    same ``flat_views`` the chunked path uses); chunked batches shard each
    chunk as-is. Falls back to the unsharded ``"jax"`` backend on a
    single-device mesh.
    """
    import jax

    from repro.launch.mesh import make_data_mesh
    from repro.parallel.sharding import leading_axis_sharding
    from repro.sweep.backend_jax import flat_views, jax_backend

    if mesh is None:
        mesh = make_data_mesh()
    n_dev = mesh.shape["data"]
    if batch.sel is None:
        flat = dataclasses.replace(
            batch, sel=np.arange(batch.n_scenarios, dtype=np.int64))
    else:
        flat = batch
    if n_dev <= 1:
        # single-device fallback: the same flat column kernel, no
        # shard_map wrapper. Delegating on the *flattened* batch (never
        # the full-grid broadcast kernel, which can differ by a few ulp
        # under XLA fusion) keeps results bitwise-identical to the
        # sharded evaluation regardless of device count.
        return jax_backend(flat)
    n = int(flat.sel.shape[0])
    chips, bits, e_mac, tpc, summary = flat_views(flat)

    with jax.enable_x64(True):
        f64 = lambda a: jax.numpy.asarray(a, dtype=jax.numpy.float64)  # noqa: E731
        shard = leading_axis_sharding(mesh)
        put = lambda a: jax.device_put(  # noqa: E731
            f64(_pad_to_multiple(a, n_dev)), shard)
        out = _sharded_columns_kernel(mesh)(
            put(chips), put(bits), put(e_mac), put(tpc),
            {f: put(a) for f, a in summary.items()},
            f64(batch.fdm_factor), f64(batch.step_hz),
            f64(batch.pipeline_eff),
        )
        return {c: np.asarray(out[c][:n], dtype=np.float64) for c in COLUMNS}


def make_sharded_backend(mesh) -> SweepBackend:
    """A ``run_sweep``-compatible backend bound to an explicit mesh —
    register it (or call it directly) to shard over a device subset, e.g.
    the 1/2/8-device parity meshes in ``tests/_shard_checks.py``."""

    def backend(batch: ScenarioBatch) -> Dict[str, np.ndarray]:
        return sharded_jax_backend(batch, mesh=mesh)

    return backend


register_backend("jax-sharded", sharded_jax_backend)
