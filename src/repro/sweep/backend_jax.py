"""JAX sweep backend: the Tab. IV column math as one jitted kernel.

The NumPy backend (``repro.sweep.engine.numpy_backend``) is the golden
oracle; this module lowers the identical closed forms to a single
``jax.jit``-compiled kernel over the stacked scenario arrays, so the
broadcast of the grid axes, every column's elementwise math, and the final
flatten fuse into one XLA executable — 1e5+-scenario grids (pareto
searches over CIM array geometry) evaluate in a few device passes instead
of dozens of NumPy temporaries. Both backends consume the same
``ScenarioBatch``, whose per-(network, arch) summaries the batch builder
reads off ONE cached ``compile_program`` call per combo (the
Workload→CompiledProgram IR in ``repro.core.program``) — neither backend
ever re-derives a mapping.

Numerics: the kernel runs in float64 (via the ``jax.enable_x64(True)``
scope, regardless of the session-wide x64 default) so it is
golden-testable against the NumPy oracle to far better than the 1e-6 the
tests assert.

Importing this module registers the backend:

    run_sweep(grid, backend="jax")
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.sweep.engine import COLUMNS, ScenarioBatch, register_backend


def _column_exprs(chips, bits, e_mac, tpc, sm, fdm_factor, step_hz,
                  pipeline_eff) -> Dict[str, jax.Array]:
    """The Tab. IV column math on broadcast-compatible views — mirrors
    ``numpy_backend`` expression-for-expression. ``sm`` maps summary field
    names to views; shared by the full-grid and chunked (flat) kernels."""
    n_tiles = sm["n_tiles"]
    onchip_j = sm["onchip_j"]
    ops = sm["ops"]
    area = sm["area_mm2"]

    per_copy = fdm_factor * step_hz / sm["bottleneck_px"]
    copies = jnp.maximum(1.0, (chips * tpc) / n_tiles)
    img_s = per_copy * copies * pipeline_eff * sm["skip_stall"]

    e_off = sm["offchip_values"] * bits * sm["offchip_pj_per_bit"] * 1e-12
    e_cim = ops * e_mac * 1e-12
    e_total = onchip_j + e_off + e_cim

    return dict(
        exec_us=sm["exec_us"],
        img_s=img_s,
        power_w=e_total * img_s,
        onchip_w=onchip_j * img_s,
        offchip_w=e_off * img_s,
        cim_w=e_cim * img_s,
        ce_tops_w=ops / e_total / 1e12,
        ops=ops,
        area_mm2=area,
        thr_tops_mm2=ops * img_s / 1e12 / area,
        img_s_per_core=img_s / (chips * tpc),
        n_chips=chips,
        n_tiles=n_tiles,
    )


@partial(jax.jit, static_argnames=("shape",))
def _columns_kernel(
    shape: Tuple[int, ...],
    chips: jax.Array, bits: jax.Array, e_mac: jax.Array, tpc: jax.Array,
    summary: Dict[str, jax.Array],
    fdm_factor: jax.Array, step_hz: jax.Array, pipeline_eff: jax.Array,
) -> Dict[str, jax.Array]:
    """All Tab. IV columns over the full grid, fused into one executable.

    The grid ``shape`` is static so XLA sees concrete broadcast shapes.
    """
    def ax(v, axis):
        shp = [1] * len(shape)
        shp[axis] = v.shape[0]
        return v.reshape(shp)

    sm = {
        f: summary[f].reshape(
            shape[0], 1, 1, 1, shape[4], shape[5], shape[6], shape[7],
            shape[8]
        )
        for f in summary
    }
    cols = _column_exprs(
        ax(chips, 1), ax(bits, 2), ax(e_mac, 3), ax(tpc, 4), sm,
        fdm_factor, step_hz, pipeline_eff,
    )
    return {c: jnp.broadcast_to(v, shape).reshape(-1) for c, v in cols.items()}


@jax.jit
def _columns_kernel_flat(
    chips: jax.Array, bits: jax.Array, e_mac: jax.Array, tpc: jax.Array,
    summary: Dict[str, jax.Array],
    fdm_factor: jax.Array, step_hz: jax.Array, pipeline_eff: jax.Array,
) -> Dict[str, jax.Array]:
    """The same column math over pre-gathered per-scenario ``(n,)`` views —
    the chunked (``ScenarioBatch.sel``) evaluation path."""
    cols = _column_exprs(chips, bits, e_mac, tpc, summary,
                         fdm_factor, step_hz, pipeline_eff)
    return {c: jnp.broadcast_to(v, chips.shape) for c, v in cols.items()}


def flat_views(batch: ScenarioBatch):
    """The per-scenario flat ``(n,)`` float64 gathers of a chunked batch:
    ``(chips, bits, e_mac, tpc, {field: summary})``. Requires
    ``batch.sel``; shared by the chunked kernel here and the mesh-sharded
    backend (``repro.parallel.shard_sweep``), which shards exactly these
    arrays over the ``("data",)`` axis."""
    assert batch.sel is not None, "flat_views needs a chunked (sel) batch"
    return (
        np.asarray(batch.axis_view(batch.chips, 1), dtype=np.float64),
        np.asarray(batch.axis_view(batch.bits, 2), dtype=np.float64),
        np.asarray(batch.axis_view(batch.e_mac, 3), dtype=np.float64),
        np.asarray(batch.axis_view(batch.tpc, 4), dtype=np.float64),
        {f: np.asarray(batch.summary_view(f), dtype=np.float64)
         for f in batch.summary},
    )


def jax_backend(batch: ScenarioBatch) -> Dict[str, np.ndarray]:
    """Evaluate a :class:`ScenarioBatch` on the jitted kernel (float64)."""
    with jax.enable_x64(True):
        f64 = lambda a: jnp.asarray(a, dtype=jnp.float64)  # noqa: E731
        if batch.sel is not None:
            # chunked mode: the batch's views gather the selected rows on
            # host; the kernel sees flat (chunk,) arrays only
            chips, bits, e_mac, tpc, summary = flat_views(batch)
            out = _columns_kernel_flat(
                f64(chips), f64(bits), f64(e_mac), f64(tpc),
                {f: f64(a) for f, a in summary.items()},
                f64(batch.fdm_factor), f64(batch.step_hz),
                f64(batch.pipeline_eff),
            )
        else:
            out = _columns_kernel(
                batch.shape,
                f64(batch.chips), f64(batch.bits), f64(batch.e_mac),
                f64(batch.tpc),
                {f: f64(a) for f, a in batch.summary.items()},
                f64(batch.fdm_factor), f64(batch.step_hz),
                f64(batch.pipeline_eff),
            )
        return {c: np.asarray(out[c], dtype=np.float64) for c in COLUMNS}


register_backend("jax", jax_backend)
