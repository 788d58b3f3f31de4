"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state. The dry-run entrypoint (launch/dryrun.py) sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; smoke tests and benches see the real single CPU device.

Mesh axes:
  single-pod: (data=16, model=16)          -> 256 chips (one v5e pod)
  multi-pod : (pod=2, data=16, model=16)   -> 512 chips

`pod` is an outer data-parallel axis (gradient reduction crosses the
inter-pod links once per step; optionally compressed via
train/grad_compress.py).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_debug_mesh(data: int = 2, model: int = 2, *, pod: int = 0):
    """Small mesh for tests (requires >= data*model host devices)."""
    if pod:
        return jax.make_mesh((pod, data, model), ("pod", "data", "model"),
                             axis_types=(AxisType.Auto,) * 3)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def make_data_mesh(devices=None):
    """1-D ``("data",)`` mesh over the visible (or given) devices.

    The scale-out substrate for the sharded sweep backend
    (``repro.parallel.shard_sweep``) and the sharded ``ProgramExecutor``
    mode: both partition one leading batch-like axis, so a flat
    data-parallel mesh is the whole topology. Accepts an explicit device
    subset so tests can build 1/2/8-device meshes from one forced-host-
    device process (``XLA_FLAGS=--xla_force_host_platform_device_count=8``,
    the SNIPPETS idiom CPU CI uses).
    """
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devices = tuple(jax.devices()) if devices is None else tuple(devices)
    return Mesh(np.asarray(devices, dtype=object), ("data",))


# TPU v5e hardware constants (roofline denominators; brief-provided)
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link
