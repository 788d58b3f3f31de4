"""The benchmark's run of one cell, driven by data.

Everything that belongs to one configuration, traffic mix, path or
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives:

* ``bench/configs/<config>.json``: the network's sizes, dtype, path and
  the limit of its output check;
* ``bench/traffic/<mix>.json``: the loop and its inputs (``traffic.py``);
* ``bench/paths/<path>.py``: ``setup(cfg, mix, seed, interpret=...)``,
  which returns a warm cell with ``call(i)``, ``images_per_call``,
  ``close()`` and ``check(outputs, limit)``;
* ``bench/metrics/<quantity>.py``: ``read(trace, record)``, which returns
  the per-layer metric or None where it finds nothing to read.

A metric's name is ``<quantity>`` or ``<quantity>.<group>``: one quantity
is split into metrics of its own for groups of cells whose runs spread
differently, so that each gets a bound of its own; the per-layer metrics
split with the end-to-end metric they move. The quantity names the reader.

``run`` sets the cell up, measures a closed-loop window, reads the
device's peak memory, frees the program, checks every call's output, and,
when traced, reduces the trace to the per-layer metrics.
"""
from __future__ import annotations

import importlib
import json
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from bench import traffic, work
from bench.network import load_config

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"

# jax.monitoring events of a lowering and of a backend compile
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_spec(name: str) -> dict:
    cells = {c["name"]: c for c in load_benchmark()["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    return cells[name]


def configure_cache() -> None:
    """JAX's persistent compilation cache at one fixed path inside the
    checkout, every compile kept, so only a cell's first run compiles."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def setup_cell(spec: dict, seed: int, *, interpret: bool = False,
               mix: Optional[dict] = None):
    """``(cfg, mix, cell)``: the cell's configuration and traffic, and its
    path's warm cell."""
    cfg = load_config(spec["config"])
    mix = mix if mix is not None else traffic.load(spec["traffic"])
    path = importlib.import_module(f"bench.paths.{cfg['path']}")
    return cfg, mix, path.setup(cfg, mix, seed, interpret=interpret)


@dataclass
class Window:
    t_start: float
    t_end: float
    latencies: List[float] = field(default_factory=list)
    outputs: List[np.ndarray] = field(default_factory=list)
    compiles: List[str] = field(default_factory=list)
    # wall-clock nanoseconds of the window and of each call, which place
    # them on a trace's timeline
    wall: Tuple[int, int] = (0, 0)
    wall_calls: List[Tuple[int, int]] = field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.t_end - self.t_start


def measure(call: Callable[[int], np.ndarray], seconds: float) -> Window:
    """Closed loop, one caller: the next call goes out when the last one's
    answer is on the host. Calls start until ``seconds`` have passed; the
    window ends when the last of them returns, so it holds all their work
    and all their time."""
    compiles: List[str] = []

    def on_event(name, secs, **kwargs):
        if name in COMPILE_EVENTS:
            compiles.append(name)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        wall0 = time.time_ns()
        t_start = time.perf_counter()
        win = Window(t_start, t_start, compiles=compiles)
        deadline = t_start + seconds
        i = 0
        t1 = t_start
        while t1 < deadline:
            w0 = time.time_ns()
            t0 = time.perf_counter()
            out = call(i)
            t1 = time.perf_counter()
            win.wall_calls.append((w0, time.time_ns()))
            win.latencies.append(t1 - t0)
            win.outputs.append(out)
            i += 1
        win.t_end = t1
        win.wall = (wall0, time.time_ns())
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    return win


def peak_bytes(devices) -> Optional[int]:
    """The peak of the fullest device, or None where the backend keeps no
    count."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return None
    return max(int(s["peak_bytes_in_use"]) for s in stats)


def quantity(metric: dict) -> str:
    """What a metric measures: its name up to the first dot."""
    return metric["name"].split(".")[0]


def lists(metric: dict, spec: dict) -> bool:
    """Whether ``metric`` is reported in cell ``spec``."""
    return spec["name"] in metric.get("workloads", [spec["name"]])


def end_to_end(bench: dict, spec: dict, win: Window, images_per_call: int,
               setup_s: float) -> Dict[str, dict]:
    """The end-to-end metrics that list this cell (all cells, where a
    metric lists none), each the value of its quantity."""
    lat = np.asarray(win.latencies)
    values = {
        "images_per_s": len(lat) * images_per_call / win.seconds,
        "call_p95_ms": float(np.percentile(lat, 95)) * 1e3,
        "setup_s": setup_s,
    }
    return {m["name"]: {"value": values[quantity(m)], "unit": m["unit"]}
            for m in bench["end_to_end"] if lists(m, spec)}


def per_layer(bench: dict, spec: dict, reduced, record: dict) -> Dict[str, dict]:
    """Each per-layer metric that lists this cell, read by its own reader;
    one that finds nothing to read is left out."""
    out = {}
    for m in bench["per_layer"]:
        if not lists(m, spec):
            continue
        reader = importlib.import_module(f"bench.metrics.{quantity(m)}")
        value = reader.read(reduced, record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(spec: dict, seed: int, seconds: float, trace: bool, t0: float, *,
        interpret: bool = False, mix: Optional[dict] = None,
        peaks_kind: Optional[str] = None,
        say: Callable[[str], None] = print) -> dict:
    """One run of cell ``spec``: the result object of the last line.
    ``peaks_kind`` names the peak table's row where it is not the
    device's own kind: for a rehearsal on the CPU alone."""
    from bench import trace as trace_mod

    bench = load_benchmark()
    devices = jax.devices()[:spec["chips"]]
    t_import = time.perf_counter() - t0
    cfg, mix, cell = setup_cell(spec, seed, interpret=interpret, mix=mix)
    setup_s = time.perf_counter() - t0
    say(f"setup: {setup_s:.3f} s; start and imports {t_import:.3f} s, "
        + ", ".join(f"{k} {v:.3f} s" for k, v in cell.phases.items()))

    tmp = None
    if trace:
        tmp = tempfile.TemporaryDirectory(prefix="bench-trace-")
        jax.profiler.start_trace(tmp.name,
                                 profiler_options=trace_mod.options())
    try:
        if trace:
            # the first call after the profiler starts can stall; it runs
            # before the window and is not counted
            cell.call(0)
        win = measure(cell.call, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    lat_ms = np.asarray(win.latencies) * 1e3
    say(f"window: {len(lat_ms)} calls in {win.seconds:.6f} s, "
        f"{len(win.compiles)} compiles inside it; call ms min "
        f"{lat_ms.min():.3f} median {np.median(lat_ms):.3f} p95 "
        f"{np.percentile(lat_ms, 95):.3f} max {lat_ms.max():.3f}, "
        f"{int((lat_ms > 2 * np.median(lat_ms)).sum())} over twice the "
        "median")
    if win.compiles:
        raise RuntimeError(f"{len(win.compiles)} compiles inside the "
                           f"measured window: {win.compiles}")
    memory = peak_bytes(devices)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory}

    images = len(win.outputs) * cell.images_per_call
    if trace:
        t_reduce = time.perf_counter()
        reduced = trace_mod.reduce_dir(tmp.name, win.wall, win.wall_calls)
        tmp.cleanup()
        say(f"trace: {reduced.op_count} device ops in "
            f"{len(win.wall_calls)} calls reduced in "
            f"{time.perf_counter() - t_reduce:.3f} s")
        record = {
            "images": images, "calls": len(win.outputs),
            "window_s": win.seconds, "cfg": cfg, "mix": mix,
            "peaks": work.peaks(peaks_kind or dev.device_kind),
        }
        metrics = per_layer(bench, spec, reduced, record)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        breakdown = reduced.breakdown()
    else:
        metrics = end_to_end(bench, spec, win, cell.images_per_call,
                             setup_s)

    t_check = time.perf_counter()
    cell.close()
    verdict = cell.check(win.outputs, cfg["check"]["max_rel_err"])
    say(f"after the window: program freed and {verdict['attempted']} "
        f"outputs checked in {time.perf_counter() - t_check:.3f} s")
    result = {"correct": verdict["correct"], "attempted": verdict["attempted"],
              "failed": verdict["failed"], "metrics": metrics,
              "device": device}
    if trace:
        result["breakdown"] = breakdown
    result["checks"] = verdict["checks"]
    return result
