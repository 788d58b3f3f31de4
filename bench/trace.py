"""Reduce a profiler trace of the measured window to device time by kind.

The harness traces its window with ``jax.profiler`` and records on the
host's wall clock when the window and each call into the program began and
ended. The trace (``*.xplane.pb``) holds, for each TPU, a line ``XLA Ops``
with one event per operation that ran on it, timed from the start of the
profile, whose wall-clock time the plane ``Task Environment`` gives; an
event's name is the operation's HLO text. The host's own trace is off
(``options``): the runtime's host events, even at the lowest level that
keeps any, stall a call now and then by tens of milliseconds. Here:

* busy time is the union of a device's ``XLA Ops`` intervals inside the
  window, averaged over the devices that ran anything;
* kernel time is the time of the operations whose HLO is a Pallas kernel
  (``custom_call_target="tpu_custom_call"``), glue time that of all others;
* each idle stretch inside the window is named by what the host was doing
  in it, from the benchmark's own host intervals: before a call's first device
  operation (the call's host work: converting and uploading the images,
  dispatch), between two of its operations, after its last one (the
  logits coming back), or between calls.
"""
from __future__ import annotations

import bisect
import re
from collections import Counter
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Tuple

KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
OPS_LINE = "XLA Ops"
ENV_PLANE = "Task Environment"
TOP = 10

_HLO = re.compile(r"%(\S+) = (\S+)(?: \S+)*? ([a-z][\w\-]*)\(")

Interval = Tuple[float, float]


def options():
    """Profiler options for the measured window: the devices' operations
    alone, with no host or Python tracing, which slow the host down."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def op_label(name: str) -> str:
    """``<instruction> <opcode> <result type>`` from an op's HLO text."""
    m = _HLO.match(name)
    if m is None:
        return name[:120]
    instr, rtype, opcode = m.groups()
    rtype = re.sub(r"\{[^}]*\}", "", rtype)        # drop the layout
    if opcode == "custom-call":
        target = re.search(r'custom_call_target="([^"]+)"', name)
        opcode = target.group(1) if target else opcode
    return f"{instr} {opcode} {rtype}"[:120]


def merge(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(iv: Interval, lo: float, hi: float) -> float:
    return max(0.0, min(iv[1], hi) - max(iv[0], lo))


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    kernel_s: float
    glue_s: float
    devices: int
    ops: Dict[str, float] = field(default_factory=dict)
    gaps: Dict[str, float] = field(default_factory=dict)
    op_count: int = 0           # device ops inside the window, all devices

    def breakdown(self) -> dict:
        def top(d):
            return [[k, v] for k, v in Counter(d).most_common(TOP)]

        return {"device_ops": top(self.ops), "idle_gaps": top(self.gaps)}


def _idle_by_host(busy: List[Interval], calls: List[Interval],
                  lo: float, hi: float) -> Dict[str, float]:
    """Idle seconds inside the window ``lo``..``hi`` by what the host was
    doing. ``busy`` is merged and sorted (``merge``), ``calls`` sorted.

    One walk per call over the busy intervals that overlap it, found by
    bisection, so the cost grows with calls plus intervals. Each sum takes
    the same non-zero terms in the same order as clipping every call
    against every interval would, so the result is the same to the bit."""
    gaps: Counter = Counter()
    starts = [b[0] for b in busy]
    covered = 0.0
    in_calls: List[float] = []
    for c0, c1 in calls:
        cs, ce = max(c0, lo), min(c1, hi)
        k = bisect.bisect_left(starts, c0)
        if k > 0 and busy[k - 1][1] > c0:
            k -= 1
        inside = []
        while k < len(busy) and busy[k][0] < c1:
            s, e = busy[k]
            in_calls.append(clip(busy[k], c0, c1))
            if s < ce and e > cs:
                inside.append((max(s, cs), min(e, ce)))
            k += 1
        if ce <= cs:
            continue
        covered += ce - cs
        if not inside:
            gaps["call without device work"] += ce - cs
            continue
        gaps["call: before its first device op"] += inside[0][0] - cs
        gaps["call: after its last device op"] += ce - inside[-1][1]
        for (_, e0), (s1, _) in zip(inside, inside[1:]):
            gaps["call: between its device ops"] += s1 - e0
    busy_all = sum(clip(b, lo, hi) for b in busy)
    gaps["between calls"] += (hi - lo - covered) - (busy_all - sum(in_calls))
    return {k: v * 1e-9 for k, v in gaps.items() if v > 0}


def profile_start_ns(data) -> int:
    """Wall-clock nanoseconds at which the profile began: the time origin
    of its events."""
    for plane in data.planes:
        if plane.name == ENV_PLANE:
            stats = dict(plane.stats)
            if "profile_start_time" in stats:
                return int(stats["profile_start_time"])
    raise ValueError(f"no profile_start_time in the trace's {ENV_PLANE!r}")


def reduce_profile(data, window: Interval,
                   calls: List[Interval]) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData``. ``window`` and ``calls`` are
    the host's wall-clock intervals (``time.time_ns()``) of the measured
    window and of each call in it."""
    p0 = profile_start_ns(data)
    lo, hi = window[0] - p0, window[1] - p0
    calls = sorted((a - p0, b - p0) for a, b in calls)
    device_lines = [ln for plane in data.planes
                    if plane.name.startswith("/device:TPU:")
                    for ln in plane.lines if ln.name == OPS_LINE]
    ops: Counter = Counter()
    kernel = glue = busy_total = 0.0
    devices = op_count = 0
    busy0: List[Interval] = []
    for ln in device_lines:
        ivs = []
        for e in ln.events:
            t = clip((e.start_ns, e.end_ns), lo, hi)
            if t <= 0:
                continue
            ivs.append((e.start_ns, e.end_ns))
            ops[op_label(e.name)] += t * 1e-9
            if KERNEL_MARK in e.name:
                kernel += t
            else:
                glue += t
        if not ivs:
            continue
        op_count += len(ivs)
        busy = merge(ivs)
        busy_total += sum(clip(b, lo, hi) for b in busy)
        if devices == 0:
            busy0 = busy
        devices += 1
    n = max(devices, 1)
    return Reduced(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_total / n * 1e-9,
        kernel_s=kernel / n * 1e-9,
        glue_s=glue / n * 1e-9,
        devices=devices,
        ops={k: v / n for k, v in ops.items()},
        gaps=_idle_by_host(busy0, calls, lo, hi),
        op_count=op_count,
    )


def reduce_file(path, window: Interval, calls: List[Interval]) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(str(path)), window, calls)


def reduce_dir(log_dir, window: Interval, calls: List[Interval]) -> Reduced:
    """Reduce the one trace that ``jax.profiler`` wrote under ``log_dir``."""
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if len(found) != 1:
        raise ValueError(f"expected one trace under {log_dir}, "
                         f"found {found}")
    return reduce_file(found[0], window, calls)
