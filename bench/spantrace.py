"""The program's own spans put onto a device trace's timeline.

``repro.core.spans`` records, when enabled, a root span ``executor.run`` per
call into the program with children ``executor.batch``, ``executor.upload``,
``executor.dispatch`` and ``executor.fetch``, stamped with the host's
``perf_counter_ns`` and one ``wall_minus_perf_ns`` that turns a stamp into
``time.time_ns()``. The profile's events are timed from its
``profile_start_time`` on that wall clock. The two clocks are then aligned
by the calls themselves: a call's ``jit_forward`` module (the trace's
``XLA Modules`` line) starts after its ``executor.dispatch`` began and ends
before its ``executor.fetch`` ended. Over the window's calls these bound the
offset between span clock and profile clock; ``align`` takes the feasible
offset nearest the wall clocks' own and reports the range's width as the
clock slack.

On the aligned clock:

* ``idle_by_span``: each idle stretch of the device inside a call goes to
  the innermost span over it (``executor.run (self)`` where no child span
  is open), stretches outside calls to ``between calls``;
* ``ops_by_layer``: device seconds by layer and role, from the scopes of the
  cell's compiled chain (``bench/scopes.py``): ``<layer>/kernel`` for the
  Pallas kernel, ``<layer>/<role>`` for the rest, ``chain/<opcode>`` for an
  op in no layer's scope, ``unmapped/<opcode>`` for one the compiled chain
  does not hold, ``<module>/<opcode>`` for ops of other modules (the
  upload's ``jit_convert_element_type``);
* ``host_in_ms`` and ``host_out_ms``: per call, the host spans before the
  chain's result is waited for, and the time from the call's last device op
  to its end;
* ``host_steps``: each host span's milliseconds a call and, from its byte
  counter (``bytes_host``, ``bytes_up``, ``bytes_down``), its bytes a call
  and their rate: a fetch far slower than its bytes need waits for the
  chain, not for the logits;
* ``slow_calls``: the calls over twice the median, each with the span that
  holds its excess.

The run's set-up is recorded too: ``build`` is its ``executor.build`` span,
the weights cast and uploaded (``bytes_weights``), with the compiles of the
call that built. Images are counted by the calls' own ``images``.

Run a traced window with spans on, and print all of it::

    python3 -m bench.spantrace --workload <cell> --seed <n> --seconds <s> \\
        [--batch <b>] [--out <dir>]

from the checkout's root. ``--out`` keeps the trace and, beside it, a JSON
of the window, the calls, the spans and the scopes of the ops the trace
holds. ``--batch`` replaces the traffic mix's batch: it is how the small
recorded trace of ``bench/tests`` (vgg11-cifar at batch 2) is made.
"""
from __future__ import annotations

import bisect
import statistics
from collections import Counter, defaultdict
from typing import Dict, List, Optional, Tuple

from bench import scopes as scopes_mod
from bench import trace

MODULES_LINE = "XLA Modules"
CHAIN_MODULE = "jit_forward"
ROOT = "executor.run"
HOST_IN = ("executor.batch", "executor.upload", "executor.dispatch")
# the byte counter of each host span that moves data
BYTES = {"executor.batch": "bytes_host", "executor.upload": "bytes_up",
         "executor.fetch": "bytes_down"}
SELF = f"{ROOT} (self)"
BETWEEN = "between calls"
# a call's module is looked for from this long before its dispatch began
PAIR_SLACK_NS = 5_000_000

Interval = Tuple[float, float]


def device_lines(data):
    """``(modules, ops)``: the ``XLA Modules`` and ``XLA Ops`` events of the
    first TPU that ran anything, as ``(start_ns, end_ns, name)``."""
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {ln.name: [(e.start_ns, e.end_ns, e.name) for e in ln.events]
                 for ln in plane.lines}
        if lines.get(trace.OPS_LINE):
            return lines.get(MODULES_LINE, []), lines[trace.OPS_LINE]
    return [], []


def calls_of(recording: dict) -> List[dict]:
    """Each root ``executor.run`` span with its children by name."""
    spans = recording["spans"]
    calls = {i: {"run": s, "children": {}} for i, s in enumerate(spans)
             if s["parent"] is None and s["name"] == ROOT
             and s["end_ns"] is not None}
    for s in spans:
        if s["parent"] in calls and s["end_ns"] is not None:
            calls[s["parent"]]["children"][s["name"]] = s
    return [calls[i] for i in sorted(calls)]


def align(calls: List[dict], modules, nominal: float) -> Dict[str, float]:
    """The offset ``c`` with ``span_ns + c`` on the profile's clock, from
    each call's ``jit_forward`` run: ``run.start >= dispatch.start + c`` and
    ``run.end <= fetch.end + c``. ``nominal`` is the offset the two wall
    clocks give; a run is paired with the call whose dispatch it follows
    first on it. Returns ``offset_ns`` (the feasible offset nearest the
    nominal one), ``clock_slack_us`` (the feasible range's width; negative
    where the bounds conflict, and then the range's middle is taken),
    ``shift_us`` (offset less nominal) and ``pairs``."""
    runs = sorted((s, e) for s, e, name in modules
                  if name.startswith(CHAIN_MODULE + "("))
    lo, hi, pairs, j = -float("inf"), float("inf"), 0, 0
    for c in calls:
        d, f = c["children"].get("executor.dispatch"), \
            c["children"].get("executor.fetch")
        if d is None or f is None:
            continue
        d0 = d["start_ns"] + nominal
        while j < len(runs) and runs[j][0] < d0 - PAIR_SLACK_NS:
            j += 1
        if j == len(runs) or runs[j][0] > f["end_ns"] + nominal:
            continue
        s, e = runs[j]
        j += 1
        hi = min(hi, s - d["start_ns"])
        lo = max(lo, e - f["end_ns"])
        pairs += 1
    if pairs == 0:
        return {"offset_ns": nominal, "clock_slack_us": float("nan"),
                "shift_us": 0.0, "pairs": 0}
    offset = min(max(nominal, lo), hi) if lo <= hi else (lo + hi) / 2
    return {"offset_ns": offset, "clock_slack_us": (hi - lo) * 1e-3,
            "shift_us": (offset - nominal) * 1e-3, "pairs": pairs}


def _depth(spans: List[dict], i: int) -> int:
    d = 0
    while spans[i]["parent"] is not None:
        i = spans[i]["parent"]
        d += 1
    return d


def idle_by_span(busy: List[Interval], recording: dict, offset: float,
                 lo: float, hi: float) -> Dict[str, float]:
    """Seconds of device idle in ``[lo, hi]`` (profile clock) by the
    innermost span open over it. ``busy`` is merged and sorted."""
    spans = recording["spans"]
    by_call = defaultdict(list)
    for i, s in enumerate(spans):
        if s["end_ns"] is not None:
            by_call[s["call"]].append(
                (s["start_ns"] + offset, s["end_ns"] + offset,
                 _depth(spans, i), s["name"]))
    out: Counter = Counter()
    for members in by_call.values():
        roots = [m for m in members if m[2] == 0 and m[3] == ROOT]
        if not roots:
            continue
        cs, ce = max(roots[0][0], lo), min(roots[0][1], hi)
        if ce <= cs:
            continue
        for a, b in _gaps(busy, cs, ce):
            cuts = sorted({a, b} | {t for m in members for t in m[:2]
                                    if a < t < b})
            for p, q in zip(cuts, cuts[1:]):
                mid = (p + q) / 2
                inner = max((m for m in members if m[0] <= mid < m[1]),
                            key=lambda m: m[2])
                out[SELF if inner[2] == 0 else inner[3]] += q - p
    idle_all = sum(q - p for p, q in _gaps(busy, lo, hi))
    out[BETWEEN] += idle_all - sum(out.values())
    return {k: v * 1e-9 for k, v in out.items() if v > 0}


def _gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The stretches of ``[lo, hi]`` that no interval of ``busy`` (merged,
    sorted) covers."""
    out, t = [], lo
    for i in range(max(bisect.bisect_right(busy, (lo, float("inf"))) - 1, 0),
                   len(busy)):
        s, e = busy[i]
        if s >= hi:
            break
        if e <= lo:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def ops_by_layer(modules, ops, lo: float, hi: float,
                 scopes: Dict[str, str]) -> Dict[str, float]:
    """Device seconds in ``[lo, hi]`` by layer and role (module docstring)."""
    runs = sorted((s, e, name.split("(")[0]) for s, e, name in modules)
    starts = [r[0] for r in runs]
    out: Counter = Counter()
    for s, e, name in ops:
        t = trace.clip((s, e), lo, hi)
        if t <= 0:
            continue
        label = trace.op_label(name)
        opcode = (label.split(" ") + [""])[1]
        i = bisect.bisect_right(starts, s) - 1
        module = runs[i][2] if i >= 0 and s < runs[i][1] else CHAIN_MODULE
        if module != CHAIN_MODULE:
            key = f"{module}/{opcode}"
        elif label not in scopes:
            key = f"unmapped/{opcode}"
        else:
            lr = scopes_mod.op_role(label, scopes)
            key = f"chain/{opcode}" if lr is None else f"{lr[0]}/{lr[1]}"
        out[key] += t * 1e-9
    return dict(out)


def in_window(calls: List[dict], wall_minus_perf: float,
              window: Interval) -> List[dict]:
    """The calls whose root span lies inside the wall-clock ``window``."""
    return [c for c in calls
            if c["run"]["start_ns"] + wall_minus_perf >= window[0]
            and c["run"]["end_ns"] + wall_minus_perf <= window[1]]


def host_in_ms(calls: List[dict]) -> Optional[float]:
    """Mean per call of the host spans up to the chain's dispatch."""
    if not calls:
        return None
    return 1e-6 * sum(c["children"][n]["end_ns"] - c["children"][n]["start_ns"]
                      for c in calls for n in HOST_IN
                      if n in c["children"]) / len(calls)


def host_out_ms(calls: List[dict], busy: List[Interval],
                offset: float) -> Optional[float]:
    """Mean per call from its last device op (aligned clock) to its end."""
    outs = []
    starts = [b[0] for b in busy]
    for c in calls:
        cs, ce = c["run"]["start_ns"] + offset, c["run"]["end_ns"] + offset
        i = bisect.bisect_left(starts, ce) - 1
        if i >= 0 and busy[i][1] > cs:
            outs.append(ce - min(busy[i][1], ce))
    return 1e-6 * sum(outs) / len(outs) if outs else None


def host_steps(calls: List[dict]) -> Dict[str, dict]:
    """For each host span of a call, its mean milliseconds a call and,
    where it counts bytes, its bytes a call and their rate in GB/s over
    the span's own time."""
    out = {}
    for name in HOST_IN + ("executor.fetch",):
        found = [c["children"][name] for c in calls if name in c["children"]]
        if not found:
            continue
        ns = sum(s["end_ns"] - s["start_ns"] for s in found)
        step = {"ms_per_call": 1e-6 * ns / len(found)}
        if name in BYTES:
            n = sum(s["counters"].get(BYTES[name], 0) for s in found)
            step["bytes_per_call"] = n / len(found)
            step["gb_per_s"] = n / ns if ns > 0 else None
        out[name] = step
    return out


def build_of(recording: dict) -> Optional[dict]:
    """The ``executor.build`` span of a recording: its seconds, the bytes
    of weights it cast and uploaded and their rate in GB/s, and the
    compiles of the call that built; None where no call built."""
    spans = recording["spans"]
    for s in spans:
        if s["name"] == "executor.build" and s["end_ns"] is not None:
            ns = s["end_ns"] - s["start_ns"]
            n = s["counters"].get("bytes_weights", 0)
            call = spans[s["parent"]] if s["parent"] is not None else s
            return {"s": ns * 1e-9, "bytes_weights": n,
                    "gb_per_s": n / ns if ns > 0 else None,
                    "compiles": call["counters"].get("compiles", 0)}
    return None


def slow_calls(calls: List[dict]) -> List[str]:
    """One line per call over twice the median, naming the span whose
    excess over its own median is the largest."""
    def dur(s):
        return s["end_ns"] - s["start_ns"]

    if not calls:
        return []
    med = statistics.median(dur(c["run"]) for c in calls)
    child_med = {n: statistics.median(dur(c["children"][n]) for c in calls
                                      if n in c["children"])
                 for n in {n for c in calls for n in c["children"]}}
    lines = []
    for i, c in enumerate(calls):
        total = dur(c["run"])
        if total <= 2 * med:
            continue
        selfs = total - sum(dur(s) for s in c["children"].values())
        excess = {n: dur(s) - child_med[n] for n, s in c["children"].items()}
        excess[SELF] = selfs - (med - sum(child_med.values()))
        name = max(excess, key=excess.get)
        lines.append(f"slow call {i}: {total * 1e-6:.3f} ms against a "
                     f"median {med * 1e-6:.3f}; {name} holds "
                     f"{excess[name] * 1e-6:.3f} ms of the excess"
                     + (f", {c['run']['counters'].get('compiles')} compiles"
                        if c["run"]["counters"].get("compiles") else ""))
    return lines


def reduce_spans(data, window: Interval, recording: dict,
                 scopes: Dict[str, str]) -> dict:
    """Everything above for one traced window (``window`` on the wall
    clock, as the harness records it)."""
    p0 = trace.profile_start_ns(data)
    lo, hi = window[0] - p0, window[1] - p0
    modules, ops = device_lines(data)
    busy = trace.merge([(s, e) for s, e, _ in ops
                        if trace.clip((s, e), lo, hi) > 0])
    wmp = recording["wall_minus_perf_ns"]
    calls = in_window(calls_of(recording), wmp, window)
    clock = align(calls, modules, wmp - p0)
    off = clock["offset_ns"]
    idle = idle_by_span(busy, recording, off, lo, hi)
    inside = sum(v for k, v in idle.items() if k != BETWEEN)
    named = sum(v for k, v in idle.items() if k not in (BETWEEN, SELF))
    return {
        "clock": clock,
        "idle_by_span": idle,
        "idle_named_share": named / inside if inside > 0 else None,
        "ops_by_layer": ops_by_layer(modules, ops, lo, hi, scopes),
        "host_in_ms_per_call": host_in_ms(calls),
        "host_out_ms_per_call": host_out_ms(calls, busy, off),
        "host_steps": host_steps(calls),
        "calls": len(calls),
        "images": sum(c["run"]["counters"].get("images", 0) for c in calls),
        "slow_calls": slow_calls(calls),
    }


def traced_window(cell, seconds: float, log_dir: str):
    """A window of ``cell`` traced as the harness traces one, with spans
    recorded from before the profiler starts: ``(window, recording)``."""
    import jax

    from bench import harness
    from repro.core import spans

    spans.enable()
    jax.profiler.start_trace(log_dir, profiler_options=trace.options())
    try:
        cell.call(0)
        win = harness.measure(cell.call, seconds)
    finally:
        jax.profiler.stop_trace()
        recording = spans.collect()
    return win, recording


def main(argv=None) -> int:
    import argparse
    import json
    import os
    import shutil
    import sys
    import tempfile
    import time
    from pathlib import Path

    t0 = time.perf_counter()
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--batch", type=int, default=None)
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)

    import jax
    from jax.profiler import ProfileData

    from bench import harness, traffic
    from repro.core import spans

    def err(line):
        print(line, file=sys.stderr, flush=True)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        err(f"spantrace: JAX's first device is {dev.platform!r}, not a TPU")
        return 2
    spec = harness.cell_spec(args.workload)
    mix = traffic.load(spec["traffic"])
    if args.batch is not None:
        mix = dict(mix, batch=args.batch)
    harness.configure_cache()
    spans.enable()
    try:
        cfg, mix, cell = harness.setup_cell(spec, args.seed, mix=mix)
    finally:
        build = build_of(spans.collect())
    err(f"setup: {time.perf_counter() - t0:.3f} s on {dev.device_kind}")
    if build is not None:
        err(f"setup: executor.build {build['s']:.3f} s for "
            f"{build['bytes_weights']} bytes of weights, "
            f"{build['compiles']} compiles in its call")
    res = {"workload": args.workload, "batch": mix["batch"],
           "seed": args.seed, "device": dev.device_kind, "build": build}

    with tempfile.TemporaryDirectory(prefix="bench-spans-") as tmp:
        win, recording = traced_window(cell, args.seconds, tmp)
        xplane = sorted(Path(tmp).glob("plugins/profile/*/*.xplane.pb"))[0]
        data = ProfileData.from_file(str(xplane))
        scopes = scopes_mod.cell_scopes(cfg["network"], mix["batch"])
        reduced = trace.reduce_profile(data, win.wall, win.wall_calls)
        spanned = reduce_spans(data, win.wall, recording, scopes)
        images = spanned["images"]
        if args.out:
            dest = Path(args.out)
            dest.mkdir(parents=True, exist_ok=True)
            stem = f"{cfg['name']}-b{mix['batch']}"
            shutil.copy(xplane, dest / f"{stem}.xplane.pb")
            labels = {trace.op_label(n) for _, _, n in device_lines(data)[1]}
            (dest / f"{stem}.json").write_text(json.dumps({
                "window": list(win.wall),
                "calls": [list(c) for c in win.wall_calls],
                "images": images, "batch": mix["batch"], "spans": recording,
                "scopes": {k: v for k, v in scopes.items() if k in labels},
            }))
    cell.close()
    err(f"window: {len(win.latencies)} calls in {win.seconds:.6f} s")
    for line in spanned["slow_calls"]:
        err(line)
    c = spanned["clock"]
    err(f"clock: clock_slack_us {c['clock_slack_us']:.1f} over {c['pairs']} "
        f"calls, offset {c['shift_us']:.1f} us from the wall clocks'")
    roles: Counter = Counter()
    for k, v in spanned["ops_by_layer"].items():
        head, role = k.split("/", 1)
        roles[role if role in scopes_mod.ROLES + ("kernel", "layer")
              else head] += 1e3 * v / images
    res.update(spanned, window_s=win.seconds,
               ms_per_img_by_role=dict(roles),
               kernel_ms_per_img=1e3 * reduced.kernel_s / images,
               glue_ms_per_img=1e3 * reduced.glue_s / images,
               device_idle_share=100 * (1 - reduced.busy_s / reduced.window_s),
               idle_gaps=reduced.gaps)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
