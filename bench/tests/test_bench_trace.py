"""The reduction from a profiler trace to device time by kind.

``data/vgg11-cifar-b2.xplane.pb`` is a trace recorded on one TPU v5e with
the harness's profiler options: one call of ``ProgramExecutor.run`` on
vgg11-cifar at batch 2 before the window, then a window of four calls.
``data/vgg11-cifar-b2.json`` holds the wall-clock nanoseconds of that
window and of each call, as the harness recorded them. The source paths
that the trace names are replaced by ``<checkout>/``, with their lengths
kept. ``data/vgg11-cifar-b2-spans.*`` is the same with the program's spans
on, two calls in its window.
"""
import bisect
import json
import random
import time
from collections import Counter
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"
KERNELS_PER_CALL = 11          # one com_matmul per vgg11 layer
FIXTURES = ("vgg11-cifar-b2", "vgg11-cifar-b2-spans")


def idle_by_host_reference(busy, calls, lo, hi):
    """The idle attribution as first written: every call clipped against
    every busy interval, so its cost grows with calls times intervals."""
    gaps = Counter()
    starts = [b[0] for b in busy]
    covered = 0.0
    for cs, ce in calls:
        cs, ce = max(cs, lo), min(ce, hi)
        if ce <= cs:
            continue
        covered += ce - cs
        i = bisect.bisect_left(starts, cs)
        if i > 0 and busy[i - 1][1] > cs:
            i -= 1
        inside = [(max(s, cs), min(e, ce)) for s, e in busy[i:]
                  if s < ce]
        if not inside:
            gaps["call without device work"] += ce - cs
            continue
        gaps["call: before its first device op"] += inside[0][0] - cs
        gaps["call: after its last device op"] += ce - inside[-1][1]
        for (_, e0), (s1, _) in zip(inside, inside[1:]):
            gaps["call: between its device ops"] += s1 - e0
    busy_in_calls = sum(trace.clip(b, cs, ce) for cs, ce in calls
                        for b in busy)
    busy_all = sum(trace.clip(b, lo, hi) for b in busy)
    gaps["between calls"] += (hi - lo - covered) - (busy_all - busy_in_calls)
    return {k: v * 1e-9 for k, v in gaps.items() if v > 0}


def made_up_window(seed):
    """A window, sorted calls and merged busy intervals drawn from
    ``seed``: calls that straddle the window's edges or hold no device
    work, busy intervals across call edges and outside the window, now
    and then no busy interval at all, and on every third seed times with
    a fraction of a nanosecond, tens of seconds into the profile, as the
    trace's event times are."""
    rng = random.Random(seed)
    frac = seed % 3 == 1
    base = 4e10 if frac else 0
    lo = base + rng.randint(0, 1000)
    hi = lo + rng.randint(1, 5000)

    def at(a, b):
        return rng.uniform(a, b) if frac else rng.randint(int(a), int(b))

    points = sorted(at(lo - 600, hi + 600)
                    for _ in range(2 * rng.randint(0, 25)))
    calls = list(zip(points[::2], points[1::2]))
    if seed % 7 == 3 and calls:                 # a call over the whole window
        calls = sorted(calls + [(lo - 10, hi + 10)])
    busy = []
    if seed % 10 != 0:
        for _ in range(rng.randint(1, 80)):
            s = at(lo - 800, hi + 800)
            busy.append((s, s + at(1, 200)))
    return trace.merge(busy), calls, lo, hi


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(DATA / "vgg11-cifar-b2.xplane.pb"))


@pytest.fixture(scope="module")
def host():
    return json.loads((DATA / "vgg11-cifar-b2.json").read_text())


@pytest.fixture(scope="module")
def reduced(profile, host):
    return trace.reduce_profile(profile, host["window"], host["calls"])


def test_window_and_device_times(host, reduced):
    lo, hi = host["window"]
    assert reduced.window_s == pytest.approx((hi - lo) * 1e-9)
    assert reduced.devices == 1
    # TPU ops run one at a time: kernel and glue time add up to busy time
    assert reduced.kernel_s + reduced.glue_s == pytest.approx(reduced.busy_s)
    assert 0 < reduced.busy_s < reduced.window_s
    # the recorded numbers, so that a change to the reduction shows
    assert reduced.window_s == pytest.approx(0.008834339)
    assert reduced.busy_s == pytest.approx(0.002236943)
    assert reduced.kernel_s == pytest.approx(0.002190595)
    assert reduced.glue_s == pytest.approx(4.6348e-05)


def test_kernels_are_the_pallas_calls(profile, reduced):
    ops = [ln for ln in profile.find_plane_with_name("/device:TPU:0").lines
           if ln.name == trace.OPS_LINE][0]
    kernels = [e for e in ops.events if trace.KERNEL_MARK in e.name]
    assert len(kernels) == 5 * KERNELS_PER_CALL
    labels = [k for k in reduced.ops if " tpu_custom_call " in k]
    assert len(labels) == KERNELS_PER_CALL
    assert sum(reduced.ops[k] for k in labels) == pytest.approx(
        reduced.kernel_s)
    # a custom call that is not a Pallas kernel is glue
    assert any(" ConcatBitcast " in k for k in reduced.ops)


def test_idle_time_is_named_by_host_spans(reduced):
    assert sum(reduced.gaps.values()) == pytest.approx(
        reduced.window_s - reduced.busy_s)
    assert set(reduced.gaps) <= {
        "call: before its first device op", "call: after its last device op",
        "call: between its device ops", "between calls",
        "call without device work"}
    br = reduced.breakdown()
    assert len(br["device_ops"]) == trace.TOP
    assert br["device_ops"][0][0] == "forward.20 tpu_custom_call f32[128,4096]"
    assert [v for _, v in br["device_ops"]] == sorted(
        (v for _, v in br["device_ops"]), reverse=True)


def test_idle_attribution_on_made_up_intervals():
    # window 0..100; calls 10..50 and 60..95; device busy 20..30, 35..45
    # and 70..90
    busy = [(20.0, 30.0), (35.0, 45.0), (70.0, 90.0)]
    gaps = trace._idle_by_host(busy, [(10.0, 50.0), (60.0, 95.0)], 0.0, 100.0)
    assert gaps == pytest.approx({
        "call: before its first device op": 20e-9,
        "call: between its device ops": 5e-9,
        "call: after its last device op": 10e-9,
        "between calls": 25e-9,
    })


@pytest.mark.parametrize("seed", range(240))
def test_idle_attribution_matches_the_reference(seed):
    busy, calls, lo, hi = made_up_window(seed)
    assert trace._idle_by_host(busy, calls, lo, hi) == (
        idle_by_host_reference(busy, calls, lo, hi))


@pytest.mark.parametrize("name", FIXTURES)
def test_recorded_traces_reduce_as_the_reference_does(name, monkeypatch):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(DATA / f"{name}.xplane.pb"))
    host = json.loads((DATA / f"{name}.json").read_text())
    got = trace.reduce_profile(data, host["window"], host["calls"])
    monkeypatch.setattr(trace, "_idle_by_host", idle_by_host_reference)
    want = trace.reduce_profile(data, host["window"], host["calls"])
    assert got.gaps and got.gaps == want.gaps
    assert got.breakdown() == want.breakdown()


@pytest.mark.timeout(60)
def test_idle_attribution_grows_with_calls_plus_intervals():
    # 3,000 back-to-back calls of 1 ms, each with 300 device ops of 2 us
    # and a gap of 1 us after each: about twice a 20 s b1 window on a v5e
    calls = [(t * 1_000_000, t * 1_000_000 + 990_000) for t in range(3000)]
    busy = [(c + 10_000 + 3_000 * k, c + 12_000 + 3_000 * k)
            for c, _ in calls for k in range(300)]
    t0 = time.perf_counter()
    gaps = trace._idle_by_host(busy, calls, 0, 3000 * 1_000_000)
    assert time.perf_counter() - t0 < 5
    assert gaps["call: between its device ops"] == pytest.approx(
        3000 * 299 * 1e-6)
    assert gaps["between calls"] == pytest.approx(3000 * 10e-6)


def test_merge_and_labels():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    hlo = ('%forward.17 = f32[1605632,128]{1,0:T(8,128)} custom-call(f32'
           '[1605632,640]{1,0:T(8,128)} %pad.8), custom_call_target='
           '"tpu_custom_call"')
    assert trace.op_label(hlo) == "forward.17 tpu_custom_call f32[1605632,128]"
    assert trace.op_label("%pad.8 = f32[4,6]{1,0} pad(f32[4,5]{1,0} %a)") == (
        "pad.8 pad f32[4,6]")


def test_a_trace_without_its_start_time_is_refused():
    class Empty:
        planes = []

    with pytest.raises(ValueError, match="profile_start_time"):
        trace.reduce_profile(Empty(), (0, 1), [])
