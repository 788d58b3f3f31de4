"""The reduction from a profiler trace to device time by kind.

``data/vgg11-cifar-b2.xplane.pb`` is a trace recorded on one TPU v5e with
the harness's profiler options: one call of ``ProgramExecutor.run`` on
vgg11-cifar at batch 2 before the window, then a window of four calls.
``data/vgg11-cifar-b2.json`` holds the wall-clock nanoseconds of that
window and of each call, as the harness recorded them. The source paths
that the trace names are replaced by ``<checkout>/``, with their lengths
kept.
"""
import json
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"
KERNELS_PER_CALL = 11          # one com_matmul per vgg11 layer


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
