"""Program spans on the device trace's clock, and ops mapped to layers.

``data/vgg11-cifar-b2-spans.xplane.pb`` is a trace recorded on one TPU v5e
by ``python3 -m bench.spantrace --workload vgg11-cifar.closed-b256 --batch
2 --seconds 0.006 --out ...``: one call before the window, then a window
of two calls, with the program's spans on. ``data/vgg11-cifar-b2-spans.
json`` holds the window and calls on the wall clock as the harness records
them, the spans, and the scope (``op_name``) of every op the trace holds,
under the label the chip gives it: ``scopes.hlo_scopes`` of the chain and
of the upload's one ``copy``, compiled for a described v5e (a test below
checks this). Source paths in the trace read ``<checkout>/``, with their
lengths kept.
"""
import json
from pathlib import Path

import pytest

from bench import scopes, spantrace, trace
from bench.metrics import (glue_ms_per_img, im2col_ms_per_img,
                           kernel_ms_per_img, pad_ms_per_img)

DATA = Path(__file__).resolve().parent / "data"
STEM = "vgg11-cifar-b2-spans"


def _span(name, start, end, parent=None, call=0, **counters):
    return {"name": name, "start_ns": start, "end_ns": end, "parent": parent,
            "call": call, "counters": counters}


def _call(i, t):
    """Spans of call ``i`` from perf time ``t``: batch 100 ns, upload 100,
    dispatch 100, fetch 700; returns them with the root's index ``5 * i``."""
    root = 5 * i
    return [_span("executor.run", t, t + 1000, call=i, images=2),
            _span("executor.batch", t, t + 100, root, i),
            _span("executor.upload", t + 100, t + 200, root, i),
            _span("executor.dispatch", t + 200, t + 300, root, i),
            _span("executor.fetch", t + 300, t + 1000, root, i)]


def test_alignment_recovers_a_known_offset_and_reports_the_slack():
    true = 7_000_000                  # span clock + true = profile clock
    spans = _call(0, 0) + _call(1, 10_000) + _call(2, 20_000)
    rec = {"wall_minus_perf_ns": 0, "spans": spans}
    calls = spantrace.calls_of(rec)
    # each module starts after its dispatch began and ends before its
    # fetch ended, by margins that differ from call to call
    margins = [(50, 30), (80, 10), (20, 60)]
    modules = []
    for c, (m0, m1) in zip(calls, margins):
        d, f = c["children"]["executor.dispatch"], c["children"]["executor.fetch"]
        modules.append((d["start_ns"] + true + m0, f["end_ns"] + true - m1,
                        "jit_forward(123)"))
        modules.append((d["start_ns"] + true - 150, d["start_ns"] + true - 140,
                        "jit_convert_element_type(9)"))
    # offset <= true + min(start margins), >= true - min(end margins):
    # the wall clocks' offset, 3 us off, is pulled into that range
    got = spantrace.align(calls, modules, nominal=true + 3_000)
    assert got["pairs"] == 3
    assert got["clock_slack_us"] == pytest.approx((20 + 10) * 1e-3)
    assert got["offset_ns"] == true + 20
    assert got["shift_us"] == pytest.approx((20 - 3_000) * 1e-3)
    # a nominal offset inside the range is kept
    inside = spantrace.align(calls, modules, nominal=true - 4)
    assert inside["offset_ns"] == true - 4 and inside["shift_us"] == 0
    # no call with its module: no alignment, said so
    none = spantrace.align(calls, [], nominal=5.0)
    assert none["pairs"] == 0 and none["offset_ns"] == 5.0


def test_idle_goes_to_the_innermost_span_over_it():
    spans = _call(0, 0) + _call(1, 2000)
    rec = {"wall_minus_perf_ns": 0, "spans": spans}
    # the device runs 250..900 in call 0 and 2250..2400, 2500..2950 in
    # call 1; the window is 0..3500
    busy = [(250.0, 900.0), (2250.0, 2400.0), (2500.0, 2950.0)]
    idle = spantrace.idle_by_span(busy, rec, 0.0, 0.0, 3500.0)
    assert idle == pytest.approx({
        "executor.batch": 200e-9, "executor.upload": 200e-9,
        "executor.dispatch": 100e-9,             # 200..250, 2200..2250
        "executor.fetch": 250e-9,                # 900..1000, 2400..2500,
                                                 # 2950..3000
        "between calls": 1500e-9,                # 1000..2000, 3000..3500
    })
    assert sum(idle.values()) == pytest.approx((3500 - 1250) * 1e-9)


def test_host_in_and_out_and_slow_calls():
    spans = []
    for i in range(5):
        spans += _call(i, 10_000 * i)
    # call 3's fetch takes 5000 ns more
    spans[5 * 3]["end_ns"] += 5000
    spans[5 * 3 + 4]["end_ns"] += 5000
    rec = {"wall_minus_perf_ns": 0, "spans": spans}
    calls = spantrace.calls_of(rec)
    assert spantrace.host_in_ms(calls) == pytest.approx(300e-6)
    busy = [(10_000.0 * i + 250, 10_000.0 * i + 800) for i in range(5)]
    assert spantrace.host_out_ms(calls, busy, 0.0) == pytest.approx(
        (4 * 200 + 5200) / 5 * 1e-6)
    lines = spantrace.slow_calls(calls)
    assert len(lines) == 1
    assert lines[0].startswith("slow call 3: 0.006 ms")
    assert "executor.fetch holds 0.005 ms" in lines[0]


def test_host_steps_read_the_byte_counters_and_build_its_span():
    spans = []
    for i in range(2):
        call = _call(i, 10_000 * i)
        call[1]["counters"] = {"bytes_host": 800}
        call[2]["counters"] = {"bytes_up": 400}
        call[4]["counters"] = {"bytes_down": 70}
        spans += call
    calls = spantrace.calls_of({"wall_minus_perf_ns": 0, "spans": spans})
    got = spantrace.host_steps(calls)
    # bytes a nanosecond are GB a second
    assert got["executor.batch"] == pytest.approx(
        {"ms_per_call": 100e-6, "bytes_per_call": 800, "gb_per_s": 8.0})
    assert got["executor.upload"]["gb_per_s"] == pytest.approx(4.0)
    assert got["executor.fetch"] == pytest.approx(
        {"ms_per_call": 700e-6, "bytes_per_call": 70, "gb_per_s": 0.1})
    assert got["executor.dispatch"] == {"ms_per_call": pytest.approx(100e-6)}

    setup = [_span("executor.run", 0, 5000, compiles=2, images=2),
             _span("executor.batch", 0, 100, 0),
             _span("executor.build", 100, 4100, 0, bytes_weights=2000)]
    assert spantrace.build_of({"spans": setup}) == pytest.approx(
        {"s": 4000e-9, "bytes_weights": 2000, "gb_per_s": 0.5,
         "compiles": 2})
    assert spantrace.build_of({"spans": _call(0, 0)}) is None


def test_layer_roles_from_op_names():
    role = scopes.layer_role
    assert role("jit(forward)/vgg11.conv0/matmul/pad/jit(_pad)/pad") == (
        "vgg11.conv0", "pad")
    assert role("jit(forward)/vgg11.conv0/matmul/unpad/slice") == (
        "vgg11.conv0", "unpad")
    assert role("jit(forward)/vgg11.conv2/im2col/concatenate") == (
        "vgg11.conv2", "im2col")
    assert role("jit(forward)/vgg16.conv1/matmul/com_matmul_vgg16.conv1/"
                "pallas_call") == ("vgg16.conv1", "matmul")
    assert role("jit(forward)/vgg11.fc0/reshape") == ("vgg11.fc0", "layer")
    # a chain without layer scopes, and parameters, are in no layer
    assert role("jit(forward)/jit(_pad)/pad") is None
    assert role("ws[10]") is None
    assert role("") is None


def test_ops_without_a_source_op_take_the_scope_of_what_they_feed():
    text = """HloModule jit_forward

%fused_computation.1 (param_0: f32[4]) -> f32[8] {
  %param_0 = f32[4]{0} parameter(0)
  %constant.1 = f32[] constant(0)
  ROOT %pad.3 = f32[8]{0} pad(%param_0, %constant.1), padding=0_4, metadata={op_name="jit(forward)/c0/matmul/pad/jit(_pad)/pad" stack_frame_id=1}
}

ENTRY %main.9 (x.1: f32[4]) -> (f32[8], f32[4]) {
  %x.1 = f32[4]{0} parameter(0), metadata={op_name="x"}
  %copy-start = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%x.1)
  %copy-done = f32[4]{0} copy-done(%copy-start)
  %pad_fusion = f32[8]{0:T(128)} fusion(%copy-done), kind=kLoop, calls=%fused_computation.1
  %negate.2 = f32[4]{0} negate(%x.1), metadata={op_name="jit(forward)/negate"}
  ROOT %tuple.3 = (f32[8]{0}, f32[4]{0}) tuple(%pad_fusion, %negate.2)
}
"""
    got = scopes.hlo_scopes(text)
    pad = "jit(forward)/c0/matmul/pad/jit(_pad)/pad"
    # a fusion: its computation's root; the copies: the fusion they feed
    assert got["pad_fusion fusion f32[8]"] == pad
    assert got["copy-done copy-done f32[4]"] == pad
    assert got["copy-start copy-start (f32[4],"] == pad
    # outside every layer, fed to nothing in one: its own, or nothing
    assert got["negate.2 negate f32[4]"] == "jit(forward)/negate"
    assert got["tuple.3 tuple (f32[8],"] == ""


def test_pieces_of_a_concatenation_take_its_scope_over_a_slice_inside():
    # the compiler's pieces of the next layer's patches: the first writes
    # into a fresh buffer, one also slices the layer before (its unpad),
    # the last carries the concatenation's own op_name
    text = """HloModule jit_forward

%fc.0 (p.0: f32[4,2]) -> f32[4,8] {
  %buf = f32[4,8]{1,0} custom-call(), custom_call_target="AllocateBuffer"
  %p.0 = f32[4,2]{1,0} parameter(0)
  %c.0 = s32[] constant(0)
  ROOT %dus.0 = f32[4,8]{1,0} dynamic-update-slice(%buf, %p.0, %c.0, %c.0)
}

%fc.1 (p.1: f32[4,8], q.1: f32[8,2]) -> f32[4,8] {
  %p.1 = f32[4,8]{1,0} parameter(0)
  %q.1 = f32[8,2]{1,0} parameter(1)
  %slice.9 = f32[4,2]{1,0} slice(%q.1), slice={[0:4], [0:2]}, metadata={op_name="jit(forward)/c4/matmul/unpad/slice"}
  %c.1 = s32[] constant(0)
  ROOT %dus.1 = f32[4,8]{1,0} dynamic-update-slice(%p.1, %slice.9, %c.1, %c.1)
}

%fc.2 (p.2: f32[4,8], q.2: f32[4,2]) -> f32[4,8] {
  %p.2 = f32[4,8]{1,0} parameter(0)
  %q.2 = f32[4,2]{1,0} parameter(1)
  %c.2 = s32[] constant(0)
  ROOT %dus.2 = f32[4,8]{1,0} dynamic-update-slice(%p.2, %q.2, %c.2, %c.2), metadata={op_name="jit(forward)/c5/im2col/concatenate"}
}

ENTRY %main (a: f32[4,2], k: f32[8,2]) -> f32[4,8] {
  %a = f32[4,2]{1,0} parameter(0)
  %k = f32[8,2]{1,0} parameter(1)
  %f.0 = f32[4,8]{1,0} fusion(%a), kind=kLoop, calls=%fc.0
  %f.1 = f32[4,8]{1,0} fusion(%f.0, %k), kind=kLoop, calls=%fc.1
  ROOT %f.2 = f32[4,8]{1,0} fusion(%f.1, %a), kind=kLoop, calls=%fc.2
}
"""
    got = scopes.hlo_scopes(text)
    concat = "jit(forward)/c5/im2col/concatenate"
    assert got["f.0 fusion f32[4,8]"] == concat
    assert got["f.1 fusion f32[4,8]"] == concat
    assert got["f.2 fusion f32[4,8]"] == concat
    # the slice itself, inside, keeps its own
    assert got["slice.9 slice f32[4,2]"] == "jit(forward)/c4/matmul/unpad/slice"


def test_the_program_scopes_every_layer_of_its_chain():
    # the chain as the executor compiles it here (CPU, Pallas interpreted)
    got = scopes.hlo_scopes(scopes.compiled_text("vgg11-cifar", 2))
    roles = {}
    for op in got.values():
        lr = scopes.layer_role(op)
        if lr is not None:
            roles.setdefault(lr[0], set()).add(lr[1])
    assert len(roles) == 11
    assert all({"im2col", "matmul", "pad", "pool"} <= roles[f"vgg11.conv{i}"]
               for i in (0, 1, 3, 5, 7))
    assert all("matmul" in roles[f"vgg11.fc{i}"] for i in range(3))


# ---- the recorded chip trace ------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(DATA / f"{STEM}.xplane.pb"))
    host = json.loads((DATA / f"{STEM}.json").read_text())
    return data, host


@pytest.fixture(scope="module")
def reduced(recorded):
    data, host = recorded
    return trace.reduce_profile(data, host["window"], host["calls"])


@pytest.fixture(scope="module")
def spanned(recorded):
    data, host = recorded
    return spantrace.reduce_spans(data, host["window"], host["spans"],
                                  host["scopes"])


@pytest.fixture
def record(recorded, monkeypatch):
    """A reader's record of the recorded window, with the cell's scope map
    the one recorded beside the trace."""
    data, host = recorded
    monkeypatch.setattr(scopes, "cell_scopes",
                        lambda network, batch: host["scopes"])
    return {"images": host["images"], "calls": len(host["calls"]),
            "cfg": {"network": "vgg11-cifar"}, "mix": {"batch": 2}}


def test_the_clocks_align_within_a_small_slack(recorded, spanned):
    clock = spanned["clock"]
    assert clock["pairs"] == len(recorded[1]["calls"])
    # the recorded window's two calls leave 1.4 ms of the offset open
    assert clock["clock_slack_us"] == pytest.approx(1405.608)


def test_every_chain_op_maps_to_a_layer(spanned):
    keys = set(spanned["ops_by_layer"])
    assert not [k for k in keys if k.startswith(("unmapped/", "chain/"))]
    layers = {k.split("/")[0] for k in keys}
    assert {f"vgg11.conv{i}" for i in range(8)} | {
        f"vgg11.fc{i}" for i in range(3)} <= layers
    assert "jit_convert_element_type" in layers


def test_kernel_and_glue_are_the_sums_by_role(spanned, reduced):
    by = spanned["ops_by_layer"]
    kernel = sum(v for k, v in by.items() if k.endswith("/kernel"))
    assert kernel == pytest.approx(reduced.kernel_s)
    assert sum(by.values()) - kernel == pytest.approx(reduced.glue_s)


def test_idle_inside_calls_is_under_named_spans(spanned, reduced):
    assert sum(spanned["idle_by_span"].values()) == pytest.approx(
        reduced.window_s - reduced.busy_s)
    assert spanned["idle_named_share"] >= 0.9
    assert spanned["host_in_ms_per_call"] > 0
    assert spanned["host_out_ms_per_call"] > 0


def test_the_glue_readers(reduced, record, spanned):
    im2col = im2col_ms_per_img.read(reduced, record)
    pad = pad_ms_per_img.read(reduced, record)
    by = spanned["ops_by_layer"]
    images = record["images"]
    assert im2col == pytest.approx(1e3 * sum(
        v for k, v in by.items() if k.endswith("/im2col")) / images)
    assert pad == pytest.approx(1e3 * sum(
        v for k, v in by.items() if k.endswith(("/pad", "/unpad"))) / images)
    assert 0 < im2col + pad < glue_ms_per_img.read(reduced, record)
    assert kernel_ms_per_img.read(reduced, record) > 0


def test_the_next_layers_patches_are_its_im2col(recorded):
    # conv5's and conv7's patches are built from pieces, some of which
    # also slice the layer before back from its blocks
    host = recorded[1]
    pieces = [k for k in host["scopes"]
              if k.endswith((" fusion f32[32,4608]", " fusion f32[8,4608]"))]
    assert len(pieces) == 18
    for k in pieces:
        want = "vgg11.conv5" if "[32," in k else "vgg11.conv7"
        assert scopes.op_role(k, host["scopes"]) == (want, "im2col"), k


def test_the_recorded_map_is_what_the_rules_give_for_a_described_v5e(
        recorded):
    # the chain at batch 2 and the upload compiled for one described v5e
    # chip; async ops print as slice-start there, as async-start on the
    # chip, so the two are matched by instruction name
    import os

    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        from jax.experimental import topologies
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.core import compile_program
    from repro.core.executor import jax_forward
    from repro.sweep.registry import resolve_network
    from jax._src import dispatch

    one = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        x, ws = scopes.chain_args("vgg11-cifar", 2)
        x = jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)
        ws = [jax.ShapeDtypeStruct(w.shape, w.dtype, sharding=one)
              for w in ws]
        forward = jax_forward(compile_program(resolve_network("vgg11-cifar")),
                              interpret=False)
        chain = jax.jit(forward).lower(x, ws).compile().as_text()
        convert = dispatch.xla_primitive_callable(
            jax.lax.convert_element_type_p, new_dtype=np.dtype(np.float32),
            weak_type=False, sharding=None)
        upload = convert.lower(x).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
    fresh = {k.split(" ")[0]: v for k, v in {
        **scopes.hlo_scopes(upload), **scopes.hlo_scopes(chain)}.items()}
    recorded_map = recorded[1]["scopes"]
    assert {k: fresh[k.split(" ")[0]] for k in recorded_map} == recorded_map


def test_the_glue_readers_find_nothing_where_the_map_lacks_an_op(
        reduced, record, recorded, monkeypatch, capsys):
    host = recorded[1]
    dropped = "constant_dynamic-update-slice_fusion.44 fusion f32[32,4608]"
    short = {k: v for k, v in host["scopes"].items() if k != dropped}
    monkeypatch.setattr(scopes, "cell_scopes", lambda network, batch: short)
    assert im2col_ms_per_img.read(reduced, record) is None
    assert pad_ms_per_img.read(reduced, record) is None
    assert "1 device ops of the trace are not in" in capsys.readouterr().err
    # and the upload's op is in the map, as chain-level
    assert scopes.op_role("copy.1 copy f32[2,32,32,3]", host["scopes"]) \
        is None


def test_the_glue_readers_find_nothing_in_a_chain_without_scopes(
        reduced, record, monkeypatch):
    monkeypatch.setattr(scopes, "cell_scopes", lambda network, batch: {
        label: "jit(forward)/jit(_pad)/pad" for label in reduced.ops})
    assert im2col_ms_per_img.read(reduced, record) is None
    assert pad_ms_per_img.read(reduced, record) is None
