"""The residual configuration: the benchmark's walk (``residual_net.py``)
and reference (``reference_residual.py``) against the program's network
and plain reference, at ResNet-50's published sizes where only shapes are
compared and at a small size where logits are."""
import copy

import jax
import numpy as np
import pytest

from bench import network, reference_residual, residual_net, work

PEAK = work.peaks("TPU v5 lite")


@pytest.fixture(scope="module")
def cfg():
    return network.load_config("resnet50-imagenet")


def small_cfg(cfg):
    """32x32 input, every width divided by 16, one block per stage, ten
    classes: projections and both pools kept."""
    small = copy.deepcopy(cfg)
    small["input"].update(height=32, width=32)
    small["stem"]["channels"] //= 16
    for stage in small["stages"]:
        stage.update(blocks=1, width=stage["width"] // 16)
    small["classifier"] = [10]
    return small


def _program_layers(cfg):
    from repro.core.mapping import bottleneck_resnet

    stages = tuple((s["width"], s["blocks"], s["stride"])
                   for s in cfg["stages"])
    return bottleneck_resnet(cfg["layer_prefix"],
                             image=cfg["input"]["height"],
                             stem=cfg["stem"]["channels"], stages=stages,
                             expansion=cfg["expansion"],
                             classes=cfg["classifier"][-1])


def test_resnet50_counts(cfg):
    net = residual_net.layers(cfg)
    assert len(net) == 54 and net[-1].kind == "fc"
    assert residual_net.macs_per_image(cfg) == 4_089_184_256
    assert sum(int(np.prod(l.weight_shape)) for l in net) == 25_502_912
    assert sum(l.joins for l in net) == 16
    assert [l.name for l in net if not l.relu] == [
        f"rn50.s{i}b0.proj" for i in range(1, 5)] + ["rn50.fc"]
    # b32: the bytes bound the call, 4.28 ms of HBM traffic against 1.33
    # ms of bfloat16 FLOPs; the joins' shortcut reads are in the bytes
    work_ = residual_net.network_work(cfg, 32, PEAK)
    assert sum(w.bytes for w in work_) / PEAK["hbm_bytes_per_s"] == \
        pytest.approx(4.28e-3, rel=0.01)
    assert residual_net.roofline_seconds(cfg, 32, PEAK) == pytest.approx(
        4.44e-3, rel=0.01)
    assert 0 < residual_net.roofline_seconds(
        cfg, 32, PEAK, only_joins=True) < residual_net.roofline_seconds(
        cfg, 32, PEAK)
    join = net[4]
    assert join.name == "rn50.s1b0.c"
    assert residual_net.layer_bytes(join, 32, 4) == 4 * (
        32 * (56 * 56 * 64 + 2 * 56 * 56 * 256) + 64 * 256)


@pytest.mark.parametrize("size", ["published", "small"])
def test_walk_is_the_programs_network(cfg, size):
    cfg = cfg if size == "published" else small_cfg(cfg)
    ours = residual_net.layers(cfg)
    theirs = _program_layers(cfg)
    if size == "published":
        from repro.sweep.registry import resolve_network

        assert tuple(theirs) == resolve_network(cfg["network"]).layers
    assert [l.name for l in ours] == [l.name for l in theirs]
    for a, b in zip(ours, theirs):
        assert (a.c_in, a.c_out, a.macs) == (b.c_in, b.c_out, b.macs)
        assert a.relu == (b.activation == "relu")
        if a.kind == "conv":
            assert (a.h_in, a.k, a.stride, a.padding) == (
                b.h_in, b.k, b.stride, b.padding)
            assert a.shortcut == b.residual_from
            assert a.reads == b.input_from
            assert a.average == b.avg_pool
            assert a.pool == ((b.pool_k, b.pool_stride, b.pool_padding)
                              if b.pool_k else None)


def test_reference_is_the_programs_reference(cfg):
    from repro.core import reference

    small = small_cfg(cfg)
    net = residual_net.layers(small)
    ws = [np.asarray(w) for w in residual_net.weights(small, net, 7)]
    x = np.random.default_rng(8).standard_normal(
        (2,) + residual_net.input_shape(small)).astype(np.float32)
    ours = np.asarray(jax.jit(reference_residual.forward(net))(x, ws))
    theirs = reference.forward(_program_layers(small), ws, x)
    assert ours.shape == (2, 10)
    scale = np.abs(theirs).max()
    # the same float32 operations, written twice
    assert np.abs(ours - theirs).max() <= 1e-6 * scale
    # the control, three bfloat16 passes, is off by far more than that
    high = np.asarray(jax.jit(reference_residual.forward(net, "high"))(x, ws))
    assert np.abs(high - theirs).max() > 1e-6 * scale


def test_the_branch_scale_is_on_the_joins_alone(cfg):
    small = small_cfg(cfg)
    net = residual_net.layers(small)
    scaled = residual_net.weights(small, net, 3)
    plain = residual_net.weights(dict(small, assumed={"branch_scale": 1.0}),
                                 net, 3)
    for layer, a, b in zip(net, scaled, plain):
        want = small["assumed"]["branch_scale"] if layer.joins else 1.0
        np.testing.assert_allclose(np.asarray(a), want * np.asarray(b),
                                   rtol=1e-6)


def test_the_residual_readers_on_a_described_v5e_chain(cfg, monkeypatch):
    """The join and shortcut readers on ResNet-50's chain at batch 2,
    compiled for one described v5e chip, with every op of it one
    microsecond long: 16 join kernels and the 4 projections' ops are
    found by scope."""
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        from jax.experimental import topologies
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e topology can be described here: {e}")
    from jax.sharding import SingleDeviceSharding

    from bench import scopes, trace
    from bench.metrics import (
        join_roofline,
        residual_roofline,
        shortcut_ms_per_img,
    )
    from repro.core import compile_program
    from repro.core.executor import jax_forward
    from repro.sweep.registry import resolve_network

    one = SingleDeviceSharding(topo.devices[0])
    x, ws = scopes.chain_args("resnet50-imagenet", 2)
    x, *ws = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)
              for a in [x] + ws]
    program = compile_program(resolve_network("resnet50-imagenet"))
    text = jax.jit(jax_forward(program, interpret=False)).lower(
        x, ws).compile().as_text()
    ops = scopes.hlo_scopes(text)
    monkeypatch.setattr(scopes, "cell_scopes", lambda network, batch: ops)
    roles = [scopes.op_role(label, ops) for label in ops]
    kernels = {r[0] for r in roles if r is not None and r[1] == "kernel"}
    assert len(kernels) == 54
    in_shortcut = [label for label in ops
                   if "shortcut" in ops[label].split("/")[:-1]]
    assert {scopes.op_role(label, ops)[0] for label in in_shortcut} == {
        f"rn50.s{i}b0.proj" for i in range(1, 5)}
    us = 1e-6
    reduced = trace.Reduced(window_s=1.0, busy_s=0.5,
                            kernel_s=len(kernels) * us, glue_s=0.1,
                            devices=1, ops={label: us for label in ops})
    record = {"images": 2, "calls": 1, "window_s": 1.0, "cfg": cfg,
              "mix": {"batch": 2}, "peaks": PEAK}
    joins = residual_net.roofline_seconds(cfg, 2, PEAK, only_joins=True)
    assert join_roofline.read(reduced, record) == pytest.approx(
        100 * joins / (16 * us))
    assert shortcut_ms_per_img.read(reduced, record) == pytest.approx(
        1e3 * len(in_shortcut) * us / 2)
    assert residual_roofline.read(reduced, record) == pytest.approx(
        100 * residual_net.roofline_seconds(cfg, 2, PEAK) / (54 * us))
    # an op the chain does not hold: the scope readers report nothing
    reduced.ops["stranger fusion f32[1]"] = us
    assert join_roofline.read(reduced, record) is None
    assert shortcut_ms_per_img.read(reduced, record) is None
