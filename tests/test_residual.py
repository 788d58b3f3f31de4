"""Residual networks on the whole-program executor.

ResNet-50 v1.5 (``resnet50-imagenet``) at its published sizes is walked
here, not run: its shapes, MACs and weights. A reduced ResNet of the same
structure (32x32 input, every width divided by 16, one block per stage,
every projection, the stem's max pool and the global average pool) runs
on both backends against the plain float32 reference
(``repro.core.reference``), and so does ``resnet18-cifar``.

Tolerance: ``max |got - ref| / max |ref|`` at most 2e-5 (``TOL``). The
float64 oracle and the float32 kernel chain differ from the float32
reference by their summation orders alone, under 2e-6 on these networks;
one bfloat16 product in the reference reads about 5e-3, and the test
checks that it fails the tolerance.
"""
import numpy as np
import pytest

from repro.core import compile_program, reference
from repro.core.executor import _chain_shapes, random_weights
from repro.core.mapping import (
    RESNET50_STAGES,
    ConvSpec,
    FCSpec,
    bottleneck_resnet,
    resnet18_cifar,
)
from repro.core.program import Workload
from repro.core.simulator import network_event_totals
from repro.sweep.registry import available_networks, resolve_network

TOL = 2e-5
SMALL_STAGES = tuple((w // 16, 1, s) for w, _, s in RESNET50_STAGES)


def small_resnet() -> Workload:
    return Workload("rn50-small", tuple(bottleneck_resnet(
        "rn50", image=32, stem=4, stages=SMALL_STAGES, classes=10)))


def rel_err(got, ref) -> float:
    return float(np.abs(np.asarray(got, np.float64) - ref).max()
                 / np.abs(ref).max())


def test_resnet50_walk_at_published_sizes():
    wl = resolve_network("resnet50-imagenet")
    layers = wl.layers
    convs = [l for l in layers if isinstance(l, ConvSpec)]
    assert len(convs) == 53 and isinstance(layers[-1], FCSpec)
    assert len(layers) == 54
    assert sum(l.macs for l in layers) == 4_089_184_256
    assert sum(l.k * l.k * l.c_in * l.c_out for l in convs) + (
        layers[-1].c_in * layers[-1].c_out) == 25_502_912
    assert sum(l.k == 1 for l in convs) == 36
    joins = [l for l in convs if l.residual_from]
    projections = [l for l in convs if l.name.endswith(".proj")]
    assert len(joins) == 16 and len(projections) == 4
    assert all(p.activation == "none" for p in projections)
    assert layers[-1].activation == "none"
    assert [p.stride for p in projections] == [1, 2, 2, 2]
    # v1.5: a stage's stride is on its first block's 3x3 convolution
    assert [l.stride for l in convs if l.name.endswith("b0.b")] == [1, 2, 2, 2]
    # "resnet50-imagenet" is found by name but priced by no sweep grid
    assert "resnet50-imagenet" not in available_networks()
    shapes = dict(zip((l.name for l in layers), _chain_shapes(layers)))
    assert shapes["rn50.stem"] == (224, 224, 3)
    assert shapes["rn50.s1b0.a"] == shapes["rn50.s1b0.proj"] == (56, 56, 64)
    assert shapes["rn50.s2b0.c"] == (28, 28, 128)
    assert shapes["rn50.s3b0.a"] == (28, 28, 512)
    assert shapes["rn50.s4b2.c"] == (7, 7, 512)
    assert shapes["rn50.fc"] == (2048,)


def test_resnet50_event_totals_match_the_closed_forms():
    program = compile_program(resolve_network("resnet50-imagenet"))
    ex = program.executor(random_weights(program, seed=0))
    assert ex.events == network_event_totals(program.workload.layers,
                                             program.arch)


@pytest.fixture(scope="module")
def small():
    wl = small_resnet()
    program = compile_program(wl)
    weights = random_weights(program, seed=5)
    images = np.random.default_rng(6).standard_normal((2, 32, 32, 3))
    return program, weights, images, reference.forward(wl, weights, images)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_small_resnet_matches_the_reference(small, backend):
    program, weights, images, ref = small
    res = program.executor(weights, backend=backend, interpret=True).run(
        images)
    assert res.outputs.shape == (2, 10)
    assert rel_err(res.outputs, ref) <= TOL
    assert res.events == network_event_totals(program.workload.layers,
                                              program.arch)


def test_a_bfloat16_product_fails_the_tolerance(small):
    program, weights, images, ref = small
    low = reference.forward(program.workload, weights, images, "bfloat16")
    assert rel_err(low, ref) > 20 * TOL


def test_small_resnet_structure():
    layers = small_resnet().layers
    names = [l.name for l in layers]
    assert names[:5] == ["rn50.stem", "rn50.s1b0.a", "rn50.s1b0.b",
                         "rn50.s1b0.proj", "rn50.s1b0.c"]
    assert sum(n.endswith(".proj") for n in names) == 4
    stem, last = layers[0], layers[-2]
    assert (stem.pool_k, stem.pool_stride, stem.pool_padding) == (3, 2, 1)
    assert last.avg_pool and last.name == "rn50.s4b0.c"


def test_resnet18_cifar_executes_on_both_backends():
    wl = resnet18_cifar()
    program = compile_program(wl)
    weights = random_weights(program, seed=2)
    images = np.random.default_rng(3).standard_normal((2, 32, 32, 3))
    ref = reference.forward(wl, weights, images)
    outs = {b: program.executor(weights, backend=b, interpret=True)
            .run(images).outputs for b in ("numpy", "jax")}
    assert rel_err(outs["jax"], outs["numpy"]) <= TOL
    assert rel_err(outs["numpy"], ref) <= TOL
    # the global average pool over the last 4x4 map is what reaches the FC
    assert wl.layers[-2].avg_pool and wl.layers[-2].h_out == 4


def test_build_counts_the_joins_and_their_shortcut_bytes(small):
    from repro.core import spans

    program, weights, images, _ = small
    ex = program.executor(weights, backend="jax", interpret=True)
    spans.enable()
    try:
        ex.run(images)
    finally:
        rec = spans.collect()
    build, = [s for s in rec["spans"] if s["name"] == "executor.build"]
    joins = [l for l in program.workload.layers
             if isinstance(l, ConvSpec) and l.residual_from]
    shortcut_bytes = sum(4 * len(images) * l.h_out * l.w_out * l.c_out
                         for l in joins)
    assert build["counters"]["joins"] == len(joins) == 4
    assert build["counters"]["residual_bytes_per_call"] == shortcut_bytes
    plan = dict(ex.plan(len(images)))
    assert [n for n, p in plan.items() if p.joins] == [l.name for l in joins]
    assert all(p.residual_bytes == 0 for p in plan.values() if not p.joins)
