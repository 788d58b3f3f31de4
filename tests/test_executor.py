"""Whole-program batched executor (repro.core.executor).

Covers the ISSUE-5 acceptance surface: (a) image→logits VGG-11 equals the
composed reference pipeline (reference_conv + max-pool + flatten +
reference_fc) on BOTH backends; (b) numpy-vs-jax agreement on randomized
multi-block programs (C > n_c and M > n_m forced); (c) batched (B>1)
equals stacked B=1 runs; (d) a program run's per-image event totals equal
the ``network_event_totals`` closed forms — including the fused-pooling
``pool_cmp`` events the executor chains through functionally.
"""
import numpy as np
import pytest

from repro.core import cache_stats, compile_program
from repro.core.executor import (
    ProgramExecutor,
    random_weights,
)
from repro.core.mapping import ConvSpec, FCSpec, resnet18_cifar, vgg11_cifar
from repro.core.program import Workload
from repro.core.simulator import (
    COMGridSim,
    DominoModel,
    EVENT_FIELDS,
    network_event_totals,
    pool_np,
    reference_conv,
    reference_fc,
)


def reference_forward(layers, weights, images):
    """The composed reference pipeline: per-image reference_conv / max-pool
    / flatten / reference_fc — independent of the executor's block walk."""
    x = np.asarray(images, dtype=np.float64)
    for l in layers:
        if isinstance(l, ConvSpec):
            y = np.stack([reference_conv(xi, weights[l.name], l) for xi in x])
            if l.pool_k > 0:
                y = pool_np(l, y)
            x = y
        else:
            if x.ndim > 2:
                x = x.reshape(len(x), -1)
            x = np.stack([reference_fc(xi, weights[l.name]) for xi in x])
    return x


def _small_multiblock_workload():
    """conv(pool)→conv→flatten→FC→FC with C > n_c and M > n_m at the
    reduced 8x8 arch geometry — every block-chain shape in one chain."""
    layers = (
        ConvSpec("c0", 3, 3, 12, 8, 8, pool_k=2),     # -> (4, 4, 12)
        ConvSpec("c1", 3, 12, 10, 4, 4),              # -> (4, 4, 10)
        FCSpec("f0", 160, 20),
        FCSpec("f1", 20, 5),
    )
    return Workload("mb-exec", layers)


SMALL_ARCH_KW = dict(n_c=8, n_m=8)


@pytest.fixture(scope="module")
def vgg11_setup():
    wl = vgg11_cifar()
    program = compile_program(wl)
    weights = random_weights(program, seed=1)
    rng = np.random.default_rng(0)
    images = rng.normal(size=(2, 32, 32, 3))
    ref = reference_forward(wl.layers, weights, images)
    return wl, program, weights, images, ref


def test_vgg11_numpy_matches_composed_reference(vgg11_setup):
    wl, program, weights, images, ref = vgg11_setup
    res = program.execute(images, weights, backend="numpy")
    assert res.outputs.shape == (2, 10)
    np.testing.assert_allclose(res.outputs, ref, rtol=1e-9, atol=1e-12)


def test_vgg11_jax_kernel_matches_composed_reference(vgg11_setup):
    wl, program, weights, images, ref = vgg11_setup
    # interpret=True: the real Pallas com_matmul path on CPU CI
    res = program.execute(images, weights, backend="jax", interpret=True)
    scale = np.abs(ref).max()
    np.testing.assert_allclose(res.outputs, ref, atol=2e-5 * scale)
    assert {f: res.events[f] for f in EVENT_FIELDS} == dict(
        network_event_totals(wl.layers, program.arch))


def test_vgg11_program_run_events_equal_network_totals(vgg11_setup):
    wl, program, weights, images, _ = vgg11_setup
    res = program.execute(images, weights)
    totals = network_event_totals(wl.layers, program.arch)
    assert {f: res.events[f] for f in EVENT_FIELDS} == dict(totals)
    # pool_cmp is genuinely exercised: VGG-11 fuses five pooling stages
    assert res.events["pool_cmp"] > 0
    # and the program's own closed-form totals agree
    assert dict(program.event_totals) == {
        f: res.events[f] for f in EVENT_FIELDS}


def test_batched_equals_stacked_single_image_runs(vgg11_setup):
    wl, program, weights, images, _ = vgg11_setup
    ex = program.executor(weights)
    batched = ex.run(images).outputs
    stacked = np.concatenate([ex.run(images[i]).outputs
                              for i in range(len(images))])
    np.testing.assert_allclose(batched, stacked, rtol=0, atol=1e-12)


def test_randomized_multiblock_numpy_vs_jax_agree():
    from repro.core.arch import DEFAULT_ARCH

    rng = np.random.default_rng(42)
    wl = _small_multiblock_workload()
    arch = DEFAULT_ARCH.replace(**SMALL_ARCH_KW)
    program = compile_program(wl, arch)
    # the reduced geometry forces real multi-block chains
    lps = program.layer_programs
    assert any(lp.c_blocks > 1 for lp in lps)
    assert any(lp.m_blocks > 1 for lp in lps)
    for trial in range(3):
        weights = random_weights(program, seed=100 + trial)
        images = rng.normal(size=(3, 8, 8, 3))
        ref = reference_forward(wl.layers, weights, images)
        rn = program.execute(images, weights, backend="numpy")
        rj = program.execute(images, weights, backend="jax", interpret=True)
        np.testing.assert_allclose(rn.outputs, ref, rtol=1e-9, atol=1e-12)
        scale = max(np.abs(ref).max(), 1e-30)
        np.testing.assert_allclose(rj.outputs, rn.outputs,
                                   atol=2e-5 * scale)
        assert {f: rn.events[f] for f in EVENT_FIELDS} == dict(
            network_event_totals(wl.layers, arch))


def test_executor_matches_comgridsim_per_layer():
    # the shared block-semantics helpers ARE COMGridSim's execution path:
    # a single-conv program through the executor equals the cycle sim
    from repro.core.arch import DEFAULT_ARCH

    rng = np.random.default_rng(9)
    layer = ConvSpec("solo", 3, 12, 10, 6, 6)
    arch = DEFAULT_ARCH.replace(**SMALL_ARCH_KW)
    program = compile_program(Workload("solo", (layer,)), arch)
    w = rng.normal(size=(3, 3, 12, 10))
    x = rng.normal(size=(6, 6, 12))
    sim = COMGridSim.from_program(program, "solo", w)
    got = program.execute(x[None], {"solo": w}).outputs
    np.testing.assert_allclose(got[0], sim.run(x), rtol=0, atol=0)


def test_fc_only_program_and_single_image_convenience():
    wl = Workload("fcs", (FCSpec("a", 12, 7), FCSpec("b", 7, 3)))
    program = compile_program(wl)
    weights = random_weights(program, seed=3)
    x = np.random.default_rng(1).normal(size=(12,))
    res = program.execute(x, weights)      # unbatched convenience input
    assert res.outputs.shape == (1, 3)
    ref = reference_fc(reference_fc(x, weights["a"]), weights["b"])
    np.testing.assert_allclose(res.outputs[0], ref, rtol=1e-12)


def test_domino_model_functional_forward_cross_check(vgg11_setup):
    wl, program, weights, images, ref = vgg11_setup
    model = DominoModel(program)
    res = model.functional_forward(images, weights)
    np.testing.assert_allclose(res.outputs, ref, rtol=1e-9, atol=1e-12)
    assert {f: res.events[f] for f in EVENT_FIELDS} == dict(
        model.program.event_totals)


def test_executor_validates_weights_and_inputs(vgg11_setup):
    wl, program, weights, images, _ = vgg11_setup
    bad = dict(weights)
    del bad[wl[0].name]
    with pytest.raises(KeyError, match="missing"):
        program.executor(bad)
    bad = dict(weights)
    bad[wl[0].name] = np.zeros((3, 3, 3, 7))
    with pytest.raises(ValueError, match="weights shape"):
        program.executor(bad)
    ex = program.executor(weights)
    with pytest.raises(ValueError, match="images shape"):
        ex.run(np.zeros((2, 16, 16, 3)))
    with pytest.raises(ValueError, match="unknown executor backend"):
        program.executor(weights, backend="torch")
    with pytest.raises(ValueError, match="weight arrays for"):
        program.executor([weights[wl[0].name]])


def test_non_chaining_workload_rejected():
    wl = Workload("broken", (
        ConvSpec("c0", 3, 3, 8, 8, 8),
        ConvSpec("c1", 3, 9, 8, 8, 8),   # c_in 9 != produced 8 channels
    ))
    program = compile_program(wl)
    with pytest.raises(ValueError, match="not an executable"):
        program.executor(random_weights(wl))


def test_residual_workloads_are_rejected_for_now():
    """A join runs only where its shortcut exists and has the shape of the
    join's output: one naming no earlier layer, or a tensor of another
    shape, is rejected; resnet18-cifar's joins are sound."""
    layers = list(resnet18_cifar().layers)
    program = compile_program(resnet18_cifar())
    program.executor(random_weights(program))
    i = next(i for i, l in enumerate(layers)
             if l.residual_from and l.c_out == 128)   # a 16x16x128 join
    for bad in ("rn.nowhere", layers[0].name):   # unknown; 32x32x64
        wl = Workload("broken-join", layers[:i] + [
            ConvSpec(**{**layers[i].__dict__, "residual_from": bad})]
            + layers[i + 1:])
        with pytest.raises(ValueError, match="joins the output"):
            compile_program(wl).executor(random_weights(wl))


def test_cache_stats_reports_bounded_caches():
    compile_program(vgg11_cifar())           # ensure at least one entry
    stats = cache_stats()
    for name in ("compile_program", "layer_schedules", "layer_table",
                 "network_event_totals"):
        info = stats[name]
        assert info.maxsize is not None      # every cache is bounded
        assert info.currsize <= info.maxsize
    assert stats["compile_program"].currsize >= 1


JAX_SPANS = ["executor.run", "executor.batch", "executor.upload",
             "executor.dispatch", "executor.fetch"]


def test_run_emits_its_span_tree_each_call(vgg11_setup):
    from repro.core import spans

    wl, program, weights, images, _ = vgg11_setup
    ex = program.executor(weights, backend="jax", interpret=True)
    spans.enable()
    try:
        first = ex.run(images)
        again = ex.run(images)
    finally:
        rec = spans.collect()
    calls = [[s for s in rec["spans"] if s["call"] == c] for c in (0, 1)]
    assert [s["name"] for s in calls[0]] == (
        JAX_SPANS[:2] + ["executor.build"] + JAX_SPANS[2:])
    assert [s["name"] for s in calls[1]] == JAX_SPANS
    for call, res in zip(calls, (first, again)):
        root, children = call[0], call[1:]
        assert root["parent"] is None
        assert all(rec["spans"][s["parent"]] is root for s in children)
        assert root["counters"]["images"] == 2
        # wall_s is read off the root span's own two clock reads
        assert res.wall_s == (root["end_ns"] - root["start_ns"]) * 1e-9
        by = {s["name"]: s["counters"] for s in children}
        assert by["executor.batch"] == {"bytes_host": images.size * 8}
        assert by["executor.upload"] == {"bytes_up": images.size * 4}
        assert by["executor.fetch"] == {"bytes_down": 2 * 10 * 4}
    # the first call of a new shape compiles, the repeat does not
    assert calls[0][0]["counters"]["compiles"] >= 1
    assert "compiles" not in calls[1][0]["counters"]
    build = calls[0][2]["counters"]
    plan = [p for _, p in ex.plan(len(images))]
    assert build == {"bytes_weights": 4 * sum(w.size
                                              for w in weights.values()),
                     "grid_steps": sum(p.steps for p in plan),
                     "pad_bytes_per_call": sum(p.pad_bytes for p in plan),
                     "residual_bytes_per_call": 0, "joins": 0}
    np.testing.assert_array_equal(first.outputs, again.outputs)


def test_numpy_backend_spans_and_untraced_wall_time():
    from repro.core import spans

    wl = _small_multiblock_workload()
    program = compile_program(wl)
    weights = random_weights(program, seed=4)
    images = np.random.default_rng(5).normal(size=(2, 8, 8, 3))
    ex = program.executor(weights)
    untraced = ex.run(images)
    assert untraced.wall_s > 0
    spans.enable()
    try:
        traced = ex.run(images)
    finally:
        rec = spans.collect()
    assert [s["name"] for s in rec["spans"]] == [
        "executor.run", "executor.batch", "executor.dispatch"]
    np.testing.assert_array_equal(traced.outputs, untraced.outputs)


def test_every_layer_is_scoped_in_the_compiled_chain(vgg11_setup):
    import re

    wl, program, weights, images, _ = vgg11_setup
    ex = program.executor(weights, backend="jax", interpret=True)
    text = ex.lower(images).compile().as_text()
    scopes = set(re.findall(r'op_name="jit\(forward\)/([^"]+)"', text))
    for l in wl.layers:
        steps = {s.split("/")[1] for s in scopes
                 if s.split("/")[0] == l.name and "/" in s}
        want = {"matmul"}
        if isinstance(l, ConvSpec):
            want |= {"im2col"} | ({"pool"} if l.pool_k else set())
        assert want <= steps, (l.name, steps)
    # nothing of the chain outside a layer's scope
    assert not [s for s in scopes
                if s.split("/")[0] not in {l.name for l in wl.layers}]
    # the chosen blocks divide every matmul: nothing is padded
    assert not any("/matmul/pad/" in s or "/matmul/unpad/" in s
                   for s in scopes)
    # blocks given that do not divide: the block padding and the slice
    # back, under the matmul
    padded = program.executor(weights, backend="jax", interpret=True,
                              block_m=128, block_n=128, block_k=128)
    scopes = set(re.findall(r'op_name="jit\(forward\)/([^"]+)"',
                            padded.lower(images).compile().as_text()))
    assert any("/matmul/pad/" in s for s in scopes)
    assert any("/matmul/unpad/" in s for s in scopes)


def test_scopes_leave_the_logits_bitwise_unchanged(monkeypatch):
    import contextlib

    import jax

    from repro.core.executor import jax_forward

    wl = _small_multiblock_workload()
    program = compile_program(wl)
    weights = random_weights(program, seed=6)
    ws = [jax.numpy.asarray(weights[l.name], dtype=np.float32)
          for l in wl.layers]
    x = jax.numpy.asarray(np.random.default_rng(7).normal(size=(3, 8, 8, 3)),
                          dtype=np.float32)
    scoped = np.asarray(jax.jit(jax_forward(program, interpret=True))(x, ws))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = np.asarray(jax.jit(jax_forward(program, interpret=True))(x, ws))
    np.testing.assert_array_equal(scoped, bare)


def _chain_kernels(program, images, **blocks):
    """``(kernel name, grid, x block shape)`` of each Pallas call in the
    jax backend's chain for ``images``, read off its jaxpr."""
    import jax

    from repro.core.executor import jax_forward

    layers = program.workload.layers
    ws = [jax.ShapeDtypeStruct(
        (l.k, l.k, l.c_in, l.c_out) if isinstance(l, ConvSpec)
        else (l.c_in, l.c_out), np.float32) for l in layers]
    x = jax.ShapeDtypeStruct(images.shape, np.float32)
    eqns = jax.make_jaxpr(jax_forward(program, interpret=True, **blocks))(
        x, ws).jaxpr.eqns
    return [(e.params["name"], tuple(e.params["grid_mapping"].grid),
             tuple(b.block_size for b in
                   e.params["grid_mapping"].block_mappings[0].block_shape))
            for e in eqns if e.primitive.name == "pallas_call"]


@pytest.mark.parametrize("blocks", [{}, {"block_m": 8},
                                    {"block_m": 128, "block_n": 128,
                                     "block_k": 128}],
                         ids=["chosen", "block_m", "given"])
def test_plan_is_what_the_chain_runs(vgg11_setup, blocks):
    wl, program, weights, images, _ = vgg11_setup
    ex = program.executor(weights, backend="jax", interpret=True, **blocks)
    plan = ex.plan(len(images))
    assert [name for name, _ in plan] == [l.name for l in wl.layers]
    want = []
    for name, p in plan:
        grid = (-(-p.m // p.bm), -(-p.n // p.bn), -(-p.k // p.bk))
        assert grid[0] * grid[1] * grid[2] == p.steps
        want.append((f"com_matmul_{name}", grid, (p.bm, p.bk)))
    assert _chain_kernels(program, images, **blocks) == want


def test_build_counts_the_plans_grid_steps_and_pad_bytes():
    from repro.core import spans

    program = compile_program(_small_multiblock_workload())
    weights = random_weights(program, seed=8)
    images = np.random.default_rng(9).normal(size=(3, 8, 8, 3))
    builds = {}
    for kind, blocks in (("chosen", {}),
                         ("given", dict(block_m=16, block_n=128,
                                        block_k=128))):
        ex = program.executor(weights, backend="jax", interpret=True,
                              **blocks)
        spans.enable()
        try:
            out = ex.run(images).outputs
        finally:
            rec = spans.collect()
        plan = [p for _, p in ex.plan(len(images))]
        build, = [s for s in rec["spans"] if s["name"] == "executor.build"]
        assert build["counters"]["grid_steps"] == sum(p.steps for p in plan)
        builds[kind] = (build["counters"], plan, out)
    counters, plan, chosen_out = builds["chosen"]
    # every shape of this chain divides into its chosen blocks
    assert counters["pad_bytes_per_call"] == 0
    assert all(p.pad_bytes == 0 for p in plan)
    # 16-row, 128-lane blocks pad all four matmuls of 3 images
    counters, plan, given_out = builds["given"]
    assert counters["pad_bytes_per_call"] == sum(p.pad_bytes for p in plan)
    assert all(p.pad_bytes > 0 for p in plan)
    np.testing.assert_allclose(given_out, chosen_out, rtol=1e-5, atol=1e-6)


def test_plan_gives_each_device_its_share_of_the_batch(monkeypatch):
    from repro.core.executor import matmul_plans
    from repro.kernels.com_matmul import matmul_plan

    program = compile_program(_small_multiblock_workload())
    ex = program.executor(random_weights(program, seed=1), backend="jax",
                          interpret=True)
    assert ex.plan(8) == matmul_plans(program, 8)
    monkeypatch.setattr(ProgramExecutor, "n_shards", property(lambda s: 4))
    for batch, rows in ((8, 2), (9, 3)):    # 9 is padded to 12 images
        whole = matmul_plans(program, batch)
        # each device runs its rows on the whole batch's blocks
        assert ex.plan(batch) == [
            (name, matmul_plan(p.m // batch * rows, p.k, p.n, 4,
                               block_m=p.bm, block_n=p.bn, block_k=p.bk))
            for name, p in whole]
