"""Work counts, the peak table, and the benchmark's data files."""
import importlib
import json

import pytest

from bench import harness, network, traffic, work

PEAK = work.peaks("TPU v5 lite")


@pytest.mark.parametrize("name, gmac", [("vgg16-imagenet", 15.47),
                                        ("vgg11-cifar", 0.172)])
def test_macs_per_image(name, gmac):
    assert work.macs_per_image(network.load_config(name)) / 1e9 == (
        pytest.approx(gmac, abs=0.005))


@pytest.mark.parametrize("name", ["vgg16-imagenet", "vgg11-cifar"])
def test_configuration_is_the_programs_network(name):
    from repro.core.mapping import ConvSpec
    from repro.sweep.registry import resolve_network

    cfg = network.load_config(name)
    ours = network.layers(cfg)
    theirs = resolve_network(cfg["network"]).layers
    assert len(ours) == len(theirs)
    for a, b in zip(ours, theirs):
        assert (a.c_in, a.c_out, a.macs) == (b.c_in, b.c_out, b.macs)
        if isinstance(b, ConvSpec):
            assert (a.h_in, a.w_in, a.k, a.stride, a.padding) == (
                b.h_in, b.w_in, b.k, b.stride, b.padding)
            assert a.pool == ((b.pool_k, b.pool_stride) if b.pool_k else None)


def test_vgg16_roofline():
    cfg = network.load_config("vgg16-imagenet")
    # batch 1: fc6's 411 MB of float32 weights bound the call
    fc6 = work.network_work(cfg, 1, PEAK)[13]
    assert fc6.bound == "memory"
    assert fc6.bytes == pytest.approx(4 * (25088 * 4096 + 25088 + 4096))
    assert work.roofline_seconds(cfg, 1, PEAK) == pytest.approx(0.81e-3,
                                                                rel=0.02)
    # batch 32: the 28x28 convolutions are compute-bound
    assert work.roofline_seconds(cfg, 32, PEAK) == pytest.approx(6.2e-3,
                                                                 rel=0.02)
    assert work.network_work(cfg, 32, PEAK)[8].bound == "compute"


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v9 imaginary")


def test_peaks_have_their_source():
    table = json.loads((network.BENCH / "peaks.json").read_text())
    for row in table.values():
        assert row["source"] and row["flops_per_s"]["bfloat16"] > 0
        assert row["hbm_bytes_per_s"] > 0


def test_every_entry_of_the_benchmark_is_found_by_name():
    bench = harness.load_benchmark()
    configs = {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        cfg = json.loads((harness.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        importlib.import_module(f"bench.paths.{cfg['path']}")
        assert 0 < cfg["check"]["max_rel_err"] < 1e-4
    for w in bench["workloads"]:
        assert w["config"] in configs
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        traffic.load(w["traffic"])
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert callable(importlib.import_module(
            f"bench.metrics.{harness.quantity(m)}").read)
        # every cell that reports the metric reports what it moves
        moved = e2e[m["moves"]]
        assert all(harness.lists(moved, w) for w in bench["workloads"]
                   if harness.lists(m, w))
    for w in bench["workloads"]:
        mine = [m["name"] for m in bench["end_to_end"] if harness.lists(m, w)]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(harness.lists(m, w) for m in bench["per_layer"])


def test_traffic_is_the_same_work_for_every_seed():
    mix = traffic.load("closed-b32")
    a = traffic.batches(dict(mix, batch=2), (4, 4, 3), 2**33 + 7)
    b = traffic.batches(dict(mix, batch=2), (4, 4, 3), 2**33 + 7)
    c = traffic.batches(dict(mix, batch=2), (4, 4, 3), 5)
    assert len(a) == mix["distinct_batches"]
    assert all((x == y).all() for x, y in zip(a, b))
    assert not (a[0] == c[0]).all()
    assert [x.shape for x in a] == [x.shape for x in c]
