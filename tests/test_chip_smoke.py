"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and its
phases pass at small sizes when a test steers its device check.

The script's own sizes (vgg16-imagenet, smollm-135m at published widths)
are for the chip; here each phase runs at a size the CPU handles in
seconds, with the Pallas kernel in interpret mode.
"""
import functools
import json
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from benchmarks.sweep import default_grid
from repro.configs import get_config
from repro.launch import compile_cache
from repro.serve import kvcache
from repro.serve.engine import Engine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_exits_nonzero_before_any_work_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""                 # no phase ran, no result line
    assert "not a TPU" in proc.stderr


def test_main_with_steered_device_check(monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "require_tpu", lambda: jax.devices()[0])
    # keep the persistent cache off: a test run must not write the checkout
    monkeypatch.setattr(compile_cache, "init_compile_cache", lambda: None)
    monkeypatch.setattr(chip_smoke, "phase_executor", functools.partial(
        chip_smoke.phase_executor, network="vgg11-cifar", batch=2,
        n_check=1, interpret=True, repeats=1))
    monkeypatch.setattr(chip_smoke, "phase_serve", functools.partial(
        chip_smoke.phase_serve, cfg=get_config("smollm-135m").reduced(),
        n_requests=2, prompt_len=4, max_new=3, max_seq=16))
    monkeypatch.setattr(chip_smoke, "phase_sweep", functools.partial(
        chip_smoke.phase_sweep, grid=default_grid()))
    assert chip_smoke.main([]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert "executor.events_match: True" in lines
    assert "serve.requests_token_identical: 2" in lines
    last = json.loads(lines[-1])
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind, "count": 1}}


def _swap_slots_0_1(monkeypatch):
    write = kvcache.SlotCache.write_prefill
    monkeypatch.setattr(kvcache.SlotCache, "write_prefill",
                        lambda self, slot, one: write(
                            self, {0: 1, 1: 0}.get(int(slot), slot), one))


def _decode_one_row_back(monkeypatch):
    step = Engine._step_impl

    def shifted(self, params, cache, tok, pos, *args):
        return step(self, params, cache, tok, pos - 1, *args)

    monkeypatch.setattr(Engine, "_step_impl", shifted)


@pytest.mark.parametrize("plant, message", [
    (_swap_slots_0_1, "batched decode logits differ"),
    (_decode_one_row_back, "batched replay does not give generate's tokens"),
], ids=["slot_swap", "decode_row_back"])
def test_serve_phase_fails_on_planted_engine_fault(monkeypatch, plant,
                                                   message):
    kw = dict(cfg=get_config("smollm-135m").reduced(), n_requests=4,
              prompt_len=8, max_new=6, max_seq=32)
    chip_smoke.phase_serve(**kw)                # passes without the fault
    plant(monkeypatch)
    with pytest.raises(chip_smoke.SmokeFailure, match=message):
        chip_smoke.phase_serve(**kw)


def test_failed_check_raises():
    with pytest.raises(chip_smoke.SmokeFailure, match="boom"):
        chip_smoke.check(False, "boom")


def test_four_chip_phase_on_forced_host_devices():
    code = ("import chip_smoke; from benchmarks.sweep import default_grid; "
            "chip_smoke.phase_four_chips(network='vgg11-cifar', batch=6, "
            "interpret=True, grid=default_grid())")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "four.executor.n_shards: 4" in proc.stdout
    assert "four.executor.bitwise_equal_one_device: True" in proc.stdout
    assert "four.sweep.bitwise_equal_jax: True" in proc.stdout
