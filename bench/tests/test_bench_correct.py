"""The output check, on the CPU at a size a test run holds.

A sound run of a cell is correct. The control, the reference at the next
precision below, is not. Nor is a run with the timed path broken
underneath: an answer altered where it is produced, or half of the batch
left out. Each drives the whole of a run but the look for a chip, with
Pallas in interpret mode and the cell's mix cut to batch 2.
"""
import time

import jax.numpy as jnp
import pytest

from bench import control, harness, traffic

CELL = "vgg11-cifar.closed-b256"
SEED = 2**31 + 12345


@pytest.fixture(scope="module")
def spec():
    return harness.cell_spec(CELL)


@pytest.fixture(scope="module")
def mix(spec):
    return dict(traffic.load(spec["traffic"]), batch=2, distinct_batches=2)


def _run(spec, mix):
    return harness.run(spec, SEED, 0.2, False, time.perf_counter(),
                       interpret=True, mix=mix, say=lambda line: None)


def test_a_sound_run_is_correct(spec, mix):
    result = _run(spec, mix)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 1
    check = result["checks"]["max_rel_err"]
    assert check["value"] <= check["limit"]
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"images_per_s.vgg11-b256",
                                      "call_p95_ms.vgg11-b256", "setup_s"}


def test_the_control_is_not_correct(spec, mix):
    (row,) = control.readings(CELL, [SEED], 0.2, interpret=True, mix=mix)
    assert row["program_correct"] and not row["control_correct"]
    assert row["control"] > 3 * row["program"]


def test_an_answer_altered_where_it_is_produced(spec, mix, monkeypatch):
    import repro.kernels.com_matmul as cm

    sound = cm.com_matmul_padded
    classes = 10

    def altered(x, w, **kw):
        out = sound(x, w, **kw)
        if w.shape[1] == classes:           # the layer that makes logits
            out = out.at[0, 0].add(1e-3)
        return out

    monkeypatch.setattr(cm, "com_matmul_padded", altered)
    result = _run(spec, mix)
    assert not result["correct"] and result["failed"] == result["attempted"]


def test_half_of_the_batch_left_out(spec, mix, monkeypatch):
    import repro.core.executor as ex

    sound = ex.jax_forward

    def half(program, **kw):
        forward = sound(program, **kw)

        def f(x, ws):
            out = forward(x[: x.shape[0] // 2], ws)
            return jnp.concatenate([out, jnp.zeros_like(out)])

        return f

    monkeypatch.setattr(ex, "jax_forward", half)
    result = _run(spec, mix)
    assert not result["correct"] and result["failed"] == result["attempted"]
