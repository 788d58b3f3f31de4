"""The program's span recorder (repro.core.spans): off by default, nested
spans with their parents, call ids and counters, and compiles counted on
the call that made them."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import monitoring

from repro.core import spans


@pytest.fixture
def recording():
    """Recording on for one test, and off again whatever the test does."""
    spans.enable()
    yield
    spans.collect()


def test_off_records_nothing_and_shares_one_noop():
    assert spans.span("a") is spans.NOOP
    assert spans.span("b", 5) is spans.NOOP
    with spans.span("a") as sp:
        sp.count("n", 3)
        spans.count("n", 4)
    assert sp is spans.NOOP and sp.start_ns is None and sp.end_ns is None
    assert spans.collect() == {"wall_minus_perf_ns": 0, "spans": []}


def test_nested_spans_parents_calls_and_counters(recording):
    with spans.span("root") as root:
        spans.count("images", 2)
        with spans.span("child") as child:
            spans.count("bytes", 10)
            spans.count("bytes", 5)
            with spans.span("grandchild"):
                spans.count("deep")
        root.count("images", 1)
        with spans.span("second child"):
            pass
    with spans.span("next root"):
        with spans.span("its child"):
            pass
    rec = spans.collect()
    got = [(s["name"], s["parent"], s["call"], s["counters"])
           for s in rec["spans"]]
    assert got == [
        ("root", None, 0, {"images": 3}),
        ("child", 0, 0, {"bytes": 15}),
        ("grandchild", 1, 0, {"deep": 1}),
        ("second child", 0, 0, {}),
        ("next root", None, 1, {}),
        ("its child", 4, 1, {}),
    ]
    for s in rec["spans"]:
        assert s["start_ns"] <= s["end_ns"]
        if s["parent"] is not None:
            p = rec["spans"][s["parent"]]
            assert p["start_ns"] <= s["start_ns"] <= s["end_ns"] <= p["end_ns"]
    assert child.end_ns == rec["spans"][1]["end_ns"]


def test_stamps_convert_to_the_wall_clock_with_one_addition(recording):
    with spans.span("now"):
        wall = time.time_ns()
    rec = spans.collect()
    s = rec["spans"][0]
    at = s["start_ns"] + rec["wall_minus_perf_ns"]
    assert abs(at - wall) < 50_000_000


def test_a_given_start_and_open_spans(recording):
    t0 = time.perf_counter_ns() - 1_000
    with spans.span("given", t0) as sp:
        pass
    assert sp.start_ns == t0 and sp.end_ns >= t0
    left_open = spans.span("open")
    left_open.__enter__()
    rec = spans.collect()
    assert rec["spans"][1]["end_ns"] is None
    assert spans.span("x") is spans.NOOP


def test_counts_outside_any_span_are_dropped(recording):
    spans.count("lost", 1)
    with spans.span("a"):
        pass
    assert spans.collect()["spans"][0]["counters"] == {}


def test_compiles_count_on_the_call_and_the_listener_goes():
    before = len(monitoring.get_event_duration_listeners())
    spans.enable()
    assert len(monitoring.get_event_duration_listeners()) == before + 1
    scale = 3.25                      # a constant no other test compiles
    f = jax.jit(lambda x: x * scale)
    with spans.span("call"):
        with spans.span("dispatch"):
            f(jnp.ones(7)).block_until_ready()
    with spans.span("repeat"):
        f(jnp.ones(7)).block_until_ready()
    rec = spans.collect()
    assert len(monitoring.get_event_duration_listeners()) == before
    call, dispatch, repeat = rec["spans"]
    assert call["counters"]["compiles"] >= 1
    assert "compiles" not in dispatch["counters"]
    assert "compiles" not in repeat["counters"]
    np.testing.assert_array_equal(np.asarray(f(jnp.ones(7))), 3.25)


def test_enable_twice_starts_afresh():
    spans.enable()
    with spans.span("dropped"):
        pass
    spans.enable()
    with spans.span("kept"):
        pass
    assert [s["name"] for s in spans.collect()["spans"]] == ["kept"]
