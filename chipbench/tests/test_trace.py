"""The trace reduction on hand-made events and on a recorded v5e trace."""
import os

import pytest

from chipbench import trace

DATA = os.path.join(os.path.dirname(__file__), "data", "tpu_small.xplane.pb")
MS = 1_000_000


def test_busy_idle_gaps_and_spans_from_known_events():
    ops = {"/device:TPU:0": [("%a = f32[] add(...)", 0, 20 * MS),
                             ("%b = f32[] mul(...)", 10 * MS, 30 * MS),
                             ("%a = f32[] add(...)", 60 * MS, 70 * MS),
                             ("%c = dot", 95 * MS, 130 * MS)]}
    spans = [("window", 5 * MS, 105 * MS), ("train_call", 5 * MS, 35 * MS),
             ("save", 35 * MS, 58 * MS), ("train_call", 58 * MS, 105 * MS)]
    r = trace.reduce_events(ops, spans)
    assert r.cut_s == 0.0
    assert r.window_s == pytest.approx(0.100)
    # busy: 5-30, 60-70, 95-105 -> 45 ms of the 100 ms window
    assert r.busy_s == pytest.approx(0.045)
    assert r.idle_share == pytest.approx(0.55)
    assert r.top_ops[0] == ("%a", pytest.approx(0.025))
    # gaps 30-60 (mostly save), 70-95 (train_call)
    assert r.idle_gaps == [("save", pytest.approx(0.030)),
                           ("train_call", pytest.approx(0.025))]
    assert r.span_count == {"train_call": 2, "save": 1}
    assert r.span_busy_s["train_call"] == pytest.approx(0.045)
    assert r.span_busy_s["save"] == 0.0


def test_a_device_trace_that_stops_early_bounds_the_readings():
    """Device events end at 40 ms while the host goes on calling: the
    readings cover the window up to the last device operation."""
    ops = {"/device:TPU:0": [("%a", 0, 10 * MS), ("%a", 20 * MS, 30 * MS),
                             ("%a", 35 * MS, 40 * MS)]}
    spans = [("window", 0, 100 * MS)] + [
        ("simulate_call", i * 20 * MS, (i + 1) * 20 * MS) for i in range(5)]
    r = trace.reduce_events(ops, spans)
    assert r.cut_s == pytest.approx(0.060)
    assert r.window_s == pytest.approx(0.040)
    assert r.busy_s == pytest.approx(0.025)
    assert r.span_count == {"simulate_call": 2}
    assert r.span_busy_s["simulate_call"] == pytest.approx(0.025)
    assert r.idle_gaps == [("simulate_call", pytest.approx(0.010)),
                           ("simulate_call", pytest.approx(0.005))]


def test_no_window_or_no_device_op_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_events({"/device:TPU:0": [("%a", 0, 1)]}, [])
    with pytest.raises(ValueError):
        trace.reduce_events({}, [("window", 0, 10)])


def test_recorded_v5e_trace():
    """Three `train_call` spans of four jitted calls, 50 ms host sleeps
    between them, recorded on one v5e chip (record_trace.py)."""
    ops, spans = trace.read_xplane(DATA, ["train_call"])
    assert list(ops) == ["/device:TPU:0"]
    assert len(ops["/device:TPU:0"]) == 48
    assert [n for n, _, _ in spans].count("train_call") == 3
    r = trace.reduce_events(ops, spans)
    assert r.cut_s == 0.0
    assert 0.15 < r.window_s < 0.25
    assert 0.9 < r.idle_share < 1.0
    assert r.span_count == {"train_call": 3}
    assert {n for n, _ in r.top_ops} >= {"%fusion", "%convolution_tanh_fusion"}
    # the three sleeps are the longest gaps, outside every train_call
    assert [n for n, _ in r.idle_gaps[:2]] == ["outside spans"] * 2
    assert all(0.045 < s < 0.06 for _, s in r.idle_gaps[:2])
