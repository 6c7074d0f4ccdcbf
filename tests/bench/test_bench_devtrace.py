"""The reduction from profiler events to busy time, idle share and the
breakdown of the result line."""

import json
import os

import pytest

from bench import devtrace

DEV, HOST = "/device:TPU:0", "/host:CPU"
DATA = os.path.join(os.path.dirname(__file__), "data", "trace_sample.json")


def _ev(plane, line, name, a, b):
    return (plane, line, name, float(a), float(b - a))


def test_union_merges_and_clips():
    assert devtrace.union([(5, 20), (0, 10), (40, 50), (90, 120)], 0, 100) \
        == [(0, 20), (40, 50), (90, 100)]


def test_reduce_small_trace():
    events = [
        _ev(DEV, "XLA Ops", "fusion.1", 0, 10),
        _ev(DEV, "XLA Ops", "sort.2", 10, 25),
        _ev(DEV, "XLA Ops", "fusion.1", 40, 50),
        _ev(DEV, "XLA Modules", "jit_program", 0, 50),   # not an op line
        _ev(HOST, "python", "bench.wait", 50, 100),
        _ev(HOST, "python", "bench.submit", 20, 40),
    ]
    out = devtrace.reduce(events, (0.0, 100.0),
                          devtrace.host_labeller(events))
    assert out["busy_s"] == pytest.approx(35e-9)
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["idle_pct"] == pytest.approx(65.0)
    ops = dict(out["breakdown"]["device_ops"])
    assert ops == pytest.approx({"fusion.1": 20e-9, "sort.2": 15e-9})
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert gaps == pytest.approx({"bench.submit": 15e-9,
                                  "bench.wait": 50e-9})


def test_nested_operations_count_once():
    events = [_ev(DEV, "XLA Ops", "%while.3 = (s32[]) while(...)", 0, 50),
              _ev(DEV, "XLA Ops", "%fusion.7 = s32[8] fusion(...)", 10, 30),
              _ev(DEV, "XLA Ops", "%fusion.7 = s32[8] fusion(...)", 35, 45),
              _ev(DEV, "XLA Ops", "%sort.1 = s32[8] sort(...)", 60, 70)]
    out = devtrace.reduce(events, (0.0, 100.0))
    assert out["busy_s"] == pytest.approx(60e-9)
    ops = dict(out["breakdown"]["device_ops"])
    assert ops == pytest.approx({"fusion.7": 30e-9, "while.3": 20e-9,
                                 "sort.1": 10e-9})


def test_innermost_span_labels_the_gap():
    events = [_ev(DEV, "XLA Ops", "op", 0, 10),
              _ev(HOST, "python", "bench.drain", 10, 100)]
    program = [_ev("program", "spans", "demux", 20, 30)]
    label = devtrace.host_labeller(events, program)
    assert label(25.0) == "demux"
    assert label(60.0) == "bench.drain"
    assert label(200.0) == "none"


def test_no_device_operation_reads_nothing():
    events = [_ev(HOST, "python", "bench.wait", 0, 100)]
    assert devtrace.reduce(events, (0.0, 100.0)) is None


def test_recorded_chip_trace():
    """A 200 ms stretch of a traced run on a TPU v5e."""
    with open(DATA) as f:
        rec = json.load(f)
    events = [tuple(e) for e in rec["events"]]
    out = devtrace.reduce(events, tuple(rec["window"]),
                          devtrace.host_labeller(events))
    assert out is not None
    assert 0.0 < out["busy_s"] <= out["window_s"]
    assert 0.0 <= out["idle_pct"] < 100.0
    assert out["breakdown"]["device_ops"]


def test_peaks_table_is_keyed_by_device_kind():
    from bench.peaks import peaks

    v5e = peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("cpu")
