"""The reduction from a profiler trace to busy time, idle gaps and
operation time."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import trace_reduce as tr  # noqa: E402

MS = 1_000_000


def test_union_merges_overlaps():
    assert tr.union([(5, 9), (0, 2), (1, 3), (9, 10)]) == [(0, 3), (5, 10)]


def test_reduce_clips_to_the_window_and_names_gaps():
    events = {
        "devices": {"/device:TPU:0": [
            ("fusion.1", 0 * MS, 20 * MS),       # starts before the window
            ("sort.2", 30 * MS, 40 * MS),
            ("fusion.1", 40 * MS, 45 * MS),      # right after sort.2
            ("fusion.3", 90 * MS, 120 * MS),     # ends after the window
        ]},
        "host": [
            ("bench_window", 10 * MS, 100 * MS),
            ("thread loop", 0, 200 * MS),
            ("pack", 50 * MS, 85 * MS),
        ],
    }
    r = tr.reduce(events)
    assert r["window_s"] == pytest.approx(0.090)
    # busy: 10-20, 30-45, 90-100 -> 35 ms
    assert r["busy_s"] == pytest.approx(0.035)
    assert r["op_s"]["fusion.1"] == pytest.approx(0.015)
    assert r["op_s"]["sort.2"] == pytest.approx(0.010)
    gaps = r["idle_gaps"]
    assert gaps[0] == ["pack", pytest.approx(0.045)]  # 45-90
    assert [g[1] for g in gaps[1:]] == [pytest.approx(0.010)]  # 20-30
    assert gaps[1][0] == "thread loop"
    b = tr.breakdown(r)
    assert b["device_ops"][0][0] == "fusion.1"


def test_no_window_or_no_device_reads_nothing():
    assert tr.reduce({"devices": {"/device:TPU:0": [("x", 0, 1)]}, "host": []}) is None
    assert tr.reduce({"devices": {}, "host": [("bench_window", 0, 1)]}) is None


def test_self_times_subtract_nested_operations():
    ops = [("while.1", 0, 100), ("fusion.2", 10, 30), ("fusion.3", 40, 60), ("copy", 120, 130)]
    assert tr.self_times(ops) == {"while.1": 60, "fusion.2": 20, "fusion.3": 20, "copy": 10}


def test_recorded_chip_trace():
    """A slice of a trace recorded on one TPU v5e (the first 0.3 s of a
    ``danish_cc.short`` window), as ``extract`` returns it."""
    import gzip
    import json

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "tpu_v5e_trace_slice.json.gz")
    with gzip.open(path, "rt", encoding="utf-8") as f:
        events = json.load(f)
    r = tr.reduce(events)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.3)
    assert r["busy_s"] == pytest.approx(0.134959604)
    assert sum(r["op_s"].values()) == pytest.approx(r["busy_s"], rel=1e-9)
    b = tr.breakdown(r)
    assert b["device_ops"][0] == ["fusion s32[32768]", pytest.approx(0.075364703)]
    assert any(name.startswith("pallas (s32[64,512]") for name, _ in b["device_ops"]) or any(
        "tpu_custom_call" in op for op in r["op_s"])
    assert b["idle_gaps"][0] == ["PjitFunction(jit(fn))", pytest.approx(0.046591558)]
    from benchmark.metrics import kernel_ms_per_kdoc

    assert kernel_ms_per_kdoc.read({"trace": r, "docs": 1000}) == pytest.approx(8.119682, rel=1e-6)
