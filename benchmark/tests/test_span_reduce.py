"""The attribution of the device's idle time to the program's spans."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import span_reduce as sr  # noqa: E402

MS = 1_000_000


def _events(lines, ops=((20 * MS, 30 * MS), (60 * MS, 70 * MS)), window=(10 * MS, 100 * MS)):
    return {"devices": {"/device:TPU:0": list(ops)}, "window": [window], "lines": lines}


def test_innermost_gives_each_instant_to_the_deepest_open_span():
    spans = [("tb.phase", 0, 100), ("tb.post", 10, 50), ("tb.device_wait", 20, 30),
             ("tb.dispatch", 60, 70)]
    assert sr.innermost(spans) == [
        ("tb.phase", 0, 10), ("tb.post", 10, 20), ("tb.device_wait", 20, 30),
        ("tb.post", 30, 50), ("tb.phase", 50, 60), ("tb.dispatch", 60, 70),
        ("tb.phase", 70, 100),
    ]


def test_nested_spans_an_uncovered_gap_and_other_threads():
    driving = [
        ("tb.chunk_fill", 0, 15 * MS),                   # 10-15 idle inside the window
        ("tb.feed_wait", 5 * MS, 14 * MS),               # innermost 10-14
        ("tb.phase", 18 * MS, 90 * MS),
        ("tb.dispatch", 18 * MS, 20 * MS),               # 18-20 idle
        ("tb.post", 30 * MS, 60 * MS),
        ("tb.device_wait", 30 * MS, 35 * MS),            # 30-35 idle
        ("tb.assemble", 35 * MS, 55 * MS),               # 35-55 idle
        # post self time 55-60, phase self time 70-90
    ]
    other = [("tb.pack", 0, 100 * MS)]                   # the pack pool: ignored
    a = sr.attribute(_events([other, driving]))
    assert a["window_ns"] == 90 * MS
    # busy 20-30 and 60-70 -> idle 70 ms
    assert a["idle_ns"] == 70 * MS
    by = a["by_span_ns"]
    assert by["tb.feed_wait"] == 4 * MS
    assert by["tb.chunk_fill"] == 1 * MS
    assert by["tb.dispatch"] == 2 * MS
    assert by["tb.device_wait"] == 5 * MS
    assert by["tb.assemble"] == 20 * MS
    assert by["tb.post"] == 5 * MS
    assert by["tb.phase"] == 20 * MS
    assert "tb.pack" not in by
    # 15-18 and 90-100 are under no span
    assert by[sr.UNCOVERED] == 13 * MS
    assert sum(by.values()) == a["idle_ns"]


def test_shares_of_a_record_add_up_to_the_idle_share(monkeypatch):
    driving = [("tb.phase", 10 * MS, 100 * MS), ("tb.dispatch", 10 * MS, 20 * MS),
               ("tb.write_enqueue", 30 * MS, 40 * MS)]
    a = sr.attribute(_events([driving]))
    monkeypatch.setattr(sr, "for_record", lambda record: a)
    from benchmark.metrics import idle_feed_share, idle_host_share, idle_transfer_share

    feed = idle_feed_share.read({})
    transfer = idle_transfer_share.read({})
    host = idle_host_share.read({})
    assert feed == pytest.approx(10 / 90)
    assert transfer == pytest.approx(10 / 90)
    assert host == pytest.approx(50 / 90)
    assert feed + transfer + host == pytest.approx(a["idle_ns"] / a["window_ns"])


def test_no_driving_line_window_or_device_reads_nothing():
    assert sr.attribute(_events([[("tb.pack", 0, 10)]])) is None
    assert sr.attribute({"devices": {}, "window": [(0, 10)],
                         "lines": [[("tb.dispatch", 0, 5)]]}) is None
    assert sr.attribute({"devices": {"/device:TPU:0": [(0, 1)]}, "window": [],
                         "lines": [[("tb.dispatch", 0, 5)]]}) is None
    assert sr.for_record({"trace_dir": None}) is None


def test_counter_readers_leave_out_a_program_without_the_counters():
    from benchmark.metrics import host_suffix_ms_per_kdoc, host_tail_ms_per_kdoc

    record = {"docs": 2000, "counters": {"stage_post_seconds": 3.0}}
    assert host_suffix_ms_per_kdoc.read(record) is None
    assert host_tail_ms_per_kdoc.read(record) is None
    record["counters"].update(stage_host_suffix_seconds=0.5, stage_host_tail_seconds=0.0)
    assert host_suffix_ms_per_kdoc.read(record) == pytest.approx(250.0)
    assert host_tail_ms_per_kdoc.read(record) == 0.0
