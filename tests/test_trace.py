"""Span tracer correctness (utils/trace.py) + the ``--trace`` /
``--run-report`` CLI surface.

Four properties, matching the observability acceptance bar:

* Spans recorded on one thread lane nest properly (a ``with`` block cannot
  partially overlap another on the same thread) and carry sane ts/dur.
* The span-name multiset is identical between the serial and overlapped
  host pipelines over the same input — overlap moves *when* stages run,
  never *what* runs.
* A chaos run (injected device faults) surfaces the resilience
  transitions as instant events: policy retries and ladder rungs.
* An end-to-end CLI run with ``--trace`` produces valid Chrome trace-event
  JSON containing all six stage spans plus at least one device-dispatch
  span, and the ``--run-report`` funnel sums exactly to the
  excluded-Parquet row count.
* Inside a ``jax.profiler`` session the same spans land in the profile's
  ``.xplane.pb`` as ``tb.<name>`` events on the emitting thread's line,
  with their args; with neither sink on, a span is the shared no-op.
"""

import json
import os
from collections import Counter

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from textblaster_tpu.cli import main
from textblaster_tpu.config.pipeline import parse_pipeline_config
from textblaster_tpu.data_model import TextDocument
from textblaster_tpu.ops.pipeline import process_documents_device
from textblaster_tpu.resilience import FAULTS
from textblaster_tpu.utils.metrics import RUN_REPORT_SCHEMA
from textblaster_tpu.utils.trace import _NULL_SPAN, TRACER

CONFIG_YAML = """
pipeline:
  - type: GopherQualityFilter
    min_doc_words: 5
resilience:
  backoff_base_s: 0.0
  backoff_max_s: 0.0
"""

GOOD = (
    "This is a sentence with a number of words that is long enough to pass "
    "the filter easily today."
)
BAD = "too short"

#: The six host-pipeline stage span names.
STAGE_SPANS = ("read", "pack", "dispatch", "device_wait", "post", "write")

#: Spans of a consumer waiting on an overlap thread's queue or future: they
#: exist only where there is such a thread, and only when it is behind.
WAIT_SPANS = ("feed_wait", "pack_wait")


@pytest.fixture(autouse=True)
def _tracer_hygiene():
    # TRACER is process-global: a test leaving it enabled (or events in the
    # ring) would contaminate every later test in the session.
    TRACER.close()
    TRACER.drain()
    yield
    TRACER.close()
    TRACER.drain()


def _docs(n=30):
    return [
        TextDocument(id=f"doc-{i}", content=GOOD if i % 3 else BAD, source="t")
        for i in range(n)
    ]


def _traced_device_run(config, docs, **kw):
    TRACER.configure(None)  # in-memory ring
    list(process_documents_device(config, iter(docs), **kw))
    TRACER.close()
    return TRACER.drain()


def test_spans_nest_within_each_lane():
    config = parse_pipeline_config(CONFIG_YAML)
    events = _traced_device_run(config, _docs(30), device_batch=16)
    spans = [e for e in events if e.get("ph") == "X"]
    assert spans, "traced run produced no spans"
    by_tid = {}
    for e in spans:
        assert e["dur"] >= 0 and e["ts"] >= 0
        by_tid.setdefault(e["tid"], []).append(e)
    for lane in by_tid.values():
        # Within a lane, sorted by start (longer span first on ties), every
        # span must either nest inside the enclosing open span or start
        # after it ends — partial overlap means broken emission.
        lane.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack = []
        for e in lane:
            while stack and e["ts"] >= stack[-1]["ts"] + stack[-1]["dur"]:
                stack.pop()
            if stack:
                enclosing = stack[-1]
                assert (
                    e["ts"] + e["dur"] <= enclosing["ts"] + enclosing["dur"]
                ), f"span {e['name']} partially overlaps {enclosing['name']}"
            stack.append(e)


def test_serial_and_overlapped_runs_emit_same_span_multiset(
    tmp_path, monkeypatch
):
    from textblaster_tpu.parallel.runner import run_pipeline

    docs = _docs(60)
    inp = tmp_path / "in.parquet"
    pq.write_table(
        pa.table(
            {
                "id": [d.id for d in docs],
                "text": [d.content for d in docs],
                "source": [d.source for d in docs],
            }
        ),
        str(inp),
    )
    config = parse_pipeline_config(CONFIG_YAML)

    def _run(tag, no_overlap):
        if no_overlap:
            monkeypatch.setenv("TEXTBLAST_NO_OVERLAP", "1")
        else:
            monkeypatch.delenv("TEXTBLAST_NO_OVERLAP", raising=False)
        TRACER.configure(None)
        run_pipeline(
            config,
            str(inp),
            str(tmp_path / f"out-{tag}.parquet"),
            str(tmp_path / f"exc-{tag}.parquet"),
            backend="tpu",
            device_batch=16,
            quiet=True,
        )
        TRACER.close()
        return Counter(
            e["name"]
            for e in TRACER.drain()
            if e.get("ph") == "X" and e["name"] not in WAIT_SPANS
        )

    serial = _run("serial", no_overlap=True)
    overlapped = _run("overlap", no_overlap=False)
    assert serial == overlapped
    for name in STAGE_SPANS + ("chunk_fill", "phase", "assemble", "write_enqueue"):
        assert serial[name] > 0, f"stage span {name} missing"


def test_chaos_run_emits_resilience_instants():
    config = parse_pipeline_config(CONFIG_YAML)
    # Transient blip: recovered by a policy retry -> a "retry" instant.
    FAULTS.inject("device.execute", OSError("device blip"), times=2)
    events = _traced_device_run(config, _docs(10), device_batch=16)
    instants = Counter(e["name"] for e in events if e.get("ph") == "i")
    assert instants["retry"] >= 1
    FAULTS.reset()

    # Budget exhaustion: the ladder splits the batch -> a "ladder_split"
    # instant (times=5 = dispatch + the 1+3 policy attempts, per
    # tests/test_fault_injection.py accounting).
    FAULTS.inject("device.execute", OSError("persistent-ish"), times=5)
    events = _traced_device_run(config, _docs(10), device_batch=16)
    instants = Counter(e["name"] for e in events if e.get("ph") == "i")
    assert instants["ladder_split"] >= 1


def test_cli_trace_and_run_report_end_to_end(tmp_path, capsys):
    docs = _docs(120)
    inp = tmp_path / "in.parquet"
    pq.write_table(
        pa.table(
            {
                "id": [d.id for d in docs],
                "text": [d.content for d in docs],
            }
        ),
        str(inp),
    )
    cfg = tmp_path / "cfg.yaml"
    cfg.write_text(CONFIG_YAML, encoding="utf-8")
    out = tmp_path / "out.parquet"
    exc = tmp_path / "exc.parquet"
    trace_path = tmp_path / "trace.json"
    report_path = tmp_path / "report.json"

    rc = main(
        [
            "run",
            "-i", str(inp),
            "-c", str(cfg),
            "-o", str(out),
            "-e", str(exc),
            "--backend", "tpu",
            "--buckets", "512,2048",
            "--quiet",
            "--trace", str(trace_path),
            "--run-report", str(report_path),
        ]
    )
    assert rc == 0
    capsys.readouterr()

    # The trace is well-formed Chrome trace-event JSON (array flavor) with
    # every stage span and at least one device dispatch.
    events = json.loads(trace_path.read_text(encoding="utf-8"))
    assert isinstance(events, list) and events
    names = Counter(e["name"] for e in events if e.get("ph") == "X")
    for stage in STAGE_SPANS:
        assert names[stage] > 0, f"stage span {stage} missing from trace"
    assert names["device_dispatch"] >= 1
    assert any(
        e.get("ph") == "M" and e["name"] == "process_name" for e in events
    )

    # The run report's funnel sums exactly to the excluded row count.
    report = json.loads(report_path.read_text(encoding="utf-8"))
    assert report["schema"] == RUN_REPORT_SCHEMA
    excluded_rows = pq.read_table(str(exc)).num_rows
    assert report["funnel"]["dropped_total"] == excluded_rows
    assert (
        sum(report["funnel"]["per_filter_dropped"].values()) == excluded_rows
    )
    assert report["funnel"]["per_filter_dropped"] == {
        "GopherQualityFilter": excluded_rows
    }
    assert report["counts"]["filtered"] == excluded_rows
    assert report["counts"]["success"] == pq.read_table(str(out)).num_rows
    assert report["stages"]["verdict"] in (
        "host-bound", "device-bound", "balanced"
    )
    assert report["occupancy"]["device_batches"] >= 1
    assert report["config"]["backend"] == "tpu"
    assert os.path.getsize(trace_path) > 0


# --- cross-host clock alignment ----------------------------------------------


def test_align_shifts_subsequent_events_and_records_offset():
    TRACER.configure(None)
    TRACER.instant("before_handshake")
    TRACER.align(2_000_000, args={"origin_wall_us": 123, "backend": "test"})
    TRACER.instant("after_handshake")
    with TRACER.span("aligned_span"):
        pass
    TRACER.close()
    events = TRACER.drain()
    by_name = {e["name"]: e for e in events}
    # The metadata event documents the offset and the handshake's inputs.
    meta = by_name["trace_clock_offset"]
    assert meta["ph"] == "M"
    assert meta["args"]["offset_us"] == 2_000_000
    assert meta["args"]["origin_wall_us"] == 123
    assert meta["args"]["backend"] == "test"
    # Pre-handshake events keep near-zero ts; post-handshake events sit a
    # full offset later — several hosts' traces interleave on one timeline.
    assert by_name["before_handshake"]["ts"] < 1_000_000
    assert by_name["after_handshake"]["ts"] >= 2_000_000
    assert by_name["aligned_span"]["ts"] >= 2_000_000


def test_align_is_noop_when_disabled():
    assert not TRACER.enabled
    TRACER.align(5_000_000)  # must not raise or queue anything
    assert TRACER.drain() == []
    TRACER.configure(None)
    TRACER.instant("tick")
    TRACER.close()
    (e,) = [x for x in TRACER.drain() if x["name"] == "tick"]
    assert e["ts"] < 1_000_000  # the disabled-time align left no offset


def test_wall_at_origin_is_recent_wall_clock():
    import time as _time

    TRACER.configure(None)
    w = TRACER.wall_at_origin_us()
    now_us = int(_time.time() * 1e6)
    # The origin was "when configure() ran": in the past, within seconds.
    assert 0 <= now_us - w < 5_000_000
    TRACER.close()
    TRACER.drain()


def test_single_process_alignment_handshake_offsets_zero():
    # The multihost startup handshake on a 1-process gang: the only host's
    # origin IS the minimum, so its offset must be exactly zero.
    from textblaster_tpu.parallel.multihost import _align_trace_clocks

    TRACER.configure(None)
    _align_trace_clocks()
    TRACER.close()
    events = TRACER.drain()
    meta = [e for e in events if e["name"] == "trace_clock_offset"]
    assert len(meta) == 1
    assert meta[0]["args"]["offset_us"] == 0
    assert "origin_wall_us" in meta[0]["args"]
    assert meta[0]["args"]["host_walls_us"] == [meta[0]["args"]["origin_wall_us"]]


# --- program spans in the profiler's trace -----------------------------------


def _profiled_run(tmp_path, config, docs, **kw):
    """A ``process_documents_device`` run inside a ``jax.profiler`` session;
    returns the host plane's lines as ``[[(name, start_ns, end_ns, args)]]``."""
    import glob

    import jax
    from jax.profiler import ProfileData

    trace_dir = str(tmp_path / "profile")
    jax.profiler.start_trace(trace_dir)
    try:
        outcomes = list(process_documents_device(config, iter(docs), **kw))
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    assert paths, "the profiler session wrote no .xplane.pb"
    lines = []
    for plane in ProfileData.from_file(sorted(paths)[-1]).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            lines.append([
                (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns), dict(e.stats))
                for e in line.events
                if e.name.startswith("tb.")
            ])
    return outcomes, [ln for ln in lines if ln]


def _driving_line(lines):
    (line,) = [ln for ln in lines if any(n == "tb.dispatch" for n, *_ in ln)]
    return line


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_profiler_session_records_program_spans_on_the_driving_line(
    tmp_path, monkeypatch
):
    config = parse_pipeline_config(CONFIG_YAML)
    # 17 docs in the 512 bucket with 16-row batches: one batch, then a
    # leftover group of one, which the host oracle takes (a tail).  The
    # suite pins tails to the device; this test needs the host tail.
    monkeypatch.delenv("TEXTBLAST_HOST_TAILS", raising=False)
    docs = [TextDocument(id=f"d{i}", content=GOOD, source="t") for i in range(17)]
    outcomes, lines = _profiled_run(tmp_path, config, docs, device_batch=16,
                                    buckets=(512, 2048))
    assert len(outcomes) == 17
    line = _driving_line(lines)
    by_name = {}
    for ev in line:
        by_name.setdefault(ev[0], []).append(ev)
    for name in ("tb.chunk_fill", "tb.phase", "tb.dispatch", "tb.device_wait",
                 "tb.assemble", "tb.host_tail", "tb.post"):
        assert by_name.get(name), f"{name} missing from the driving line"
    # Nesting: every dispatch and post inside a phase, every wait and
    # assembly inside a post; chunk fills are not inside a phase.
    phases = by_name["tb.phase"]
    for name in ("tb.dispatch", "tb.post"):
        for ev in by_name[name]:
            assert any(_inside(ev, ph) for ph in phases), name
    posts = by_name["tb.post"]
    for name in ("tb.device_wait", "tb.assemble", "tb.host_tail"):
        for ev in by_name[name]:
            assert any(_inside(ev, po) for po in posts), name
    for ev in by_name["tb.chunk_fill"]:
        assert not any(_inside(ev, ph) for ph in phases)
    # One batch: pack, dispatch, wait and assembly share its number.
    (dispatch,) = by_name["tb.dispatch"]
    batch = dispatch[3]["batch"]
    assert batch >= 0
    assert [ev[3]["batch"] for ev in by_name["tb.device_wait"]] == [batch]
    assert [ev[3]["batch"] for ev in by_name["tb.assemble"]] == [batch]
    packs = [ev for ln in lines for ev in ln if ev[0] == "tb.pack"]
    assert [ev[3]["batch"] for ev in packs] == [batch]
    (wait,) = by_name["tb.device_wait"]
    assert wait[3]["leaves"] > 0 and wait[3]["bytes"] > 0
    (tail,) = by_name["tb.host_tail"]
    assert tail[3] == {"kind": "tail", "docs": 1}
    (phase,) = phases
    assert phase[3] == {"chunk": by_name["tb.chunk_fill"][0][3]["chunk"],
                        "phase": 0, "docs_in": 17, "batches": 1, "survivors": 0}


def test_profiler_session_records_host_suffix_block(tmp_path):
    from tokenizers import Tokenizer
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import Whitespace

    tok = Tokenizer(WordLevel({"[UNK]": 0}, unk_token="[UNK]"))
    tok.pre_tokenizer = Whitespace()
    tok_path = str(tmp_path / "tokenizer.json")
    tok.save(tok_path)
    config = parse_pipeline_config(f"""
pipeline:
  - type: GopherQualityFilter
    min_doc_words: 5
  - type: TokenCounter
    tokenizer_name: "{tok_path}"
""")
    docs = _docs(32)
    outcomes, lines = _profiled_run(tmp_path, config, docs, device_batch=16)
    kept = [o for o in outcomes if o.kind == o.SUCCESS]
    assert kept and all("token_count" in o.document.metadata for o in kept)
    line = _driving_line(lines)
    suffix = [ev for ev in line if ev[0] == "tb.host_suffix"]
    assemble = {ev[3]["batch"]: ev for ev in line if ev[0] == "tb.assemble"}
    # One block per batch with passing rows, after that batch's assembly,
    # and its documents are exactly the kept ones.
    assert suffix
    for ev in suffix:
        a = assemble[ev[3]["batch"]]
        assert a[2] <= ev[1]
    assert sum(ev[3]["docs"] for ev in suffix) == len(kept)
    # TokenCounter's batched encode took every block of two or more.
    for ev in suffix:
        assert ev[3]["batched"] == (ev[3]["docs"] if ev[3]["docs"] >= 2 else 0)
    assert any(ev[3]["batched"] for ev in suffix)
    assert not [ev for ln in lines if ln is not line for ev in ln
                if ev[0] == "tb.host_suffix"]


def test_span_is_the_shared_null_span_with_both_sinks_off():
    from jax.profiler import TraceAnnotation

    assert not TRACER.enabled and not TraceAnnotation.is_enabled()
    assert TRACER.span("dispatch", {"batch": 1}) is _NULL_SPAN
    assert not TRACER.span("phase").live


def test_profiler_session_alone_yields_live_spans_and_no_json_events(tmp_path):
    import jax

    jax.profiler.start_trace(str(tmp_path / "p"))
    try:
        with TRACER.span("phase", {"chunk": 0}) as sp:
            assert sp.live and sp is not _NULL_SPAN
            sp.add_args({"survivors": 3})
    finally:
        jax.profiler.stop_trace()
    assert TRACER.drain() == []


def test_json_tracing_and_profiler_session_record_the_same_span(tmp_path):
    import jax

    TRACER.configure(None)
    jax.profiler.start_trace(str(tmp_path / "p"))
    try:
        with TRACER.span("host_tail", {"kind": "tail"}) as sp:
            assert sp.live
            sp.add_args({"docs": 2})
    finally:
        jax.profiler.stop_trace()
    TRACER.close()
    (ev,) = [e for e in TRACER.drain() if e.get("ph") == "X"]
    assert ev["name"] == "host_tail"
    assert ev["args"] == {"kind": "tail", "docs": 2}
