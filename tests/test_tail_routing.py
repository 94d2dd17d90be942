"""Host-oracle tail routing (ops/pipeline.py process_chunk).

End-of-stream leftover groups below the per-phase threshold go to the host
oracle instead of a padded device batch: by document count on XLA:CPU, by
fill of the padded lanes on accelerators (the fill-rule tests below call
the rule directly, or substitute it for the backend check).  The host path is bit-exact, so
outcomes must be identical either way; what these tests pin down is the
routing itself and its accounting (worker_host_tail_total vs the overflow
fallback counter) — the conftest disables tail routing suite-wide so the
parity tests exercise device kernels for every doc, and these tests
re-enable it locally.
"""

import numpy as np

from textblaster_tpu.config.pipeline import parse_pipeline_config
from textblaster_tpu.ops import pipeline as ops_pipeline
from textblaster_tpu.ops.geometry import DeviceGeometry
from textblaster_tpu.ops.packing import (
    HOST_TAIL_FILL,
    HOST_TAIL_FILL_FIRST,
    fill_tail_rows,
    iter_packed_batches,
)
from textblaster_tpu.ops.pipeline import process_documents_device
from textblaster_tpu.orchestration import process_documents_host
from textblaster_tpu.pipeline_builder import build_pipeline_from_config
from textblaster_tpu.data_model import TextDocument
from textblaster_tpu.utils.metrics import METRICS

# Three phases: boundaries after langid and after gopher_quality.
_CONFIG = """
pipeline:
  - type: LanguageDetectionFilter
    min_confidence: 0.1
    allowed_languages: [ "dan", "eng" ]
  - type: GopherQualityFilter
    min_doc_words: 2
    min_avg_word_length: 1.0
    max_avg_word_length: 20.0
    min_stop_words: 0
  - type: FineWebQualityFilter
    line_punct_thr: 0.0
    line_punct_exclude_zero: false
    short_line_thr: 1.0
    short_line_length: 5
    char_duplicates_ratio: 1.0
    new_line_ratio: 1.0
"""


def _docs(n=19):
    rng = np.random.default_rng(3)
    words = "det er en god dag og vi skal ud at se solen over byen".split()
    docs = []
    for i in range(n):
        k = int(rng.integers(8, 40))
        text = " ".join(words[int(rng.integers(0, len(words)))] for _ in range(k))
        docs.append(TextDocument(id=f"t{i}", source="s", content=text + "."))
    return docs


def _run_device(monkeypatch, host_tails: str):
    monkeypatch.setenv("TEXTBLAST_HOST_TAILS", host_tails)
    config = parse_pipeline_config(_CONFIG)
    return list(
        process_documents_device(config, iter(_docs()), device_batch=8)
    )


def test_tail_routing_counts_and_matches_host(monkeypatch):
    config = parse_pipeline_config(_CONFIG)
    host = {
        o.document.id: (o.kind, o.reason)
        for o in process_documents_host(build_pipeline_from_config(config), iter(_docs()))
    }

    tails0 = METRICS.get("worker_host_tail_total")
    fb0 = METRICS.get("worker_host_fallback_total")
    outcomes = _run_device(monkeypatch, "on")
    assert METRICS.get("worker_host_tail_total") > tails0  # routing happened
    assert METRICS.get("worker_host_fallback_total") == fb0  # not conflated
    assert {o.document.id: (o.kind, o.reason) for o in outcomes} == host


def test_tail_routing_disabled_keeps_docs_on_device(monkeypatch):
    config = parse_pipeline_config(_CONFIG)
    host = {
        o.document.id: (o.kind, o.reason)
        for o in process_documents_host(build_pipeline_from_config(config), iter(_docs()))
    }
    tails0 = METRICS.get("worker_host_tail_total")
    outcomes = _run_device(monkeypatch, "off")
    assert METRICS.get("worker_host_tail_total") == tails0
    assert {o.document.id: (o.kind, o.reason) for o in outcomes} == host


# --- the accelerator's fill rule --------------------------------------------

_GEO = DeviceGeometry.uniform((512, 2048, 8192), 64)
_HALF = {b: 32 for b in _GEO.buckets}


def _sized(n, chars):
    return [
        TextDocument(id=f"f{i}", source="s", content="a" * chars)
        for i in range(n)
    ]


def _leftovers(docs):
    """The packer's items for a stream that is all leftovers."""
    return list(iter_packed_batches(iter(docs), geometry=_GEO, half_rows=_HALF))


def test_fill_rule_packs_small_group_at_half_rows():
    assert fill_tail_rows([1500] * 20, 2048, 64, 32) == 32
    [(batch, host)] = _leftovers(_sized(20, 1500))
    assert host == []
    assert batch.cps.shape == (32, 2048)
    assert len(batch.docs) == 20


def test_fill_rule_sends_sparse_group_to_host():
    # 20 docs of 100 chars in the 512 bucket fill 2,000 of 32 x 512 lanes.
    assert 20 * 100 < HOST_TAIL_FILL * 32 * 512
    assert fill_tail_rows([100] * 20, 512, 64, 32) is None
    [(batch, host)] = _leftovers(_sized(20, 100))
    assert batch is None
    assert [d.id for d in host] == [f"f{i}" for i in range(20)]
    # Phase 0's program is the cheapest: there the same group rides the
    # device.
    assert fill_tail_rows([100] * 20, 512, 64, 32, HOST_TAIL_FILL_FIRST) == 32


def test_fill_rule_packs_large_group_at_full_rows():
    assert fill_tail_rows([1500] * 33, 2048, 64, 32) == 64
    [(batch, host)] = _leftovers(_sized(40, 1500))
    assert host == []
    assert batch.cps.shape == (64, 2048)
    assert len(batch.docs) == 40


def _long_docs(n=22):
    rng = np.random.default_rng(5)
    words = "det er en god dag og vi skal ud at se solen over byen".split()
    docs = []
    for i in range(n):
        k = int(rng.integers(70, 100))
        text = " ".join(words[int(rng.integers(0, len(words)))] for _ in range(k))
        docs.append(TextDocument(id=f"l{i}", source="s", content=text + "."))
    return docs


def test_fill_rule_end_to_end_matches_host(monkeypatch):
    # 22 documents at 16 rows leave a group of 6 in every phase: after phase
    # 0 the count rule gives it to the host, the fill rule packs it at the
    # 8-row half program.
    monkeypatch.setenv("TEXTBLAST_HOST_TAILS", "on")
    monkeypatch.setattr(ops_pipeline, "_tails_by_fill", lambda: True)
    config = parse_pipeline_config(_CONFIG)
    host = {
        o.document.id: (o.kind, o.reason, dict(o.document.metadata))
        for o in process_documents_host(
            build_pipeline_from_config(config), iter(_long_docs())
        )
    }
    tails0 = METRICS.get("worker_host_tail_total")
    device_tails0 = METRICS.get("worker_device_tail_total")
    outcomes = list(
        process_documents_device(config, iter(_long_docs()), device_batch=16)
    )
    assert METRICS.get("worker_device_tail_total") > device_tails0
    assert METRICS.get("worker_host_tail_total") == tails0
    assert {
        o.document.id: (o.kind, o.reason, dict(o.document.metadata))
        for o in outcomes
    } == host
