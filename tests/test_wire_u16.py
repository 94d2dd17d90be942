"""uint16 wire format (ops/pipeline.py): BMP batches upload as uint16 on
accelerator backends (halving the host-to-device transfer); rows containing
supplementary-plane chars are routed to the host oracle.  Forced on here
(TEXTBLAST_WIRE=u16) so the CPU suite executes the exact accelerator path.
"""

import numpy as np
import pytest

from textblaster_tpu.config.pipeline import parse_pipeline_config
from textblaster_tpu.data_model import ProcessingOutcome, TextDocument
from textblaster_tpu.ops.pipeline import CompiledPipeline, process_documents_device
from textblaster_tpu.orchestration import process_documents_host
from textblaster_tpu.pipeline_builder import build_pipeline_from_config

YAML = """
pipeline:
  - type: LanguageDetectionFilter
    min_confidence: 0.5
    allowed_languages: [ "dan", "eng" ]
  - type: GopherQualityFilter
    min_doc_words: 4
    min_stop_words: 1
    stop_words: [ "og", "the", "er", "i" ]
"""


def _docs():
    texts = [
        "Det er en god dag i dag, og vi skal ud at gå en lang tur i skoven.",
        "The quick brown fox jumps over the lazy dog and the old stone bridge.",
        # Astral chars (emoji, plane-1): must route to the host oracle under
        # the u16 wire, with identical decisions.
        "Great news 😀🎉 the team shipped it and everyone is happy today.",
        "kort.",
        "𝒜 mathematical script letter starts this otherwise plain sentence.",
    ]
    return [TextDocument(id=f"w{i}", source="t", content=t) for i, t in enumerate(texts)]


def test_u16_wire_matches_oracle_and_routes_astral(monkeypatch):
    from textblaster_tpu.utils.metrics import METRICS

    monkeypatch.setenv("TEXTBLAST_WIRE", "u16")
    config = parse_pipeline_config(YAML)
    host = {
        o.document.id: o
        for o in process_documents_host(
            build_pipeline_from_config(config), iter(_docs())
        )
    }
    pipeline = CompiledPipeline(config, batch_size=8, buckets=(512,))
    assert pipeline.wire_u16
    before = METRICS.get("worker_host_fallback_total")
    dev = {
        o.document.id: o
        for o in process_documents_device(config, iter(_docs()), pipeline=pipeline)
    }
    routed = METRICS.get("worker_host_fallback_total") - before
    assert routed == 2  # exactly the two astral docs
    assert set(host) == set(dev)
    for k in host:
        assert host[k].kind == dev[k].kind, k
        assert host[k].reason == dev[k].reason, k
        assert host[k].document.metadata == dev[k].document.metadata, k


def test_u16_wire_guard_refuses_astral_batch(monkeypatch):
    # The dispatch guard is the last line of defense if routing is bypassed.
    from textblaster_tpu.ops.packing import pack_documents

    monkeypatch.setenv("TEXTBLAST_WIRE", "u16")
    config = parse_pipeline_config(YAML)
    pipeline = CompiledPipeline(config, batch_size=8, buckets=(512,))
    batch = pack_documents(
        [TextDocument(id="a", source="t", content="emoji 😀 text")],
        batch_size=8,
        max_len=512,
    )
    with pytest.raises(RuntimeError, match="astral"):
        pipeline.dispatch_batch(batch)


def test_cp32_wire_unchanged(monkeypatch):
    monkeypatch.setenv("TEXTBLAST_WIRE", "cp32")
    config = parse_pipeline_config(YAML)
    pipeline = CompiledPipeline(config, batch_size=8, buckets=(512,))
    assert not pipeline.wire_u16
    host = {
        o.document.id: o
        for o in process_documents_host(
            build_pipeline_from_config(config), iter(_docs())
        )
    }
    dev = {
        o.document.id: o
        for o in process_documents_device(config, iter(_docs()), pipeline=pipeline)
    }
    assert {k: v.kind for k, v in host.items()} == {
        k: v.kind for k, v in dev.items()
    }
