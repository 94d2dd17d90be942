"""TokenCounter's batched encode: one ``encode_batch`` per batch gives the
counts, outcomes and metadata of one ``encode`` per document, and the
host suffix counts the documents it took that way."""

import json
import os

import pytest

from benchmark.generator import block_docs, load_mix, mix_path
from textblaster_tpu.config.pipeline import parse_pipeline_config
from textblaster_tpu.data_model import ProcessingOutcome, TextDocument
from textblaster_tpu.executor import PipelineExecutor
from textblaster_tpu.filters.token_counter import TokenCounter
from textblaster_tpu.ops.pipeline import process_documents_device
from textblaster_tpu.orchestration import (
    execute_processing_batch,
    execute_processing_pipeline,
)
from textblaster_tpu.utils.metrics import METRICS

STANDIN = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "textblaster_tpu", "data", "tokenizers", "gpt2",
)
SEED = 3250000101
EDGE = [
    "",
    " \t\n  ",
    "emoji \U0001F600\U0001F680 and \U0001F9D1‍\U0001F4BB",
    "漢字とかな。中文文本",
    "Det er en god dag. " * 3158,  # 60,002 chars
    "plain text",
]


def _docs(texts):
    return [TextDocument(id=f"d{i}", source="s", content=t) for i, t in enumerate(texts)]


def _texts(case):
    if case == "edge":
        return EDGE
    return block_docs(load_mix(mix_path(case)), SEED, 0)[1]


@pytest.fixture(scope="module")
def counter():
    # The shipped config's name: the hub cache's gpt2, else the stand-in.
    return TokenCounter("gpt2")


@pytest.mark.parametrize("case", ["mixed", "short", "edge"])
def test_batched_counts_equal_per_document_encode(counter, case):
    texts = _texts(case)
    tok = counter._tokenizer
    want = [str(len(tok.encode(t, add_special_tokens=True).tokens)) for t in texts]
    before = counter.batched_docs
    out = counter.process_batch(_docs(texts))
    assert counter.batched_docs - before == len(texts)
    assert [d.metadata["token_count"] for d in out] == want
    # The same metadata as one call per document, stand-in stamp included.
    assert [d.metadata for d in out] == [counter.process(d).metadata for d in _docs(texts)]
    assert all(
        ("token_count_tokenizer" in d.metadata) == counter._standin for d in out
    )


def test_raising_document_gets_its_own_error_and_neighbours_succeed(counter):
    texts = ["first doc", None, "third doc"]  # None makes both encodes raise
    batch = execute_processing_batch(PipelineExecutor([counter]), _docs(texts))
    single = [
        execute_processing_pipeline(PipelineExecutor([counter]), d)
        for d in _docs(texts)
    ]
    assert [o.kind for o in batch] == [
        ProcessingOutcome.SUCCESS, ProcessingOutcome.ERROR, ProcessingOutcome.SUCCESS,
    ]
    assert batch[1].error_message == single[1].error_message
    assert "TokenCounter" in batch[1].error_message
    assert [o.document.metadata for o in batch] == [o.document.metadata for o in single]


def _padded_tokenizer(tmp_path):
    from tokenizers import Tokenizer

    tok = Tokenizer.from_file(os.path.join(STANDIN, "tokenizer.json"))
    tok.enable_padding()
    path = str(tmp_path / "tokenizer.json")
    tok.save(path)
    return path


def _merges_txt(tmp_path):
    from textblaster_tpu import native

    if not native.available():
        pytest.skip("native core unavailable")
    with open(os.path.join(STANDIN, "tokenizer.json"), encoding="utf-8") as f:
        merges = json.load(f)["model"]["merges"]
    path = str(tmp_path / "merges.txt")
    with open(path, "w", encoding="utf-8") as f:
        f.write("#version: 0.2\n")
        for m in merges:
            f.write((m if isinstance(m, str) else " ".join(m)) + "\n")
    return path


@pytest.mark.parametrize("kind", ["padding", "merges"])
def test_padding_and_native_bpe_take_the_loop(tmp_path, kind):
    path = _padded_tokenizer(tmp_path) if kind == "padding" else _merges_txt(tmp_path)
    counter = TokenCounter(path)
    texts = _texts("short")[:64] + EDGE
    want = [counter.process(d).metadata["token_count"] for d in _docs(texts)]
    if kind == "padding":
        # One document pads to itself: per-document encode is the count.
        assert counter._tokenizer.padding is not None
        assert want == [
            str(len(counter._tokenizer.encode(t, add_special_tokens=True)))
            for t in texts
        ]
    out = counter.process_batch(_docs(texts))
    assert [d.metadata["token_count"] for d in out] == want
    assert counter.batched_docs == 0


def test_single_document_takes_the_loop(counter):
    before = counter.batched_docs
    (out,) = counter.process_batch(_docs(["one document"]))
    assert out.metadata["token_count"] == str(
        len(counter._tokenizer.encode("one document", add_special_tokens=True))
    )
    assert counter.batched_docs == before


def test_host_suffix_counts_the_batched_documents(tmp_path):
    config = parse_pipeline_config(f"""
pipeline:
  - type: GopherQualityFilter
    min_doc_words: 2
  - type: TokenCounter
    tokenizer_name: "{STANDIN}"
""")
    texts = [f"document number {i} says hello to the world." for i in range(32)]
    before = METRICS.get("worker_host_suffix_batched_total")
    outcomes = list(process_documents_device(config, iter(_docs(texts)), device_batch=16))
    kept = [o for o in outcomes if o.kind == ProcessingOutcome.SUCCESS]
    assert len(kept) == 32
    assert METRICS.get("worker_host_suffix_batched_total") - before == 32
    tok = TokenCounter(STANDIN)._tokenizer
    for o in kept:
        assert o.document.metadata["token_count"] == str(
            len(tok.encode(o.document.content, add_special_tokens=True))
        )
