"""Aggregation tests over injected outcome streams — the broker-bypass seam
(``/root/reference/tests/producer_tests.rs:324-573``), cross-read with raw
pyarrow as the independent oracle."""

import pyarrow.parquet as pq

from textblaster_tpu.data_model import ProcessingOutcome, TextDocument
from textblaster_tpu.orchestration import (
    PARQUET_WRITE_BATCH_SIZE,
    aggregate_results_from_stream,
)


def doc(i, content="text content."):
    return TextDocument(id=f"doc-{i}", content=content, source="s")


def test_mixed_outcomes_routed(tmp_path):
    out = str(tmp_path / "out.parquet")
    excl = str(tmp_path / "excl.parquet")
    stream = [
        ProcessingOutcome.success(doc(1)),
        ProcessingOutcome.filtered(doc(2), "bad quality"),
        ProcessingOutcome.success(doc(3)),
        ProcessingOutcome.error(doc(4), "boom", "w-1"),
        ProcessingOutcome.filtered(doc(5), "other reason"),
    ]
    result = aggregate_results_from_stream(stream, out, excl)
    assert (result.received, result.success, result.filtered, result.errors) == (
        5, 2, 2, 1,
    )
    kept = pq.read_table(out)
    excluded = pq.read_table(excl)
    assert kept.column("id").to_pylist() == ["doc-1", "doc-3"]
    assert excluded.column("id").to_pylist() == ["doc-2", "doc-5"]
    # Error outcomes are in neither file (quirk #2).
    all_ids = kept.column("id").to_pylist() + excluded.column("id").to_pylist()
    assert "doc-4" not in all_ids


def test_batching_flushes_remainders(tmp_path):
    out = str(tmp_path / "out.parquet")
    excl = str(tmp_path / "excl.parquet")
    n = PARQUET_WRITE_BATCH_SIZE + 7
    stream = (ProcessingOutcome.success(doc(i)) for i in range(n))
    result = aggregate_results_from_stream(stream, out, excl)
    assert result.success == n
    assert pq.read_table(out).num_rows == n
    assert pq.read_table(excl).num_rows == 0


def test_published_count_short_stream_warns(tmp_path, caplog):
    out = str(tmp_path / "out.parquet")
    excl = str(tmp_path / "excl.parquet")
    stream = [ProcessingOutcome.success(doc(1))]
    result = aggregate_results_from_stream(stream, out, excl, published_count=5)
    assert result.received == 1


def test_output_dirs_created(tmp_path):
    out = str(tmp_path / "nested" / "dir" / "out.parquet")
    excl = str(tmp_path / "other" / "excl.parquet")
    aggregate_results_from_stream(
        [ProcessingOutcome.success(doc(1))], out, excl
    )
    assert pq.read_table(out).num_rows == 1


def test_filtered_doc_metadata_roundtrip(tmp_path):
    out = str(tmp_path / "out.parquet")
    excl = str(tmp_path / "excl.parquet")
    d = doc(1)
    d.metadata["gopher_quality_filter_status"] = "filtered"
    d.metadata["gopher_quality_filter_reasons"] = "gopher_short_doc (2, required 3)"
    aggregate_results_from_stream(
        [ProcessingOutcome.filtered(d, "gopher_short_doc (2, required 3)")],
        out,
        excl,
    )
    import json

    md = json.loads(pq.read_table(excl).column("metadata")[0].as_py())
    assert md["gopher_quality_filter_status"] == "filtered"


def test_execute_processing_batch_matches_per_document_calls():
    from textblaster_tpu.errors import DocumentFiltered, UnexpectedError
    from textblaster_tpu.executor import PipelineExecutor, ProcessingStep
    from textblaster_tpu.orchestration import (
        execute_processing_batch,
        execute_processing_pipeline,
    )
    from textblaster_tpu.utils.metrics import FILTER_DROP_PREFIX, METRICS

    class Judge(ProcessingStep):
        name = "Judge"

        def process(self, d):
            if d.id == "doc-2":
                raise DocumentFiltered(d, "too short")
            if d.id == "doc-3":
                raise UnexpectedError("broken")
            d.metadata["judged"] = "yes"
            return d

    names = [
        "worker_tasks_processed_total", "worker_tasks_filtered_total",
        "worker_tasks_failed_total", "worker_active_tasks",
        FILTER_DROP_PREFIX + "Judge",
        "worker_task_processing_duration_seconds::count",
    ]

    def run(fn):
        before = METRICS.all_values()
        outcomes = fn(PipelineExecutor([Judge()]), [doc(i) for i in range(1, 6)])
        after = METRICS.all_values()
        deltas = {n: after.get(n, 0.0) - before.get(n, 0.0) for n in names}
        return [o.to_dict() for o in outcomes], deltas

    single, single_deltas = run(
        lambda ex, docs: [execute_processing_pipeline(ex, d) for d in docs]
    )
    batch, batch_deltas = run(execute_processing_batch)
    assert batch == single
    assert batch_deltas == single_deltas
    assert single_deltas["worker_tasks_processed_total"] == 3
    assert single_deltas["worker_tasks_filtered_total"] == 1
    assert single_deltas["worker_tasks_failed_total"] == 1
    assert single_deltas["worker_active_tasks"] == 0
    assert single_deltas["worker_task_processing_duration_seconds::count"] == 5
