"""Executor tests, following ``/root/reference/tests/executor_test.rs`` —
mock steps with injectable behavior, ordering, short-circuit, batch."""

import pytest

from textblaster_tpu.data_model import TextDocument
from textblaster_tpu.errors import DocumentFiltered, StepError, UnexpectedError
from textblaster_tpu.executor import PipelineExecutor, ProcessingStep


class MockStep(ProcessingStep):
    def __init__(self, name, fn=None, fail=False):
        self.name = name
        self.fn = fn
        self.fail = fail
        self.calls = 0

    def process(self, document):
        self.calls += 1
        if self.fail:
            raise UnexpectedError(f"{self.name} failed")
        if self.fn:
            return self.fn(document)
        return document


class FilteringStep(ProcessingStep):
    name = "FilteringStep"

    def process(self, document):
        document.metadata["filtered_by"] = self.name
        raise DocumentFiltered(document, "test filter reason")


class SmartErrorStep(ProcessingStep):
    """Fails only for a specific doc id (executor_test.rs:352-376)."""

    name = "SmartErrorStep"

    def __init__(self, bad_id):
        self.bad_id = bad_id

    def process(self, document):
        if document.id == self.bad_id:
            raise UnexpectedError("doc-specific failure")
        return document


def doc(id="d1", content="content"):
    return TextDocument(id=id, content=content, source="s")


def test_empty_pipeline_passes_through():
    ex = PipelineExecutor([])
    d = doc()
    assert ex.run_single(d) is d


def test_steps_run_in_order():
    order = []

    def mk(name):
        def fn(d):
            order.append(name)
            d.metadata[name] = "ran"
            return d

        return MockStep(name, fn=fn)

    ex = PipelineExecutor([mk("first"), mk("second"), mk("third")])
    out = ex.run_single(doc())
    assert order == ["first", "second", "third"]
    assert set(out.metadata) == {"first", "second", "third"}


def test_error_short_circuits():
    s1 = MockStep("ok1")
    s2 = MockStep("boom", fail=True)
    s3 = MockStep("never")
    ex = PipelineExecutor([s1, s2, s3])
    with pytest.raises(StepError) as ei:
        ex.run_single(doc())
    assert ei.value.step_name == "boom"
    assert s3.calls == 0


def test_filtered_wrapped_in_step_error():
    ex = PipelineExecutor([FilteringStep()])
    with pytest.raises(StepError) as ei:
        ex.run_single(doc())
    inner = ei.value.filtered()
    assert inner is not None
    assert inner.reason == "test filter reason"
    assert inner.document.metadata["filtered_by"] == "FilteringStep"


def test_batch_mixed_results_input_order():
    ex = PipelineExecutor([SmartErrorStep(bad_id="bad")])
    docs = [doc("good1"), doc("bad"), doc("good2")]
    results = ex.run_batch(docs)
    assert isinstance(results[0], TextDocument) and results[0].id == "good1"
    assert isinstance(results[1], StepError)
    assert isinstance(results[2], TextDocument) and results[2].id == "good2"


class FilterIdStep(ProcessingStep):
    """Filters only a specific doc id."""

    name = "FilterIdStep"

    def __init__(self, bad_id):
        self.bad_id = bad_id

    def process(self, document):
        if document.id == self.bad_id:
            raise DocumentFiltered(document, "filtered mid-batch")
        document.metadata[self.name] = "passed"
        return document


class CountingBatchStep(MockStep):
    """Records the documents of each ``process_batch`` call."""

    def __init__(self, name, **kw):
        super().__init__(name, **kw)
        self.batches = []

    def process_batch(self, documents):
        self.batches.append([d.id for d in documents])
        return super().process_batch(documents)


def _pipeline():
    def stamp(d):
        d.metadata["stamped"] = d.id
        return d

    return [
        CountingBatchStep("first", fn=stamp),
        FilterIdStep(bad_id="filtered"),
        SmartErrorStep(bad_id="broken"),
        CountingBatchStep("last"),
    ]


def _describe(result):
    if isinstance(result, StepError):
        inner = result.filtered()
        return ("error", result.step_name, str(result),
                inner.reason if inner else None, type(result.__cause__))
    return ("doc", result.id, dict(result.metadata))


def test_step_major_batch_equals_run_single_per_document():
    ids = ["a", "filtered", "b", "broken", "c"]
    single = []
    for i in ids:
        try:
            single.append(PipelineExecutor(_pipeline()).run_single(doc(i)))
        except StepError as e:
            single.append(e)
    steps = _pipeline()
    batch = PipelineExecutor(steps).run_batch([doc(i) for i in ids])
    assert [_describe(r) for r in batch] == [_describe(r) for r in single]
    assert [_describe(r)[1] for r in batch] == [
        "a", "FilterIdStep", "b", "SmartErrorStep", "c",
    ]
    # One call per step, over the documents still alive.
    assert steps[0].batches == [ids]
    assert steps[-1].batches == [["a", "b", "c"]]


def test_batch_short_circuits_a_failing_document():
    never = CountingBatchStep("never")
    ex = PipelineExecutor([MockStep("boom", fail=True), never])
    results = ex.run_batch([doc("x"), doc("y")])
    assert all(isinstance(r, StepError) and r.step_name == "boom" for r in results)
    assert isinstance(results[0].source, UnexpectedError)
    assert never.batches == [] and never.calls == 0
