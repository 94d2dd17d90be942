"""Resilience layer unit tests: RetryPolicy (fake clock — no real sleeps),
the error classifier, the circuit breaker, and the dead-letter sink."""

import random

import pyarrow.parquet as pq
import pytest

from textblaster_tpu.data_model import ProcessingOutcome, TextDocument
from textblaster_tpu.errors import (
    CheckpointError,
    DocumentFiltered,
    ParquetError,
    RetryExhaustedError,
    StepError,
)
from textblaster_tpu.resilience import (
    DEADLETTER_SCHEMA,
    CircuitBreaker,
    DeadLetterSink,
    RetryPolicy,
    classify_error,
    is_oom_error,
    is_retryable_error,
)
from textblaster_tpu.utils.metrics import METRICS


class XlaRuntimeError(Exception):
    """Stand-in with the name the classifier matches on (jaxlib's class
    location varies by version, so matching is by type name)."""


def _policy(**kw):
    sleeps = []
    kw.setdefault("sleep", sleeps.append)
    kw.setdefault("jitter", 0.0)
    return RetryPolicy(**kw), sleeps


def _flaky(fail_times, exc=None):
    """A callable failing the first ``fail_times`` calls."""
    calls = [0]

    def fn():
        calls[0] += 1
        if calls[0] <= fail_times:
            raise exc if exc is not None else OSError(f"blip {calls[0]}")
        return "ok"

    fn.calls = calls
    return fn


# --- backoff schedule -------------------------------------------------------


def test_backoff_schedule_exponential_capped():
    policy, sleeps = _policy(
        max_retries=4, base_delay=0.1, max_delay=0.5, multiplier=2.0
    )
    fn = _flaky(4)
    assert policy.run(fn) == "ok"
    assert fn.calls[0] == 5
    assert sleeps == pytest.approx([0.1, 0.2, 0.4, 0.5])


def test_jitter_is_bounded_and_seeded():
    mk = lambda: RetryPolicy(  # noqa: E731
        max_retries=3,
        base_delay=0.1,
        multiplier=1.0,
        jitter=0.5,
        sleep=lambda s: None,
        rng=random.Random(1234),
    )
    a, b = mk(), mk()
    da = [a.delay_for(i) for i in range(8)]
    db = [b.delay_for(i) for i in range(8)]
    assert da == db  # seeded rng -> deterministic schedule
    assert all(0.1 <= d <= 0.15 + 1e-9 for d in da)
    assert len(set(da)) > 1  # actually jittered


def test_no_sleep_when_base_delay_zero():
    policy, sleeps = _policy(max_retries=3, base_delay=0.0)
    assert policy.run(_flaky(2)) == "ok"
    assert sleeps == []


# --- retry/exhaustion/fatal semantics --------------------------------------


def test_exhaustion_wraps_last_error():
    policy, sleeps = _policy(max_retries=2, base_delay=0.01)
    fn = _flaky(99)
    with pytest.raises(RetryExhaustedError) as ei:
        policy.run(fn, seam="device")
    assert fn.calls[0] == 3  # 1 try + 2 retries
    assert len(sleeps) == 2
    assert ei.value.attempts == 3
    assert ei.value.seam == "device"
    assert isinstance(ei.value.last, OSError)
    assert ei.value.__cause__ is ei.value.last
    assert "blip 3" in str(ei.value)


def test_zero_retries_still_classifies():
    policy, sleeps = _policy(max_retries=0)
    with pytest.raises(RetryExhaustedError) as ei:
        policy.run(_flaky(1))
    assert ei.value.attempts == 1
    assert sleeps == []


def test_fatal_error_not_retried():
    policy, sleeps = _policy(max_retries=5)
    boom = StepError("GopherQualityFilter", DocumentFiltered(TextDocument(), "short"))
    fn = _flaky(99, exc=boom)
    with pytest.raises(StepError) as ei:
        policy.run(fn)
    assert ei.value is boom  # re-raised untouched, not wrapped
    assert fn.calls[0] == 1
    assert sleeps == []


def test_nested_policies_do_not_multiply_attempts():
    inner, _ = _policy(max_retries=2)
    outer, _ = _policy(max_retries=5)
    fn = _flaky(99)
    with pytest.raises(RetryExhaustedError):
        outer.run(lambda: inner.run(fn))
    # RetryExhaustedError is deterministic to the outer loop: the inner
    # budget (3 calls) is spent exactly once.
    assert fn.calls[0] == 3


def test_on_retry_observer_and_metrics():
    before = METRICS.get("resilience_retries_checkpoint_total")
    before_total = METRICS.get("resilience_retries_total")
    seen = []
    policy, _ = _policy(max_retries=3, base_delay=0.0)
    policy.run(_flaky(2), seam="checkpoint", on_retry=lambda e, a: seen.append(a))
    assert seen == [1, 2]
    assert METRICS.get("resilience_retries_checkpoint_total") - before == 2
    assert METRICS.get("resilience_retries_total") - before_total == 2


# --- classifier -------------------------------------------------------------


def test_classifier_transient_families():
    assert is_retryable_error(OSError("disk hiccup"))
    assert is_retryable_error(TimeoutError())
    assert is_retryable_error(ConnectionResetError())
    assert is_retryable_error(MemoryError())
    assert is_retryable_error(XlaRuntimeError("RESOURCE_EXHAUSTED: hbm"))
    assert is_retryable_error(XlaRuntimeError("UNAVAILABLE: connection lost"))
    assert is_retryable_error(
        ParquetError("connection reset while reading footer")
    )
    assert is_retryable_error(
        RuntimeError("response body closed before all bytes were read")
    )


def test_classifier_deterministic_families():
    assert classify_error(XlaRuntimeError("INVALID_ARGUMENT: bad shape")) == "fatal"
    assert classify_error(ParquetError("Invalid magic bytes")) == "fatal"
    assert classify_error(CheckpointError("different input")) == "fatal"
    assert classify_error(DocumentFiltered(TextDocument(), "r")) == "fatal"
    assert classify_error(StepError("X", DocumentFiltered(TextDocument(), "r"))) == "fatal"
    assert classify_error(ValueError("nope")) == "fatal"
    assert classify_error(KeyboardInterrupt()) == "fatal"
    assert (
        classify_error(RetryExhaustedError("device", 4, OSError("x"))) == "fatal"
    )


def test_oom_detection_unwraps_exhaustion():
    assert is_oom_error(MemoryError())
    assert is_oom_error(XlaRuntimeError("RESOURCE_EXHAUSTED: out of memory"))
    assert is_oom_error(
        RetryExhaustedError("device", 4, XlaRuntimeError("ran out of memory"))
    )
    assert not is_oom_error(OSError("disk hiccup"))


# --- circuit breaker --------------------------------------------------------


def test_breaker_trips_at_threshold_and_latches():
    trips_before = METRICS.get("resilience_breaker_trips_total")
    b = CircuitBreaker(threshold=3, name="test")
    for _ in range(2):
        b.record_failure("boom")
    assert not b.tripped
    b.record_success()  # success resets the streak
    assert b.consecutive_failures == 0
    for _ in range(3):
        b.record_failure("boom")
    assert b.tripped
    assert METRICS.get("resilience_breaker_trips_total") - trips_before == 1
    b.record_success()  # latched open for the run's lifetime
    assert b.tripped


# --- dead-letter sink -------------------------------------------------------


def _error_outcome(i=0):
    doc = TextDocument(
        id=f"doc-{i}",
        content="bad text",
        source="s.parquet",
        metadata={"language": "xx"},
    )
    msg = "Error in processing step 'C4BadWordsFilter': no list for 'xx'"
    return ProcessingOutcome.error(doc, msg, f"worker-{i}")


def test_deadletter_outcome_row_parses_step(tmp_path):
    path = str(tmp_path / "errors.parquet")
    with DeadLetterSink(path) as sink:
        sink.record_outcome(_error_outcome())
        sink.record_read_error(ParquetError("row quarantined: row group 2"))
    t = pq.read_table(path)
    assert t.schema.names == list(DEADLETTER_SCHEMA.names)
    rows = t.to_pylist()
    assert rows[0]["id"] == "doc-0"
    assert rows[0]["step"] == "C4BadWordsFilter"
    assert rows[0]["worker"] == "worker-0"
    assert "no list for 'xx'" in rows[0]["reason"]
    assert rows[0]["metadata"] == '{"language":"xx"}'
    assert rows[1]["step"] == "read"
    assert rows[1]["id"] is None


def test_deadletter_empty_file_is_well_formed(tmp_path):
    path = str(tmp_path / "errors.parquet")
    DeadLetterSink(path).close()
    t = pq.read_table(path)
    assert t.num_rows == 0
    assert t.schema.names == list(DEADLETTER_SCHEMA.names)


def test_deadletter_buffers_and_flushes(tmp_path):
    path = str(tmp_path / "errors.parquet")
    before = METRICS.get("deadletter_rows_total")
    sink = DeadLetterSink(path, batch_size=10)
    for i in range(25):
        sink.record_outcome(_error_outcome(i))
    sink.close()
    assert METRICS.get("deadletter_rows_total") - before == 25
    t = pq.read_table(path)
    assert t.num_rows == 25
    assert [r["id"] for r in t.to_pylist()] == [f"doc-{i}" for i in range(25)]
    with pytest.raises(ParquetError, match="closed"):
        sink.record_read_error(ParquetError("late"))
