"""Chaos suite: faults injected at every guarded seam through *production*
control flow (the FAULTS sites, no monkeypatching), proving the acceptance
property: a run with a transient fault at any seam completes with the same
kept/excluded outputs as a fault-free run, and the degradation is observable
in METRICS.

Ladder rung accounting (single batch, so fire counts are deterministic):
``process_chunk`` dispatch consumes fire 1 (caught, handed to the ladder
with nothing in flight); the ladder's in-policy attempts consume fires
2..2+max_retries.  With the default ``max_retries=3``, ``times=2`` recovers
via a policy retry, ``times=5`` exhausts the full batch and succeeds on the
split rung, and a large ``times`` falls all the way to the host rung.
"""

import os

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from textblaster_tpu.checkpoint import run_checkpointed
from textblaster_tpu.config.pipeline import parse_pipeline_config
from textblaster_tpu.errors import PipelineError
from textblaster_tpu.ops.pipeline import process_documents_device
from textblaster_tpu.parallel.runner import run_pipeline
from textblaster_tpu.resilience import FAULTS
from textblaster_tpu.utils.metrics import METRICS

pytestmark = pytest.mark.chaos

# Zero backoff: chaos tests drive many retries and must never sleep for real.
CONFIG_YAML = """
pipeline:
  - type: GopherQualityFilter
    min_doc_words: 5
resilience:
  backoff_base_s: 0.0
  backoff_max_s: 0.0
  breaker_threshold: 2
"""

GOOD = (
    "This is a sentence with a number of words that is long enough to pass "
    "the filter easily today."
)
BAD = "too short"


@pytest.fixture
def config():
    return parse_pipeline_config(CONFIG_YAML)


def _write_input(path, n=50, row_group_size=None, languages=None):
    rows = {
        "id": [f"doc-{i}" for i in range(n)],
        "text": [GOOD if i % 3 else BAD for i in range(n)],
    }
    if languages is not None:
        rows["metadata"] = [
            '{"language": "%s"}' % languages[i % len(languages)]
            for i in range(n)
        ]
    kw = {} if row_group_size is None else {"row_group_size": row_group_size}
    pq.write_table(pa.table(rows), path, **kw)


def _docs(n=10):
    from textblaster_tpu.data_model import TextDocument

    return [
        TextDocument(id=f"doc-{i}", content=GOOD if i % 3 else BAD, source="t")
        for i in range(n)
    ]


def _outcome_key(outcomes):
    return {
        o.document.id: (o.kind, o.reason, o.document.content,
                        dict(o.document.metadata))
        for o in outcomes
    }


def _metric_deltas(fn, *names):
    before = {n: METRICS.get(n) for n in names}
    result = fn()
    return result, {n: METRICS.get(n) - before[n] for n in names}


# --- tier-1 guard: the injector is inert in production paths ----------------


def test_faults_inert_by_default():
    assert not FAULTS.active()
    # With nothing armed, fire() is a no-op falsy check — production seams
    # pay nothing and raise nothing.
    assert FAULTS.fire("device.execute") is None
    assert FAULTS.fire("read.batch") is None
    assert FAULTS.fire("checkpoint.commit") is None
    assert FAULTS.fired("device.execute") == 0


def test_fault_sites_are_planted_in_production_code():
    import inspect

    from textblaster_tpu import checkpoint as ckpt_mod
    from textblaster_tpu.io import parquet_reader
    from textblaster_tpu.ops import pipeline as ops_pipeline

    assert 'FAULTS.fire("read.batch")' in inspect.getsource(parquet_reader)
    assert 'FAULTS.fire("device.execute")' in inspect.getsource(ops_pipeline)
    assert 'FAULTS.fire("checkpoint.commit")' in inspect.getsource(ckpt_mod)


# --- read seam --------------------------------------------------------------


def test_read_transient_fault_recovers_byte_identical(tmp_path, config):
    inp = str(tmp_path / "in.parquet")
    _write_input(inp, n=50, row_group_size=10)

    clean_out = str(tmp_path / "clean_out.parquet")
    clean_excl = str(tmp_path / "clean_excl.parquet")
    run_pipeline(config, inp, clean_out, clean_excl, backend="host", quiet=True)

    FAULTS.inject("read.batch", OSError("transient read blip"), times=2)
    out = str(tmp_path / "out.parquet")
    excl = str(tmp_path / "excl.parquet")
    result, deltas = _metric_deltas(
        lambda: run_pipeline(config, inp, out, excl, backend="host", quiet=True),
        "resilience_retries_read_total",
    )
    assert result.received == 50 and result.read_errors == 0
    assert deltas["resilience_retries_read_total"] == 2
    assert FAULTS.fired("read.batch") == 2
    with open(clean_out, "rb") as a, open(out, "rb") as b:
        assert a.read() == b.read()
    with open(clean_excl, "rb") as a, open(excl, "rb") as b:
        assert a.read() == b.read()


def test_unreadable_row_group_quarantined(tmp_path, config):
    inp = str(tmp_path / "in.parquet")
    _write_input(inp, n=50, row_group_size=10)

    # Deterministic corruption (fatal to the classifier): group 2's fetch
    # fails once, immediately — no retry budget is spent on it.
    FAULTS.inject("read.batch", ValueError("corrupt page"), after_calls=2)
    out = str(tmp_path / "out.parquet")
    excl = str(tmp_path / "excl.parquet")
    errs = str(tmp_path / "errors.parquet")
    result, deltas = _metric_deltas(
        lambda: run_pipeline(
            config, inp, out, excl, backend="host", quiet=True,
            errors_file=errs,
        ),
        "resilience_quarantined_rows_total",
        "deadletter_rows_total",
    )
    # The 10 rows of the dead group are accounted (item<->row exactness),
    # every other row processes normally.
    assert result.read_errors == 10
    assert result.received == 40
    assert deltas["resilience_quarantined_rows_total"] == 10
    assert deltas["deadletter_rows_total"] == 10
    dead = pq.read_table(errs).to_pylist()
    assert len(dead) == 10
    assert all(r["step"] == "read" for r in dead)
    assert all("corrupt page" in r["reason"] for r in dead)
    kept = pq.read_table(out).num_rows
    excluded = pq.read_table(excl).num_rows
    assert kept + excluded == 40


# --- device seam: the degradation ladder ------------------------------------


def test_device_retry_rung_recovers(config):
    clean = list(process_documents_device(config, iter(_docs(10)),
                                          device_batch=16))
    FAULTS.inject("device.execute", OSError("device blip"), times=2)
    faulted, deltas = _metric_deltas(
        lambda: list(
            process_documents_device(config, iter(_docs(10)), device_batch=16)
        ),
        "resilience_retries_device_total",
        "resilience_ladder_split_total",
        "resilience_ladder_host_total",
    )
    assert _outcome_key(faulted) == _outcome_key(clean)
    assert deltas["resilience_retries_device_total"] == 1
    assert deltas["resilience_ladder_split_total"] == 0
    assert deltas["resilience_ladder_host_total"] == 0


def test_device_split_rung_recovers(config):
    clean = list(process_documents_device(config, iter(_docs(10)),
                                          device_batch=16))
    # times=5: dispatch + the full-batch policy budget (1 + 3 retries) all
    # fail; both half-batches then dispatch clean.
    FAULTS.inject("device.execute", OSError("persistent-ish"), times=5)
    faulted, deltas = _metric_deltas(
        lambda: list(
            process_documents_device(config, iter(_docs(10)), device_batch=16)
        ),
        "resilience_ladder_split_total",
        "resilience_ladder_host_total",
        "resilience_retry_exhausted_total",
        "resilience_breaker_trips_total",
    )
    assert _outcome_key(faulted) == _outcome_key(clean)
    assert deltas["resilience_ladder_split_total"] == 1
    assert deltas["resilience_ladder_host_total"] == 0
    assert deltas["resilience_retry_exhausted_total"] == 1
    assert deltas["resilience_breaker_trips_total"] == 0
    assert FAULTS.fired("device.execute") == 5


def test_split_rung_of_half_row_batch_uses_warm_program(config):
    # A faulted half-row tail batch splits into halves packed at the
    # bucket's half-row count again — the program warmup installed for the
    # split rung — and compiles nothing new mid-incident.
    from textblaster_tpu.ops.packing import pack_documents
    from textblaster_tpu.ops.pipeline import CompiledPipeline

    pipeline = CompiledPipeline(config, buckets=(512,), batch_size=32)
    half = pipeline._warm_half_rows(512)
    assert half == 16
    assert [rows for *_, rows in pipeline._warmup_jobs()] == [32, 16]

    def run():
        return pipeline._execute_packed(
            pack_documents(_docs(10), half, 512), phase=0
        )

    clean = run()
    keys = set(pipeline._jitted)
    assert (512, 0, half) in keys
    # times=4: the full-batch policy budget (1 + 3 retries) fails; both
    # halves then dispatch clean.
    FAULTS.inject("device.execute", OSError("persistent-ish"), times=4)
    faulted, deltas = _metric_deltas(
        run, "resilience_ladder_split_total", "resilience_ladder_host_total"
    )
    assert deltas["resilience_ladder_split_total"] == 1
    assert deltas["resilience_ladder_host_total"] == 0
    assert FAULTS.fired("device.execute") == 4
    assert set(pipeline._jitted) == keys
    assert _outcome_key(faulted[0]) == _outcome_key(clean[0])


def test_device_outage_host_rung_and_breaker(config):
    docs = _docs(40)
    clean = list(process_documents_device(config, iter(docs), device_batch=8))
    # Permanent outage: every device dispatch fails.  Each batch falls to the
    # host rung; after breaker_threshold=2 consecutive host-rung batches the
    # breaker trips and the rest of the run never touches the device again.
    FAULTS.inject("device.execute", OSError("chip gone"), times=100_000)
    faulted, deltas = _metric_deltas(
        lambda: list(
            process_documents_device(config, iter(docs), device_batch=8)
        ),
        "resilience_ladder_host_total",
        "resilience_breaker_trips_total",
    )
    assert _outcome_key(faulted) == _outcome_key(clean)
    assert deltas["resilience_ladder_host_total"] == 40  # every doc, host-run
    assert deltas["resilience_breaker_trips_total"] == 1
    assert METRICS.get("resilience_breaker_open") == 1
    # Tripped breaker stops dispatching: fires stop well short of what 5
    # batches x full ladder would consume if the breaker were ignored.
    fired_total = FAULTS.fired("device.execute")
    assert fired_total < 100_000


def test_device_deterministic_error_propagates(config):
    # A fatal (deterministic) error must NOT degrade: it repeats identically
    # on host and hides a real bug if absorbed.
    FAULTS.inject("device.execute", ValueError("shape bug"), times=10)
    with pytest.raises(ValueError, match="shape bug"):
        list(process_documents_device(config, iter(_docs(10)), device_batch=16))


# --- checkpoint commit seam -------------------------------------------------


def test_checkpoint_commit_transient_fault_retries(tmp_path, config):
    inp = str(tmp_path / "in.parquet")
    _write_input(inp)

    plain_out = str(tmp_path / "p_out.parquet")
    plain_excl = str(tmp_path / "p_excl.parquet")
    run_checkpointed(
        config, inp, plain_out, plain_excl,
        ckpt_dir=str(tmp_path / "ck0"), chunk_size=16, backend="host",
    )

    FAULTS.inject("checkpoint.commit", OSError("fsync blip"), times=2)
    out = str(tmp_path / "out.parquet")
    excl = str(tmp_path / "excl.parquet")
    result, deltas = _metric_deltas(
        lambda: run_checkpointed(
            config, inp, out, excl,
            ckpt_dir=str(tmp_path / "ck1"), chunk_size=16, backend="host",
        ),
        "resilience_retries_checkpoint_total",
    )
    assert result.received == 50
    assert deltas["resilience_retries_checkpoint_total"] == 2
    for a, b in ((plain_out, out), (plain_excl, excl)):
        with open(a, "rb") as fa, open(b, "rb") as fb:
            assert fa.read() == fb.read()


def test_checkpoint_commit_exhaustion_then_resume(tmp_path, config):
    from textblaster_tpu.errors import RetryExhaustedError

    inp = str(tmp_path / "in.parquet")
    _write_input(inp)

    plain_out = str(tmp_path / "p_out.parquet")
    plain_excl = str(tmp_path / "p_excl.parquet")
    run_checkpointed(
        config, inp, plain_out, plain_excl,
        ckpt_dir=str(tmp_path / "ck0"), chunk_size=16, backend="host",
    )

    out = str(tmp_path / "out.parquet")
    excl = str(tmp_path / "excl.parquet")
    ckpt = str(tmp_path / "ck1")
    # First commit succeeds (after_calls=1); the retry budget (1 + 3
    # retries) is then spent entirely on the second commit -> the run dies
    # like a crash at the second chunk boundary, with a valid cursor for
    # chunk one on disk.
    FAULTS.inject(
        "checkpoint.commit", OSError("disk full-ish"), after_calls=1, times=4
    )
    with pytest.raises(RetryExhaustedError):
        run_checkpointed(
            config, inp, out, excl, ckpt_dir=ckpt, chunk_size=16,
            backend="host",
        )
    FAULTS.reset()
    result = run_checkpointed(
        config, inp, out, excl, ckpt_dir=ckpt, chunk_size=16, backend="host",
    )
    assert result.received == 50
    for a, b in ((plain_out, out), (plain_excl, excl)):
        with open(a, "rb") as fa, open(b, "rb") as fb:
            assert fa.read() == fb.read(), b


# --- kill-point sweep: crash at every checkpoint boundary -------------------


def _kill_sweep(tmp_path, config, points, chunk_size=12):
    from textblaster_tpu.errors import CheckpointError

    inp = str(tmp_path / "in.parquet")
    _write_input(inp)

    ref_out = str(tmp_path / "ref_out.parquet")
    ref_excl = str(tmp_path / "ref_excl.parquet")
    run_checkpointed(
        config, inp, ref_out, ref_excl,
        ckpt_dir=str(tmp_path / "ck_ref"), chunk_size=chunk_size,
        backend="host",
    )

    for point in points:
        out = str(tmp_path / f"out_{point}.parquet")
        excl = str(tmp_path / f"excl_{point}.parquet")
        ckpt = str(tmp_path / f"ck_{point}")
        with pytest.raises(CheckpointError, match="fault injection"):
            run_checkpointed(
                config, inp, out, excl, ckpt_dir=ckpt,
                chunk_size=chunk_size, backend="host",
                stop_after_chunks=point,
            )
        result = run_checkpointed(
            config, inp, out, excl, ckpt_dir=ckpt, chunk_size=chunk_size,
            backend="host",
        )
        assert result.received == 50, f"kill point {point}"
        for a, b in ((ref_out, out), (ref_excl, excl)):
            with open(a, "rb") as fa, open(b, "rb") as fb:
                assert fa.read() == fb.read(), f"kill point {point}: {b}"
        assert not os.path.exists(ckpt)


def test_kill_sweep_first_boundaries(tmp_path, config):
    _kill_sweep(tmp_path, config, points=(1, 2))


@pytest.mark.slow
def test_kill_sweep_every_boundary(tmp_path, config):
    # 50 rows / chunk_size 12 -> 5 chunks; kill after each committed chunk.
    _kill_sweep(tmp_path, config, points=(1, 2, 3, 4, 5))


# --- dead-letter sink end-to-end --------------------------------------------

BADWORDS_YAML = """
pipeline:
  - type: C4BadWordsFilter
    default_language: en
    keep_fraction: 0.0
    fail_on_missing_language: false
    seed: 1
resilience:
  backoff_base_s: 0.0
"""


@pytest.fixture
def synthetic_step_crash(monkeypatch):
    """Make C4BadWordsFilter raise a *hard* (non-filtered) error for docs
    tagged language 'xx' — the executor wraps it in StepError and the worker
    loop emits an Error outcome, the thing the dead-letter sink exists for.
    (No YAML-reachable step has a per-document hard-error path: badwords
    misses become DocumentFiltered by design, so the crash is synthesized.)
    """
    from textblaster_tpu.filters.c4_badwords import C4BadWordsFilter

    real = C4BadWordsFilter.process

    def process(self, document):
        if document.metadata.get("language") == "xx":
            raise RuntimeError("synthetic step crash for 'xx'")
        return real(self, document)

    monkeypatch.setattr(C4BadWordsFilter, "process", process)


def test_deadletter_e2e_and_default_unchanged(tmp_path, synthetic_step_crash):
    config = parse_pipeline_config(BADWORDS_YAML)
    inp = str(tmp_path / "in.parquet")
    # Every 4th row is tagged 'xx' -> hard Error outcome (see fixture).
    _write_input(inp, n=40, languages=("en", "en", "en", "xx"))

    # Default run: errored rows land in NEITHER file and no third file
    # appears anywhere.
    out0 = str(tmp_path / "d_out.parquet")
    excl0 = str(tmp_path / "d_excl.parquet")
    r0 = run_pipeline(config, inp, out0, excl0, backend="host", quiet=True)
    assert r0.errors == 10
    assert sorted(os.listdir(tmp_path)) == sorted(
        ["in.parquet", "d_out.parquet", "d_excl.parquet"]
    )

    # Opt-in run: same kept/excluded bytes, plus the dead-letter file.
    out1 = str(tmp_path / "e_out.parquet")
    excl1 = str(tmp_path / "e_excl.parquet")
    errs = str(tmp_path / "errors.parquet")
    r1 = run_pipeline(
        config, inp, out1, excl1, backend="host", quiet=True, errors_file=errs
    )
    assert r1.errors == 10
    for a, b in ((out0, out1), (excl0, excl1)):
        with open(a, "rb") as fa, open(b, "rb") as fb:
            assert fa.read() == fb.read()
    dead = pq.read_table(errs).to_pylist()
    assert len(dead) == 10
    assert all(r["step"] == "C4BadWordsFilter" for r in dead)
    assert all(r["worker"] == "host-0" for r in dead)
    assert all("synthetic step crash" in r["reason"] for r in dead)
    assert {r["id"] for r in dead} == {f"doc-{i}" for i in range(3, 40, 4)}
    assert all(r["metadata"] == '{"language":"xx"}' for r in dead)


def test_deadletter_checkpointed_crash_resume_no_dupes(
    tmp_path, synthetic_step_crash
):
    from textblaster_tpu.errors import CheckpointError

    config = parse_pipeline_config(BADWORDS_YAML)
    inp = str(tmp_path / "in.parquet")
    _write_input(inp, n=40, languages=("en", "en", "en", "xx"))

    out = str(tmp_path / "out.parquet")
    excl = str(tmp_path / "excl.parquet")
    errs = str(tmp_path / "errors.parquet")
    ckpt = str(tmp_path / "ck")
    with pytest.raises(CheckpointError, match="fault injection"):
        run_checkpointed(
            config, inp, out, excl, ckpt_dir=ckpt, chunk_size=12,
            backend="host", errors_file=errs, stop_after_chunks=2,
        )
    assert not os.path.exists(errs)  # dead-letter finalizes with the outputs
    result = run_checkpointed(
        config, inp, out, excl, ckpt_dir=ckpt, chunk_size=12,
        backend="host", errors_file=errs,
    )
    assert result.errors == 10
    dead = pq.read_table(errs).to_pylist()
    # Exactly one dead-letter row per errored doc: none lost before the
    # crash, none recorded twice across the resume.
    assert sorted(r["id"] for r in dead) == sorted(
        f"doc-{i}" for i in range(3, 40, 4)
    )
    assert not os.path.exists(ckpt)


def test_deadletter_includes_null_text_rows(tmp_path, config):
    inp = str(tmp_path / "in.parquet")
    rows = {
        "id": [f"doc-{i}" for i in range(10)],
        "text": [None if i == 4 else GOOD for i in range(10)],
    }
    pq.write_table(pa.table(rows), inp)
    out = str(tmp_path / "out.parquet")
    excl = str(tmp_path / "excl.parquet")
    errs = str(tmp_path / "errors.parquet")
    result = run_pipeline(
        config, inp, out, excl, backend="host", quiet=True, errors_file=errs
    )
    assert result.read_errors == 1
    dead = pq.read_table(errs).to_pylist()
    assert len(dead) == 1
    assert dead[0]["step"] == "read"
    assert "null text" in dead[0]["reason"]
