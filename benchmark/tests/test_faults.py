"""A whole run without the harness's look for a chip, on the CPU at a small
size: sound, it is correct; with the timed path broken underneath, it is
not.  Also: a run without a TPU exits non-zero and prints no result."""

import json
import os
import subprocess
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "tiny.json")


def _run(workload="danish_cc.mixed"):
    cell = harness.load_cell(workload)
    cell.mix_path = TINY
    harness.place_caches()
    return harness.run(cell, 2**31 + 99, 1.0, False, time.monotonic(),
                       require_tpu=False, ref_workers=1)


def _alter_answer(monkeypatch):
    from textblaster_tpu.ops.pipeline import CompiledPipeline

    orig = CompiledPipeline._assemble_row

    def altered(self, evals, row, doc):
        out = orig(self, evals, row, doc)
        if row == 3:
            doc.metadata["Detected language"] = "Swedish"
        return out

    monkeypatch.setattr(CompiledPipeline, "_assemble_row", altered)


def _drop_half(monkeypatch):
    from textblaster_tpu.ops.pipeline import CompiledPipeline

    orig = CompiledPipeline.process_chunk

    def half(self, docs):
        for i, outcome in enumerate(orig(self, docs)):
            if i % 2 == 0:
                yield outcome

    monkeypatch.setattr(CompiledPipeline, "process_chunk", half)


def test_sound_run_is_correct():
    line = _run()
    assert line["correct"] and line["failed"] == 0
    assert line["checks"] == {"mismatched_docs": {"value": 0, "limit": 0}}
    assert set(line["metrics"]) == {"docs_per_s", "setup_s"}
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("fault", [_alter_answer, _drop_half])
def test_broken_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    line = _run()
    assert not line["correct"]
    assert line["checks"]["mismatched_docs"]["value"] > 0


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "danish_cc.mixed",
         "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
