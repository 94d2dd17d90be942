"""A data mesh driven by one process takes the one-chip path on every chip.

``process_documents_device`` dispatches every batch for every local chip, so
such a mesh gets what one chip has: the u16 wire, the in-flight window, the
ladder's split rung, leftover groups routed by fill onto the warm half-row
programs, and a default geometry that gives each chip the one-chip rows.
Runs on 4 of the suite's 8 virtual CPU devices; the decisions are held to the
benchmark's plain reference (``benchmark/reference``), which imports nothing
of the program.
"""

import os

import jax
import numpy as np
import pytest
import yaml

from benchmark import compare, generator
from benchmark.reference import Reference
from textblaster_tpu.config.pipeline import load_pipeline_config
from textblaster_tpu.data_model import TextDocument
from textblaster_tpu.ops import pipeline as ops_pipeline
from textblaster_tpu.ops.pipeline import (
    CompiledPipeline,
    default_batch_size,
    process_documents_device,
)
from textblaster_tpu.orchestration import aggregate_results_from_stream
from textblaster_tpu.parallel import mesh as mesh_mod
from textblaster_tpu.parallel.mesh import data_mesh
from textblaster_tpu.utils.metrics import METRICS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DANISH_CC = os.path.join(ROOT, "benchmark", "configs", "danish_cc.yaml")
CHIPS = 4


@pytest.fixture
def mesh4():
    return data_mesh(jax.devices()[:CHIPS])


def test_default_geometry_gives_each_chip_the_one_chip_rows(mesh4):
    config = load_pipeline_config(DANISH_CC)
    buckets = (512, 2048)
    one = default_batch_size(buckets)
    p = CompiledPipeline(config, buckets=buckets, mesh=mesh4)
    assert p.one_controller and p.chips == CHIPS
    assert p.geometry.batch_sizes == (CHIPS * one,) * len(buckets)
    assert p.geometry.source == "default"
    # An explicit batch is the global batch, split over the chips.
    q = CompiledPipeline(config, buckets=buckets, batch_size=64, mesh=mesh4)
    assert q.geometry.batch_sizes == (64, 64)
    # A multi-host runtime's mesh keeps the batch as given, and its wire.
    r = CompiledPipeline(config, buckets=buckets, mesh=mesh4, multihost=True)
    assert not r.one_controller and not r.wire_u16
    assert r.geometry.batch_sizes == (one, one)


@pytest.mark.parametrize(
    "full,chips,half",
    [(256, 4, 128), (64, 4, 32), (96, 4, 64), (16, 4, 16), (512, 8, 256), (64, 1, 32)],
)
def test_half_rows_keep_the_row_tile_on_every_chip(full, chips, half):
    got = CompiledPipeline._split_rows(full, chips)
    assert got == half
    assert got == full or got % (chips * 8) == 0


def test_mesh_warm_half_rows_are_tile_multiples(mesh4):
    p = CompiledPipeline(
        load_pipeline_config(DANISH_CC), buckets=(512, 2048), batch_size=256,
        mesh=mesh4,
    )
    rows = sorted({r for *_, r in p._warmup_jobs()})
    assert rows == [128, 256]
    assert all(r % (CHIPS * 8) == 0 for r in rows)


def test_mesh_takes_the_one_chip_path_and_matches_the_reference(
    mesh4, monkeypatch, tmp_path
):
    # The accelerator's wire and leftover rule on the CPU mesh.
    monkeypatch.setenv("TEXTBLAST_WIRE", "u16")
    monkeypatch.setenv("TEXTBLAST_HOST_TAILS", "on")
    monkeypatch.setattr(ops_pipeline, "_tails_by_fill", lambda: True)
    config = load_pipeline_config(DANISH_CC)
    config.overlap.pipeline_depth = 3
    mix = generator.load_mix(generator.mix_path("mixed"))
    ids, texts = generator.block_docs(mix, 2**31 + 77, 0)
    ids, texts = ids[:240], texts[:240]

    pipeline = CompiledPipeline(
        config, buckets=(512, 2048), batch_size=64, mesh=mesh4
    )
    assert pipeline.wire_u16
    dispatched = []
    real_dispatch = pipeline.dispatch_batch

    def spy(batch, phase=0):
        dispatched.append(batch.batch_size)
        return real_dispatch(batch, phase)

    monkeypatch.setattr(pipeline, "dispatch_batch", spy)
    inflight = []
    real_set = METRICS.set

    def watch(name, value):
        if name == "inflight_batches":
            inflight.append(value)
        return real_set(name, value)

    monkeypatch.setattr(METRICS, "set", watch)
    wire = []
    real_shard = mesh_mod.shard_batch

    def shard_spy(mesh, cps, lengths):
        wire.append(cps.dtype)
        return real_shard(mesh, cps, lengths)

    monkeypatch.setattr(mesh_mod, "shard_batch", shard_spy)
    device_tails0 = METRICS.get("worker_device_tail_total")
    upload0 = METRICS.get("stage_mesh_upload_seconds")

    docs = [TextDocument(id=i, source="s", content=t) for i, t in zip(ids, texts)]
    kept, excluded = str(tmp_path / "kept.parquet"), str(tmp_path / "excl.parquet")
    aggregate_results_from_stream(
        process_documents_device(config, iter(docs), pipeline=pipeline),
        kept, excluded,
    )

    with open(DANISH_CC, encoding="utf-8") as f:
        ref = Reference(yaml.safe_load(f)["pipeline"], "float64")
    bad, notes = compare.mismatches(
        ids, [ref(t) for t in texts], compare.written(kept, excluded)
    )
    assert bad == 0, notes
    # Leftover groups rode the warm half-row program (8 rows per chip).
    assert 32 in dispatched
    assert METRICS.get("worker_device_tail_total") > device_tails0
    assert set(wire) == {np.dtype(np.uint16)}
    assert max(inflight) > 1  # the window kept more than one batch in flight
    assert METRICS.get("stage_mesh_upload_seconds") > upload0
