#!/usr/bin/env python3
"""Chip smoke run: the whole cleaning pipeline on a TPU, through the CLI.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the 4-device data-mesh path only

It writes a seeded Common-Crawl-like Parquet shard (16,384 documents in 4
row groups, Danish/English, lengths 60 chars to 30k chars) under the
gitignored ``.scratch/chip_smoke/``, runs ``textblast run --backend tpu`` on
the shipped ``configs/pipeline_config.yaml`` in this process, then
``--backend host`` on the same shard, and fails unless:

* JAX's first device is a TPU and the host has exactly ``--chips`` of them;
* the device and host-oracle outputs agree row for row (kept rows with
  their metadata, excluded rows with their filter reasons);
* the run report shows no retries, breaker trips or host-rung documents,
  and no document was rerouted to the host oracle;
* every Pallas gate reports its kernel compiled for the TPU (sort, scan,
  fused, dependency chain), and at every bucket of the ladder each traced
  phase program dispatches a scan kernel, the fused kernel runs wherever its
  gate admits the width, and every row sort is the Pallas sort (a width that
  fell back to ``lax.sort`` or a lax-only program fails the run);
* with ``--chips 4``, every chip held device memory (the shards were not all
  placed on device 0).

The docs/s it prints is a smoke number, not a benchmark: one pass, cold or
warm compile cache, oracle comparison in the same process.  The last line of
standard output is ``{"ok": true, "device": {...}}`` and appears only when
every check passed.  ``JAX_COMPILATION_CACHE_DIR``, when set, holds every
compiled artifact (JAX's cache and the serialized-executable store).
"""

from __future__ import annotations

import argparse
import collections
import json
import logging
import os
import sys
import time

N_DOCS = 16_384
SEED = 20261015
CONFIG = "configs/pipeline_config.yaml"


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


class _WarmupLog(logging.Handler):
    """Keeps the numbers of the pipeline's own warmup log record."""

    def __init__(self) -> None:
        super().__init__(logging.INFO)
        self.stats = None

    def emit(self, record: logging.LogRecord) -> None:
        if record.msg.startswith("warmup:"):
            keys = ("programs", "total_s", "trace_s", "compile_s",
                    "cache_load_s", "cache_hits", "programs_")
            self.stats = dict(zip(keys, record.args))


def _rows(path: str) -> list:
    import pyarrow.parquet as pq

    rows = pq.read_table(path).to_pylist()
    return sorted(rows, key=lambda r: r["id"])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="TPU chips on this host; 4 runs the data-mesh path")
    args = ap.parse_args()

    repo = os.path.dirname(os.path.abspath(__file__))
    os.chdir(repo)
    # TokenCounter(gpt2) must not reach for the network on an egress-less
    # machine: local hub cache, else the vendored stand-in.
    os.environ.setdefault("HF_HUB_OFFLINE", "1")

    import jax

    from textblaster_tpu import native
    from textblaster_tpu.cli import main as textblast
    from textblaster_tpu.config.pipeline import load_pipeline_config
    from textblaster_tpu.ops import pallas_scan, pallas_sort
    from textblaster_tpu.ops.pipeline import CompiledPipeline
    from textblaster_tpu.utils.compile_cache import default_aot_dir
    from textblaster_tpu.utils.metrics import METRICS
    from textblaster_tpu.utils.synthetic import write_cc_like_shard

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"JAX's first device is {dev.platform!r}, not a TPU")
    if len(devices) != args.chips:
        fail(f"{len(devices)} TPU chips visible, --chips {args.chips} asked")
    print(f"device: {dev.device_kind} x{len(devices)} ({dev.platform})", flush=True)
    print(f"native core loaded: {native.available()}", flush=True)

    work = os.path.join(repo, ".scratch", "chip_smoke")
    os.makedirs(work, exist_ok=True)
    shard = os.path.join(work, "shard.parquet")
    t = time.perf_counter()
    n_docs, n_bytes = write_cc_like_shard(shard, N_DOCS, SEED)
    print(f"shard: {n_docs} docs, {n_bytes / 1e6:.1f} MB UTF-8, 4 row groups "
          f"({time.perf_counter() - t:.1f}s)", flush=True)

    warm_log = _WarmupLog()
    pipe_logger = logging.getLogger("textblaster_tpu.ops.pipeline")
    pipe_logger.addHandler(warm_log)
    pipe_logger.setLevel(logging.INFO)

    def run(backend: str) -> dict:
        out = {k: os.path.join(work, f"{backend}-{k}") for k in
               ("kept.parquet", "excluded.parquet", "report.json")}
        t0 = time.perf_counter()
        rc = textblast([
            "run", "-i", shard, "-c", CONFIG, "--backend", backend,
            "-o", out["kept.parquet"], "-e", out["excluded.parquet"],
            "--run-report", out["report.json"], "--quiet",
        ])
        if rc != 0:
            fail(f"textblast run --backend {backend} exited {rc}")
        with open(out["report.json"], encoding="utf-8") as f:
            out["report"] = json.load(f)
        out["wall_s"] = time.perf_counter() - t0
        return out

    fallback_before = METRICS.get("worker_host_fallback_total")
    tails_before = METRICS.get("worker_host_tail_total")
    device = run("tpu")
    rerouted = int(METRICS.get("worker_host_fallback_total") - fallback_before)
    tails = int(METRICS.get("worker_host_tail_total") - tails_before)

    ws = warm_log.stats
    if ws is None:
        fail("the device run logged no warmup")
    print(f"warmup: {ws['programs']} programs in {ws['total_s']:.1f}s "
          f"(trace {ws['trace_s']:.1f}s, compile {ws['compile_s']:.1f}s summed "
          f"over threads, {ws['cache_hits']}/{ws['programs']} executable-store "
          "hits)", flush=True)
    print(f"compile cache: jax {jax.config.jax_compilation_cache_dir}, "
          f"executables {default_aot_dir()}", flush=True)

    counts = device["report"]["counts"]
    print(f"device run: {counts['received']} docs in {device['wall_s']:.1f}s "
          f"= {counts['received'] / device['wall_s']:.1f} docs/s, warmup "
          "included (smoke number, not a benchmark); "
          f"{counts['success']} kept, {counts['filtered']} excluded, "
          f"{counts['errors']} errored, {tails} end-of-stream tail docs on the "
          "host", flush=True)

    res = device["report"]["resilience"]
    faults = {k: res.get(k, 0) for k in (
        "resilience_retries_total", "resilience_breaker_trips_total",
        "resilience_ladder_host_total")}
    faults["worker_host_fallback_total"] = rerouted
    if any(faults.values()):
        fail(f"the device path fell back: {faults}")

    gates = pallas_scan.probe_kernels()
    print(f"pallas gates: {gates}, interpret mode: "
          f"{pallas_sort.interpret_forced()}", flush=True)
    if not all(gates.values()) or pallas_sort.interpret_forced():
        fail("a Pallas kernel is not running compiled on the TPU")

    mesh = None
    if len(devices) > 1:
        from textblaster_tpu.parallel.mesh import data_mesh

        mesh = data_mesh()
    probe = CompiledPipeline(load_pipeline_config(CONFIG), mesh=mesh)
    for length in probe.geometry.buckets:
        per_phase = [probe.scan_dispatch_counts(length, p)
                     for p in range(len(probe.phases))]
        print(f"kernel dispatches, bucket {length}: {per_phase}", flush=True)
        total = collections.Counter()
        for c in per_phase:
            total.update(c)
        if not all(c.get("fused", 0) + c.get("pallas_scan", 0) for c in per_phase):
            fail(f"a bucket-{length} program traced no Pallas scan kernel")
        rows = probe.geometry.batch_for(length) // len(devices)
        if pallas_scan.fused_scan_ok(rows, length) and not total["fused"]:
            fail(f"the bucket-{length} programs traced no fused kernel")
        if total["lax_sort"] or not total["pallas_sort"]:
            fail(f"bucket {length}: a row sort took lax.sort ({dict(total)})")

    if len(devices) > 1:
        peaks = [d.memory_stats()["peak_bytes_in_use"] for d in devices]
        print(f"peak device bytes per chip: {peaks}", flush=True)
        if min(peaks) < 2**20:
            fail("a chip held no data: the mesh did not spread the shards")

    host = run("host")
    print(f"host oracle: {host['report']['counts']['received']} docs in "
          f"{host['wall_s']:.1f}s", flush=True)
    for part in ("kept.parquet", "excluded.parquet"):
        d_rows, h_rows = _rows(device[part]), _rows(host[part])
        if d_rows != h_rows:
            bad = next((i for i, (a, b) in enumerate(zip(d_rows, h_rows))
                        if a != b), min(len(d_rows), len(h_rows)))
            fail(f"{part}: device and host oracle differ ({len(d_rows)} vs "
                 f"{len(h_rows)} rows, first at sorted row {bad})")
    print(f"parity: {counts['success']} kept and {counts['filtered']} excluded "
          "rows identical to the host oracle", flush=True)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
