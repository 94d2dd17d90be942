"""One run of one benchmark cell.

Set-up (counted in ``setup_s``, from process start to the first admitted
document): the generator process starts writing the cell's seeded shards,
JAX reaches the chip, the program builds its ``CompiledPipeline`` for the
cell's configuration with the default geometry and warms it through its own
``maybe_warmup`` (executable store, else compile), and the host executors
are built once.

The window drives what ``textblast run --backend tpu`` drives, in the same
order: the program's Parquet reader (``orchestration.read_documents``) over
the generator's shards behind the overlap read-ahead
(``utils.overlap.prefetch_iter``), ``ops.pipeline.process_documents_device``
with the warmed pipeline, and ``orchestration.aggregate_results_from_stream``
writing the kept and excluded Parquet files.  The loop is closed: the
pipeline pulls documents as fast as it takes them.  Admission stops at the
first block boundary after ``seconds``; the window ends when the last
admitted document's outcome is written.

After the window: the device's peak memory, the metric readers, then the
plain reference over every admitted document (``compare.py``).
"""

from __future__ import annotations

import importlib.util
import json
import multiprocessing as mp
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterator, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
STATE = os.path.join(ROOT, ".bench")
CACHE_DIR = os.path.join(STATE, "cache", "jax")

#: Executable-store cap (MB): one cell's 30 programs are 10-46 MB each.
AOT_CACHE_MB = "4096"

#: Parquet read batch of ``textblast run`` (its ``read_batch_size``).
READ_BATCH = 1024

#: Blocks the generator may run ahead of the reader.
GENERATOR_AHEAD = 3


class BenchError(RuntimeError):
    """A run that cannot be measured: it exits non-zero with no result."""


@dataclass
class Cell:
    name: str
    config: str
    traffic: str
    chips: int
    pipeline_yaml: str
    mix_path: str
    metrics: Dict[str, str] = field(default_factory=dict)  # name -> unit
    end_to_end: List[str] = field(default_factory=list)
    per_layer: List[str] = field(default_factory=list)


def load_cell(name: str, bench_json: Optional[str] = None) -> Cell:
    """The cell ``name`` of BENCHMARK.json, with the metrics it reports."""
    path = bench_json or os.path.join(ROOT, "BENCHMARK.json")
    with open(path, encoding="utf-8") as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    config = next(c for c in spec["configs"] if c["name"] == w["config"])
    pipeline_yaml = os.path.join(ROOT, config["file"])[: -len(".json")] + ".yaml"
    from benchmark.generator import mix_path

    cell = Cell(name, w["config"], w["traffic"], int(w["chips"]), pipeline_yaml,
                mix_path(w["traffic"]))
    for m in spec["end_to_end"]:
        if name in m.get("workloads", [name]):
            cell.end_to_end.append(m["name"])
            cell.metrics[m["name"]] = m["unit"]
    for m in spec["per_layer"]:
        listed = m.get("workloads")
        if (name in listed) if listed is not None else (m["moves"] in cell.end_to_end):
            cell.per_layer.append(m["name"])
            cell.metrics[m["name"]] = m["unit"]
    return cell


def place_caches() -> None:
    """Fixed in-checkout compile cache and executable store, the store's
    cap raised so one cell's programs fit, no hub cache or network for the
    tokenizer.  Must run before JAX is imported."""
    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["TEXTBLAST_AOT_CACHE_MB"] = AOT_CACHE_MB
    os.environ["HF_HUB_OFFLINE"] = "1"
    os.environ["HF_HOME"] = os.path.join(STATE, "hf-none")


class Feed:
    """The generator process, and the reader's side of its shards."""

    def __init__(self, mix_file: str, seed: int, out_dir: str) -> None:
        from benchmark import generator

        self._gen = generator
        self.out_dir = out_dir
        os.makedirs(out_dir, exist_ok=True)
        ctx = mp.get_context("spawn")
        self.consumed = ctx.Value("i", 0)
        self._stop = ctx.Event()
        self.proc = ctx.Process(
            target=generator.serve,
            args=(mix_file, seed, out_dir, GENERATOR_AHEAD, self.consumed, self._stop),
            daemon=True,
        )
        self.proc.start()
        self.wait_s = 0.0

    def shard(self, block: int) -> str:
        return self._gen.shard_path(self.out_dir, block)

    def documents(self, read_documents: Callable) -> Iterator:
        """Every document of the stream, shard after shard, through the
        program's reader; counts the time spent waiting for the generator."""
        block = 0
        while True:
            path = self.shard(block)
            t = time.monotonic()
            while not os.path.exists(path):
                if not self.proc.is_alive():
                    raise BenchError("the traffic generator stopped")
                time.sleep(0.002)
            self.wait_s += time.monotonic() - t
            yield from read_documents(path, batch_size=READ_BATCH)
            block += 1
            self.consumed.value = block

    def close(self) -> None:
        self._stop.set()
        self.proc.join(30)
        if self.proc.is_alive():
            self.proc.terminate()
            self.proc.join(10)


class Admission:
    """Admits documents until the first block boundary after the
    deadline; the first admission starts the window."""

    def __init__(self, docs, seconds: float, block: int, annotate: bool) -> None:
        self._docs = iter(docs)
        self.seconds = seconds
        self.block = block
        self.count = 0
        self.t_first: Optional[float] = None
        self._deadline = 0.0
        self._done = False
        self._annotate = annotate
        self._span = None

    def __iter__(self):
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        if self.count % self.block == 0:
            now = time.monotonic()
            if self.t_first is None:
                self.t_first = now
                self._deadline = now + self.seconds
                if self._annotate:
                    import jax

                    self._span = jax.profiler.TraceAnnotation("bench_window")
                    self._span.__enter__()
            elif now >= self._deadline:
                self._done = True
                raise StopIteration
        item = next(self._docs)
        self.count += 1
        return item

    def close(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None


def devices_for(chips: int, require_tpu: bool):
    """The devices the cell runs on; without the chips it asks for, a
    BenchError (no result is printed)."""
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (first device: {devs[0].platform})")
    if len(devs) < chips:
        raise BenchError(f"{len(devs)} device(s) found, the cell asks for {chips}")
    return devs[:chips]


def device_peaks(kind: str) -> Dict:
    with open(os.path.join(BENCH, "peaks.json"), encoding="utf-8") as f:
        peaks = json.load(f)["devices"]
    if kind not in peaks:
        raise BenchError(f"no peaks for device kind {kind!r} in benchmark/peaks.json")
    return peaks[kind]


def read_metrics(names: List[str], record: Dict) -> Dict[str, float]:
    """Each metric from its reader ``benchmark/metrics/<name>.py``; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for name in names:
        path = os.path.join(BENCH, "metrics", name + ".py")
        spec = importlib.util.spec_from_file_location(f"benchmark_metric_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(record)
        if value is not None:
            out[name] = value
    return out


def _warm_host_path(pipeline) -> None:
    """Build the host executors (language model, tokenizer, native core)
    now rather than at the first host tail inside the window."""
    from textblaster_tpu import native
    from textblaster_tpu.data_model import TextDocument
    from textblaster_tpu.errors import StepError

    native.available()
    doc = TextDocument(id="warm", source="warm", content="Det er en god dag. " * 20)
    for ex in (pipeline.host_executor, pipeline.host_suffix_executor):
        try:
            ex.run_single(doc)
        except StepError:
            pass


class Bench:
    """Set-up shared by every window of one process: the devices, the
    program's pipeline for the cell's configuration, built with the default
    geometry and warmed through its own ``maybe_warmup``, and the host
    executors."""

    def __init__(self, cell: Cell, require_tpu: bool = True) -> None:
        from textblaster_tpu.config.pipeline import load_pipeline_config
        from textblaster_tpu.ops.pipeline import CompiledPipeline, maybe_warmup
        from textblaster_tpu.utils.compile_cache import enable_compilation_cache

        self.cell = cell
        #: Host-clock instants that end each part of set-up.
        self.marks: Dict[str, float] = {}
        self.devs = devices_for(cell.chips, require_tpu)
        self.marks["chip"] = time.monotonic()
        self.peaks = device_peaks(self.devs[0].device_kind) if require_tpu else None
        enable_compilation_cache()
        self.config = load_pipeline_config(cell.pipeline_yaml)
        mesh = None
        if cell.chips > 1:
            from textblaster_tpu.parallel.mesh import data_mesh

            mesh = data_mesh()
        self.pipeline = CompiledPipeline(self.config, mesh=mesh)
        self.marks["build"] = time.monotonic()
        self.warm = maybe_warmup(self.pipeline)
        self.marks["warmup"] = time.monotonic()
        _warm_host_path(self.pipeline)
        self.marks["host"] = time.monotonic()

    def window(self, feed: Feed, seconds: float, block: int, run_dir: str,
               trace: bool) -> Dict:
        """One measured window over ``feed``; returns its record."""
        import jax

        from textblaster_tpu.ops.pipeline import process_documents_device
        from textblaster_tpu.orchestration import (
            aggregate_results_from_stream,
            read_documents,
        )
        from textblaster_tpu.utils.metrics import METRICS
        from textblaster_tpu.utils.overlap import prefetch_iter

        oc = self.config.overlap
        overlapped = oc.enabled
        docs = feed.documents(read_documents)
        if overlapped:
            docs = prefetch_iter(docs, depth=oc.read_ahead, block=max(64, READ_BATCH // 4))
        gate = Admission(docs, seconds, block, annotate=trace)
        kept = os.path.join(run_dir, "output", "kept.parquet")
        excluded = os.path.join(run_dir, "output", "excluded.parquet")
        trace_dir = os.path.join(run_dir, "trace")
        before = METRICS.all_values()
        wait_before = feed.wait_s
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            outcomes = process_documents_device(self.config, gate, pipeline=self.pipeline)
            result = aggregate_results_from_stream(
                outcomes, kept, excluded, write_queue=oc.write_queue if overlapped else 0
            )
            t_end = time.monotonic()
        finally:
            gate.close()
            if trace:
                jax.profiler.stop_trace()
            if overlapped:
                docs.close()
        after = METRICS.all_values()
        if gate.t_first is None:
            raise BenchError("no document was admitted")
        peak = 0
        for d in self.devs:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        return {
            "docs": gate.count,
            "t_first": gate.t_first,
            "window_s": t_end - gate.t_first,
            "counters": {k: v - before.get(k, 0.0) for k, v in after.items() if "::" not in k},
            "warmup": self.warm.to_dict() if self.warm is not None else None,
            "outcomes": {"kept": result.success, "excluded": result.filtered,
                         "errors": result.errors},
            "generator_wait_s": feed.wait_s - wait_before,
            "memory_peak_bytes": peak,
            "kept": kept,
            "excluded": excluded,
            "trace_dir": trace_dir if trace else None,
        }


def check(cell: Cell, feed: Feed, record: Dict, block: int, workers: int,
          precision: str = "float64"):
    """(mismatched documents, notes, documents written, the outcomes the
    reference gives) for the window in ``record``."""
    from benchmark import compare

    n = record["docs"]
    ids, texts = compare.admitted_docs((feed.shard(b) for b in range(n // block + 1)), n)
    expected = compare.reference_outcomes(cell.pipeline_yaml, texts, workers, precision)
    got = compare.written(record["kept"], record["excluded"])
    bad, notes = compare.mismatches(ids, expected, got)
    return bad, notes, sum(1 for i in ids if i in got), (ids, texts, expected)


def reference_workers() -> int:
    return max(1, min(12, (os.cpu_count() or 2) - 1))


def run(cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
        require_tpu: bool = True, ref_workers: Optional[int] = None,
        keep: Optional[str] = None) -> Dict:
    """One run: set-up, one window, its metrics and the reference check.
    Returns the result line as a dict (``checks`` last)."""
    from benchmark import generator, trace_reduce

    block = int(generator.load_mix(cell.mix_path)["block_docs"])
    run_dir = os.path.join(STATE, "runs", cell.name)
    shutil.rmtree(run_dir, ignore_errors=True)
    feed = Feed(cell.mix_path, seed, os.path.join(run_dir, "input"))
    try:
        bench = Bench(cell, require_tpu)
        record = bench.window(feed, seconds, block, run_dir, trace)
    finally:
        feed.close()
    devs, peaks = bench.devs, bench.peaks
    marks = [t0] + list(bench.marks.values()) + [record["t_first"]]
    record["setup_parts_s"] = dict(zip(list(bench.marks) + ["feed"],
                                       (b - a for a, b in zip(marks, marks[1:]))))
    del bench
    record["setup_s"] = record["t_first"] - t0
    record["trace"] = None
    if trace:
        xplane = trace_reduce.find_xplane(record["trace_dir"])
        record["trace"] = trace_reduce.reduce(trace_reduce.extract(xplane))
    values = read_metrics(cell.per_layer if trace else cell.end_to_end, record)
    bad, notes, n_written, _ = check(cell, feed, record, block,
                                     ref_workers or reference_workers())
    if keep:
        os.makedirs(keep, exist_ok=True)
        if record["trace_dir"]:
            shutil.copytree(record["trace_dir"], os.path.join(keep, "trace"), dirs_exist_ok=True)
        with open(os.path.join(keep, "record.json"), "w", encoding="utf-8") as f:
            json.dump(record, f, indent=1, default=str)
    shutil.rmtree(run_dir, ignore_errors=True)

    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs),
              "memory_peak_bytes": record["memory_peak_bytes"]}
    line = {
        "correct": bad == 0 and record["docs"] > 0,
        "attempted": record["docs"],
        "failed": record["docs"] - n_written,
        "metrics": {k: {"value": v, "unit": cell.metrics[k]} for k, v in values.items()},
        "device": device,
    }
    reduced = record["trace"]
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = trace_reduce.breakdown(reduced)
    for key in ("window_s", "setup_s", "setup_parts_s", "generator_wait_s", "outcomes",
                "warmup"):
        line[key] = record[key]
    if peaks:
        line["memory_peak_share"] = record["memory_peak_bytes"] / peaks["hbm_bytes"]
    for note in notes:
        print(f"mismatch: {note}", file=sys.stderr)
    line["checks"] = {"mismatched_docs": {"value": bad, "limit": 0}}
    return line
