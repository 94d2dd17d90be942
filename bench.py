"""Benchmark: full-pipeline docs/sec/chip, device path vs CPU oracle baseline.

Measures the BASELINE.json metric — documents/second/chip through the full
Danish cleaning pipeline (langid + Gopher repetition + Gopher quality + C4 +
FineWeb) at decision parity with the CPU reference path — on a synthetic
CC-MAIN-like shard (seeded generator; the environment has no network for a
real CC fetch).

Always prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "docs/s", "vs_baseline": N, ...}
where vs_baseline is the speedup of the compiled device path over the
single-process CPU oracle on the same shard.  Extra fields record the
platform actually used and decision parity.

Platform: ``BENCH_PLATFORM``, else the first entry of ``JAX_PLATFORMS``, else
``tpu``.  A platform that is asked for and does not come up is an error —
there is no fallback to the CPU.

Usage:
  python bench.py            # headline full-pipeline metric
  python bench.py c4         # one of the BASELINE.json configs:
                             #   c4 | gopher_quality | gopher_rep | langid | full
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Optional

import numpy as np

N_DOCS = 4096
# Oracle runs the FULL corpus (no subsample extrapolation): at measured
# oracle rates (600-7000 docs/s) a 4096-doc pass costs single-digit seconds,
# and decision parity is then checked on every document.  BENCH_CPU_SAMPLE
# overrides for quick experiments.
CPU_SAMPLE = int(os.environ.get("BENCH_CPU_SAMPLE", str(N_DOCS)))
SEED = 20260729

# Long-doc config: fewer, much longer documents exercising the 8k-32k
# buckets that dominate compile time and were previously unmeasured
# (VERDICT r3 weak #9).  The mid bucket matters: without 16384 the p50~13k
# docs pad 2.4x and the scan-bound regime pays it directly (like-for-like
# CPU A/B: 33.1 -> 39.7 docs/s).
LONGDOC_N_DOCS = 512
# Scan-bound at padded width: the finer ladder cut padded compute from
# 1.48x to 1.21x of real chars and took the CPU record from 0.90x to 1.11x
# the oracle (partial batches cost little at 8-row batches).
LONGDOC_BUCKETS = (4096, 8192, 12288, 16384, 24576, 32768)

# Short-doc config: the skew the occupancy work targets.  Most web-crawl
# shards are dominated by sub-500-char documents; under the default ladder
# they all land in the 512 bucket but ride device batches sized for the
# ladder's widest program, so most padded codepoint lanes are waste.
# BENCH_AUTO_GEOMETRY=1 runs the same corpus through a calibrated geometry
# (ops/geometry.py) for the A/B.
SHORTDOC_N_DOCS = 8192

# Device batch rows.  BENCH_BATCH overrides; otherwise the platform-aware
# default from ops.pipeline.default_batch_size applies (TPU: large batches
# amortize per-dispatch cost; XLA:CPU: small batches keep the
# per-op working set L2-resident — the measured knee that flipped every
# sub-1.0 CPU config above the oracle).
def _device_batch() -> Optional[int]:
    raw = os.environ.get("BENCH_BATCH")
    if not raw:
        return None  # CompiledPipeline resolves the platform default
    try:
        n = int(raw)
    except ValueError:
        n = 0
    if n < 8:
        _log("bad BENCH_BATCH; using platform default")
        return None
    return n


def _bench_name() -> str:
    name = os.environ.get("BENCH_CONFIG", "full")
    if len(sys.argv) > 1:
        name = sys.argv[1]
    return name


def _metric_name(name: str) -> str:
    return (
        "docs_per_sec_per_chip_full_danish_pipeline"
        if name == "full"
        else f"docs_per_sec_per_chip_{name}"
    )

# Length buckets: every generated doc fits in 2048 chars; bucketing cuts the
# average padded row vs one 4096 bucket (the per-bucket programs are smaller
# and compile faster too; the persistent cache in .cache/jax makes repeat
# runs near-instant).  BENCH_BUCKETS=comma,separated overrides.  The CPU
# default adds a 1536 bucket (+8-11% measured: docs in (1024,1536] stop
# paying the 2048-row cost); the TPU default keeps three buckets — each
# extra bucket is one more set of programs to compile at warmup.
_DEFAULT_BUCKETS = (512, 1024, 1536, 2048)
_TPU_BUCKETS = (512, 1024, 2048)


def buckets_for_platform(platform: str, bench_name: str = "full"):
    if os.environ.get("BENCH_BUCKETS"):
        return _buckets()
    if bench_name == "longdoc":
        return LONGDOC_BUCKETS
    # "shortdoc" deliberately keeps the default ladder: the config exists to
    # measure what corpus-blind geometry costs on a short-skewed corpus (and
    # what BENCH_AUTO_GEOMETRY=1 recovers).
    return _DEFAULT_BUCKETS if platform == "cpu" else _TPU_BUCKETS


def _buckets():
    raw = os.environ.get("BENCH_BUCKETS")
    if not raw:
        return _DEFAULT_BUCKETS
    try:
        bs = tuple(sorted(int(x) for x in raw.split(",") if x.strip()))
    except ValueError:
        bs = ()
    # The largest bucket must fit the generated docs (max 1901 chars +
    # packer margin) or the "device" rate quietly measures the host
    # fallback path instead.
    if not bs or any(b < 64 for b in bs) or max(bs) < 2048:
        print(
            f"[bench] bad BENCH_BUCKETS={raw!r}; using {_DEFAULT_BUCKETS}",
            file=sys.stderr,
        )
        return _DEFAULT_BUCKETS
    return bs


BUCKETS = _buckets()

_T0 = time.perf_counter()


def _log(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - _T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


def _resolve_platform() -> str:
    """The platform asked for: BENCH_PLATFORM, else the first entry of
    JAX_PLATFORMS, else tpu."""
    forced = os.environ.get("BENCH_PLATFORM", "").strip()
    if forced:
        return forced
    listed = os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip()
    return listed or "tpu"


from textblaster_tpu.utils.synthetic import (  # noqa: E402
    DANISH_WORDS as _DANISH_WORDS,
    ENGLISH_WORDS as _ENGLISH_WORDS,
)


def _make_longdocs(rng: np.random.Generator):
    """Long documents (~4k-30k chars): web-dump pages, transcripts, listy
    boilerplate — the raggedness axis SURVEY.md §5 calls out."""
    from textblaster_tpu.data_model import TextDocument

    docs = []
    for i in range(LONGDOC_N_DOCS):
        kind = rng.random()
        words = _DANISH_WORDS if kind < 0.7 else _ENGLISH_WORDS
        n_sentences = int(rng.integers(60, 420))
        lines = []
        for _ in range(n_sentences):
            n_w = int(rng.integers(4, 18))
            ws = [words[int(rng.integers(0, len(words)))] for _ in range(n_w)]
            lines.append(" ".join(ws).capitalize() + ".")
        parts = []
        j = 0
        while j < len(lines):
            k = int(rng.integers(1, 6))
            parts.append(" ".join(lines[j : j + k]))
            j += k
        content = "\n".join(parts)
        if kind > 0.95:
            # Dense repetition at length: the dup-table worst case.
            content = ("Samme lange linje her igen og igen.\n" * 200)[:8000]
        docs.append(TextDocument(id=f"ldoc-{i}", source="bench", content=content))
    return docs


def _make_shortdocs(rng: np.random.Generator):
    """Short-doc-skewed corpus (~85% under 500 chars, thin long tail): the
    length distribution where corpus-blind geometry wastes the most padded
    lanes."""
    from textblaster_tpu.data_model import TextDocument

    docs = []
    for i in range(SHORTDOC_N_DOCS):
        kind = rng.random()
        words = _DANISH_WORDS if kind < 0.7 else _ENGLISH_WORDS
        # 85% of docs: 1-4 sentences (~60-450 chars); 15%: the usual 3-28
        # sentence spread up to ~1900 chars.
        n_sentences = int(
            rng.integers(1, 5) if rng.random() < 0.85 else rng.integers(3, 28)
        )
        lines = []
        for _ in range(n_sentences):
            n_w = int(rng.integers(4, 18))
            ws = [words[int(rng.integers(0, len(words)))] for _ in range(n_w)]
            lines.append(" ".join(ws).capitalize() + ".")
        docs.append(
            TextDocument(
                id=f"sdoc-{i}", source="bench", content="\n".join(lines)
            )
        )
    return docs


def _make_docs(rng: np.random.Generator):
    from textblaster_tpu.data_model import TextDocument

    docs = []
    for i in range(N_DOCS):
        kind = rng.random()
        words = _DANISH_WORDS if kind < 0.7 else _ENGLISH_WORDS
        # Max doc ~28 sentences x ~130 chars; the pinned-seed max is 1901
        # chars, which must stay under the largest bucket minus the packer
        # margin (2048-4) or the "device" rate measures the host fallback.
        n_sentences = int(rng.integers(3, 28))
        lines = []
        for _ in range(n_sentences):
            n_w = int(rng.integers(4, 18))
            ws = [words[int(rng.integers(0, len(words)))] for _ in range(n_w)]
            sent = " ".join(ws).capitalize() + "."
            lines.append(sent)
        # Group sentences into lines/paragraphs like web text.
        content_parts = []
        j = 0
        while j < len(lines):
            k = int(rng.integers(1, 5))
            content_parts.append(" ".join(lines[j : j + k]))
            j += k
        content = "\n".join(content_parts)
        if kind > 0.95:
            content = "Samme linje her igen.\n" * int(rng.integers(5, 30))
        elif kind > 0.9:
            content = content[: int(rng.integers(10, 60))]
        docs.append(TextDocument(id=f"doc-{i}", source="bench", content=content))
    return docs


# The BASELINE.json benchmark configs.  BENCH_CONFIG selects one; the default
# "full" is the headline metric the driver records.
_BENCH_CONFIGS = {
    # C4QualityFilter single-step pipeline (10k-doc Parquet shard)
    "c4": """
pipeline:
  - type: C4QualityFilter
    split_paragraph: true
    remove_citations: true
    filter_no_terminal_punct: true
    min_num_sentences: 5
    min_words_per_line: 3
    max_word_length: 1000
    filter_lorem_ipsum: true
    filter_javascript: true
    filter_curly_bracket: true
    filter_policy: true
""",
    # GopherQualityFilter (word-count / symbol-ratio / stop-word heuristics)
    "gopher_quality": """
pipeline:
  - type: GopherQualityFilter
    min_doc_words: 50
    max_doc_words: 100000
    min_avg_word_length: 3.0
    max_avg_word_length: 10.0
    max_symbol_word_ratio: 0.1
    max_bullet_lines_ratio: 0.9
    max_ellipsis_lines_ratio: 0.3
    max_non_alpha_words_ratio: 0.8
    min_stop_words: 2
    stop_words: [og, er, det, en, vi, at, den, i]
""",
    # GopherRepetitionFilter (duplicate line/para + n-gram frequency)
    "gopher_rep": """
pipeline:
  - type: GopherRepetitionFilter
    dup_line_frac: 0.3
    dup_para_frac: 0.3
    dup_line_char_frac: 0.2
    dup_para_char_frac: 0.2
    top_n_grams: [[2, 0.2], [3, 0.18], [4, 0.16]]
    dup_n_grams: [[5, 0.15], [6, 0.14], [7, 0.13], [8, 0.12], [9, 0.11], [10, 0.1]]
""",
    # LanguageDetectionFilter (langid, en-only keep)
    "langid": """
pipeline:
  - type: LanguageDetectionFilter
    min_confidence: 0.65
    allowed_languages: [eng]
""",
    # C4BadWordsFilter at realistic list scale (~400 entries, ~20 distinct
    # pattern lengths — the per-length window-hash pass count is the device
    # cost driver; VERDICT r4 item 4).  The list is generated at bench start
    # (utils/synthwords.py) and wired via cache_base_path in _load_config.
    "badwords": """
pipeline:
  - type: C4BadWordsFilter
    default_language: en
    keep_fraction: 0.0
    fail_on_missing_language: true
""",
}

_BADWORDS_SEED = 515


def _badwords_cache_dir():
    import pathlib

    d = pathlib.Path(".scratch") / "bench_badwords_cache"
    d.mkdir(parents=True, exist_ok=True)
    from textblaster_tpu.utils.synthwords import synth_badwords

    words = synth_badwords(_BADWORDS_SEED, n=400)
    (d / "en").write_text("\n".join(words) + "\n", encoding="utf-8")
    return d, words


def _load_config(name: str):
    from textblaster_tpu.config.pipeline import parse_pipeline_config

    import yaml as _yaml

    if name == "badwords":
        config = parse_pipeline_config(_BENCH_CONFIGS[name])
        config.pipeline[0].params.cache_base_path, _ = _badwords_cache_dir()
        return config
    if name in _BENCH_CONFIGS:
        return parse_pipeline_config(_BENCH_CONFIGS[name])
    # "full" / "longdoc" / "shortdoc": the shipped Danish pipeline minus
    # TokenCounter
    # (host-side BPE step; the bench measures the device-covered filter
    # pipeline).
    with open("configs/pipeline_config.yaml", encoding="utf-8") as f:
        raw = _yaml.safe_load(f)
    raw["pipeline"] = [s for s in raw["pipeline"] if s["type"] != "TokenCounter"]
    return parse_pipeline_config(_yaml.safe_dump(raw))


def _bench_docs(name: str, rng: np.random.Generator):
    if name == "longdoc":
        return _make_longdocs(rng)
    if name == "shortdoc":
        return _make_shortdocs(rng)
    return _make_docs(rng)


def _fleet_child(name: str, k: int, n: int) -> None:
    """One fleet worker: build the oracle pipeline, process docs[k::n].

    Setup (imports, doc generation) happens before READY; the timed region
    is only the processing loop, so the measurement isolates steady-state
    contention from Python startup (both matter for a real fleet, but the
    reference's workers are long-lived — startup amortizes to zero there)."""
    os.chdir(os.path.dirname(os.path.abspath(__file__)))
    from textblaster_tpu.orchestration import process_documents_host
    from textblaster_tpu.pipeline_builder import build_pipeline_from_config

    config = _load_config(name)
    executor = build_pipeline_from_config(config)
    rng = np.random.default_rng(SEED)
    docs = _bench_docs(name, rng)[k::n]
    print("READY", flush=True)
    sys.stdin.readline()
    t0 = time.perf_counter()
    outcomes = list(process_documents_host(executor, iter(docs)))
    print(
        json.dumps(
            {"n": len(outcomes), "elapsed": round(time.perf_counter() - t0, 3)}
        ),
        flush=True,
    )


def _measure_fleet(name: str, n_workers: int):
    """Aggregate oracle docs/s with ``n_workers`` concurrent single-thread
    processes on this box.  Returns (aggregate_rate, per_child) or None."""
    import subprocess as sp

    procs = []
    try:
        for k in range(n_workers):
            procs.append(
                sp.Popen(
                    [
                        sys.executable,
                        "-c",
                        f"import bench; bench._fleet_child({name!r}, {k}, {n_workers})",
                    ],
                    stdin=sp.PIPE,
                    stdout=sp.PIPE,
                    stderr=sp.DEVNULL,
                    text=True,
                    # Oracle-only children: CPU by env, so none of them
                    # reaches for the chip this process holds.
                    env=dict(os.environ, JAX_PLATFORMS="cpu"),
                )
            )
        for p in procs:
            line = p.stdout.readline()
            if line.strip() != "READY":
                raise RuntimeError(f"fleet child failed: {line!r}")
        t0 = time.perf_counter()
        for p in procs:
            p.stdin.write("go\n")
            p.stdin.flush()
        per_child = [json.loads(p.stdout.readline()) for p in procs]
        wall = time.perf_counter() - t0
        for p in procs:
            p.wait(timeout=60)
        total_docs = sum(c["n"] for c in per_child)
        return total_docs / wall, per_child
    except Exception as e:  # noqa: BLE001
        _log(f"fleet measurement failed: {e}")
        return None
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()


def main() -> int:
    os.chdir(os.path.dirname(os.path.abspath(__file__)))
    bench_name = _bench_name()

    platform = _resolve_platform()
    _log(f"platform: {platform}")
    import jax

    jax.config.update("jax_platforms", platform)
    if platform == "cpu":
        # Packed-int64 sort2 path (~4.4x on XLA:CPU; pallas_sort.sort2).
        jax.config.update("jax_enable_x64", True)
    # Raises when the platform asked for does not come up.
    _log(f"devices: {jax.devices()}")
    from textblaster_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()

    from textblaster_tpu.ops.pipeline import (
        CompiledPipeline,
        process_documents_device,
    )
    from textblaster_tpu.orchestration import process_documents_host
    from textblaster_tpu.pipeline_builder import build_pipeline_from_config

    config = _load_config(bench_name)

    rng = np.random.default_rng(SEED)
    docs = _bench_docs(bench_name, rng)
    if bench_name == "badwords":
        _, _bw_words = _badwords_cache_dir()
        # ~5% of docs get a real (boundary-separated) list hit; ~0.5% get a
        # fold-hazard codepoint so the host-routing tax is measured honestly.
        for d in docs:
            r = rng.random()
            if r < 0.05:
                d.content += " " + _bw_words[int(rng.integers(0, len(_bw_words)))]
            elif r < 0.055:
                d.content += " ſ"
    cpu_sample = min(CPU_SAMPLE, len(docs))
    _log(f"generated {len(docs)} docs (max {max(len(d.content) for d in docs)} chars)")

    # --- CPU oracle baseline (single process; the reference-equivalent path).
    # Best-of-3 for both sides: the host's cores are shared, so any single
    # pass can eat a foreign CPU burst.  Taking the best pass for the oracle AND the device path
    # applies the same rule to both sides of the ratio; the per-pass raw
    # times and the 1-minute load average bracketing each side are recorded
    # so a contaminated record is *visibly* contaminated (VERDICT r4 item 3:
    # two rounds of driver-vs-evidence disagreement traced to foreign CPU
    # bursts landing inside one side's passes).
    executor = build_pipeline_from_config(config)
    load_before_oracle = os.getloadavg()[0]
    oracle_pass_s = []
    oracle_cpu_frac = []  # process_time/wall per pass: <1 => core was shared
    for _ in range(3):
        sample = [d.copy() for d in docs[:cpu_sample]]
        t0 = time.perf_counter()
        c0 = time.process_time()
        host_outcomes = list(process_documents_host(executor, iter(sample)))
        wall = time.perf_counter() - t0
        oracle_pass_s.append(round(wall, 3))
        oracle_cpu_frac.append(round((time.process_time() - c0) / wall, 3))
    load_after_oracle = os.getloadavg()[0]
    cpu_elapsed = min(oracle_pass_s)
    cpu_rate = len(sample) / cpu_elapsed
    _log(
        f"CPU oracle: {cpu_rate:.1f} docs/s over {len(sample)} docs "
        f"(passes {oracle_pass_s}, load {load_before_oracle:.2f}->"
        f"{load_after_oracle:.2f})"
    )

    # --- Fleet scaling measurement (VERDICT r4 item 9): the north-star
    # denominator is a 32-worker fleet, previously modeled as a pure 32x of
    # the single-core oracle.  Measure what concurrent worker processes
    # actually deliver on THIS box (full config only; BENCH_FLEET=0 skips).
    # On a 1-core box the workers time-slice one core, so the measured
    # aggregate is NOT a fleet measurement — it bounds scheduling+I/O
    # overhead, and the 32x-linear model stays as the (disclosed) upper
    # bound a real 32-core fleet cannot exceed.
    fleet = None
    if bench_name == "full" and os.environ.get("BENCH_FLEET", "1") != "0":
        measured = {}
        for n_workers in (2,):
            r = _measure_fleet(bench_name, n_workers)
            if r is not None:
                measured[str(n_workers)] = round(r[0], 2)
        if measured:
            n_cores = os.cpu_count() or 1
            fleet = {
                "workers_measured_docs_per_sec": measured,
                "singleproc_docs_per_sec": round(cpu_rate, 2),
                "box_cores": n_cores,
                "parallel_efficiency_2proc": round(
                    measured.get("2", 0.0) / cpu_rate, 3
                ),
                "model": "north_star = 32 x single-core oracle (upper bound)",
                "confound": (
                    "1-core box: concurrent workers time-slice the core; a "
                    "real fleet gives each worker its own core, so measured "
                    "aggregate here is a lower bound on per-worker efficiency"
                    if n_cores < 2
                    else "multi-core box: curve is directly meaningful"
                ),
            }
            _log(f"fleet scaling: {fleet['workers_measured_docs_per_sec']}")

    # --- Device path: warmup (compile) then timed run.  ONE CompiledPipeline
    # serves both, so the timed run executes already-warmed programs and
    # never bills a compile or an executable (re)load to the measurement.
    _log(f"device backend: {jax.default_backend()}")
    bench_buckets = buckets_for_platform(platform, bench_name)
    device_batch = _device_batch()
    # BENCH_AUTO_GEOMETRY=1: calibrate the device geometry from the corpus
    # (what `textblast run --auto-geometry` does from the stream head) and
    # run the same measurement through it — the occupancy A/B against the
    # default ladder above.
    geometry = None
    if os.environ.get("BENCH_AUTO_GEOMETRY") == "1":
        from textblaster_tpu.ops.geometry import calibrate_geometry

        geometry = calibrate_geometry(
            [len(d.content) for d in docs], backend=jax.default_backend()
        )
        _log(f"auto geometry: {geometry.describe()}")
    pipeline = CompiledPipeline(
        config,
        buckets=bench_buckets,
        batch_size=device_batch,
        geometry=geometry,
    )
    # Concurrent AOT compile of every (bucket, phase) program, then a
    # full-corpus warm pass (a small warm slice would leave some shapes cold
    # and bill their compiles to the timed run).  The parallel compiles cost
    # ~the slowest program instead of the sum.
    t0 = time.perf_counter()
    warm_stats = pipeline.warmup_parallel()
    compile_s = warm_stats.total_s
    _log(
        f"parallel AOT warmup done in {warm_stats.total_s:.1f}s "
        f"(trace {warm_stats.trace_s:.1f}s, compile {warm_stats.compile_s:.1f}s, "
        f"cache-load {warm_stats.cache_load_s:.2f}s, "
        f"{warm_stats.cache_hits}/{warm_stats.programs} AOT hits)"
    )
    warm = [d.copy() for d in docs]
    list(process_documents_device(config, iter(warm), pipeline=pipeline))
    warmup_s = time.perf_counter() - t0
    _log(f"device warmup (compile+first pass) done in {warmup_s:.1f}s")

    # Cold-vs-warm AOT cache A/B: a FRESH CompiledPipeline against the store
    # the warmup above just populated measures exactly what a re-invocation
    # pays — executable loads instead of trace+compile.  The first warmup's
    # stats stand in for the cold side when it really ran cold (no hits).
    aot_ab = {"supported": False}
    if os.environ.get("BENCH_AOT_AB", "1") != "0":
        try:
            from textblaster_tpu.utils.compile_cache import aot_cache_enabled

            aot_ab["supported"] = aot_cache_enabled()
            if aot_ab["supported"]:
                p_warm = CompiledPipeline(
                    config,
                    buckets=bench_buckets,
                    batch_size=device_batch,
                    geometry=geometry,
                )
                ws = p_warm.warmup_parallel()
                aot_ab.update(
                    cold_warmup_s=(
                        round(warm_stats.total_s, 3)
                        if warm_stats.cache_hits == 0
                        else None
                    ),
                    cold_cache_hits=warm_stats.cache_hits,
                    warm_warmup_s=round(ws.total_s, 3),
                    warm_cache_load_s=round(ws.cache_load_s, 3),
                    warm_cache_hits=ws.cache_hits,
                    programs=ws.programs,
                )
                _log(
                    f"AOT cache A/B: warm start {ws.total_s:.3f}s "
                    f"({ws.cache_hits}/{ws.programs} hits) vs "
                    f"cold {warm_stats.total_s:.1f}s"
                )
                del p_warm
        except Exception as e:  # never bill a cache problem to the bench
            aot_ab["error"] = str(e)
            _log(f"AOT cache A/B skipped: {e}")

    from textblaster_tpu.utils.metrics import (
        METRICS,
        build_run_report,
        metrics_snapshot,
        occupancy_report,
        occupancy_snapshot,
        stage_breakdown,
        stage_snapshot,
    )

    stage_before = stage_snapshot()
    occupancy_before = occupancy_snapshot()
    report_before = metrics_snapshot()
    report_wall_t0 = time.perf_counter()
    fallbacks_before = METRICS.get("worker_host_fallback_total")
    tails_before = METRICS.get("worker_host_tail_total")
    hazards_before = METRICS.get("worker_fold_hazard_rows_total")
    load_before_dev = os.getloadavg()[0]
    device_pass_s = []
    device_cpu_frac = []  # meaningful on the cpu platform; low on TPU (waits)
    for _ in range(3):
        run_docs = [d.copy() for d in docs]
        t0 = time.perf_counter()
        c0 = time.process_time()
        dev_outcomes = list(
            process_documents_device(config, iter(run_docs), pipeline=pipeline)
        )
        wall = time.perf_counter() - t0
        device_pass_s.append(round(wall, 3))
        device_cpu_frac.append(round((time.process_time() - c0) / wall, 3))
    load_after_dev = os.getloadavg()[0]
    # Stage breakdown over exactly the 3 timed passes: localizes regressions
    # to a stage (read/pack/dispatch/device-wait/post/write) and says whether
    # the run was host- or device-bound.
    stage_report = stage_breakdown(stage_before)
    # Occupancy over exactly the 3 timed passes: how much of the padded
    # codepoint volume the device computed was real document content.
    occ_report = occupancy_report(occupancy_before)
    # Full run report over the same window (stage/occupancy/resilience/
    # funnel), embedded in the record so one JSON blob carries the whole
    # observability surface for the timed passes.
    from textblaster_tpu.data_model import ProcessingOutcome as _PO

    pass_counts = {
        "received": 3 * len(run_docs),
        "success": 3 * sum(1 for o in dev_outcomes if o.kind == _PO.SUCCESS),
        "filtered": 3 * sum(1 for o in dev_outcomes if o.kind == _PO.FILTERED),
        "errors": 3 * sum(1 for o in dev_outcomes if o.kind == _PO.ERROR),
    }
    run_report = build_run_report(
        baseline=report_before,
        wall_time_s=time.perf_counter() - report_wall_t0,
        counts=pass_counts,
        provenance={"entry": "bench.py", "passes": 3, "n_docs": len(run_docs)},
    )
    dev_elapsed = min(device_pass_s)
    dev_rate = len(run_docs) / dev_elapsed
    _log(
        f"device: {dev_rate:.1f} docs/s over {len(run_docs)} docs "
        f"(passes {device_pass_s}, load {load_before_dev:.2f}->"
        f"{load_after_dev:.2f})"
    )
    # Read the honesty counters HERE: they must cover exactly the 3 timed
    # passes, not the parity pass below (which also re-runs fallbacks).
    fallback_frac = round(
        (METRICS.get("worker_host_fallback_total") - fallbacks_before)
        / max(3 * len(run_docs), 1),
        4,
    )
    tail_frac = round(
        (METRICS.get("worker_host_tail_total") - tails_before)
        / max(3 * len(run_docs), 1),
        4,
    )
    fold_hazard_frac = round(
        (METRICS.get("worker_fold_hazard_rows_total") - hazards_before)
        / max(3 * len(run_docs), 1),
        4,
    )

    # --- Decision parity: a dedicated device pass with host-tail routing OFF
    # (TEXTBLAST_HOST_TAILS=off, as the parity test suites run), so every row
    # in the parity denominator was decided by device kernels, not the
    # bit-exact host tail path (ADVICE r3 item 3).  Compared against the
    # full-corpus oracle outcomes.
    host_by_id = {o.document.id: o.kind for o in host_outcomes}
    prev_tails = os.environ.get("TEXTBLAST_HOST_TAILS")
    os.environ["TEXTBLAST_HOST_TAILS"] = "off"
    try:
        parity_outcomes = list(
            process_documents_device(
                config, iter([d.copy() for d in docs]), pipeline=pipeline
            )
        )
    finally:
        if prev_tails is None:
            os.environ.pop("TEXTBLAST_HOST_TAILS", None)
        else:
            os.environ["TEXTBLAST_HOST_TAILS"] = prev_tails
    dev_by_id = {o.document.id: o.kind for o in parity_outcomes}
    agree = sum(
        1 for k, v in host_by_id.items() if dev_by_id.get(k) == v
    )
    parity = agree / max(len(host_by_id), 1)

    # --- Pallas kernel on/off A/B (BENCH_PALLAS=0 skips).  A fresh pipeline
    # traced under TEXTBLAST_PALLAS=off runs the lax scans/sorts; the default
    # pipeline runs whatever kernels the backend supports.  Decisions must be
    # byte-identical three ways (kernels-on vs kernels-off vs host oracle) —
    # the kernels are an execution-schedule change, never a semantic one.  On
    # XLA:CPU both sides trace the same lax programs (kernels auto-decline),
    # so the A/B doubles as the no-regression check there.
    def _kernel_pass(p):
        run = [d.copy() for d in docs]
        t0 = time.perf_counter()
        outs = list(
            process_documents_device(config, iter(run), pipeline=p)
        )
        return len(outs) / (time.perf_counter() - t0), outs

    pallas_report = None
    if os.environ.get("BENCH_PALLAS", "1") != "0":
        from textblaster_tpu.ops.pallas_scan import pallas_scan_supported
        from textblaster_tpu.ops.pallas_sort import pallas_sort_supported

        try:
            scan_active = pallas_scan_supported()
            sort_active = pallas_sort_supported()
            prev_pallas = os.environ.get("TEXTBLAST_PALLAS")
            os.environ["TEXTBLAST_PALLAS"] = "off"
            try:
                p_off = CompiledPipeline(
                    config,
                    buckets=bench_buckets,
                    batch_size=device_batch,
                    geometry=geometry,
                )
                p_off.warmup_parallel()
                _kernel_pass(p_off)  # untimed warm pass
                off_rate, off_out = _kernel_pass(p_off)
            finally:
                if prev_pallas is None:
                    os.environ.pop("TEXTBLAST_PALLAS", None)
                else:
                    os.environ["TEXTBLAST_PALLAS"] = prev_pallas
            on_rate, on_out = _kernel_pass(pipeline)
            on_by_id = {o.document.id: o.kind for o in on_out}
            off_by_id = {o.document.id: o.kind for o in off_out}
            three_way = sum(
                1
                for k, v in host_by_id.items()
                if on_by_id.get(k) == v and off_by_id.get(k) == v
            ) / max(len(host_by_id), 1)
            pallas_report = {
                "scan_kernel_active": scan_active,
                "sort_kernel_active": sort_active,
                "on_docs_per_sec": round(on_rate, 2),
                "off_docs_per_sec": round(off_rate, 2),
                "speedup": round(on_rate / off_rate, 4),
                "parity_on_off_host": round(three_way, 6),
            }
            _log(
                f"pallas A/B: {on_rate:.1f} docs/s on vs {off_rate:.1f} off "
                f"(x{pallas_report['speedup']}, scan_active={scan_active}, "
                f"3-way parity {three_way:.4f})"
            )
            del p_off
        except Exception as e:  # never bill a kernel A/B problem to the bench
            pallas_report = {"error": str(e)}
            _log(f"pallas A/B skipped: {e}")

    # --- Fused megakernel on/off A/B (BENCH_FUSED=0 skips).  A fresh
    # pipeline traced under TEXTBLAST_FUSED=off runs the staged per-scan
    # path (individual Pallas kernels where supported, else lax); the
    # default pipeline fuses each (bucket, phase)'s filter scans into one
    # pallas_call.  Same three-way contract as the pallas A/B: decisions
    # byte-identical fused vs staged vs host oracle.  On XLA:CPU both
    # timed arms trace the same lax programs (kernels auto-decline), so
    # the dispatch counts below are taken at *trace* level under
    # TEXTBLAST_PALLAS_INTERPRET=1 — jax.eval_shape only, no execution —
    # which is where the fused-vs-staged structural difference lives.
    fused_report = None
    if os.environ.get("BENCH_FUSED", "1") != "0":
        from textblaster_tpu.ops.pallas_scan import fused_enabled

        try:
            prev_fused = os.environ.get("TEXTBLAST_FUSED")
            os.environ["TEXTBLAST_FUSED"] = "off"
            try:
                p_nf = CompiledPipeline(
                    config,
                    buckets=bench_buckets,
                    batch_size=device_batch,
                    geometry=geometry,
                )
                p_nf.warmup_parallel()
                _kernel_pass(p_nf)  # untimed warm pass
                nf_rate, nf_out = _kernel_pass(p_nf)
            finally:
                if prev_fused is None:
                    os.environ.pop("TEXTBLAST_FUSED", None)
                else:
                    os.environ["TEXTBLAST_FUSED"] = prev_fused
            f_rate, f_out = _kernel_pass(pipeline)
            f_by_id = {o.document.id: o.kind for o in f_out}
            nf_by_id = {o.document.id: o.kind for o in nf_out}
            three_way = sum(
                1
                for k, v in host_by_id.items()
                if f_by_id.get(k) == v and nf_by_id.get(k) == v
            ) / max(len(host_by_id), 1)

            # Per-(bucket, phase) scan dispatch counts, both arms.
            dispatches = {}
            tot_on = tot_off = 0
            prev_int = os.environ.get("TEXTBLAST_PALLAS_INTERPRET")
            os.environ["TEXTBLAST_PALLAS_INTERPRET"] = "1"
            try:
                for length in pipeline.geometry.buckets:
                    for phase in range(len(pipeline.phases)):
                        on_c = pipeline.scan_dispatch_counts(length, phase)
                        prev2 = os.environ.get("TEXTBLAST_FUSED")
                        os.environ["TEXTBLAST_FUSED"] = "off"
                        try:
                            off_c = pipeline.scan_dispatch_counts(
                                length, phase
                            )
                        finally:
                            if prev2 is None:
                                os.environ.pop("TEXTBLAST_FUSED", None)
                            else:
                                os.environ["TEXTBLAST_FUSED"] = prev2
                        tot_on += sum(on_c.values())
                        tot_off += sum(off_c.values())
                        dispatches[f"{length}/p{phase}"] = {
                            "fused": on_c,
                            "staged": off_c,
                        }
            finally:
                if prev_int is None:
                    os.environ.pop("TEXTBLAST_PALLAS_INTERPRET", None)
                else:
                    os.environ["TEXTBLAST_PALLAS_INTERPRET"] = prev_int
            fused_report = {
                "fused_enabled": fused_enabled(),
                "on_docs_per_sec": round(f_rate, 2),
                "off_docs_per_sec": round(nf_rate, 2),
                "speedup": round(f_rate / nf_rate, 4),
                "parity_on_off_host": round(three_way, 6),
                "scan_dispatches_on": tot_on,
                "scan_dispatches_off": tot_off,
                "scan_dispatches": dispatches,
            }
            _log(
                f"fused A/B: {f_rate:.1f} docs/s on vs {nf_rate:.1f} off "
                f"(x{fused_report['speedup']}, dispatches {tot_on} vs "
                f"{tot_off}, 3-way parity {three_way:.4f})"
            )
            del p_nf
        except Exception as e:  # never bill a kernel A/B problem to the bench
            fused_report = {"error": str(e)}
            _log(f"fused A/B skipped: {e}")

    # --- Dependency-chain fusion on/off A/B (BENCH_DEPFUSE=0 skips).  A
    # fresh pipeline traced under TEXTBLAST_DEPFUSE=off runs each filter's
    # dependent scans as separate staged dispatches (the pre-chain layout);
    # the default collapses each dependency chain — hash -> dedup tables,
    # word cumsum -> n_words consumers, sentence DFA -> boundary counters —
    # into one multi-pass chain_scan kernel whose intermediate streams stay
    # in VMEM.  Decisions must stay byte-identical on vs off vs host oracle;
    # dispatch counts are trace-level under interpret, as in the fused A/B.
    depfuse_report = None
    if os.environ.get("BENCH_DEPFUSE", "1") != "0":
        from textblaster_tpu.ops.pallas_scan import depfuse_enabled

        try:
            prev_df = os.environ.get("TEXTBLAST_DEPFUSE")
            os.environ["TEXTBLAST_DEPFUSE"] = "off"
            try:
                p_nd = CompiledPipeline(
                    config,
                    buckets=bench_buckets,
                    batch_size=device_batch,
                    geometry=geometry,
                )
                p_nd.warmup_parallel()
                _kernel_pass(p_nd)  # untimed warm pass
                nd_rate, nd_out = _kernel_pass(p_nd)
            finally:
                if prev_df is None:
                    os.environ.pop("TEXTBLAST_DEPFUSE", None)
                else:
                    os.environ["TEXTBLAST_DEPFUSE"] = prev_df
            d_rate, d_out = _kernel_pass(pipeline)
            d_by_id = {o.document.id: o.kind for o in d_out}
            nd_by_id = {o.document.id: o.kind for o in nd_out}
            three_way = sum(
                1
                for k, v in host_by_id.items()
                if d_by_id.get(k) == v and nd_by_id.get(k) == v
            ) / max(len(host_by_id), 1)

            dispatches = {}
            tot_on = tot_off = 0
            prev_int = os.environ.get("TEXTBLAST_PALLAS_INTERPRET")
            os.environ["TEXTBLAST_PALLAS_INTERPRET"] = "1"
            try:
                for length in pipeline.geometry.buckets:
                    for phase in range(len(pipeline.phases)):
                        on_c = pipeline.scan_dispatch_counts(length, phase)
                        prev2 = os.environ.get("TEXTBLAST_DEPFUSE")
                        os.environ["TEXTBLAST_DEPFUSE"] = "off"
                        try:
                            off_c = pipeline.scan_dispatch_counts(
                                length, phase
                            )
                        finally:
                            if prev2 is None:
                                os.environ.pop("TEXTBLAST_DEPFUSE", None)
                            else:
                                os.environ["TEXTBLAST_DEPFUSE"] = prev2
                        tot_on += sum(on_c.values())
                        tot_off += sum(off_c.values())
                        dispatches[f"{length}/p{phase}"] = {
                            "depfuse": on_c,
                            "staged": off_c,
                        }
            finally:
                if prev_int is None:
                    os.environ.pop("TEXTBLAST_PALLAS_INTERPRET", None)
                else:
                    os.environ["TEXTBLAST_PALLAS_INTERPRET"] = prev_int
            depfuse_report = {
                "depfuse_enabled": depfuse_enabled(),
                "on_docs_per_sec": round(d_rate, 2),
                "off_docs_per_sec": round(nd_rate, 2),
                "speedup": round(d_rate / nd_rate, 4),
                "parity_on_off_host": round(three_way, 6),
                "scan_dispatches_on": tot_on,
                "scan_dispatches_off": tot_off,
                "scan_dispatches": dispatches,
            }
            _log(
                f"depfuse A/B: {d_rate:.1f} docs/s on vs {nd_rate:.1f} off "
                f"(x{depfuse_report['speedup']}, dispatches {tot_on} vs "
                f"{tot_off}, 3-way parity {three_way:.4f})"
            )
            del p_nd
        except Exception as e:  # never bill a kernel A/B problem to the bench
            depfuse_report = {"error": str(e)}
            _log(f"depfuse A/B skipped: {e}")

    # --- Negotiated fault-guard overhead, fault-free (BENCH_RESILIENCE=0
    # skips).  The multi-host lockstep rounds run under the negotiated guard
    # by default (resilience/negotiated.py); its only per-round addition is
    # one 1-int verdict allgather, so future PRs watch this A/B to see if
    # the guard ever starts costing throughput.  Single process here, so the
    # verdict negotiation is in-process — this bounds the protocol/Python
    # cost, not the wire latency of a real pod.
    resilience_report = None
    if os.environ.get("BENCH_RESILIENCE", "1") != "0":
        from textblaster_tpu.parallel.multihost import run_local_shard

        def _shard_pass(guard_on: bool) -> float:
            run = [d.copy() for d in docs]
            t0 = time.perf_counter()
            n = len(
                run_local_shard(
                    config, run, buckets=pipeline.geometry.buckets,
                    pipeline=pipeline, fault_guard=guard_on,
                )
            )
            return n / (time.perf_counter() - t0)

        _shard_pass(False)  # untimed warm pass (mesh-path program variants)
        neg_before = {
            k: METRICS.get(k)
            for k in (
                "resilience_negotiated_rounds_total",
                "resilience_negotiated_retries_total",
                "resilience_negotiated_degraded_rounds_total",
            )
        }
        off_rate = _shard_pass(False)
        on_rate = _shard_pass(True)
        resilience_report = {
            "guard_on_docs_per_sec": round(on_rate, 2),
            "guard_off_docs_per_sec": round(off_rate, 2),
            "overhead_frac": round(1.0 - on_rate / off_rate, 4),
            "negotiated_rounds": int(
                METRICS.get("resilience_negotiated_rounds_total")
                - neg_before["resilience_negotiated_rounds_total"]
            ),
            "negotiated_retries": int(
                METRICS.get("resilience_negotiated_retries_total")
                - neg_before["resilience_negotiated_retries_total"]
            ),
            "degraded_rounds": int(
                METRICS.get("resilience_negotiated_degraded_rounds_total")
                - neg_before["resilience_negotiated_degraded_rounds_total"]
            ),
            "processes": 1,
        }
        _log(
            f"resilience guard: {on_rate:.1f} docs/s on vs "
            f"{off_rate:.1f} off "
            f"(overhead {resilience_report['overhead_frac']:+.2%}, "
            f"{resilience_report['negotiated_rounds']} rounds, "
            f"{resilience_report['negotiated_retries']} retries, "
            f"{resilience_report['degraded_rounds']} degraded)"
        )

    # --- Stall-watchdog on/off A/B (BENCH_WATCHDOG=0 skips).  The armed
    # arm runs with a generous per-stage deadline (nothing actually stalls,
    # so the watchdog only pays its readiness polls / bounded queue waits);
    # the disarmed arm is the default zero-cost path.  Parity must be 1.0 —
    # the deadline is scheduling-only — and the overhead should sit within
    # run-to-run noise.
    watchdog_report = None
    if os.environ.get("BENCH_WATCHDOG", "1") != "0":
        from textblaster_tpu.resilience.watchdog import WATCHDOG

        try:
            stalls_before = METRICS.get("watchdog_stalls_total")
            wd_off_rate, wd_off_out = _kernel_pass(pipeline)
            WATCHDOG.configure(120.0)
            try:
                wd_on_rate, wd_on_out = _kernel_pass(pipeline)
            finally:
                WATCHDOG.reset()
            wd_on_by_id = {o.document.id: o.kind for o in wd_on_out}
            wd_off_by_id = {o.document.id: o.kind for o in wd_off_out}
            wd_parity = sum(
                1 for k, v in wd_off_by_id.items() if wd_on_by_id.get(k) == v
            ) / max(len(wd_off_by_id), 1)
            watchdog_report = {
                "on_docs_per_sec": round(wd_on_rate, 2),
                "off_docs_per_sec": round(wd_off_rate, 2),
                "overhead_frac": round(1.0 - wd_on_rate / wd_off_rate, 4),
                "parity": round(wd_parity, 6),
                "stalls": int(
                    METRICS.get("watchdog_stalls_total") - stalls_before
                ),
            }
            _log(
                f"watchdog A/B: {wd_on_rate:.1f} docs/s armed vs "
                f"{wd_off_rate:.1f} disarmed "
                f"(overhead {watchdog_report['overhead_frac']:+.2%}, "
                f"parity {wd_parity:.4f}, "
                f"stalls {watchdog_report['stalls']})"
            )
        except Exception as e:  # never bill a watchdog A/B problem to the bench
            watchdog_report = {"error": str(e)}
            _log(f"watchdog A/B skipped: {e}")

    # --- Event-journal + SLO on/off A/B (BENCH_EVENTS=0 skips).  The armed
    # arm writes a real JSONL journal (the full spill path, not just the
    # ring) and runs the SLO engine with two objectives; the disarmed arm is
    # the default one-attribute-check path.  Decisions must be byte-identical
    # — observability never touches outcomes — and the combined overhead has
    # a 2% docs/s budget.
    events_report = None
    if os.environ.get("BENCH_EVENTS", "1") != "0":
        import tempfile as _ev_tempfile

        from textblaster_tpu.utils.events import EVENTS
        from textblaster_tpu.utils.slo import SLO

        try:
            ev_off_rate, ev_off_out = _kernel_pass(pipeline)
            emitted_before = METRICS.get("events_emitted_total")
            with _ev_tempfile.TemporaryDirectory() as ev_dir:
                EVENTS.configure(os.path.join(ev_dir, "bench-events.jsonl"))
                SLO.configure(
                    {"availability": 0.999, "throughput_floor": 0.001},
                    tick_s=0.5,
                )
                try:
                    ev_on_rate, ev_on_out = _kernel_pass(pipeline)
                finally:
                    SLO.reset()
                    EVENTS.close()
            ev_on_by_id = {o.document.id: o.kind for o in ev_on_out}
            ev_off_by_id = {o.document.id: o.kind for o in ev_off_out}
            ev_parity = sum(
                1 for k, v in ev_off_by_id.items() if ev_on_by_id.get(k) == v
            ) / max(len(ev_off_by_id), 1)
            ev_overhead = 1.0 - ev_on_rate / ev_off_rate
            events_report = {
                "on_docs_per_sec": round(ev_on_rate, 2),
                "off_docs_per_sec": round(ev_off_rate, 2),
                "overhead_frac": round(ev_overhead, 4),
                "overhead_budget_frac": 0.02,
                "within_budget": bool(ev_overhead <= 0.02),
                "parity": round(ev_parity, 6),
                "events_emitted": int(
                    METRICS.get("events_emitted_total") - emitted_before
                ),
            }
            _log(
                f"events+SLO A/B: {ev_on_rate:.1f} docs/s armed vs "
                f"{ev_off_rate:.1f} disarmed "
                f"(overhead {events_report['overhead_frac']:+.2%} vs 2% "
                f"budget, parity {ev_parity:.4f}, "
                f"{events_report['events_emitted']} events)"
            )
        except Exception as e:  # never bill an events A/B problem to the bench
            events_report = {"error": str(e)}
            _log(f"events A/B skipped: {e}")

    # --- Multi-host overlap A/B (BENCH_MULTIHOST_OVERLAP=0 skips).  Real
    # 2-process coordinated CLI runs on the local box: overlapped lockstep
    # window (--pipeline-depth 3) vs serial (--no-overlap --pipeline-depth 1),
    # same input, same pipeline, shared AOT cache (one untimed warm run
    # populates it so neither timed arm pays compile).  Throughput is the
    # lockstep-section rate from each arm's merged --run-report (received
    # docs over the max-over-hosts multihost_lockstep_seconds_total), which
    # isolates the windowed round loop from reader/merge overheads.  Decision
    # parity between the two arms must be 1.0 — the window is a scheduling
    # change, not a semantic one.
    mh_overlap_report = None
    mh_reform_report = None
    mh_speculate_report = None
    _mh_overlap_on = os.environ.get("BENCH_MULTIHOST_OVERLAP", "1") != "0"
    _mh_reform_on = os.environ.get("BENCH_REFORM", "0") == "1"
    _mh_spec_on = os.environ.get("BENCH_SPECULATE", "1") != "0"
    if _mh_overlap_on or _mh_reform_on or _mh_spec_on:
        import socket
        import tempfile

        import pyarrow as pa
        import pyarrow.parquet as pq

        _MH_YAML = """
pipeline:
  - type: LanguageDetectionFilter
    min_confidence: 0.5
    allowed_languages: [ "dan", "eng" ]
  - type: GopherRepetitionFilter
    dup_line_frac: 0.3
    top_n_grams: [[2, 0.25]]
    dup_n_grams: [[5, 0.15]]
  - type: GopherQualityFilter
    min_doc_words: 4
    min_stop_words: 1
    stop_words: [ "og", "the", "er", "i" ]
"""

        def _mh_pass(root, inp, tag, extra_args, extra_env=None):
            out = os.path.join(root, f"{tag}-kept.parquet")
            exc = os.path.join(root, f"{tag}-exc.parquet")
            rep = os.path.join(root, f"{tag}-report.json")
            with socket.socket() as s:
                s.bind(("localhost", 0))
                port = s.getsockname()[1]
            env = {
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
                "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
                "HOME": os.environ.get("HOME", "/root"),
                "TEXTBLAST_AOT_CACHE_DIR": os.path.join(root, "aot"),
            }
            env.update(extra_env or {})
            procs = [
                subprocess.Popen(
                    [
                        sys.executable, "-m", "textblaster_tpu.cli", "run",
                        "--coordinator", f"localhost:{port}",
                        "--num-processes", "2", "--process-id", str(pid),
                        "-i", inp, "-o", out, "-e", exc,
                        "-c", os.path.join(root, "cfg.yaml"),
                        "--buckets", "512,2048",
                        # 96 local docs / 16 rows = ~6 rounds per phase, so
                        # the K-deep window actually opens (the CPU default
                        # of 64 rows would leave ~1 round per phase).
                        "--device-batch", "16",
                        # The report contract: passed on every process (the
                        # metrics allgather is collective); rank 0 writes it.
                        "--run-report", rep,
                        "--quiet", *extra_args,
                    ],
                    env=env, stdout=subprocess.PIPE,
                    stderr=subprocess.STDOUT, text=True,
                )
                for pid in (0, 1)
            ]
            logs = [p.communicate(timeout=700)[0] for p in procs]
            for p, lg in zip(procs, logs):
                if p.returncode != 0:
                    raise RuntimeError(
                        f"mh {tag} rank failed ({p.returncode}): {lg[-400:]}"
                    )
            with open(rep, encoding="utf-8") as f:
                return json.load(f), out, exc

        def _mh_rate(rep):
            secs = max(
                (
                    h["metrics"].get("multihost_lockstep_seconds_total", 0.0)
                    for h in rep.get("hosts", [])
                ),
                default=0.0,
            )
            n = rep["counts"].get("received", 0)
            return (n / secs if secs > 0 else 0.0), secs

        def _mh_rows(path):
            return pq.read_table(path).to_pylist() if os.path.exists(path) else []

        def _mh_input(root, n=192):
            picked = [d for d in docs if len(d.content) <= 2040][:n]
            with open(os.path.join(root, "cfg.yaml"), "w",
                      encoding="utf-8") as f:
                f.write(_MH_YAML)
            inp = os.path.join(root, "input.parquet")
            pq.write_table(
                pa.table(
                    {
                        "id": [d.id for d in picked],
                        "text": [d.content for d in picked],
                        "source": [d.source or "bench" for d in picked],
                    }
                ),
                inp,
            )
            return picked, inp

    if _mh_overlap_on:
        try:
            with tempfile.TemporaryDirectory(prefix="bench-mh-") as root:
                mh_docs, inp = _mh_input(root)
                _mh_pass(root, inp, "warm", ["--pipeline-depth", "1"])
                se_rep, se_out, se_exc = _mh_pass(
                    root, inp, "serial",
                    ["--no-overlap", "--pipeline-depth", "1"],
                )
                ov_rep, ov_out, ov_exc = _mh_pass(
                    root, inp, "overlap", ["--pipeline-depth", "3"]
                )
                ov_rate, ov_s = _mh_rate(ov_rep)
                se_rate, se_s = _mh_rate(se_rep)
                ov_rows = (_mh_rows(ov_out), _mh_rows(ov_exc))
                se_rows = (_mh_rows(se_out), _mh_rows(se_exc))
                ids = set()
                agree = 0
                for side in (0, 1):
                    by_id = {
                        r["id"]: (side, r.get("text"), r.get("metadata"))
                        for r in se_rows[side]
                    }
                    for r in ov_rows[side]:
                        ids.add(r["id"])
                        if by_id.get(r["id"]) == (
                            side, r.get("text"), r.get("metadata")
                        ):
                            agree += 1
                    ids.update(by_id)
                parity = agree / max(len(ids), 1)
                res = ov_rep.get("resilience", {})
                mh_overlap_report = {
                    "overlapped_docs_per_sec": round(ov_rate, 2),
                    "serial_docs_per_sec": round(se_rate, 2),
                    "speedup": round(ov_rate / se_rate, 4) if se_rate else 0.0,
                    "decision_parity": round(parity, 6),
                    "ordered_identical": ov_rows == se_rows,
                    "negotiated_depth": int(
                        res.get("multihost_negotiated_depth", 0)
                    ),
                    "window_stall_s": round(
                        sum(
                            h["metrics"].get(
                                "multihost_window_stall_seconds_total", 0.0
                            )
                            for h in ov_rep.get("hosts", [])
                        ),
                        3,
                    ),
                    "window_replayed_rounds": int(
                        res.get("multihost_window_replayed_rounds_total", 0)
                    ),
                    # Speculative cross-phase dispatch counters from the
                    # overlapped arm (speculation rides the window by
                    # default): rounds launched past a phase barrier, rounds
                    # voided by a joint fault, and barriers whose per-round
                    # exchanges collapsed into the combined post.
                    "speculation": {
                        "speculated_rounds": int(
                            res.get("multihost_speculated_rounds_total", 0)
                        ),
                        "voided_rounds": int(
                            res.get("multihost_voided_rounds_total", 0)
                        ),
                        "barrier_elisions": int(
                            res.get("multihost_barrier_elisions_total", 0)
                        ),
                        "depth": int(
                            res.get("multihost_speculate_depth", 0)
                        ),
                    },
                    "lockstep_s": {
                        "overlapped": round(ov_s, 3),
                        "serial": round(se_s, 3),
                    },
                    # Total allgather posts per arm (max over hosts; both
                    # hosts post in lockstep, so the rows agree).  The
                    # batched verdict exchange drains a K-deep window's
                    # fault flags in ONE vector post, so the overlapped arm
                    # must come in below serial's one-post-per-round.
                    "exchange_posts": {
                        "overlapped": int(max(
                            (h["metrics"].get(
                                "multihost_exchange_posts_total", 0)
                             for h in ov_rep.get("hosts", [])),
                            default=0,
                        )),
                        "serial": int(max(
                            (h["metrics"].get(
                                "multihost_exchange_posts_total", 0)
                             for h in se_rep.get("hosts", [])),
                            default=0,
                        )),
                    },
                    "n_docs": len(mh_docs),
                    "processes": 2,
                }
                _log(
                    f"multihost overlap: {ov_rate:.1f} docs/s depth="
                    f"{mh_overlap_report['negotiated_depth']} vs "
                    f"{se_rate:.1f} serial "
                    f"(x{mh_overlap_report['speedup']}, parity {parity:.4f}, "
                    f"ordered={mh_overlap_report['ordered_identical']}, "
                    f"stall {mh_overlap_report['window_stall_s']}s)"
                )
        except Exception as e:  # never bill a 2-proc spawn problem to the bench
            mh_overlap_report = {"error": f"{type(e).__name__}: {e}"[:500]}
            _log(f"multihost overlap A/B skipped: {e}")

    # --- Speculative cross-phase dispatch A/B (BENCH_SPECULATE=0 skips).
    # Two fault-free coordinated 2-process runs on the file-lease transport
    # (--pipeline-depth 3 both ways), speculation on (the default) vs
    # TEXTBLAST_SPECULATE=off.  On the file transport every exchange post
    # is a slot file + peer poll, so the barrier elision (verdicts + join
    # sweep + schedule negotiation in ONE vector post) shows up directly as
    # strictly fewer posts per interior phase barrier, and launching the
    # next phase's confirmed rounds before the tail verdicts convene shows
    # up as reduced window stall.  Outputs must be ordered-identical —
    # speculation is a scheduling change, never a semantic one.
    if _mh_spec_on:
        try:
            with tempfile.TemporaryDirectory(prefix="bench-spec-") as root:
                sp_docs, inp = _mh_input(root)
                sp_args = [
                    "--exchange-transport", "file", "--pipeline-depth", "3",
                    # The bench box is still settling from the main timed
                    # passes; the default 10s lease TTL is tight enough
                    # that a load-starved heartbeat gets a rank evicted
                    # mid-run, so give the liveness layer headroom — this
                    # arm measures barrier posts, not lease churn.
                    "--lease-ttl-s", "30",
                ]
                # One untimed warm run populates the shared AOT cache for
                # both arms: the speculation knob is scheduling-only and
                # deliberately excluded from compile-cache keys, so the two
                # arms run the same executables.
                _mh_pass(root, inp, "warm", sp_args,
                         {"TEXTBLAST_SPECULATE": "off"})
                off_rep, off_out, off_exc = _mh_pass(
                    root, inp, "spec-off", sp_args,
                    {"TEXTBLAST_SPECULATE": "off"},
                )
                on_rep, on_out, on_exc = _mh_pass(
                    root, inp, "spec-on", sp_args
                )
                on_rate, on_s = _mh_rate(on_rep)
                off_rate, off_s = _mh_rate(off_rep)
                on_rows = (_mh_rows(on_out), _mh_rows(on_exc))
                off_rows = (_mh_rows(off_out), _mh_rows(off_exc))
                ids = set()
                agree = 0
                for side in (0, 1):
                    by_id = {
                        r["id"]: (side, r.get("text"), r.get("metadata"))
                        for r in off_rows[side]
                    }
                    for r in on_rows[side]:
                        ids.add(r["id"])
                        if by_id.get(r["id"]) == (
                            side, r.get("text"), r.get("metadata")
                        ):
                            agree += 1
                    ids.update(by_id)
                parity = agree / max(len(ids), 1)
                on_res = on_rep.get("resilience", {})

                def _stall(rep):
                    return round(
                        sum(
                            h["metrics"].get(
                                "multihost_window_stall_seconds_total", 0.0
                            )
                            for h in rep.get("hosts", [])
                        ),
                        3,
                    )

                def _posts(rep):
                    return int(max(
                        (h["metrics"].get("multihost_exchange_posts_total", 0)
                         for h in rep.get("hosts", [])),
                        default=0,
                    ))

                mh_speculate_report = {
                    "speculate_docs_per_sec": round(on_rate, 2),
                    "classic_docs_per_sec": round(off_rate, 2),
                    "speedup": (
                        round(on_rate / off_rate, 4) if off_rate else 0.0
                    ),
                    "decision_parity": round(parity, 6),
                    "ordered_identical": on_rows == off_rows,
                    "window_stall_s": {
                        "speculate": _stall(on_rep),
                        "classic": _stall(off_rep),
                    },
                    # Allgather posts per arm (max over hosts; lockstep, so
                    # the rows agree).  The combined barrier post must put
                    # the speculate arm strictly below classic on the file
                    # transport — each saved post is a saved slot-file
                    # round-trip.
                    "exchange_posts": {
                        "speculate": _posts(on_rep),
                        "classic": _posts(off_rep),
                    },
                    "speculated_rounds": int(
                        on_res.get("multihost_speculated_rounds_total", 0)
                    ),
                    "voided_rounds": int(
                        on_res.get("multihost_voided_rounds_total", 0)
                    ),
                    "barrier_elisions": int(
                        on_res.get("multihost_barrier_elisions_total", 0)
                    ),
                    "lockstep_s": {
                        "speculate": round(on_s, 3),
                        "classic": round(off_s, 3),
                    },
                    "n_docs": len(sp_docs),
                    "processes": 2,
                }
                _log(
                    f"speculative dispatch: {on_rate:.1f} docs/s vs "
                    f"{off_rate:.1f} classic "
                    f"(x{mh_speculate_report['speedup']}, "
                    f"parity {parity:.4f}, "
                    f"posts {_posts(on_rep)} vs {_posts(off_rep)}, "
                    f"stall {_stall(on_rep)}s vs {_stall(off_rep)}s, "
                    f"speculated="
                    f"{mh_speculate_report['speculated_rounds']})"
                )
        except Exception as e:  # never bill a 2-proc spawn problem to the bench
            mh_speculate_report = {"error": f"{type(e).__name__}: {e}"[:500]}
            _log(f"speculative dispatch A/B skipped: {e}")

    # --- Exchange-transport A/B (BENCH_REFORM=1 enables; off by default —
    # four 2-proc runs).  Fault-free coordinated runs, the default XLA/KV
    # funnel vs the file-lease transport (--exchange-transport file), same
    # input, same pipeline.  The file transport trades coordination-service
    # KV round-trips for shared-filesystem polling; this measures what that
    # costs per run when nothing dies — the steady-state price of carrying
    # the gang-reformation machinery.  Outputs must be ordered-identical
    # (the transport moves bytes, not decisions) and the fault-free file
    # arm must report zero reformations, else the deadline/lease-ttl
    # defaults are too tight for this box.
    if _mh_reform_on:
        try:
            with tempfile.TemporaryDirectory(prefix="bench-reform-") as root:
                rf_docs, inp = _mh_input(root)
                # One untimed warm run per arm: the kv arm compiles under
                # jax.distributed's global mesh while the file arm never
                # initializes it and compiles collective-free local
                # programs — different executables, so each arm has to
                # populate its own AOT cache entries.
                _mh_pass(root, inp, "warm-kv", ["--exchange-transport", "kv"])
                _mh_pass(
                    root, inp, "warm-file", ["--exchange-transport", "file"]
                )
                kv_rep, kv_out, kv_exc = _mh_pass(
                    root, inp, "kv", ["--exchange-transport", "kv"]
                )
                fl_rep, fl_out, fl_exc = _mh_pass(
                    root, inp, "file", ["--exchange-transport", "file"]
                )
                kv_rate, kv_s = _mh_rate(kv_rep)
                fl_rate, fl_s = _mh_rate(fl_rep)
                kv_rows = (_mh_rows(kv_out), _mh_rows(kv_exc))
                fl_rows = (_mh_rows(fl_out), _mh_rows(fl_exc))
                fl_res = fl_rep.get("resilience", {})
                mh_reform_report = {
                    "kv_docs_per_sec": round(kv_rate, 2),
                    "file_docs_per_sec": round(fl_rate, 2),
                    "file_over_kv": (
                        round(fl_rate / kv_rate, 4) if kv_rate else 0.0
                    ),
                    "ordered_identical": kv_rows == fl_rows,
                    "lockstep_s": {
                        "kv": round(kv_s, 3),
                        "file": round(fl_s, 3),
                    },
                    "file_reformations": int(
                        fl_res.get("multihost_gang_reformations_total", 0)
                    ),
                    # Allgather posts per arm (max over hosts) — on the
                    # file transport every post is a slot file + poll, so
                    # the batched verdict exchange's saved posts are saved
                    # filesystem round-trips here.
                    "exchange_posts": {
                        "kv": int(max(
                            (h["metrics"].get(
                                "multihost_exchange_posts_total", 0)
                             for h in kv_rep.get("hosts", [])),
                            default=0,
                        )),
                        "file": int(max(
                            (h["metrics"].get(
                                "multihost_exchange_posts_total", 0)
                             for h in fl_rep.get("hosts", [])),
                            default=0,
                        )),
                    },
                    "n_docs": len(rf_docs),
                    "processes": 2,
                }
                _log(
                    f"exchange transport: file {fl_rate:.1f} docs/s vs kv "
                    f"{kv_rate:.1f} (x{mh_reform_report['file_over_kv']}, "
                    f"ordered={mh_reform_report['ordered_identical']}, "
                    f"reformations={mh_reform_report['file_reformations']})"
                )
        except Exception as e:  # never bill a 2-proc spawn problem to the bench
            mh_reform_report = {"error": f"{type(e).__name__}: {e}"[:500]}
            _log(f"exchange transport A/B skipped: {e}")

    # --- Tracing overhead, A/B (BENCH_TRACE=0 skips).  The span tracer is
    # a single attribute check when off; when on it adds two clock reads +
    # one locked list append per span.  This measures both sides on the
    # device path so regressions in the "off" fast path (the default for
    # production runs) or runaway "on" cost (> ~2%) are caught by the bench.
    trace_report = None
    if os.environ.get("BENCH_TRACE", "1") != "0":
        import tempfile

        from textblaster_tpu.utils.trace import TRACER

        trace_tmp = os.path.join(tempfile.gettempdir(), "bench_trace.json")
        on_pass_s = []
        trace_events = 0
        try:
            for _ in range(2):
                TRACER.configure(trace_tmp)
                run = [d.copy() for d in docs]
                t0 = time.perf_counter()
                list(
                    process_documents_device(
                        config, iter(run), pipeline=pipeline
                    )
                )
                on_pass_s.append(time.perf_counter() - t0)
                TRACER.close()
            with open(trace_tmp) as f:
                trace_events = sum(1 for line in f if '"ph"' in line)
        finally:
            TRACER.close()
            if os.path.exists(trace_tmp):
                os.remove(trace_tmp)
        on_rate = len(docs) / min(on_pass_s)
        trace_report = {
            "trace_on_docs_per_sec": round(on_rate, 2),
            "trace_off_docs_per_sec": round(dev_rate, 2),
            "overhead_frac": round(1.0 - on_rate / dev_rate, 4),
            "trace_events": int(trace_events),
        }
        _log(
            f"trace: {on_rate:.1f} docs/s on vs {dev_rate:.1f} off "
            f"(overhead {trace_report['overhead_frac']:+.2%}, "
            f"{trace_events} events)"
        )

    # --- Doc-sampling telemetry overhead, A/B (BENCH_TELEMETRY=0 skips).
    # Both arms run the full pipeline INCLUDING the Parquet write seam
    # (aggregate_results_from_stream into temp files) — lineages only close
    # at the write, so a device-only pass would measure the marks but never
    # the completion path.  Off must be free (one attribute check per seam);
    # on is 1-in-BENCH_DOC_SAMPLE docs paying a crc32 + dict stamp per stage.
    telemetry_report = None
    if os.environ.get("BENCH_TELEMETRY", "1") != "0":
        import shutil
        import tempfile

        from textblaster_tpu.orchestration import aggregate_results_from_stream
        from textblaster_tpu.utils.metrics import latency_report
        from textblaster_tpu.utils.telemetry import TELEMETRY

        sample_rate = int(os.environ.get("BENCH_DOC_SAMPLE", "8"))
        telem_tmp = tempfile.mkdtemp(prefix="bench_telem_")

        def _telem_pass(tag: str) -> float:
            run = [d.copy() for d in docs]
            t0 = time.perf_counter()
            aggregate_results_from_stream(
                process_documents_device(config, iter(run), pipeline=pipeline),
                output_file=os.path.join(telem_tmp, f"{tag}_out.parquet"),
                excluded_file=os.path.join(telem_tmp, f"{tag}_exc.parquet"),
            )
            return time.perf_counter() - t0

        try:
            telem_off_s = [_telem_pass(f"off{i}") for i in range(2)]
            telem_base = metrics_snapshot()
            sampled_before = METRICS.get("doc_sampled_total")
            TELEMETRY.configure(sample_rate, start_ticker=False)
            telem_on_s = []
            for i in range(2):
                telem_on_s.append(_telem_pass(f"on{i}"))
                TELEMETRY.roll_window()  # deterministic window per pass
            telem_latency = latency_report(telem_base)
            telem_windows = TELEMETRY.snapshot()["windows"]
            telem_off_rate = len(docs) / min(telem_off_s)
            telem_on_rate = len(docs) / min(telem_on_s)
            telemetry_report = {
                "doc_sample_rate": sample_rate,
                "telemetry_on_docs_per_sec": round(telem_on_rate, 2),
                "telemetry_off_docs_per_sec": round(telem_off_rate, 2),
                "overhead_frac": round(1.0 - telem_on_rate / telem_off_rate, 4),
                "sampled_docs": int(
                    METRICS.get("doc_sampled_total") - sampled_before
                ),
                "latency": telem_latency["stages"],
                "last_window": telem_windows[-1] if telem_windows else None,
            }
            _log(
                f"telemetry: {telem_on_rate:.1f} docs/s sampled 1-in-"
                f"{sample_rate} vs {telem_off_rate:.1f} off "
                f"(overhead {telemetry_report['overhead_frac']:+.2%}, "
                f"{telemetry_report['sampled_docs']} docs sampled)"
            )
        except Exception as e:  # never bill a telemetry problem to the bench
            telemetry_report = {"error": f"{type(e).__name__}: {e}"[:500]}
            _log(f"telemetry A/B skipped: {e}")
        finally:
            TELEMETRY.close()
            shutil.rmtree(telem_tmp, ignore_errors=True)

    # --- Device-profiling overhead, A/B (BENCH_PROFILE=0 skips).  Both
    # arms run the full pipeline INCLUDING the Parquet write seam, like the
    # telemetry A/B: the profiler's dispatch seam fires inside the device
    # fetch, but the honest denominator is end-to-end docs/s.  Off must be
    # free (one attribute check per dispatch); on pays an HDR observe + a
    # gauge set + a heap push per dispatch and must stay within ~2%.
    profile_report = None
    if os.environ.get("BENCH_PROFILE", "1") != "0":
        import shutil
        import tempfile

        from textblaster_tpu.orchestration import aggregate_results_from_stream
        from textblaster_tpu.utils.profiler import (
            PROFILER,
            device_profile_report,
        )

        prof_tmp = tempfile.mkdtemp(prefix="bench_prof_")

        def _prof_pass(tag: str) -> float:
            run = [d.copy() for d in docs]
            t0 = time.perf_counter()
            aggregate_results_from_stream(
                process_documents_device(config, iter(run), pipeline=pipeline),
                output_file=os.path.join(prof_tmp, f"{tag}_out.parquet"),
                excluded_file=os.path.join(prof_tmp, f"{tag}_exc.parquet"),
            )
            return time.perf_counter() - t0

        try:
            prof_off_s = [_prof_pass(f"off{i}") for i in range(2)]
            prof_base = metrics_snapshot()
            PROFILER.configure()
            # Warmup already ran with profiling off, so the compile-time
            # capture never fired — re-register the installed executables'
            # cost models directly (no compiles, no cache traffic).
            pipeline.register_installed_costs(include_split_rows=False)
            prof_on_s = [_prof_pass(f"on{i}") for i in range(2)]
            dp = device_profile_report(baseline=prof_base)
            prof_off_rate = len(docs) / min(prof_off_s)
            prof_on_rate = len(docs) / min(prof_on_s)
            profile_report = {
                "profile_on_docs_per_sec": round(prof_on_rate, 2),
                "profile_off_docs_per_sec": round(prof_off_rate, 2),
                "overhead_frac": round(1.0 - prof_on_rate / prof_off_rate, 4),
                "cost_fingerprint": dp.get("cost_fingerprint"),
                "dispatch": dp.get("dispatch"),
                "top_dispatches": dp.get("top_dispatches", [])[:3],
            }
            _log(
                f"profile: {prof_on_rate:.1f} docs/s on vs "
                f"{prof_off_rate:.1f} off "
                f"(overhead {profile_report['overhead_frac']:+.2%}, "
                f"fingerprint "
                f"{str(profile_report['cost_fingerprint'])[:12]})"
            )
        except Exception as e:  # never bill a profiler problem to the bench
            profile_report = {"error": f"{type(e).__name__}: {e}"[:500]}
            _log(f"profile A/B skipped: {e}")
        finally:
            PROFILER.close()
            shutil.rmtree(prof_tmp, ignore_errors=True)

    # Noise self-diagnosis: spreads over the raw passes plus the load
    # averages bracketing each side.  The bench's own process keeps a 1-core
    # box at load ~1; sustained load beyond ~1.8 means a foreign process was
    # competing during that side's passes and the ratio is suspect.
    oracle_spread = round((max(oracle_pass_s) - cpu_elapsed) / cpu_elapsed, 3)
    device_spread = round((max(device_pass_s) - dev_elapsed) / dev_elapsed, 3)
    noise_flags = []
    # process_time/wall is the direct core-sharing signal: the oracle is
    # pure in-process CPU work, so a best pass below ~0.75 means a foreign
    # process held the core during it.  (Load averages carry a false
    # positive: the 8-thread AOT warmup's 1-min tail overlaps the first
    # device passes; they are still recorded below for context.)
    if max(oracle_cpu_frac) < 0.75:
        noise_flags.append("oracle_core_shared")
    if jax.default_backend() == "cpu" and max(device_cpu_frac) < 0.75:
        noise_flags.append("device_core_shared")
    if oracle_spread > 0.2:
        noise_flags.append("oracle_spread_high")
    if device_spread > 0.2:
        noise_flags.append("device_spread_high")

    result = {
        "metric": _metric_name(bench_name),
        "value": round(dev_rate, 2),
        "unit": "docs/s",
        "vs_baseline": round(dev_rate / cpu_rate, 3),
        "oracle_pass_s": oracle_pass_s,
        "device_pass_s": device_pass_s,
        "oracle_cpu_frac": oracle_cpu_frac,
        "device_cpu_frac": device_cpu_frac,
        "oracle_spread": oracle_spread,
        "device_spread": device_spread,
        "load_1m": {
            "oracle": [round(load_before_oracle, 2), round(load_after_oracle, 2)],
            "device": [round(load_before_dev, 2), round(load_after_dev, 2)],
        },
        "noise_flags": noise_flags,
        "cpu_baseline_docs_per_sec": round(cpu_rate, 2),
        # The BASELINE.json north star divides by a 32-worker CPU fleet.  The
        # reference's workers are embarrassingly parallel (one queue, no
        # shared state), so the fleet rate is modeled as 32x the single-core
        # oracle measured here — this box has one core, a real fleet can't
        # be run on it.
        "cpu_baseline_workers": 1,
        "north_star_docs_per_sec": round(32 * cpu_rate, 2),
        "vs_32_worker_fleet": round(dev_rate / (32 * cpu_rate), 4),
        **({"fleet_scaling": fleet} if fleet else {}),
        "decision_parity": round(parity, 6),
        "parity_denominator": len(host_by_id),
        "n_docs": len(run_docs),
        "device_batch": pipeline.batch_size,
        "buckets": list(pipeline.buckets),
        # The geometry actually dispatched (buckets + per-bucket batch rows
        # + provenance) and its occupancy over the 3 timed passes: real vs
        # padded codepoint lanes, waste ratio, per-bucket dispatch counts.
        "geometry": pipeline.geometry.to_dict(),
        "occupancy": occ_report,
        "platform": jax.default_backend(),
        # Warmup cost, split by where it went: trace (serial Python),
        # compile (XLA, summed across pool threads), AOT-cache executable
        # loads.  warmup_s additionally includes the full warm pass.
        "warmup_s": round(warmup_s, 1),
        "warmup_compile_s": round(compile_s, 1),
        "warmup_trace_s": round(warm_stats.trace_s, 2),
        "warmup_cache_load_s": round(warm_stats.cache_load_s, 3),
        "warmup_programs": warm_stats.programs,
        "warmup_aot_hits": warm_stats.cache_hits,
        # Cold-vs-warm serialized-executable cache A/B: what a re-invocation
        # with the same geometry/config/jax pays instead of recompiling.
        "aot_cache": aot_ab,
        # Pallas kernel on/off A/B + three-way decision parity
        # (kernels-on vs kernels-off vs host oracle).
        **({"pallas": pallas_report} if pallas_report else {}),
        # Fused megakernel on/off A/B: docs/s, three-way parity, and
        # per-(bucket, phase) scan dispatch counts (trace-level, counted
        # under interpret so the structural reduction shows on any backend).
        **({"fused": fused_report} if fused_report else {}),
        # Dependency-chain fusion on/off A/B: docs/s, three-way parity
        # gate, and per-(bucket, phase) dispatch counts with the multi-pass
        # chains on (TEXTBLAST_DEPFUSE default) vs staged (off).
        **({"depfuse": depfuse_report} if depfuse_report else {}),
        # Per-stage wall seconds across the 3 timed passes + the host-bound
        # vs device-bound verdict (stages overlap, so the sum can exceed
        # wall time; compare stages to each other).
        "stage_breakdown": stage_report,
        # Docs the device path re-ran on the host oracle (outliers / table
        # overflow) during the 3 timed passes.  A high rate means the
        # headline number is partly the Python path — it must stay near zero
        # for the record to be honest.
        "host_fallback_frac": fallback_frac,
        # Docs deliberately routed to the host oracle as end-of-stream tail
        # groups (scheduling choice, distinct from fallbacks; the host path
        # is bit-exact, so parity is unaffected — only throughput attribution).
        "host_tail_frac": tail_frac,
        # Bad-words rows re-decided by the host regex (fold-hazard
        # codepoints) during the timed passes — per-row regex work, the
        # third and finest host-path class.
        "fold_hazard_frac": fold_hazard_frac,
        # Fault-free A/B of the negotiated multi-host fault guard (docs/s
        # with the per-round verdict protocol on vs off) + its counters.
        **({"resilience": resilience_report} if resilience_report else {}),
        # Stall-watchdog armed/disarmed A/B (generous deadline, nothing
        # stalls): parity must be 1.0 and the armed overhead within noise —
        # the disarmed default pays one attribute check per seam.
        **({"watchdog": watchdog_report} if watchdog_report else {}),
        # Event-journal + SLO-engine armed/disarmed A/B (real JSONL spill,
        # two live objectives): parity must be 1.0 and the combined
        # overhead within the 2% docs/s budget; off must be free.
        **({"events": events_report} if events_report else {}),
        # Overlapped-vs-serial multi-host lockstep A/B (2 coordinated
        # processes on this box): lockstep-section docs/s both ways, the
        # negotiated window depth, window stall seconds, and decision
        # parity between the arms (must be 1.0 — scheduling, not semantics).
        **({"multihost_overlap": mh_overlap_report} if mh_overlap_report else {}),
        # Speculation on/off A/B through the 2-process coordinated path on
        # the file-lease transport: lockstep docs/s both ways, window stall
        # and exchange-post counts per arm (the barrier elision must show
        # as strictly fewer posts), and an ordered-parity gate (must be
        # 1.0 — speculation re-orders work, never decisions).
        **({"multihost_speculate": mh_speculate_report}
           if mh_speculate_report else {}),
        # KV-vs-file exchange-transport A/B (BENCH_REFORM=1): the fault-free
        # steady-state cost of the gang-reformation carrier, with ordered
        # output parity and a zero-reformation sanity gate.
        **({"exchange_transport": mh_reform_report} if mh_reform_report else {}),
        # Trace on/off A/B over the device path: the span tracer must stay
        # within ~2% of the untraced rate when on and free when off.
        **({"trace": trace_report} if trace_report else {}),
        # Doc-sampling telemetry on/off A/B through the full write path:
        # per-stage tail quantiles for the sampled docs plus the overhead
        # the 1-in-N sampler costs (off must be free, on low single digits).
        **({"telemetry": telemetry_report} if telemetry_report else {}),
        # Device-profiling on/off A/B through the full write path: the cost
        # fingerprint, per-(bucket, phase) device-time quantiles with
        # modeled-vs-achieved bytes/s, and the overhead the observatory
        # costs (off must be free, on within ~2%).
        **({"device_profile": profile_report} if profile_report else {}),
        # The merged observability report for the 3 timed passes — same
        # schema as `--run-report` (stages, occupancy, resilience, funnel).
        "run_report": run_report,
    }
    print(json.dumps(result))
    return 0


def _fail_record(exc: BaseException) -> None:
    # Emit a parseable record even on catastrophic failure so every round
    # leaves perf evidence (or a structured reason there is none).
    print(
        json.dumps(
            {
                "metric": _metric_name(_bench_name()),
                "value": 0.0,
                "unit": "docs/s",
                "vs_baseline": 0.0,
                "error": f"{type(exc).__name__}: {exc}"[:500],
            }
        )
    )


if __name__ == "__main__":
    try:
        sys.exit(main())
    except (SystemExit, KeyboardInterrupt):
        raise
    except BaseException as e:  # noqa: BLE001
        _fail_record(e)
        sys.exit(1)
