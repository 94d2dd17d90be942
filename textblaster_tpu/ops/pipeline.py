"""The compiled filter pipeline: config -> one XLA program per shape bucket.

This is the device replacement for the reference's executor + worker loop
(SURVEY.md §7 stage 3): the whole filter chain is traced once into a single
``jit`` function mapping a packed batch to per-filter integer statistics.
Sequential observable semantics (a doc filtered at step k gets no step-k+1
metadata; C4's rewrite feeds downstream steps) are preserved by:

* computing every step's stats unconditionally on device (masked work is
  free compared to divergent control flow — XLA semantics), and
* resolving order, short-circuiting, metadata stamping, and reason-string
  formatting on the host from the integer stats, with float64 arithmetic
  identical to the oracle filters'.

Steps with no device kernel (TokenCounter; C4BadWordsFilter when no local
word list is available) run as host oracle steps.  If they appear as a suffix
of the config, the device prefix still runs compiled; any other placement
falls back to the host executor for the whole pipeline.  Documents that
overflow kernel table bounds (pathological line/word counts) are re-run on
the host oracle — the outlier path SURVEY.md §5 calls for.
"""

from __future__ import annotations

import dataclasses
import itertools
import logging
import os
import time as _time_mod
from collections import deque
from functools import lru_cache, partial
from typing import Callable, Dict, Iterable, Iterator, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..config.pipeline import (
    OverlapConfig,
    PipelineConfig,
    ResilienceConfig,
    StepConfig,
)
from ..data_model import ProcessingOutcome, TextDocument
from ..errors import PipelineError, RetryExhaustedError
from ..filters.c4_quality import CITATION_RE
from ..filters.common import fmt2, fmt4, rust_bool, rust_float, rust_lines
from ..filters.gopher_quality import DEFAULT_STOP_WORDS
from ..filters.fineweb_quality import DEFAULT_STOP_CHARS
from ..models.langid import ISO_TO_NAME, LANGUAGES, NAME_TO_ISO, LangIdModel
from ..orchestration import execute_processing_batch, execute_processing_pipeline
from ..pipeline_builder import build_pipeline_from_config
from ..resilience.breaker import CircuitBreaker
from ..resilience.faults import FAULTS
from ..resilience.retry import RetryPolicy
from ..resilience.watchdog import WATCHDOG
from ..utils.metrics import FILTER_DROP_PREFIX, METRICS
from ..utils.profiler import PROFILER
from ..utils.telemetry import TELEMETRY
from ..utils.events import EVENTS
from ..utils.trace import TRACER
from ..utils.overlap import prefetch_iter
from .badwords import badwords_matches_multi
from .langid_tpu import langid_scores
from .geometry import DeviceGeometry
from .packing import (
    DEFAULT_BUCKETS,
    HOST_TAIL_FILL,
    HOST_TAIL_FILL_FIRST,
    PACK_MARGIN,
    PackedBatch,
    iter_packed_batches,
    pack_documents,
)
from .stats import (
    C4Params,
    c4_stage,
    fineweb_stats,
    gopher_quality_stats,
    gopher_rep_stats,
    hash_string,
    structure,
)

logger = logging.getLogger(__name__)

__all__ = ["CompiledPipeline", "process_documents_device", "device_step_types"]

_DEVICE_STEPS = {
    "LanguageDetectionFilter",
    "GopherRepetitionFilter",
    "GopherQualityFilter",
    "C4QualityFilter",
    "FineWebQualityFilter",
    "C4BadWordsFilter",
}

_CJK_BADWORDS_LANGS = ("ja", "th", "zh")  # c4_filters.rs:70

#: Window sentinel: the breaker refused this batch's dispatch — the drain
#: sends it straight to the host rung without recording a breaker failure
#: (the device was never asked, so there is nothing new to count).
_BREAKER_OPEN = object()


#: Documents per chunk per chip in ``process_documents_device``: a mesh's
#: chunk grows with its chips, so each bucket fills as many batches per
#: chunk as on one chip.
CHUNK_DOCS = 4096


def device_step_types() -> frozenset:
    return frozenset(_DEVICE_STEPS)


@lru_cache(maxsize=64)
def _badwords_tables_cached(default_language: str, cache_base_path, stat_key):
    from ..filters.c4_badwords import load_local_badwords
    from .badwords import BadwordTables

    words = load_local_badwords(default_language, cache_base_path)
    if not words:
        # Unavailable or empty: the host filter owns the semantics
        # (download, passed_no_regex, fail_on_missing_language).
        return None
    return BadwordTables.build(
        words, check_boundaries=default_language not in _CJK_BADWORDS_LANGS
    )


def _badwords_list_stat(default_language: str, cache_base_path):
    """(mtime_ns, size) of the on-disk list, or None when absent — part of
    the cache key so a list that appears or changes during a long-lived
    process is observed instead of a stale table (or stale None) sticking
    for the process lifetime."""
    import os

    from ..filters.c4_badwords import local_badwords_path

    path = local_badwords_path(default_language, cache_base_path)
    try:
        st = os.stat(path)
    except OSError:
        return None
    return (st.st_mtime_ns, st.st_size)


def _badwords_tables(step: StepConfig):
    """BadwordTables for the step's default language from local lists only,
    or None (-> host execution).  Cached per (lang, cache path, file stat);
    the cache also makes the `_step_on_device` check and `_build_fn` see one
    consistent value even if the on-disk list disappears between them."""
    p = step.params
    stat_key = _badwords_list_stat(p.default_language, p.cache_base_path)
    return _badwords_tables_cached(p.default_language, p.cache_base_path, stat_key)


def _badwords_all_tables(step: StepConfig) -> Dict[str, object]:
    """Tables for EVERY language with a locally available list (vendored or
    cache dir) — one device pass then decides docs of all these languages,
    not just the default (VERDICT r3 weak #7).  Languages without local
    lists keep full host semantics (download / passed_no_regex /
    fail_on_missing_language)."""
    from ..filters.c4_badwords import BADWORDS_LANGS

    p = step.params
    out: Dict[str, object] = {}
    for lang in BADWORDS_LANGS:
        stat_key = _badwords_list_stat(lang, p.cache_base_path)
        if stat_key is None:
            continue
        tables = _badwords_tables_cached(lang, p.cache_base_path, stat_key)
        if tables is not None:
            out[lang] = tables
    return out


def _step_on_device_base(step: StepConfig) -> bool:
    """Device eligibility from config alone (no filesystem consulted)."""
    return step.type in _DEVICE_STEPS


def _step_on_device(step: StepConfig) -> bool:
    if not _step_on_device_base(step):
        return False
    if step.type == "C4BadWordsFilter" and _badwords_tables(step) is None:
        return False
    return True


def _table_sizes(length: int) -> Tuple[int, int]:
    """(max line/para slots, max word slots) for a bucket of ``length``.

    Word slots assume >= 4 chars per word+separator on average; denser docs
    hit ``word_overflow`` and take the (counted, bit-exact) host fallback.
    The cap halves the duplicate-table sort volume vs ``length // 2``."""
    max_lines = min(length, max(128, length // 8))
    max_words = min(16384, max(256, length // 4))
    return max_lines, max_words


class _Decision:
    """Host-side result for one step on one doc."""

    __slots__ = ("passed", "reason", "stamps", "extra")

    def __init__(self, passed: bool, reason: str = "", stamps=None, extra=None):
        self.passed = passed
        self.reason = reason
        self.stamps = stamps or []  # list[(key, value)] in stamp order
        self.extra = extra


class _StepEval:
    """Batch-vectorized verdicts for one step (see finalizer section notes)."""

    __slots__ = (
        "passed",
        "overflow",
        "decide",
        "pass_stamps",
        "pass_stamp_fn",
        "c4_line_keep",
        "c4_n_lines",
        "c4_rewrite_identity",
        "badwords_matches",
        "badwords_default_language",
        "badwords_fold_hazard",
    )

    def __init__(self, passed, decide, pass_stamps, overflow=None):
        self.passed = passed
        self.overflow = overflow
        self.decide = decide
        # Constant stamps for passing rows; None means even passing rows need
        # decide() (per-row stamp values or host-side work) — unless
        # pass_stamp_fn supplies the per-row stamps from batch-precomputed
        # arrays (the assemble_phase fast path).
        self.pass_stamps = pass_stamps
        self.pass_stamp_fn = None
        self.c4_line_keep = None
        self.c4_n_lines = None
        self.c4_rewrite_identity = None
        self.badwords_matches = None
        self.badwords_default_language = None
        self.badwords_fold_hazard = None


def default_batch_size(buckets=DEFAULT_BUCKETS) -> int:
    """Rows per device batch when the caller didn't choose.

    XLA:CPU throughput is cache-residency-bound: per-op working sets beyond
    the L2 fall to memory bandwidth, and the measured knee on the bench box
    is ~128k int32 lanes per batch — dropping the full-pipeline batch from
    1024 to 64 rows at 2048-char buckets took a pass from 6.5 s to 3.3 s
    (oracle 6.0 s), flipping every sub-1.0 bench config above the oracle.
    Accelerators amortize the per-dispatch cost and keep the round-1024
    heuristic, scaled down for very wide buckets so a batch stays ~8 MB.
    """
    max_bucket = max(buckets)
    if jax.default_backend() == "cpu":
        return max(8, min(256, (64 * 2048) // max_bucket))
    return max(64, min(1024, (1024 * 2048) // max_bucket))


def record_occupancy(batch: PackedBatch) -> None:
    """Occupancy telemetry for one device dispatch (see utils/metrics.py):
    real codepoints vs padded lanes actually computed, plus a per-bucket
    dispatch counter.  Called at every dispatch seam (single-host
    ``dispatch_batch``, the multi-host lockstep loop) so the waste ratio in
    the CLI/bench reports reflects what the device really executed."""
    rows, length = batch.cps.shape
    METRICS.inc("occupancy_device_batches_total")
    METRICS.inc("occupancy_padded_lanes_total", float(rows) * float(length))
    METRICS.inc("occupancy_real_codepoints_total", float(int(batch.lengths.sum())))
    METRICS.inc(f"occupancy_dispatches_bucket_{length}")


# Step types that cheaply kill many documents: a phase boundary after them
# lets the runner repack survivors and skip the expensive downstream kernels
# for already-filtered rows — the device analogue of the host executor's
# short-circuit (executor.rs:30-57).
_PHASE_BOUNDARY_AFTER = frozenset({"LanguageDetectionFilter", "GopherQualityFilter"})

def _wire_u16() -> bool:
    """uint16 device uploads (see CompiledPipeline.__init__ note).

    ``TEXTBLAST_WIRE=u16|cp32`` pins it; the default is u16 on accelerator
    backends (halves the host-to-device transfer) and cp32 on CPU (no
    transfer to save; the widen would be pure cost)."""
    import os

    w = os.environ.get("TEXTBLAST_WIRE", "")
    if w == "u16":
        return True
    if w == "cp32":
        return False
    return jax.default_backend() == "tpu"


# Steps whose decisions depend on word segmentation (word counts, stop
# words, word n-gram tables, words-per-line) — the steps that force
# dictionary-script documents onto the host oracle (see __init__).
_WORD_TABLE_STEPS = frozenset(
    {
        "GopherRepetitionFilter",
        "GopherQualityFilter",
        "C4QualityFilter",
        "FineWebQualityFilter",
    }
)


def _split_phases(steps: List[StepConfig]) -> List[List[int]]:
    phases: List[List[int]] = []
    cur: List[int] = []
    for i, s in enumerate(steps):
        cur.append(i)
        if s.type in _PHASE_BOUNDARY_AFTER and i < len(steps) - 1:
            phases.append(cur)
            cur = []
    if cur:
        phases.append(cur)
    return phases or [[]]


@dataclasses.dataclass
class WarmupStats:
    """Timing breakdown of one ``warmup_parallel`` call.

    ``total_s`` is wall time; ``trace_s``/``compile_s``/``cache_load_s``
    attribute where it went (compile_s and cache_load_s are summed across
    pool threads, so they can exceed total_s on multi-core).
    ``float(stats)`` is ``total_s`` for drop-in use where the old float
    return was consumed."""

    total_s: float = 0.0
    trace_s: float = 0.0
    compile_s: float = 0.0
    cache_load_s: float = 0.0
    programs: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    cache_stores: int = 0

    def __float__(self) -> float:
        return self.total_s

    def to_dict(self) -> Dict[str, float]:
        return dataclasses.asdict(self)


def _toggle_xla_compilation_cache(on: bool) -> bool:
    """Flip ``jax_enable_compilation_cache`` and force jax to notice.

    jax memoizes whether the cache is used the first time any compile
    consults it, so a plain config update after that point is silently
    ignored; the public ``reset_cache()`` clears the memo (checked against
    jax 0.9.0).  Returns True iff the flag changed."""
    from jax.experimental.compilation_cache import compilation_cache

    if bool(jax.config.jax_enable_compilation_cache) == on:
        return False
    jax.config.update("jax_enable_compilation_cache", on)
    compilation_cache.reset_cache()
    return True


def _tails_by_fill() -> bool:
    """Whether leftover groups are routed by fill (accelerators) rather
    than by count (XLA:CPU, where the count rule was set); see
    ``CompiledPipeline._run_phase``."""
    return jax.default_backend() != "cpu"


def should_warmup(warmup: Optional[bool] = None) -> bool:
    """Resolve the warmup tri-state: explicit flag > ``TEXTBLAST_WARMUP``
    env > backend default (accelerators warm — cold TPU compiles
    dominate startup; CPU stays lazy — first-dispatch compiles there are
    cheap and a warm AOT cache makes them cheaper)."""
    if warmup is not None:
        return warmup
    env = os.environ.get("TEXTBLAST_WARMUP", "").lower()
    if env:
        return env not in ("0", "off", "false")
    return jax.default_backend() == "tpu"


def maybe_warmup(
    pipeline: "CompiledPipeline", warmup: Optional[bool] = None
) -> Optional[WarmupStats]:
    """Warm ``pipeline`` when the resolved policy says so (see
    :func:`should_warmup`); every runner entry point (streaming,
    checkpointed, multi-host) funnels through this so the AOT executable
    cache is consulted uniformly.  Returns the stats, or None if skipped."""
    if pipeline.fully_host or not pipeline.device_steps:
        METRICS.set("pipeline_warmup_done", 1)
        return None
    if not should_warmup(warmup):
        METRICS.set("pipeline_warmup_done", 1)
        return None
    ws = pipeline.warmup_parallel()
    METRICS.set("pipeline_warmup_done", 1)
    logger.info(
        "warmup: %d programs in %.2fs (trace %.2fs, compile %.2fs, "
        "cache-load %.2fs, %d/%d AOT hits)",
        ws.programs, ws.total_s, ws.trace_s, ws.compile_s,
        ws.cache_load_s, ws.cache_hits, ws.programs,
    )
    if EVENTS.enabled:
        EVENTS.emit("warmup_complete", programs=ws.programs,
                    total_s=round(ws.total_s, 3), cache_hits=ws.cache_hits,
                    cache_misses=ws.cache_misses, compile_s=round(ws.compile_s, 3))
    return ws


class CompiledPipeline:
    """A pipeline config compiled for device execution."""

    def __init__(
        self,
        config: PipelineConfig,
        buckets=DEFAULT_BUCKETS,
        batch_size: Optional[int] = None,
        mesh=None,
        phase_split: bool = True,
        geometry: Optional[DeviceGeometry] = None,
        multihost: bool = False,
    ) -> None:
        """``multihost``: built by a multi-host runtime
        (``parallel/multihost.py``), whose processes negotiate one program
        sequence and wire; its mesh keeps the int32 wire, the batch as
        given and none of the one-controller path below."""
        self.config = config
        self.mesh = mesh
        #: Chips one dispatch spans.
        self.chips = mesh.devices.size if mesh is not None else 1
        #: One controller dispatches every batch for every chip: no mesh, or
        #: a mesh this process drives alone.  Such a pipeline takes the
        #: one-chip path on each chip: the u16 wire, warm dispatch, the
        #: ladder's split rung, leftover groups routed onto warm half-row
        #: programs, the in-flight window and donated inputs.
        self.one_controller = mesh is None or not multihost
        if geometry is not None:
            # Calibrated (or checkpoint-recorded) geometry supersedes the
            # buckets/batch_size knobs; mesh runs need every per-bucket batch
            # divisible by the device count.
            if mesh is not None:
                geometry = geometry.with_batch_multiple(self.chips)
            self.geometry = geometry
        else:
            bs = tuple(sorted(buckets))
            default = default_batch_size(bs)
            explicit = bool(batch_size)  # None or 0 — CLI passes ints through
            if not explicit:
                # Each chip of a one-controller mesh holds what one chip
                # holds alone; an explicit batch is the global batch.
                batch_size = default * (self.chips if self.one_controller else 1)
            if mesh is not None:
                batch_size = max(self.chips, (batch_size // self.chips) * self.chips)
            src = "explicit" if explicit and batch_size != default else "default"
            self.geometry = DeviceGeometry.uniform(bs, batch_size, source=src)
        self.buckets = self.geometry.buckets
        # The representative (largest) per-dispatch row count: chunk sizing,
        # host-tail thresholds, and multi-host sharding key off it.
        self.batch_size = self.geometry.max_batch

        steps = list(config.pipeline)
        n_device = 0
        # Badwords tables are resolved ONCE here and carried on the instance:
        # _build_fn may run much later (first batch of a new bucket length),
        # and the on-disk list can have changed or vanished by then — the
        # plan must use exactly the tables this placement decision saw.
        self._badwords_device_tables: Dict[int, object] = {}
        for s in steps:
            if s.type == "C4BadWordsFilter" and _step_on_device_base(s):
                if _badwords_tables(s) is None:  # default language must exist
                    break
                self._badwords_device_tables[n_device] = _badwords_all_tables(s)
            elif not _step_on_device(s):
                break
            n_device += 1
        self.device_steps = steps[:n_device]
        self.host_steps = steps[n_device:]
        if self.device_steps:
            # Kernel probes run now: a probe cannot run inside the trace of
            # the programs whose kernels it gates.
            from .pallas_scan import probe_kernels

            probe_kernels()
        # Host-only fallback when un-kerneled steps precede device steps.
        self.fully_host = any(_step_on_device(s) for s in self.host_steps)

        # Documents containing dictionary-segmented scripts (Han/kana/Thai…)
        # are decided by the host oracle whenever a word-table kernel is in
        # the pipeline: the host word splitter now approximates ICU's
        # dictionary segmentation for those scripts (utils/cjk.py), which
        # the kernels' UAX#29-lite run-whole tables cannot express.  Routing
        # is a correctness fallback (counts in worker_host_fallback_total),
        # the same pattern as kernel-table overflows.
        self._route_dict_scripts = any(
            s.type in _WORD_TABLE_STEPS for s in self.device_steps
        )

        # Wire format: accelerator uploads dominate TPU pass time (round-5
        # window: ~0.5 s of a 1.7 s c4 pass was the 32 MB int32 upload at
        # ~65 MB/s), and BMP codepoints fit uint16 exactly.  Rows containing
        # supplementary-plane chars (emoji etc.) are routed to the host
        # oracle instead — decisions stay bit-identical, attribution is the
        # fallback counter.  Multi-host meshes keep int32 (one format keeps
        # lockstep simple).
        self.wire_u16 = self.one_controller and _wire_u16()

        # Multi-phase short-circuiting: always on single-controller runs
        # (including single-process meshes — one controller dispatches for
        # every local device, so there is no lockstep problem and the v5e-8
        # north-star config gets the phasing win).  Multi-PROCESS SPMD jobs
        # must dispatch identical program sequences; run_local_shard
        # (parallel/multihost.py) makes that safe by negotiating per-phase
        # round counts over allgather, so phases stay enabled there too.
        # TEXTBLAST_PHASES=off (or phase_split=False) pins the single fused
        # program.
        import os as _os

        if phase_split and _os.environ.get("TEXTBLAST_PHASES") != "off":
            self.phases = _split_phases(self.device_steps)
            # A content-REWRITING step in a non-final phase would make later
            # phases' host-fallback reruns re-run the rewrite on already
            # rewritten content; bit-exactness would then rest on the rewrite
            # being idempotent (plausible, unverified — ADVICE r3).  Only
            # split when every rewriting step sits in the final phase.
            if any(
                self.device_steps[i].type == "C4QualityFilter"
                for ph in self.phases[:-1]
                for i in ph
            ):
                self.phases = [list(range(len(self.device_steps)))]
        else:
            self.phases = [list(range(len(self.device_steps)))]

        self._host_executor = None
        self._host_suffix_executor = None
        self._jitted: Dict[Tuple, Callable] = {}
        self._badwords_steps: Dict[int, object] = {}

        # Degradation ladder state (see _execute_packed): retry the batch ->
        # split it in half -> rerun the docs on the host oracle, with a
        # breaker that abandons the device path for the run after N
        # consecutive batches fell all the way to the host rung.
        rc = getattr(config, "resilience", None) or ResilienceConfig()
        self._retry = RetryPolicy.from_config(rc)
        self._breaker = CircuitBreaker(
            rc.breaker_threshold,
            cooldown_s=getattr(rc, "breaker_cooldown_s", 0.0),
        )
        self._split_retry = rc.split_retry

        # Overlapped host pipeline (see process_chunk): depth of the device
        # in-flight window and the pack-stage thread pool.  Multi-host
        # meshes stay serial here (their runtime keeps its own window).
        self._overlap = getattr(config, "overlap", None) or OverlapConfig()
        self._pack_pool_obj = None
        # Sequence numbers shared by every span of one batch and of one
        # chunk (utils/trace.py), so a trace joins pack, dispatch, wait and
        # assembly of the same batch.
        self._batch_ids = itertools.count()
        self._chunks = 0  # chunks started: the next chunk's number
        # The post stage's parts exist from the start, so a run with no
        # host tail reads 0 rather than nothing.
        METRICS.inc("stage_host_suffix_seconds", 0.0)
        METRICS.inc("worker_host_suffix_batched_total", 0)
        METRICS.inc("stage_host_tail_seconds", 0.0)
        METRICS.inc("worker_device_tail_total", 0)
        if mesh is not None:
            METRICS.inc("stage_mesh_upload_seconds", 0.0)

    def _badwords_host_step(self, idx: int):
        """The real host C4BadWordsFilter for device step ``idx`` — runs only
        on kernel-flagged candidates (shared regex cache + RNG across docs)."""
        if idx not in self._badwords_steps:
            from ..pipeline_builder import build_step

            self._badwords_steps[idx] = build_step(self.device_steps[idx])
        return self._badwords_steps[idx]

    # --- host executors -----------------------------------------------------

    @property
    def host_executor(self):
        if self._host_executor is None:
            self._host_executor = build_pipeline_from_config(self.config)
        return self._host_executor

    @property
    def host_suffix_executor(self):
        if self._host_suffix_executor is None:
            from ..executor import PipelineExecutor
            from ..pipeline_builder import build_step

            self._host_suffix_executor = PipelineExecutor(
                [build_step(s) for s in self.host_steps]
            )
        return self._host_suffix_executor

    # --- device program -----------------------------------------------------

    def _build_fn(self, length: int, phase: int = 0, jit: bool = True) -> Callable:
        max_lines, max_words = _table_sizes(length)
        plans = []
        for i in self.phases[phase]:
            step = self.device_steps[i]
            p = step.params
            if step.type == "LanguageDetectionFilter":
                plans.append(("langid", i, None))
            elif step.type == "GopherQualityFilter":
                stop_words = (
                    p.stop_words if p.stop_words is not None else list(DEFAULT_STOP_WORDS)
                )
                hashes = tuple(sorted({hash_string(w) for w in stop_words}))
                plans.append(("gopher_quality", i, hashes))
            elif step.type == "GopherRepetitionFilter":
                plans.append(
                    (
                        "gopher_rep",
                        i,
                        (
                            tuple(n for n, _ in p.top_n_grams),
                            tuple(n for n, _ in p.dup_n_grams),
                        ),
                    )
                )
            elif step.type == "C4QualityFilter":
                plans.append(
                    (
                        "c4",
                        i,
                        C4Params(
                            split_paragraph=p.split_paragraph,
                            remove_citations=p.remove_citations,
                            filter_no_terminal_punct=p.filter_no_terminal_punct,
                            min_num_sentences=p.min_num_sentences,
                            min_words_per_line=p.min_words_per_line,
                            max_word_length=p.max_word_length,
                            filter_lorem_ipsum=p.filter_lorem_ipsum,
                            filter_javascript=p.filter_javascript,
                            filter_curly_bracket=p.filter_curly_bracket,
                            filter_policy=p.filter_policy,
                        ),
                    )
                )
            elif step.type == "FineWebQualityFilter":
                stop_chars = (
                    tuple(sorted(p.stop_chars))
                    if p.stop_chars is not None
                    else tuple(sorted(DEFAULT_STOP_CHARS))
                )
                plans.append(("fineweb", i, (stop_chars, p.short_line_length)))
            elif step.type == "C4BadWordsFilter":
                plans.append(("badwords", i, self._badwords_device_tables[i]))

        # Mosaic pallas_call has no GSPMD partitioning rule, so multi-device
        # programs run the sort kernels under shard_map over the data axis —
        # the stats entry points take the mesh explicitly (pallas_sort.sort2).
        mesh = self.mesh if self.mesh is not None and self.mesh.devices.size > 1 else None

        # Unit hashes are consumed only by the Gopher steps; phases without
        # them (e.g. the c4+fineweb phase) skip both polynomial-hash scans.
        needs_hashes = any(
            kind in ("gopher_quality", "gopher_rep") for kind, _, _ in plans
        )

        def fn(cps, lengths):
            if self.mesh is not None:
                # Bare pallas_call has no GSPMD rule: tracing under
                # mesh_tracing(mesh) makes every scan kernel dispatch through
                # shard_map over the data axis instead (the pallas_sort.sort2
                # pattern), so mesh programs keep the Pallas scans.  A mesh
                # without a usable data axis still declines to the lax scans.
                from .pallas_scan import mesh_tracing

                with mesh_tracing(self.mesh):
                    return inner(cps, lengths)
            return inner(cps, lengths)

        def inner(cps, lengths):
            if self.wire_u16:
                # Wire is uint16; every kernel computes in int32.  The widen
                # fuses into the first consumer on device.
                cps = cps.astype(jnp.int32)
            out: Dict[str, jax.Array] = {}
            state = {"cps": cps, "lengths": lengths, "st": None}

            def get_structure():
                if state["st"] is None:
                    state["st"] = structure(
                        state["cps"], state["lengths"], with_hashes=needs_hashes
                    )
                return state["st"]

            return _eval_plans(plans, state, out, get_structure, max_lines, max_words)

        def _eval_plans(plans, state, out, get_structure, max_lines, max_words):
            for kind, i, arg in plans:
                if kind == "langid":
                    scores, n_grams = langid_scores(
                        state["cps"], state["lengths"], mesh=mesh
                    )
                    out[f"{i}:scores"] = scores
                    out[f"{i}:n_grams"] = n_grams
                elif kind == "gopher_quality":
                    for k, v in gopher_quality_stats(get_structure(), arg).items():
                        out[f"{i}:{k}"] = v
                elif kind == "gopher_rep":
                    top_ns, dup_ns = arg
                    stats = gopher_rep_stats(
                        get_structure(), top_ns, dup_ns, max_lines, max_words,
                        mesh=mesh,
                    )
                    for k, v in stats.items():
                        out[f"{i}:{k}"] = v
                elif kind == "c4":
                    stats, new_cps, new_lengths = c4_stage(
                        state["cps"], state["lengths"], arg, max_lines, mesh=mesh
                    )
                    for k, v in stats.items():
                        out[f"{i}:{k}"] = v
                    # Downstream steps see the rewritten batch (sequential
                    # pipeline semantics — executor.rs:30-57 analogue).
                    state.update(cps=new_cps, lengths=new_lengths, st=None)
                elif kind == "fineweb":
                    stop_chars, short_len = arg
                    fw = fineweb_stats(
                        get_structure(), stop_chars, max_lines, short_len, mesh=mesh
                    )
                    for k, v in fw.items():
                        out[f"{i}:{k}"] = v
                elif kind == "badwords":
                    per_lang, per_hazard = badwords_matches_multi(
                        state["cps"], state["lengths"], arg
                    )
                    for lang, m in per_lang.items():
                        out[f"{i}:match:{lang}"] = m
                        out[f"{i}:hazard:{lang}"] = per_hazard[lang]
            return out

        # The program's name carries its (bucket, phase): the profiler's
        # dispatch and device-module events then say which program ran.
        fn.__name__ = fn.__qualname__ = f"tb_b{length}_p{phase}"
        if not jit:
            # Raw traceable fn (scan_dispatch_counts traces it under
            # jax.eval_shape to count dispatches without compiling).
            return fn
        kwargs = {}
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            from ..parallel.mesh import DATA_AXIS, batch_sharding

            # Outputs must stay data-sharded (leading dim on the data axis,
            # trailing dims replicated): without out_shardings XLA may pick a
            # replicated layout, and the multi-host path reads each process's
            # addressable rows as *its* documents' stats — replication would
            # silently hand every host process-0's rows.
            kwargs.update(
                in_shardings=(
                    batch_sharding(self.mesh, 2),
                    batch_sharding(self.mesh, 1),
                ),
                out_shardings=NamedSharding(self.mesh, PartitionSpec(DATA_AXIS)),
            )
        if self.one_controller and jax.default_backend() == "tpu":
            # Each dispatch uploads fresh numpy arrays, so the input buffers
            # are never reused host-side: donating them lets XLA alias the
            # [B, L] codepoint upload into scratch instead of holding both
            # live — with a K-deep in-flight window the biggest buffer would
            # otherwise exist K+1 times.  CPU stays undonated (XLA:CPU often
            # can't use the donation and warns per call).
            kwargs["donate_argnums"] = (0, 1)
        return jax.jit(fn, **kwargs)

    def _fn_for(
        self, length: int, phase: int = 0, rows: Optional[int] = None
    ) -> Callable:
        """Program for one (bucket length, phase) — and, for the ladder's
        split rung, a separate cache entry per non-standard row count:
        ``warmup_parallel`` installs AOT executables fixed to the bucket's
        geometry rows, which a half-sized batch must never hit."""
        if rows is not None and rows != self.geometry.batch_for(length):
            key = (length, phase, rows)
        else:
            key = (length, phase)
        if key not in self._jitted:
            self._jitted[key] = self._build_fn(length, phase)
        return self._jitted[key]

    def scan_dispatch_counts(
        self, length: int, phase: int = 0, rows: Optional[int] = None
    ) -> Dict[str, int]:
        """Per-kind scan dispatch counts for one traced (bucket, phase)
        program: "fused" / "pallas_scan" kernel calls and "lax_scan"
        schedules, "pallas_sort" / "lax_sort" row sorts.  A multi-pass
        dependency chain (``chain_scan``) books as ONE "fused" dispatch
        however many passes and groups it carries — the whole point of the
        chain is that its intermediate streams never leave VMEM, so one
        kernel launch is the honest count.  Traces the raw program under
        ``jax.eval_shape`` (no compile, no device execution); the
        dispatch-count gates and the profiler's sentinel read it."""
        from .pallas_scan import count_scan_dispatches

        rows = rows or self.geometry.batch_for(length)
        raw = self._build_fn(length, phase, jit=False)
        wire = jnp.uint16 if self.wire_u16 else jnp.int32
        cps = jax.ShapeDtypeStruct((rows, length), wire)
        lens = jax.ShapeDtypeStruct((rows,), jnp.int32)
        with count_scan_dispatches() as counts:
            jax.eval_shape(raw, cps, lens)
        return dict(counts)

    @staticmethod
    def _split_rows(full: int, chips: int = 1) -> int:
        """Row count the degradation ladder's split rung packs each half to:
        half the batch, rounded UP to the 8-row sublane tile on every chip
        (a multiple of ``chips`` × 8) so the split program keeps the (fused)
        Pallas kernels — ``pallas_scan_ok`` / ``fused_scan_ok`` require
        rows % 8 == 0 on a chip, and pack_documents already pads rows beyond
        the doc count."""
        from .pallas_sort import ROWS

        tile = ROWS * chips
        half = (full + 1) // 2
        return min(full, ((half + tile - 1) // tile) * tile)

    def _warm_half_rows(self, length: int) -> int:
        """The half-row count warmup installs for bucket ``length`` (the
        split rung's), or the bucket's full rows where it installs none."""
        full = self.geometry.batch_for(length)
        if self._split_retry and self.one_controller:
            return self._split_rows(full, self.chips)
        return full

    def _warmup_jobs(self, include_split_rows: bool = True):
        """``(program key, length, phase, rows)`` tuples warmup must cover:
        every (bucket, phase) at geometry rows — plus the degradation
        ladder's half-split row count, which ``_execute_packed`` packs both
        halves to and ``_fn_for`` keys separately.  Without pre-seeding,
        those programs (fused-kernel variants included — the split rows are
        ROWS-aligned via ``_split_rows`` so they trace the same fused path,
        multi-pass ``chain_scan`` chains and all; ``TEXTBLAST_PALLAS`` is
        fingerprinted by the AOT cache via ``_TRACE_ENV_KNOBS``, so each
        setting pre-seeds its own executables)
        always compiled cold *mid-incident*, stacking a 15-29 s compile
        stall on top of whatever fault tripped the split."""
        jobs = []
        for length in self.buckets:
            full = self.geometry.batch_for(length)
            variants = [full]
            sub = self._warm_half_rows(length)
            if include_split_rows and sub != full:
                variants.append(sub)
            for phase in range(len(self.phases)):
                for rows in variants:
                    key = (length, phase) if rows == full else (length, phase, rows)
                    jobs.append((key, length, phase, rows))
        return jobs

    def warmup_parallel(
        self,
        max_workers: int = 8,
        aot_cache=None,
        include_split_rows: bool = True,
    ) -> "WarmupStats":
        """Install every warmup program (see ``_warmup_jobs``), cheapest
        source first: serialized AOT executable cache, else trace + compile.

        **AOT cache.**  Each program is first looked up in the serialized
        executable store (``utils.compile_cache.AOTExecutableCache``),
        keyed by geometry + filter-config fingerprints, jax version,
        backend, topology, shape, and the trace-shaping env knobs.  A hit
        deserializes a finished executable — no trace, no lower, no
        compile — so a warm start loads every (bucket, phase) program in
        well under a second instead of the 15-29 s cold path.  Misses are
        compiled and stored back.  ``TEXTBLAST_NO_COMPILE_CACHE=1``
        bypasses both directions; pass ``aot_cache`` to use a specific
        store (bench A/B, tests).

        **Pool.**  Store loads and XLA compilation release the GIL, so
        both run on a thread pool; tracing is Python (GIL-bound) and runs
        serially between them.

        On accelerator backends each pool thread also fires ONE throwaway
        execution of its program (zero-filled batch): the first dispatch
        pays a load/setup cost the compile does not.  CPU backends skip
        it: no device load to hide, and a full-batch execution costs real
        pass time.

        Returns a :class:`WarmupStats` breakdown (``float()`` of it is
        total wall seconds).
        """
        import time as _time
        from concurrent.futures import ThreadPoolExecutor
        from threading import Lock

        import numpy as _np

        from ..utils.compile_cache import (
            AOTExecutableCache,
            config_fingerprint,
            program_cache_key,
        )
        from ..utils.profiler import program_cost

        stats = WarmupStats()
        t0 = _time.perf_counter()
        warm_dispatch = self.one_controller and jax.default_backend() != "cpu"
        wire = jnp.uint16 if self.wire_u16 else jnp.int32
        wire_name = "uint16" if self.wire_u16 else "int32"
        backend = jax.default_backend()

        cache = aot_cache if aot_cache is not None else AOTExecutableCache()
        try:
            cfg_fp = config_fingerprint(self.config)
            geo_fp = self.geometry.fingerprint()
        except Exception as e:  # pragma: no cover - exotic config objects
            logger.warning("AOT cache disabled (unfingerprintable): %s", e)
            cache = None

        def cache_key(length, phase, rows):
            return program_cache_key(
                config_fp=cfg_fp,
                geometry_fp=geo_fp,
                backend=backend,
                length=length,
                phase=phase,
                rows=rows,
                wire=wire_name,
                n_devices=self.chips,
                mesh=self.mesh is not None,
            )

        jobs = [
            job for job in self._warmup_jobs(include_split_rows)
            if not (job[0] in self._jitted
                    and not hasattr(self._jitted[job[0]], "lower"))
        ]  # less the already installed executables
        aot_keys = [
            cache_key(length, phase, rows) if cache is not None else None
            for _, length, phase, rows in jobs
        ]

        def timed_load(aot_key):
            t = _time.perf_counter()
            return cache.load(aot_key), _time.perf_counter() - t

        # AOT-cache loads on the pool (deserializing releases the GIL), then
        # serial traces for the misses.
        loads = [(None, 0.0)] * len(jobs)
        if cache is not None and jobs:
            with ThreadPoolExecutor(max_workers=max_workers) as pool:
                loads = list(pool.map(timed_load, aot_keys))
        to_compile = []  # (key, length, rows, lowered, aot_key)
        loaded = []  # (key, length, rows, compiled) — warm-dispatch only
        for (key, length, phase, rows), aot_key, (compiled, load_s) in zip(
            jobs, aot_keys, loads
        ):
            stats.programs += 1
            if cache is not None:
                stats.cache_load_s += load_s
                if compiled is not None:
                    stats.cache_hits += 1
                    self._jitted[key] = compiled
                    if PROFILER.enabled:
                        # Cost model survives the cache hit: the sidecar
                        # holds the numbers captured at compile time; a
                        # missing sidecar (pre-profiler entry) falls back
                        # to re-analyzing the deserialized executable and
                        # backfills the sidecar for the next warm start.
                        cost = cache.load_cost(aot_key)
                        source = "aot-sidecar"
                        if cost is None:
                            cost = program_cost(compiled)
                            source = "aot-recompute"
                            if cost is not None:
                                cache.store_cost(aot_key, cost)
                        PROFILER.record_program_cost(
                            length, phase, rows, cost, source
                        )
                    if warm_dispatch:
                        loaded.append((key, length, rows, compiled))
                    continue
                stats.cache_misses += 1
            fn = self._fn_for(length, phase, rows=rows)
            cps = jax.ShapeDtypeStruct((rows, length), wire)
            lens = jax.ShapeDtypeStruct((rows,), jnp.int32)
            t = _time.perf_counter()
            lowered = fn.lower(cps, lens)
            stats.trace_s += _time.perf_counter() - t
            to_compile.append((key, length, rows, lowered, aot_key))

        lock = Lock()

        def dispatch_zero(compiled, length, rows):
            wire_np = _np.uint16 if self.wire_u16 else _np.int32
            z = _np.zeros((rows, length), dtype=wire_np)
            zl = _np.zeros((rows,), dtype=_np.int32)
            if self.mesh is not None:
                from ..parallel.mesh import shard_batch

                z, zl = shard_batch(self.mesh, z, zl)
            else:
                z, zl = jnp.asarray(z), jnp.asarray(zl)
            jax.block_until_ready(compiled(z, zl))

        def compile_one(item):
            # A compile error (shape, VMEM) repeats identically: no retry.
            key, length, rows, lowered, aot_key = item
            t = _time.perf_counter()
            compiled = lowered.compile()
            with lock:
                stats.compile_s += _time.perf_counter() - t
            if PROFILER.enabled:
                cost = program_cost(compiled)
                PROFILER.record_program_cost(
                    key[0], key[1], rows, cost, "compile"
                )
                if cache is not None and aot_key is not None and cost:
                    cache.store_cost(aot_key, cost)
            if cache is not None and aot_key is not None:
                if cache.store(aot_key, compiled):
                    with lock:
                        stats.cache_stores += 1
            if warm_dispatch:
                dispatch_zero(compiled, length, rows)
            return key, compiled

        def load_one(item):
            key, length, rows, compiled = item
            dispatch_zero(compiled, length, rows)

        # Compiles that will be stored must NOT be served by XLA's own
        # persistent compilation cache: cache-served executables serialize
        # without their kernel object code (deserialize fails "Symbols not
        # found" on XLA:CPU), so the AOT store would fill with entries every
        # future process evicts.  Flipping the enable flag alone is not
        # enough — jax memoizes is_cache_used() at first compile — so the
        # memo must be reset around the toggle.  Nothing else compiles
        # during warmup; everything is restored before the first dispatch.
        xla_cache_disabled = False
        if cache is not None and to_compile:
            xla_cache_disabled = _toggle_xla_compilation_cache(False)
        try:
            with ThreadPoolExecutor(max_workers=max_workers) as pool:
                if loaded:
                    list(pool.map(load_one, loaded))
                for key, compiled in pool.map(compile_one, to_compile):
                    self._jitted[key] = compiled
        finally:
            if xla_cache_disabled:
                _toggle_xla_compilation_cache(True)
        stats.total_s = _time.perf_counter() - t0
        return stats

    def register_installed_costs(
        self, include_split_rows: bool = True
    ) -> int:
        """Re-register the installed executables' static cost models with
        the PROFILER — for observers armed AFTER warmup (bench A/B,
        tests): the warmup seams only capture when profiling was on at
        compile/load time, and a second ``warmup_parallel`` skips programs
        that are already installed.  Returns the number registered."""
        from ..utils.profiler import program_cost

        n = 0
        for key, length, phase, rows in self._warmup_jobs(
            include_split_rows
        ):
            fn = self._jitted.get(key)
            if fn is None or hasattr(fn, "lower"):
                continue  # missing, or still a jitted wrapper (no analysis)
            cost = program_cost(fn)
            if cost:
                PROFILER.record_program_cost(
                    length, phase, rows, cost, "installed"
                )
                n += 1
        return n

    # --- host finalizers ----------------------------------------------------
    #
    # Threshold logic is evaluated ONCE per batch in vectorized numpy (float64
    # ratios from the device's integer stats — identical arithmetic to the
    # oracle filters'); per-row Python runs only to format reason strings and
    # stamps for rows that need them.  The per-batch eval objects carry:
    #   passed [B] bool   — step verdict per row (badwords: provisional)
    #   overflow [B] bool — row hit a kernel table bound (host-oracle rerun)
    #   pass_stamps       — constant stamps for passing rows (None: per-row)
    #   decide(row, doc)  — full _Decision (fail rows / per-row-stamp steps)

    def _eval_step(self, step: StepConfig, idx: int, stats: Dict[str, np.ndarray]):
        try:
            fn = _EVALS[step.type]
        except KeyError:
            raise PipelineError(f"no finalizer for step {step.type}") from None
        return fn(self, step, idx, stats)

    def _eval_langid(self, step: StepConfig, idx: int, stats) -> "_StepEval":
        p = step.params
        scores = np.asarray(stats[f"{idx}:scores"])
        n_grams = np.asarray(stats[f"{idx}:n_grams"]).astype(np.int64)
        best, conf = LangIdModel.decide_batch(scores, n_grams)

        valid = n_grams > 0
        allowed = [c for c in p.allowed_languages if c in ISO_TO_NAME]
        lang_allowed = np.array(
            [NAME_TO_ISO[lang] in allowed for lang in LANGUAGES], dtype=bool
        )[best]
        conf_ok = conf >= p.min_confidence
        passed = valid & lang_allowed & conf_ok
        joined = "; ".join(allowed)

        def decide(row: int, doc: TextDocument) -> _Decision:
            if not valid[row]:
                return _Decision(False, "Language could not be confidently detected")
            stamps = [
                ("Detected language", LANGUAGES[best[row]]),
                ("Detected language confidence", rust_float(conf[row])),
            ]
            if not lang_allowed[row]:
                return _Decision(
                    False,
                    f'Document is not any of the following languages: "{joined}"',
                    stamps,
                )
            if not conf_ok[row]:
                return _Decision(
                    False,
                    "Language detection confidence is not satified: "
                    f"{rust_float(conf[row])} < {rust_float(p.min_confidence)}",
                    stamps,
                )
            return _Decision(True, stamps=stamps)

        # Langid stamps are per-row even on pass (detected language + conf),
        # but their values come straight from the batch arrays: a stamp
        # function (vectorized language-name take, same rust_float formatting
        # as decide) lets passing rows skip decide() entirely.
        ev = _StepEval(passed=passed, decide=decide, pass_stamps=None)
        lang_names = np.asarray(LANGUAGES, dtype=object)[best]

        def pass_stamp_fn(row: int, doc: TextDocument) -> None:
            doc.metadata["Detected language"] = lang_names[row]
            doc.metadata["Detected language confidence"] = rust_float(conf[row])

        ev.pass_stamp_fn = pass_stamp_fn
        return ev

    def _eval_gopher_rep(self, step: StepConfig, idx: int, stats) -> "_StepEval":
        p = step.params
        g = lambda key: np.asarray(stats[f"{idx}:{key}"]).astype(np.int64)  # noqa: E731
        overflow = np.asarray(stats[f"{idx}:seg_overflow"], dtype=bool) | np.asarray(
            stats[f"{idx}:word_overflow"], dtype=bool
        )
        trimmed = g("trimmed_len")
        empty = trimmed == 0
        char_len = np.maximum(trimmed, 1).astype(np.float64)

        # (cond [B], ratio [B], reason template parts) per check, in the
        # oracle's check order.
        checks = []

        def add(cond, ratio, label, thr):
            checks.append((cond, ratio, label, thr))

        ratio = g("para_dup_elems") / np.maximum(g("n_paragraphs"), 1)
        if p.dup_para_frac is not None:
            add(ratio > p.dup_para_frac, ratio, "dup_para_frac", p.dup_para_frac)
        ratio = g("para_dup_bytes") / char_len
        if p.dup_para_char_frac is not None:
            add(
                ratio > p.dup_para_char_frac,
                ratio,
                "dup_para_char_frac",
                p.dup_para_char_frac,
            )
        ratio = g("line_dup_elems") / np.maximum(g("n_lines"), 1)
        if p.dup_line_frac is not None:
            add(ratio > p.dup_line_frac, ratio, "dup_line_frac", p.dup_line_frac)
        ratio = g("line_dup_bytes") / char_len
        if p.dup_line_char_frac is not None:
            add(
                ratio > p.dup_line_char_frac,
                ratio,
                "dup_line_char_frac",
                p.dup_line_char_frac,
            )
        for n, thr in p.top_n_grams:
            if n > 0:
                ratio = g(f"top_{n}") / char_len
                add(ratio > thr, ratio, f"top_{n}_gram", thr)
        for n, thr in p.dup_n_grams:
            if n > 0:
                ratio = g(f"dup_{n}") / char_len
                add(ratio > thr, ratio, f"duplicated_{n}_n_grams", thr)

        any_cond = empty.copy()
        for cond, _, _, _ in checks:
            any_cond |= cond
        passed = ~any_cond

        def decide(row: int, doc: TextDocument) -> _Decision:
            if empty[row]:
                return _Decision(
                    False,
                    "skipping empty content",
                    [
                        ("gopher_repetition_filter_status", "filtered"),
                        ("gopher_repetition_filter_reason", "skipping empty content"),
                    ],
                )
            reasons = [
                f"{label} (ratio {fmt2(ratio[row])}, max {fmt2(thr)})"
                for cond, ratio, label, thr in checks
                if cond[row]
            ]
            rs = "; ".join(reasons)
            return _Decision(
                False,
                rs,
                [
                    ("gopher_repetition_filter_status", "filtered"),
                    ("gopher_repetition_filter_reasons", rs),
                ],
            )

        return _StepEval(
            passed=passed,
            overflow=overflow,
            decide=decide,
            pass_stamps=(("gopher_repetition_filter_status", "passed"),),
        )

    def _eval_gopher_quality(self, step: StepConfig, idx: int, stats) -> "_StepEval":
        p = step.params
        g = lambda key: np.asarray(stats[f"{idx}:{key}"]).astype(np.int64)  # noqa: E731
        n_non_symbol = g("n_non_symbol")
        n_words = g("n_words")
        sum_len = g("sum_word_len")
        avg = np.zeros(len(n_words), dtype=np.float64)
        np.divide(sum_len, n_non_symbol, out=avg, where=n_non_symbol > 0)
        n_total = np.maximum(n_words, 1).astype(np.float64)
        hash_ratio = g("hash_count") / n_total
        ellipsis_ratio = g("ellipsis_units") / n_total
        n_lines_f = np.maximum(g("n_lines"), 1).astype(np.float64)
        bullet_ratio = g("bullet_lines") / n_lines_f
        ell_lines_ratio = g("ellipsis_lines") / n_lines_f
        alpha_ratio = g("alpha_words") / n_total
        stop_count = g("stop_words")

        # (cond [B], reason_fn(row) -> str) in the oracle's check order.
        checks = []
        if p.min_doc_words is not None:
            checks.append(
                (
                    n_non_symbol < p.min_doc_words,
                    lambda r: f"gopher_short_doc ({n_non_symbol[r]} non-symbol words, "
                    f"required {p.min_doc_words})",
                )
            )
        if p.max_doc_words is not None:
            checks.append(
                (
                    n_non_symbol > p.max_doc_words,
                    lambda r: f"gopher_long_doc ({n_non_symbol[r]} non-symbol words, "
                    f"max {p.max_doc_words})",
                )
            )
        if p.min_avg_word_length is not None:

            def _below_avg(r: int) -> str:
                suffix = (
                    " - 0 non-symbol words"
                    if n_non_symbol[r] == 0 and p.min_avg_word_length > 0.0
                    else ""
                )
                return (
                    f"gopher_below_avg_threshold (avg len {fmt2(avg[r])}, "
                    f"required {fmt2(p.min_avg_word_length)}{suffix})"
                )

            checks.append((avg < p.min_avg_word_length, _below_avg))
        if p.max_avg_word_length is not None:
            checks.append(
                (
                    (n_non_symbol > 0) & (avg > p.max_avg_word_length),
                    lambda r: f"gopher_above_avg_threshold (avg len {fmt2(avg[r])}, "
                    f"max {fmt2(p.max_avg_word_length)})",
                )
            )
        if p.max_symbol_word_ratio is not None:
            checks.append(
                (
                    hash_ratio > p.max_symbol_word_ratio,
                    lambda r: f"gopher_too_many_hashes (ratio {fmt2(hash_ratio[r])}, "
                    f"max {fmt2(p.max_symbol_word_ratio)})",
                )
            )
            checks.append(
                (
                    ellipsis_ratio > p.max_symbol_word_ratio,
                    lambda r: "gopher_too_many_ellipsis_units "
                    f"(ratio {fmt2(ellipsis_ratio[r])}, "
                    f"max {fmt2(p.max_symbol_word_ratio)})",
                )
            )
        if p.max_bullet_lines_ratio is not None:
            checks.append(
                (
                    bullet_ratio > p.max_bullet_lines_ratio,
                    lambda r: f"gopher_too_many_bullets (ratio {fmt2(bullet_ratio[r])}, "
                    f"max {fmt2(p.max_bullet_lines_ratio)})",
                )
            )
        if p.max_ellipsis_lines_ratio is not None:
            checks.append(
                (
                    ell_lines_ratio > p.max_ellipsis_lines_ratio,
                    lambda r: "gopher_too_many_end_ellipsis_lines "
                    f"(ratio {fmt2(ell_lines_ratio[r])}, "
                    f"max {fmt2(p.max_ellipsis_lines_ratio)})",
                )
            )
        if p.max_non_alpha_words_ratio is not None:
            checks.append(
                (
                    alpha_ratio < p.max_non_alpha_words_ratio,
                    lambda r: "gopher_below_alpha_threshold "
                    f"(alpha ratio {fmt2(alpha_ratio[r])}, "
                    f"required min {fmt2(p.max_non_alpha_words_ratio)})",
                )
            )
        if p.min_stop_words is not None and p.min_stop_words > 0:
            checks.append(
                (
                    stop_count < p.min_stop_words,
                    lambda r: f"gopher_too_few_stop_words (found {stop_count[r]}, "
                    f"required {p.min_stop_words})",
                )
            )

        any_cond = np.zeros(len(n_words), dtype=bool)
        for cond, _ in checks:
            any_cond |= cond
        passed = ~any_cond

        def decide(row: int, doc: TextDocument) -> _Decision:
            rs = "; ".join(fn(row) for cond, fn in checks if cond[row])
            return _Decision(
                False,
                rs,
                [
                    ("gopher_quality_filter_status", "filtered"),
                    ("gopher_quality_filter_reasons", rs),
                ],
            )

        return _StepEval(
            passed=passed,
            decide=decide,
            pass_stamps=(("gopher_quality_filter_status", "passed"),),
        )

    def _eval_c4(self, step: StepConfig, idx: int, stats) -> "_StepEval":
        p = step.params
        overflow = np.asarray(stats[f"{idx}:line_overflow"], dtype=bool)
        rewrite_identity = np.asarray(stats[f"{idx}:rewrite_identity"], dtype=bool)
        lorem = np.asarray(stats[f"{idx}:has_lorem"], dtype=bool)
        curly = np.asarray(stats[f"{idx}:has_curly"], dtype=bool)
        early = lorem | curly
        n_sent = np.asarray(stats[f"{idx}:n_sentences"]).astype(np.int64)
        n_lines = np.asarray(stats[f"{idx}:n_lines"]).astype(np.int64)
        line_keep = np.asarray(stats[f"{idx}:line_keep"])
        drops = [
            (np.asarray(stats[f"{idx}:{key}"]).astype(np.int64), name)
            for key, name in (
                ("drop_too_long", "line-filter-too_long_word"),
                ("drop_no_term", "line-filter-no_terminal_punc"),
                ("drop_few_words", "line-filter-too_few_words"),
            )
        ]
        few_sent = (
            (n_sent < p.min_num_sentences)
            if p.min_num_sentences > 0
            else np.zeros(len(n_sent), dtype=bool)
        )
        passed = ~early & ~few_sent

        def decide(row: int, doc: TextDocument) -> _Decision:
            if early[row]:
                reasons = []
                if lorem[row]:
                    reasons.append("lorem_ipsum")
                if curly[row]:
                    reasons.append("curly_bracket")
                rs = "; ".join(reasons)
                return _Decision(
                    False,
                    rs,
                    [("c4_filter_status", "filtered"), ("c4_filter_reasons", rs)],
                    extra={"rewrite": False},
                )
            rs = (
                f"too_few_sentences (found {n_sent[row]}, "
                f"required {p.min_num_sentences})"
            )
            stamps = [("c4_filter_status", "filtered"), ("c4_filter_reasons", rs)]
            stamps += [(name, str(c[row])) for c, name in drops if c[row] > 0]
            return _Decision(
                False,
                rs,
                stamps,
                extra={
                    "rewrite": not rewrite_identity[row],
                    "keep_mask": line_keep[row][: n_lines[row]],
                },
            )

        ev = _StepEval(
            passed=passed,
            overflow=overflow,
            decide=decide,
            pass_stamps=(("c4_filter_status", "passed"),),
        )
        ev.c4_line_keep = line_keep
        ev.c4_n_lines = n_lines
        ev.c4_rewrite_identity = rewrite_identity
        return ev

    def _eval_badwords(self, step: StepConfig, idx: int, stats) -> "_StepEval":
        p = step.params
        matches = {
            lang: np.asarray(stats[f"{idx}:match:{lang}"], dtype=bool)
            for lang in self._badwords_device_tables.get(idx, {})
        }
        hazards = {
            lang: np.asarray(stats[f"{idx}:hazard:{lang}"], dtype=bool)
            for lang in self._badwords_device_tables.get(idx, {})
        }

        def decide(row: int, doc: TextDocument) -> _Decision:
            # The device kernel delivers the regex-match verdict for every
            # language with local tables (ops/badwords.py — a spurious match
            # needs a double 32-bit hash collision, ~2^-64).  Matched docs
            # only draw the keep fraction here; docs in uncompiled languages
            # run the full host filter (download / passed_no_regex /
            # fail_on_missing_language, c4_filters.rs:456-552).  Seeded
            # keep-fraction draws are per-document (hash of seed + doc id),
            # independent of batch order (filters/c4_badwords.py RNG note).
            from ..errors import DocumentFiltered

            host_step = self._badwords_host_step(idx)
            doc_lang = doc.metadata.get("language", p.default_language)
            m = matches.get(doc_lang)
            if m is not None and hazards[doc_lang][row]:
                # Observability: host-regex re-decisions for fold-hazard
                # rows are host-path work (one regex search, not a full
                # pipeline rerun) — counted under their own name so bench
                # honesty metrics stay complete.
                METRICS.inc("worker_fold_hazard_rows_total")
            if m is None or hazards[doc_lang][row]:
                # Uncompiled language, or the row contains a codepoint whose
                # IGNORECASE folding this language's table cannot express
                # (ops/badwords.py module docstring) — the host regex decides.
                try:
                    host_step.process(doc)  # stamps metadata itself
                except DocumentFiltered as e:
                    return _Decision(False, e.reason)
                return _Decision(True)
            if not m[row]:
                doc.metadata["c4_badwords_filter_status"] = "passed"
                return _Decision(True)
            if (
                p.keep_fraction > 0.0
                and host_step._keep_draw(doc.id) < p.keep_fraction
            ):
                doc.metadata["c4_badwords_filter_status"] = "passed_kept_by_fraction"
                return _Decision(True)
            reason = "document_removed_with_badwords"
            doc.metadata["c4_badwords_filter_status"] = "filtered"
            doc.metadata["c4_badwords_filter_reason"] = reason
            return _Decision(False, reason)

        # passed is never consulted for badwords evals: _assemble_row's
        # badwords branch short-circuits on badwords_matches before the
        # generic ev.passed path.
        ev = _StepEval(passed=None, decide=decide, pass_stamps=None)
        ev.badwords_matches = matches
        ev.badwords_default_language = p.default_language
        ev.badwords_fold_hazard = hazards
        return ev

    def _eval_fineweb(self, step: StepConfig, idx: int, stats) -> "_StepEval":
        p = step.params
        overflow = np.asarray(stats[f"{idx}:line_overflow"], dtype=bool)
        g = lambda key: np.asarray(stats[f"{idx}:{key}"]).astype(np.int64)  # noqa: E731
        n_lines = g("n_nonblank_lines")
        empty = n_lines == 0
        nl_f = np.maximum(n_lines, 1).astype(np.float64)
        punct_ratio = g("lines_ending_stop") / nl_f
        punct_fail = (punct_ratio < p.line_punct_thr) & ~(
            (punct_ratio == 0.0) & p.line_punct_exclude_zero
        )
        short_ratio = g("short_lines") / nl_f
        short_fail = short_ratio > p.short_line_thr
        total_chars = g("total_chars_no_newline")
        dup_ratio = np.zeros(len(n_lines), dtype=np.float64)
        np.divide(g("dup_line_bytes"), total_chars, out=dup_ratio, where=total_chars > 0)
        dup_fail = dup_ratio > p.char_duplicates_ratio
        n_words = g("n_words")
        newlines = g("newline_count")
        list_ratio = np.zeros(len(n_lines), dtype=np.float64)
        np.divide(newlines, n_words, out=list_ratio, where=n_words > 0)
        list_fail = np.where(
            n_words == 0, newlines > 0, list_ratio > p.new_line_ratio
        )
        passed = ~(empty | punct_fail | short_fail | dup_fail | list_fail)

        def decide(row: int, doc: TextDocument) -> _Decision:
            def fail(reason, outcome_reason=""):
                return _Decision(
                    False,
                    outcome_reason or reason,
                    [
                        ("fineweb_filter_status", "filtered"),
                        ("fineweb_filter_reason", reason),
                    ],
                )

            # First failing check wins (fineweb_quality.rs check order).
            if empty[row]:
                return fail("empty document", outcome_reason="empty")
            if punct_fail[row]:
                return fail(
                    f"line_punct_ratio: {fmt4(punct_ratio[row])} < threshold "
                    f"{fmt4(p.line_punct_thr)} (exclude_zero: "
                    f"{rust_bool(p.line_punct_exclude_zero)})"
                )
            if short_fail[row]:
                return fail(
                    f"short_line_ratio: {fmt4(short_ratio[row])} > threshold "
                    f"{fmt4(p.short_line_thr)}"
                )
            if dup_fail[row]:
                return fail(
                    f"char_dup_ratio: {fmt4(dup_ratio[row])} > threshold "
                    f"{fmt4(p.char_duplicates_ratio)}"
                )
            if n_words[row] == 0:
                return fail("list_ratio_no_words (newlines present but no words)")
            return fail(
                f"list_ratio: {fmt4(list_ratio[row])} > threshold "
                f"{fmt4(p.new_line_ratio)}"
            )

        return _StepEval(passed=passed, overflow=overflow, decide=decide, pass_stamps=())

    # --- batch processing ---------------------------------------------------

    def _rewrite_c4(self, doc: TextDocument, step: StepConfig, keep_mask) -> None:
        """Apply the device line-keep mask to rebuild C4's rewritten content —
        the string half of c4_filters.rs:192-258; decisions came from device.
        Units are lines (split_paragraph) or sentences (c4_filters.rs:150-156)."""
        if step.params.split_paragraph:
            lines = rust_lines(doc.content)
        else:
            from ..utils.text import split_into_sentences

            lines = split_into_sentences(doc.content)
        n = len(keep_mask)
        if step.params.remove_citations:
            # CITATION_RE can only match where a '[' exists — skip the regex
            # for the (overwhelmingly common) bracket-free lines.
            kept = [
                CITATION_RE.sub("", s) if "[" in s else s
                for i, line in enumerate(lines)
                if i < n and keep_mask[i]
                for s in (line.strip(),)
            ]
        else:
            kept = [
                line.strip() for i, line in enumerate(lines) if i < n and keep_mask[i]
            ]
        doc.content = "\n".join(kept).strip()

    def dispatch_batch(
        self, batch: PackedBatch, phase: int = 0
    ) -> Dict[str, jax.Array]:
        """Launch the compiled program for a batch and return the on-device
        stats WITHOUT blocking (JAX async dispatch) — the caller overlaps the
        previous batch's host-side assembly with this batch's device compute
        (the double-buffered feed SURVEY.md §2.5 maps prefetch/QoS onto)."""
        if WATCHDOG.enabled:
            # Beat scope lets an injected device hang (chaos kind "hang")
            # be rescued by the stage deadline on this thread; disabled,
            # the seam pays exactly this one attribute check.
            with WATCHDOG.stage_beat("device_fetch"):
                FAULTS.fire("device.execute")
        else:
            FAULTS.fire("device.execute")
        record_occupancy(batch)
        if TELEMETRY.enabled:
            TELEMETRY.mark("dispatch", (d.id for d in batch.docs))
        with TRACER.span(
            "device_dispatch",
            {"batch": batch.seq, "bucket": batch.max_len,
             "rows": batch.batch_size, "phase": phase, "chips": self.chips},
        ) as sp:
            fn = self._fn_for(batch.max_len, phase, rows=batch.batch_size)
            cps, lengths = batch.cps, batch.lengths
            if self.wire_u16:
                # Astral rows were routed to the host oracle upstream
                # (process_chunk); a slip here would truncate silently,
                # so guard with one cheap vectorized check.
                if int(cps.max(initial=0)) >= 0x10000:
                    raise RuntimeError(
                        "astral codepoint reached the uint16 wire — "
                        "routing invariant broken"
                    )
                cps = cps.astype(np.uint16)
            if sp.live:
                sp.add_args({"bytes": int(cps.nbytes + lengths.nbytes)})
            if self.mesh is not None:
                from ..parallel.mesh import shard_batch

                t0 = _time_mod.perf_counter()
                cps, lengths = shard_batch(self.mesh, cps, lengths)
                METRICS.inc(
                    "stage_mesh_upload_seconds", _time_mod.perf_counter() - t0
                )
            return fn(cps, lengths)

    def dispatch_lockstep(
        self, batch: PackedBatch, phase: int, sharding2, sharding1
    ) -> Dict[str, jax.Array]:
        """Launch one multi-host lockstep round (async) from this process's
        local rows of the global batch.

        The multi-host analogue of :meth:`dispatch_batch`: the fault seam the
        negotiated guard wraps (``FAULTS`` site ``"multihost.round"`` fires
        here, so chaos tests can fail the launch on one host only), but the
        arrays are assembled per-process (``make_array_from_process_local_data``
        against the caller's global shardings) and occupancy is NOT recorded —
        the caller records it once per round so negotiated re-dispatches don't
        skew the telemetry.  ``batch`` is any pre-packed ``PackedBatch`` —
        the lockstep window packs rounds ahead on the shared pack pool and
        hands the resolved batches here, so this seam must stay pack-free.

        Fires ``"device.execute"`` too (the same device-dispatch seam as
        :meth:`dispatch_batch`), so hang chaos armed on the device seam
        lands on the lockstep path as well and escalates through the
        negotiated local-fault verdict."""
        if WATCHDOG.enabled:
            with WATCHDOG.stage_beat("device_fetch"):
                FAULTS.fire("device.execute")
                FAULTS.fire("multihost.round")
        else:
            FAULTS.fire("device.execute")
            FAULTS.fire("multihost.round")
        if TELEMETRY.enabled:
            TELEMETRY.mark("dispatch", (d.id for d in batch.docs))
        with TRACER.span(
            "device_dispatch",
            {"bucket": batch.max_len, "rows": batch.batch_size,
             "phase": phase, "lockstep": True},
        ):
            fn = self._fn_for(batch.max_len, phase)
            g_cps = jax.make_array_from_process_local_data(
                sharding2, batch.cps
            )
            g_len = jax.make_array_from_process_local_data(
                sharding1, batch.lengths
            )
            return fn(g_cps, g_len)

    # --- degradation ladder -------------------------------------------------

    def _device_fetch(
        self, batch: PackedBatch, phase: int, inflight=None
    ) -> Dict[str, np.ndarray]:
        """Dispatch + transfer for one batch under the device RetryPolicy.

        ``inflight`` is an already-dispatched stats tree (the overlap path):
        the first attempt only has to fetch it; every re-attempt re-dispatches
        from scratch.  Returns host-side numpy stats (``jax.device_get`` on
        numpy is identity, so ``assemble_phase`` takes them unchanged).
        """
        import time

        first = [inflight]

        def attempt() -> Dict[str, np.ndarray]:
            stats = first[0]
            first[0] = None
            if stats is None:
                stats = self.dispatch_batch(batch, phase)
            if TELEMETRY.enabled:
                TELEMETRY.mark("device_wait", (d.id for d in batch.docs))
            if WATCHDOG.enabled:
                # Deadline-bounded readiness poll so the blocking
                # device_get below cannot wedge this rank; a StallError
                # here enters the same retry → ladder path as a raised
                # device fault.
                WATCHDOG.wait_device_ready(
                    "device_fetch", jax.tree_util.tree_leaves(stats)
                )
            t0 = time.perf_counter()
            try:
                with TRACER.span(
                    "device_wait",
                    {"batch": batch.seq, "bucket": batch.max_len,
                     "phase": phase},
                ) as sp:
                    if sp.live:
                        # What the fetch moves: a slow wait on few bytes
                        # is the device (or the GIL), not the transfer.
                        leaves = jax.tree_util.tree_leaves(stats)
                        sp.add_args({
                            "leaves": len(leaves),
                            "bytes": int(sum(x.nbytes for x in leaves)),
                        })
                    out = jax.device_get(stats)
                    if PROFILER.enabled:
                        # Duration must be taken inside the span: the event
                        # is emitted at __exit__, so args attached later
                        # would miss the trace.
                        sp.add_args(
                            PROFILER.record_dispatch(
                                batch.max_len,
                                phase,
                                batch.batch_size,
                                time.perf_counter() - t0,
                            )
                        )
                    return out
            finally:
                # Time blocked on device results (transfer + any compute not
                # yet finished).  Identity-fast for already-numpy stats, so
                # re-attempts after a host-side fetch don't double-count.
                METRICS.inc(
                    "stage_device_wait_seconds", time.perf_counter() - t0
                )

        return self._retry.run(attempt, seam="device")

    def _host_rerun(self, docs: List[TextDocument]) -> List[ProcessingOutcome]:
        """Bottom rung: the full host-oracle pipeline, bit-identical to the
        device path by the same contract the overflow fallback relies on
        (docs are re-stamped identically even mid-phase)."""
        METRICS.inc("resilience_ladder_host_total", len(docs))
        outcomes = self._host_block(
            "host_tail", {"kind": "ladder", "docs": len(docs)},
            "stage_host_tail_seconds", self.host_executor, docs,
        )
        return [o for o in outcomes if o is not None]

    @staticmethod
    def _host_block(
        span: str, args: Dict, counter: str, executor, docs: List[TextDocument]
    ) -> List[Optional[ProcessingOutcome]]:
        """``execute_processing_pipeline`` over ``docs`` in order, as one
        span whose clock reads also feed ``counter``.  A hard error leaves
        ``None`` in its document's place."""
        t0 = _time_mod.perf_counter()
        try:
            with TRACER.span(span, args):
                return [execute_processing_pipeline(executor, d) for d in docs]
        finally:
            METRICS.inc(counter, _time_mod.perf_counter() - t0)

    def _host_suffix_block(
        self, seq: int, docs: List[TextDocument]
    ) -> List[ProcessingOutcome]:
        """The host steps after the last phase over one batch's survivors,
        step by step (``execute_processing_batch``), as the ``host_suffix``
        span; ``batched`` counts the documents a step took in one batch
        call."""
        executor = self.host_suffix_executor
        t0 = _time_mod.perf_counter()
        try:
            with TRACER.span("host_suffix", {"batch": seq, "docs": len(docs)}) as sp:
                before = executor.batched_docs()
                outcomes = execute_processing_batch(executor, docs)
                batched = executor.batched_docs() - before
                METRICS.inc("worker_host_suffix_batched_total", batched)
                sp.add_args({"batched": batched})
                return outcomes
        finally:
            METRICS.inc("stage_host_suffix_seconds", _time_mod.perf_counter() - t0)

    def _execute_packed(
        self, batch: PackedBatch, phase: int, inflight=None
    ) -> Tuple[List[ProcessingOutcome], List[TextDocument]]:
        """One packed batch through the degradation ladder.

        Rungs: (1) retry the whole batch under the device RetryPolicy;
        (2) split it in half and retry each half — OOM recovery, and a
        bisection that saves the healthy half of a poisoned batch; (3) rerun
        the documents on the host oracle.  Deterministic errors (fatal per
        the classifier) propagate immediately — the ladder only absorbs
        transient device faults.  The circuit breaker counts batches that
        fell to the host rung; once tripped, the run stays on the host
        backend (no more device dispatches to time out on) until the
        half-open cooldown grants a probe.
        """
        if inflight is _BREAKER_OPEN or (
            inflight is None and not self._breaker.allow_request()
        ):
            # The breaker refused the dispatch (window sentinel) or refuses
            # the re-dispatch now: host rung, with no breaker recording —
            # the device was never asked.
            return self._host_rerun(batch.docs), []
        try:
            stats = self._device_fetch(batch, phase, inflight)
        except RetryExhaustedError:
            pass  # descend the ladder below
        else:
            self._breaker.record_success()
            return self.assemble_phase(batch, stats, phase)

        fell_to_host = False
        outcomes: List[ProcessingOutcome] = []
        survivors: List[TextDocument] = []
        if self._split_retry and self.one_controller and len(batch.docs) > 1:
            # Split rung.  Both halves pack to the bucket's half-row count,
            # the program warmup installed for this rung — also when the
            # faulted batch is itself a half-row tail group, so no program
            # compiles mid-incident.
            METRICS.inc("resilience_ladder_split_total")
            TRACER.instant(
                "ladder_split", {"bucket": batch.max_len, "phase": phase}
            )
            if EVENTS.enabled:
                EVENTS.emit("ladder_split", batch=batch.max_len,
                            depth=len(batch.docs), phase=phase)
            sub_rows = self._warm_half_rows(batch.max_len)
            mid = (len(batch.docs) + 1) // 2
            for part in (batch.docs[:mid], batch.docs[mid:]):
                if not part:
                    continue
                sub = pack_documents(part, sub_rows, batch.max_len)
                sub.seq = batch.seq
                try:
                    stats = self._device_fetch(sub, phase)
                except RetryExhaustedError:
                    fell_to_host = True
                    outcomes.extend(self._host_rerun(part))
                else:
                    o, s = self.assemble_phase(sub, stats, phase)
                    outcomes.extend(o)
                    survivors.extend(s)
        else:
            fell_to_host = True
            outcomes.extend(self._host_rerun(batch.docs))

        if fell_to_host:
            TRACER.instant(
                "ladder_host", {"bucket": batch.max_len, "phase": phase}
            )
            if EVENTS.enabled:
                EVENTS.emit("ladder_host", batch=batch.max_len, phase=phase)
            self._breaker.record_failure("device batch fell to host rung")
        else:
            self._breaker.record_success()
        return outcomes, survivors

    def assemble_phase(
        self,
        batch: PackedBatch,
        device_stats: Dict[str, jax.Array],
        phase: int = 0,
    ) -> Tuple[List[ProcessingOutcome], List[TextDocument]]:
        """Blocking half for one phase: transfer stats, resolve
        order/short-circuit/reason strings per document.

        Returns ``(outcomes, survivors)``: outcomes are final (filtered docs,
        host-fallback reruns, and — on the last phase — passes); survivors
        are documents that passed a non-final phase and continue to the next.

        The host calls of a batch run as two blocks after the row walk — the
        overflow reruns on the host oracle, then the host steps that follow
        the last phase (TokenCounter) — each outcome kept in its row's place,
        so the ``assemble``, ``host_tail`` and ``host_suffix`` spans and
        counters split the post stage without moving an outcome.
        """
        n_rows = len(batch.docs)
        last = phase == len(self.phases) - 1
        outcomes: List[Optional[ProcessingOutcome]] = []
        survivors: List[TextDocument] = []
        # (slot in outcomes, doc) for the deferred host calls.
        overflow_rows: List[Tuple[int, TextDocument]] = []
        suffix_rows: List[Tuple[int, TextDocument]] = []
        with TRACER.span(
            "assemble",
            {"batch": batch.seq, "bucket": batch.max_len, "phase": phase,
             "rows": n_rows},
        ):
            if TELEMETRY.enabled:
                TELEMETRY.mark("assemble", (d.id for d in batch.docs))
            # ONE bundled transfer: each per-key np.asarray is its own
            # synchronous device round trip; jax.device_get moves the whole
            # tree in one call.
            stats = jax.device_get(device_stats)
            # Rows where any step hit a kernel table bound rerun the host
            # oracle.  Phase-boundary note: a doc overflowing in a later
            # phase carries the earlier phases' metadata stamps; the
            # full-pipeline host rerun re-stamps the identical values
            # (device/host stamp parity), so the outcome is still
            # bit-identical to a pure host run.
            step_ids = self.phases[phase]
            evals = [
                (self.device_steps[i], self._eval_step(self.device_steps[i], i, stats))
                for i in step_ids
            ]
            overflow_any = np.zeros(n_rows, dtype=bool)
            for _, ev in evals:
                if ev.overflow is not None:
                    overflow_any |= ev.overflow[:n_rows]
            # Vectorized pass-row fast path: one batch-level AND of every
            # step's verdict finds the rows that pass the whole phase; their
            # only side effects are metadata pass-stamps (constant, or
            # per-row via a batch-precomputed stamp function), so they skip
            # the per-row decide() walk.  Rows that fail, overflow, need a
            # non-identity C4 rewrite, or hit a step without a batch verdict
            # (badwords: the doc's language is only known per row) keep the
            # per-row path.
            fast_mask = None
            if n_rows:
                fast_mask = ~overflow_any
                for _, ev in evals:
                    if ev.passed is None or (
                        ev.pass_stamps is None and ev.pass_stamp_fn is None
                    ):
                        fast_mask = None
                        break
                    fast_mask &= ev.passed[:n_rows]
                    if ev.c4_line_keep is not None:
                        fast_mask &= ev.c4_rewrite_identity[:n_rows]
            for row, doc in enumerate(batch.docs):
                if fast_mask is not None and fast_mask[row]:
                    # Passed every step: stamp in step order, exactly what
                    # _assemble_row's pass branches would have written.
                    for _, ev in evals:
                        if ev.pass_stamps is not None:
                            for k, v in ev.pass_stamps:
                                doc.metadata[k] = v
                        else:
                            ev.pass_stamp_fn(row, doc)
                    outcome = None
                elif overflow_any[row]:
                    METRICS.inc("worker_host_fallback_total")
                    overflow_rows.append((len(outcomes), doc))
                    outcomes.append(None)
                    continue
                else:
                    outcome = self._assemble_row(evals, row, doc)
                if outcome is None:  # passed every step of this phase
                    if not last:
                        survivors.append(doc)
                        continue
                    if self.host_steps:
                        suffix_rows.append((len(outcomes), doc))
                        outcomes.append(None)
                        continue
                    outcome = ProcessingOutcome.success(doc)
                outcomes.append(outcome)
        if overflow_rows:
            self._fill_slots(outcomes, overflow_rows, self._host_block(
                "host_tail", {"kind": "overflow", "docs": len(overflow_rows)},
                "stage_host_tail_seconds", self.host_executor,
                [d for _, d in overflow_rows],
            ))
        if suffix_rows:
            self._fill_slots(outcomes, suffix_rows, self._host_suffix_block(
                batch.seq, [d for _, d in suffix_rows]
            ))
        # A hard error has no outcome (reference quirk).
        return [o for o in outcomes if o is not None], survivors

    @staticmethod
    def _fill_slots(outcomes, rows, done) -> None:
        """Put each outcome of the deferred ``(slot, doc)`` rows in its
        slot."""
        for (slot, _), outcome in zip(rows, done, strict=True):
            outcomes[slot] = outcome

    def phase_previewable(self, phase: int) -> bool:
        """True when every step of ``phase`` carries a full batch verdict
        mask, so the phase's survivor count is derivable from device stats
        alone (:meth:`preview_phase_survivors`).

        Config-derived only — every lockstep host answers identically for
        the same config, which is what lets the speculative phase barrier
        (parallel/multihost.py) treat previewability as shared state
        without exchanging it.  Badwords is out (per-row host regex +
        keep-fraction RNG, ``passed=None``); C4 is out because its rewrite
        re-routes survivors by post-rewrite length (and a non-final C4
        phase is impossible anyway — the constructor collapses those)."""
        return all(
            self.device_steps[i].type in _PREVIEWABLE_STEPS
            for i in self.phases[phase]
        )

    def preview_phase_survivors(
        self,
        batch: PackedBatch,
        device_stats: Dict[str, jax.Array],
        phase: int,
    ) -> int:
        """Exact survivor count for one resolved round of a previewable
        non-final phase — the batch-vectorized half of
        :meth:`assemble_phase` without any per-row work or side effects.

        The speculative phase barrier posts these counts piggybacked on
        the tail verdict exchange, so the next phase's round schedule can
        be negotiated in the same allgather the tail flags ride.  A row
        survives iff it overflowed no kernel table and passed every step
        of the phase — identical to the rows ``assemble_phase`` appends to
        ``survivors``, which the barrier asserts after assembly.  The
        stats tree must already be host-side (``_timed_stats`` output);
        evaluating it here and again in ``assemble_phase`` is safe because
        the step finalizers are pure over the stats arrays."""
        assert self.phase_previewable(phase), (
            "preview_phase_survivors called on a non-previewable phase — "
            "the barrier must gate on phase_previewable or hosts desync "
            "on the exchange vector width"
        )
        stats = jax.device_get(device_stats)
        n_rows = len(batch.docs)
        mask = np.ones(n_rows, dtype=bool)
        for i in self.phases[phase]:
            ev = self._eval_step(self.device_steps[i], i, stats)
            if ev.overflow is not None:
                mask &= ~ev.overflow[:n_rows]
            mask &= ev.passed[:n_rows]
        return int(mask.sum())

    def assemble_batch(
        self, batch: PackedBatch, device_stats: Dict[str, jax.Array]
    ) -> List[ProcessingOutcome]:
        """Single-phase form (the multi-host lockstep path): every device
        step evaluated from one program's stats."""
        assert len(self.phases) == 1, "assemble_batch requires a single-phase pipeline"
        outcomes, _ = self.assemble_phase(batch, device_stats, 0)
        return outcomes

    def process_batch(self, batch: PackedBatch) -> List[ProcessingOutcome]:
        assert len(self.phases) == 1
        return self.assemble_batch(batch, self.dispatch_batch(batch))

    def _timed_pack(
        self,
        docs: List[TextDocument],
        batch_size: int,
        max_len: int,
        seq: Optional[int] = None,
    ) -> PackedBatch:
        """``pack_documents`` with the pack-stage wall clock attached and
        the batch's sequence number ``seq`` (the next one when not given).

        Runs once per batch on the pack pool's hot path — the clock comes
        from the module-scope import, not a per-call ``import time``."""
        if seq is None:
            seq = next(self._batch_ids)
        if TELEMETRY.enabled:
            TELEMETRY.mark("pack", (d.id for d in docs))
        t0 = _time_mod.perf_counter()
        try:
            with TRACER.span(
                "pack", {"batch": seq, "rows": len(docs), "bucket": max_len}
            ):
                batch = pack_documents(
                    docs, batch_size=batch_size, max_len=max_len
                )
                batch.seq = seq
                return batch
        finally:
            METRICS.inc("stage_pack_seconds", _time_mod.perf_counter() - t0)

    def _pack_pool(self):
        # One process-wide pool shared with the multi-host lockstep window
        # (utils/overlap.py) — pack work releases the GIL, and every caller
        # resolves its own futures FIFO, so sharing changes no ordering.
        if self._pack_pool_obj is None:
            from ..utils.overlap import shared_pack_pool

            self._pack_pool_obj = shared_pack_pool(
                max(1, self._overlap.pack_workers)
            )
        return self._pack_pool_obj

    def _packed_source(
        self, docs_iter, host_tail_max, half_rows, min_fill, route_fn,
        overlapped,
    ):
        """The packer stage for one phase.

        Serial: the grouping generator inline, packing on the caller's
        thread.  Overlapped: the generator runs ahead on a prefetch thread
        and each ``pack`` is a thread-pool future (the encode/scatter work
        releases the GIL), so grouping+packing of batch i+1.. overlap the
        caller's dispatch/assembly of batch i.  Either way items arrive in
        the generator's order — the overlap changes timing, never sequence.

        Returns ``(iterable of (batch_or_future, fallback_docs), close_fn)``.
        """
        kwargs = dict(
            geometry=self.geometry,
            host_tail_max=host_tail_max,
            half_rows=half_rows,
            min_fill=min_fill,
            route_fn=route_fn,
            overflow_flush=max(1, self._overlap.overflow_flush),
        )
        if not overlapped:
            gen = iter_packed_batches(docs_iter, pack_fn=self._timed_pack, **kwargs)
            return gen, lambda: None
        pool = self._pack_pool()

        def submit(docs, batch_size, max_len):
            # Numbered in submission (= consumption) order; the future
            # carries the number so a wait on it names its batch.
            seq = next(self._batch_ids)
            future = pool.submit(
                self._timed_pack, docs, batch_size=batch_size,
                max_len=max_len, seq=seq,
            )
            future.batch_seq = seq
            return future

        gen = iter_packed_batches(docs_iter, pack_fn=submit, **kwargs)
        pf = prefetch_iter(
            gen, depth=max(2, self._overlap.pack_workers + 1), block=1,
            label="pack",
        )
        return pf, pf.close

    def _dispatch_window(self, batch: PackedBatch, phase: int, no_overlap: bool):
        """Breaker-gated async dispatch for the in-flight window.

        Returns the in-flight stats tree, ``None`` on a retryable launch
        failure (the drain's ladder re-dispatches from scratch), or the
        ``_BREAKER_OPEN`` sentinel when the breaker refused the request.
        Deterministic errors propagate — the ladder only absorbs transient
        device faults.
        """
        if not self._breaker.allow_request():
            return _BREAKER_OPEN
        try:
            stats = self.dispatch_batch(batch, phase)
            if no_overlap:
                if WATCHDOG.enabled:
                    WATCHDOG.wait_device_ready(
                        "device_fetch", jax.tree_util.tree_leaves(stats)
                    )
                jax.block_until_ready(stats)
            return stats
        except Exception as e:  # noqa: BLE001
            if self._retry.classify(e) != "retryable":
                raise
            WATCHDOG.escalated(e)
            # Failed launch: hand the batch to the ladder with nothing in
            # flight (its first retry attempt re-dispatches).
            logger.warning("Device dispatch failed (phase %d): %s", phase, e)
            return None

    def process_chunk(self, docs: List[TextDocument]) -> Iterator[ProcessingOutcome]:
        """Run one chunk of documents through every phase, repacking the
        survivors between phases (device-side short-circuit).

        Batches ride a FIFO in-flight window ``pipeline_depth`` deep: batch
        i's host assembly/post-passes run while batches i+1..i+K compute on
        the device.  Outcomes are emitted in the strict FIFO order of the
        packer's output items at EVERY depth — the window moves the waits,
        never the sequence — so serial (depth 1, or --no-overlap) and
        overlapped runs produce byte-identical outcome streams by
        construction.

        Each phase is one ``phase`` span (the chunk's number, the phase,
        documents in, batches, survivors).
        """
        no_overlap = os.environ.get("TEXTBLAST_NO_OVERLAP") == "1"
        overlapped = (
            self._overlap.enabled and not no_overlap and self.one_controller
        )
        depth = max(1, self._overlap.pipeline_depth) if overlapped else 1
        chunk_id = self._chunks
        self._chunks += 1
        current: List[TextDocument] = docs
        if self._route_dict_scripts or self.wire_u16:
            from ..utils.cjk import has_astral, has_dict_script

            route_dict = self._route_dict_scripts
            route_astral = self.wire_u16
            # Routing decisions recorded at route_fn time (the packer calls
            # route_fn once per non-over-length doc), so the fallback
            # classification below reuses them instead of re-running the
            # has_dict_script/has_astral scans on every fallback doc.
            routed: Dict[int, bool] = {}

            def _host_routed(doc: TextDocument) -> bool:
                decision = (route_dict and has_dict_script(doc.content)) or (
                    route_astral and has_astral(doc.content)
                )
                routed[id(doc)] = decision
                return decision

        else:
            _host_routed = None
            routed = {}
        for phase in range(len(self.phases)):
            with TRACER.span(
                "phase",
                {"chunk": chunk_id, "phase": phase, "docs_in": len(current)},
            ) as span:
                survivors, n_batches = yield from self._run_phase(
                    current,
                    phase,
                    depth,
                    overlapped,
                    no_overlap,
                    # Phase 0 only: later phases' survivors already passed it.
                    _host_routed if phase == 0 else None,
                    routed,
                )
                if span.live:
                    span.add_args(
                        {"batches": n_batches, "survivors": len(survivors)}
                    )
            current = survivors
            if not current:
                break

    def _run_phase(
        self, current, phase, depth, overlapped, no_overlap, route, routed
    ):
        """One phase of :meth:`process_chunk`: yields its final outcomes in
        window order and returns ``(survivors, batches dispatched)``.

        The phase's leftover groups (``iter_packed_batches``) are routed by
        count on XLA:CPU: at most 1/16 of the bucket's rows in phase 0, half
        of them after, go to the host oracle.  On an accelerator they are
        routed by fill: a group of at most the bucket's warm half-row count
        packs at it, and only a group that fills less than
        ``HOST_TAIL_FILL`` of its padded lanes (``HOST_TAIL_FILL_FIRST`` in
        phase 0, whose program is the cheapest) goes to the host.  Documents
        in groups the count rule would have given the host and the fill rule
        sends to the device count in ``worker_device_tail_total``."""
        n_batches = 0
        survivors: List[TextDocument] = []
        # FIFO window entries: ("batch", (batch, stats)) dispatched and
        # awaiting assembly, or ("host", docs) fallback groups awaiting
        # their host-oracle pass.  ``inflight`` counts batch entries only.
        window: deque = deque()
        inflight = 0
        # Leftover-group routing.  The count rule, set on XLA:CPU: the first
        # phase's program is cheap (it exists to kill docs early), so the
        # device wins even for small groups; later phases carry the
        # expensive kernels and the (bit-exact) host oracle wins below
        # ~half a batch.  Accelerators take the fill rule instead and keep
        # the count thresholds only to count what the fill rule moved to
        # the device.  Multi-host meshes keep every doc on device (shard
        # accounting), and TEXTBLAST_HOST_TAILS=off pins tails to the
        # device too (the parity suites use it so device kernels decide
        # every doc).
        host_tail_max = 0
        half_rows = None
        min_fill = HOST_TAIL_FILL_FIRST if phase == 0 else HOST_TAIL_FILL
        if self.one_controller and os.environ.get("TEXTBLAST_HOST_TAILS") != "off":
            # Per-bucket: the cutoff tracks each bucket's own row budget
            # (with a uniform geometry this is the historical scalar).
            div = 16 if phase == 0 else 2
            host_tail_max = {
                b: self.geometry.batch_for(b) // div
                for b in self.geometry.buckets
            }
            if _tails_by_fill():
                half_rows = {
                    b: self._warm_half_rows(b) for b in self.geometry.buckets
                }
        over_length = self.buckets[-1] - PACK_MARGIN

        def _process_fallback(fallback_docs):
            kind = "tail"
            for doc in fallback_docs:
                # Over-length and routed (dict-script/astral) docs are
                # genuine fallbacks; leftover tail groups are deliberate
                # routing — count them apart so the bench's honesty
                # metric stays meaningful.
                if len(doc.content) > over_length:
                    METRICS.inc("worker_host_fallback_total")
                    if kind == "tail":
                        kind = "over_length"
                elif route is not None and routed.get(id(doc), False):
                    METRICS.inc("worker_host_fallback_total")
                    kind = "routed"
                else:
                    METRICS.inc("worker_host_tail_total")
            outs = self._host_block(
                "host_tail", {"kind": kind, "docs": len(fallback_docs)},
                "stage_host_tail_seconds", self.host_executor, fallback_docs,
            )
            return [o for o in outs if o is not None]

        def _drain_front():
            nonlocal inflight
            kind, payload = window.popleft()
            ta = _time_mod.perf_counter()
            with TRACER.span("post", {"kind": kind, "phase": phase}):
                if kind == "batch":
                    inflight -= 1
                    METRICS.set("inflight_batches", inflight)
                    TRACER.counter("inflight_batches", inflight)
                    b, stats = payload
                    outcomes, alive = self._execute_packed(b, phase, stats)
                    survivors.extend(alive)
                else:
                    outcomes = _process_fallback(payload)
            METRICS.inc("stage_post_seconds", _time_mod.perf_counter() - ta)
            return outcomes

        src, src_close = self._packed_source(
            iter(current),
            host_tail_max=host_tail_max,
            half_rows=half_rows,
            min_fill=min_fill,
            route_fn=route,
            overlapped=overlapped,
        )
        try:
            for item, fallback in src:
                if item is not None:
                    # Overlapped items are pack futures; resolving here
                    # keeps FIFO order (futures complete out of order,
                    # but we only ever wait on the oldest).
                    if hasattr(item, "result"):
                        if item.done():
                            batch = item.result()
                        else:
                            with TRACER.span(
                                "pack_wait", {"batch": item.batch_seq}
                            ):
                                if WATCHDOG.enabled:
                                    WATCHDOG.wait("pack_wait", item.done)
                                batch = item.result()
                    else:
                        batch = item
                    if overlapped:
                        METRICS.set("queue_depth_pack", src.qsize())
                        TRACER.counter("queue_depth_pack", src.qsize())
                    n_batches += 1
                    if (
                        half_rows is not None
                        and len(batch.docs) <= host_tail_max[batch.max_len]
                    ):
                        # Only a leftover group can be this small.
                        METRICS.inc("worker_device_tail_total", len(batch.docs))
                    td = _time_mod.perf_counter()
                    with TRACER.span(
                        "dispatch",
                        {"batch": batch.seq, "bucket": batch.max_len,
                         "rows": batch.batch_size, "phase": phase},
                    ):
                        stats = self._dispatch_window(
                            batch, phase, no_overlap
                        )
                    METRICS.inc(
                        "stage_dispatch_seconds", _time_mod.perf_counter() - td
                    )
                    window.append(("batch", (batch, stats)))
                    inflight += 1
                    METRICS.set("inflight_batches", inflight)
                    TRACER.counter("inflight_batches", inflight)
                if fallback:
                    window.append(("host", fallback))
                # Host groups at the front never block on the device —
                # draining them early IS the read/post overlap; batch
                # entries drain once more than ``depth`` are in flight.
                while window and (
                    window[0][0] == "host" or inflight > depth
                ):
                    yield from _drain_front()
            while window:
                yield from _drain_front()
        finally:
            src_close()
            METRICS.set("inflight_batches", 0)
        return survivors, n_batches

    _BADWORDS_PASS_STAMPS = (("c4_badwords_filter_status", "passed"),)

    def _assemble_row(
        self, evals, row: int, doc: TextDocument
    ) -> Optional[ProcessingOutcome]:
        """Walk one row through this phase's steps; ``None`` means it passed
        them all (the caller decides success vs next-phase survival)."""
        for step, ev in evals:
            if ev.badwords_matches is not None:
                # Fast path for non-matching docs of any device-compiled
                # language (the common case — no host work at all); matches
                # and uncompiled languages go through decide().
                doc_lang = doc.metadata.get("language", ev.badwords_default_language)
                m = ev.badwords_matches.get(doc_lang)
                if (
                    m is not None
                    and not m[row]
                    and not ev.badwords_fold_hazard[doc_lang][row]
                ):
                    for k, v in self._BADWORDS_PASS_STAMPS:
                        doc.metadata[k] = v
                    continue
            elif ev.passed[row] and ev.pass_stamps is not None:
                for k, v in ev.pass_stamps:
                    doc.metadata[k] = v
                if ev.c4_line_keep is not None and not ev.c4_rewrite_identity[row]:
                    # Identity rewrites (every line kept, already trimmed —
                    # the common clean-text case) skip the per-doc Python
                    # string rebuild; the device proved content equality.
                    self._rewrite_c4(
                        doc, step, ev.c4_line_keep[row][: ev.c4_n_lines[row]]
                    )
                continue
            decision = ev.decide(row, doc)
            for k, v in decision.stamps:
                doc.metadata[k] = v
            if ev.c4_line_keep is not None and decision.extra is not None:
                if decision.extra.get("rewrite"):
                    self._rewrite_c4(doc, step, decision.extra["keep_mask"])
            if not decision.passed:
                # Funnel attribution: the device-path twin of the host seam
                # in orchestration.execute_processing_pipeline — together
                # the only two creators of FILTERED outcomes.
                METRICS.inc(FILTER_DROP_PREFIX + step.type)
                return ProcessingOutcome.filtered(doc, decision.reason)
        return None


#: Step types whose batch eval always yields a full per-row verdict mask
#: (``_StepEval.passed`` is an array, never None) — the set
#: ``phase_previewable`` checks.  Badwords decides per-row on the host;
#: C4 rewrites survivor content.
_PREVIEWABLE_STEPS = frozenset(
    {
        "LanguageDetectionFilter",
        "GopherRepetitionFilter",
        "GopherQualityFilter",
        "FineWebQualityFilter",
    }
)

_EVALS = {
    "LanguageDetectionFilter": CompiledPipeline._eval_langid,
    "GopherRepetitionFilter": CompiledPipeline._eval_gopher_rep,
    "GopherQualityFilter": CompiledPipeline._eval_gopher_quality,
    "C4QualityFilter": CompiledPipeline._eval_c4,
    "C4BadWordsFilter": CompiledPipeline._eval_badwords,
    "FineWebQualityFilter": CompiledPipeline._eval_fineweb,
}


def process_documents_device(
    config: PipelineConfig,
    docs: Iterable[Union[TextDocument, PipelineError]],
    device_batch: Optional[int] = None,
    on_read_error=None,
    buckets=DEFAULT_BUCKETS,
    mesh=None,
    pipeline: Optional[CompiledPipeline] = None,
    geometry: Optional[DeviceGeometry] = None,
    warmup: Optional[bool] = None,
) -> Iterator[ProcessingOutcome]:
    """Device-backed processing loop: packs the stream into bucketed batches,
    runs the compiled pipeline, assembles outcomes in input order per batch.

    Outcome **ordering** is deterministic but not input order: documents are
    grouped by length bucket and emitted in the packer's strict FIFO item
    order, with up to ``overlap.pipeline_depth`` batches in flight (assembly
    of batch k overlaps device compute of batches k+1..k+K).  The order is
    identical at every depth — serial and overlapped runs produce the same
    outcome stream.  Output row order is NOT contractual — the reference has
    none either (its results queue returns worker-completion order,
    producer_logic.rs:141-176); tests compare outputs as id-keyed sets.

    Pass a prebuilt ``pipeline`` to reuse its compiled programs across
    multiple streams (the checkpointed runner processes one chunk per call)."""
    if pipeline is None:
        pipeline = CompiledPipeline(
            config,
            buckets=buckets,
            batch_size=device_batch,
            mesh=mesh,
            geometry=geometry,
        )
        # TPU compiles are the dominant cold-start cost and run
        # serially if left to first dispatch; compile everything concurrently
        # up front — a populated AOT executable cache makes this a sub-second
        # load instead of a 15-29 s compile.
        maybe_warmup(pipeline, warmup)

    if pipeline.fully_host or not pipeline.device_steps:
        if pipeline.device_steps and pipeline.fully_host:
            logger.warning(
                "Pipeline has un-kerneled steps before device steps; "
                "running fully on host."
            )
        from ..orchestration import process_documents_host

        yield from process_documents_host(
            pipeline.host_executor, docs, on_read_error=on_read_error
        )
        return

    def doc_stream():
        for item in docs:
            if isinstance(item, PipelineError):
                logger.warning("Error reading document for task. Skipping. %s", item)
                if on_read_error is not None:
                    on_read_error(item)
                continue
            yield item

    # Macro-chunks through the phased pipeline: each chunk runs phase by
    # phase with survivors repacked between phases, and one batch in flight
    # per phase (assembly overlaps device compute).  Larger chunks amortize
    # the partial batches each phase flushes at its end.
    from itertools import islice

    chunk_size = max(4 * pipeline.batch_size, CHUNK_DOCS * pipeline.chips)
    stream = doc_stream()
    while True:
        # The number process_chunk gives this chunk's phase spans.
        with TRACER.span("chunk_fill", {"chunk": pipeline._chunks}) as span:
            chunk = list(islice(stream, chunk_size))
            if span.live:
                span.add_args({"docs": len(chunk)})
        if not chunk:
            break
        yield from pipeline.process_chunk(chunk)
