"""Device-time attribution: cost-model capture/persistence, fingerprint
determinism, the dispatch-histogram merge, and the regression sentinel.

The compile-bearing tests use a deliberately tiny workload (one filter,
one bucket, 8 rows — a single ~10 s interpret-mode compile) so they stay
inside the tier-1 gate; the full sentinel check that recompiles the whole
embedded workload is marked ``slow``.
"""

import glob
import json
import os
import subprocess
import sys

import pytest

from textblaster_tpu.config.pipeline import parse_pipeline_config
from textblaster_tpu.ops.pipeline import CompiledPipeline
from textblaster_tpu.utils.compile_cache import AOTExecutableCache
from textblaster_tpu.utils.metrics import Metrics
from textblaster_tpu.utils.profiler import (
    PROFILER,
    SENTINEL_SCHEMA,
    compare_profiles,
    device_profile_report,
    device_time_family,
    main as sentinel_main,
    program_key,
)

pytestmark = pytest.mark.profile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(REPO, "profiles", "sentinel_baseline.json")

_MIN_YAML = """
pipeline:
  - type: GopherQualityFilter
    min_doc_words: 4
    min_stop_words: 1
    stop_words: [ "og", "the", "er", "i" ]
"""


@pytest.fixture
def interp(monkeypatch):
    """Pin the trace-shaping knobs to their defaults + interpret mode, so
    compiled programs (and their cost models) are machine-independent."""
    for k in ("TEXTBLAST_PALLAS", "TEXTBLAST_NO_COMPILE_CACHE"):
        monkeypatch.delenv(k, raising=False)
    monkeypatch.setenv("TEXTBLAST_PALLAS_INTERPRET", "1")


@pytest.fixture
def profiler():
    yield PROFILER
    PROFILER.close()
    PROFILER.configure()  # drop this test's captured state...
    PROFILER.close()  # ...and leave the seams disarmed


def _clean_env(**extra):
    env = {
        k: v
        for k, v in os.environ.items()
        if not k.startswith("TEXTBLAST_")
    }
    env["TEXTBLAST_PALLAS_INTERPRET"] = "1"
    env.update(extra)
    return env


def _warm(cache_dir):
    """One cold-or-warm warmup of the tiny workload with profiling on;
    returns (warmup stats, fingerprint, {program_key: source})."""
    config = parse_pipeline_config(_MIN_YAML)
    pipeline = CompiledPipeline(config, buckets=(256,), batch_size=8)
    cache = AOTExecutableCache(cache_dir=str(cache_dir))
    PROFILER.configure()
    stats = pipeline.warmup_parallel(
        aot_cache=cache, include_split_rows=False
    )
    fp = PROFILER.cost_fingerprint()
    sources = {
        pk: rec["source"] for pk, rec in PROFILER.cost_entries().items()
    }
    return stats, fp, sources


# --------------------------------------------------------------------------
# Cost model: determinism + AOT-cache survival


def test_cost_fingerprint_deterministic_across_cold_warmups(
    interp, profiler, tmp_path
):
    _, fp_a, src_a = _warm(tmp_path / "cache_a")
    _, fp_b, src_b = _warm(tmp_path / "cache_b")
    assert fp_a is not None
    assert fp_a == fp_b
    pk = program_key(256, 0, 8)
    assert src_a == {pk: "compile"}
    assert src_b == {pk: "compile"}


def test_cost_model_survives_aot_cache_hit(interp, profiler, tmp_path):
    cache_dir = tmp_path / "cache"
    cold, fp_cold, src_cold = _warm(cache_dir)
    assert cold.cache_stores == 1
    assert src_cold == {program_key(256, 0, 8): "compile"}
    sidecars = glob.glob(str(cache_dir / "*.cost.json"))
    assert len(sidecars) == 1, "compile must write the cost sidecar"

    # Warm start: the executable deserializes, the sidecar restores the
    # exact cost model — fingerprint identical to the cold run's.
    warm, fp_warm, src_warm = _warm(cache_dir)
    assert warm.cache_hits == 1 and warm.cache_stores == 0
    assert fp_warm == fp_cold
    assert src_warm == {program_key(256, 0, 8): "aot-sidecar"}
    report = device_profile_report(values=Metrics().all_values())
    assert report["cost_fingerprint"] == fp_cold
    assert report["cost_model"][program_key(256, 0, 8)]["flops"] > 0

    # Pre-profiler cache entry (sidecar missing): the hit path re-analyzes
    # the deserialized executable and backfills the sidecar.
    os.remove(sidecars[0])
    again, fp_again, src_again = _warm(cache_dir)
    assert again.cache_hits == 1
    assert fp_again == fp_cold
    assert src_again == {program_key(256, 0, 8): "aot-recompute"}
    assert glob.glob(str(cache_dir / "*.cost.json")), "sidecar backfilled"


def test_record_dispatch_feeds_histogram_and_roofline(interp, profiler):
    PROFILER.configure()
    PROFILER.record_program_cost(
        256, 0, 8, {"flops": 1000, "bytes_accessed": 4000}, "compile"
    )
    info = PROFILER.record_dispatch(256, 0, 8, 0.002)
    assert info["bucket"] == 256 and info["phase"] == 0
    assert info["modeled_bytes"] == 4000
    assert info["achieved_bytes_per_s"] == int(4000 / 0.002)
    top = PROFILER.top_dispatches()
    assert len(top) == 1 and top[0]["seconds"] == 0.002


# --------------------------------------------------------------------------
# 2-host HDR merge


def test_two_host_hdr_merge_matches_single_registry(profiler):
    fam = device_time_family(256, 0)
    host_a, host_b, single = Metrics(), Metrics(), Metrics()
    for us in (120, 3_500, 80_000):
        host_a.observe_hdr(fam, us)
        single.observe_hdr(fam, us)
    for us in (90, 5_000):
        host_b.observe_hdr(fam, us)
        single.observe_hdr(fam, us)
    # The multihost snapshot merge sums flat snapshots key-wise — the HDR
    # encoding (per-bucket counts + sum + count) makes that sum exact.
    merged = {}
    for vals in (host_a.all_values(), host_b.all_values()):
        for k, v in vals.items():
            merged[k] = merged.get(k, 0) + v
    rep_merged = device_profile_report(values=merged)
    rep_single = device_profile_report(values=single.all_values())
    assert rep_merged["dispatch"] == rep_single["dispatch"]
    assert rep_merged["dispatch"]["b256/p0"]["count"] == 5
    assert rep_merged["dispatch"]["b256/p0"]["p99_s"] >= 0.08


# --------------------------------------------------------------------------
# compare_profiles tolerance bands


def _profile(counts, cost=None):
    entry = {"dispatch_counts": dict(counts)}
    if cost is not None:
        entry["cost"] = dict(cost)
    return {
        "schema": SENTINEL_SCHEMA,
        "cost_fingerprint": "f" * 64,
        "programs": {"b256/p0/r8": entry},
    }


def test_compare_identical_profiles_pass():
    p = _profile({"fused": 5}, {"flops": 1000})
    status, findings = compare_profiles(p, p)
    assert status == "pass" and findings == []


def test_compare_cost_drift_warn_band():
    base = _profile({"fused": 5}, {"flops": 1000})
    cur = _profile({"fused": 5}, {"flops": 1030})  # +3%: warn, not fail
    status, findings = compare_profiles(
        base, cur, warn_tol=0.01, fail_tol=0.05
    )
    assert status == "warn"
    assert any("WARN" in f and "flops" in f for f in findings)


def test_compare_cost_drift_fail_band():
    base = _profile({"fused": 5}, {"flops": 1000})
    cur = _profile({"fused": 5}, {"flops": 1100})  # +10%: fail
    status, findings = compare_profiles(
        base, cur, warn_tol=0.01, fail_tol=0.05
    )
    assert status == "fail"
    assert any("FAIL" in f and "flops" in f for f in findings)


def test_compare_dispatch_count_drift_names_program():
    base = _profile({"fused": 5})
    cur = _profile({"fused": 2, "lax_scan": 10})
    status, findings = compare_profiles(base, cur)
    assert status == "fail"
    assert any("b256/p0/r8" in f and "dispatch counts" in f for f in findings)


def test_compare_missing_program_fails():
    base = _profile({"fused": 5})
    cur = dict(base, programs={})
    status, findings = compare_profiles(base, cur)
    assert status == "fail"
    assert any("vanished" in f for f in findings)


def test_counts_only_side_skips_cost_bands():
    base = _profile({"fused": 5}, {"flops": 1000})
    cur = _profile({"fused": 5})  # no cost captured: counts still gate
    status, findings = compare_profiles(base, cur)
    assert status == "pass" and findings == []


# --------------------------------------------------------------------------
# Sentinel CLI


def test_check_missing_baseline_is_informative_skip(tmp_path, capsys):
    rc = sentinel_main(["--check", str(tmp_path / "nope.json")])
    assert rc == 0
    out = capsys.readouterr().out
    assert "SKIP" in out and "no baseline" in out


def test_check_rejects_schema_mismatch(tmp_path, capsys):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"schema": "something-else/v9"}))
    rc = sentinel_main(["--check", str(bad)])
    assert rc == 1
    assert "schema" in capsys.readouterr().out


def test_sentinel_counts_check_passes_against_checked_in_baseline(tmp_path):
    """Tier-1 gate: the machine-independent half of the sentinel against
    the checked-in interpret-mode baseline."""
    proc = subprocess.run(
        [
            sys.executable,
            "-m",
            "textblaster_tpu.utils.profiler",
            "--check",
            BASELINE,
            "--counts-only",
        ],
        env=_clean_env(TEXTBLAST_AOT_CACHE_DIR=str(tmp_path / "aot")),
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "PASS" in proc.stdout


def test_sentinel_counts_check_passes_with_join_modules_imported(tmp_path):
    """The elastic scale-out layer (admission transport hooks, the
    autoscale supervisor, the membership join/roster API) must be inert at
    import time: loading it before the sentinel runs must not change the
    program set or dispatch counts the baseline pins."""
    script = (
        "import textblaster_tpu.parallel.multihost\n"
        "import textblaster_tpu.parallel.autoscale\n"
        "import textblaster_tpu.resilience.membership\n"
        "import sys\n"
        "from textblaster_tpu.utils.profiler import main\n"
        f"sys.exit(main(['--check', {BASELINE!r}, '--counts-only']))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", script],
        env=_clean_env(TEXTBLAST_AOT_CACHE_DIR=str(tmp_path / "aot")),
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "PASS" in proc.stdout


def test_sentinel_check_fails_on_depfuse_off(tmp_path):
    """A flipped kernel hatch (``TEXTBLAST_PALLAS=off``) must fail the
    check, naming the drifted (bucket, phase) entries and the knob — fast:
    the counts stage fails before any compile."""
    proc = subprocess.run(
        [
            sys.executable,
            "-m",
            "textblaster_tpu.utils.profiler",
            "--check",
            BASELINE,
        ],
        env=_clean_env(
            TEXTBLAST_PALLAS="off",
            TEXTBLAST_AOT_CACHE_DIR=str(tmp_path / "aot"),
        ),
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "dispatch counts drifted" in proc.stdout
    assert "b256/p0/r16" in proc.stdout
    assert "TEXTBLAST_PALLAS" in proc.stdout  # env drift note


@pytest.mark.slow
def test_sentinel_full_check_passes_against_checked_in_baseline(tmp_path):
    """The full check — recompiles the sentinel workload and applies the
    cost tolerance bands (minutes on CPU interpret; slow tier)."""
    proc = subprocess.run(
        [
            sys.executable,
            "-m",
            "textblaster_tpu.utils.profiler",
            "--check",
            BASELINE,
        ],
        env=_clean_env(TEXTBLAST_AOT_CACHE_DIR=str(tmp_path / "aot")),
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=1200,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert proc.stdout.strip().splitlines()[-1].startswith("PASS")
