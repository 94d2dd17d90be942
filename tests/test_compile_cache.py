"""Serialized AOT executable store (``utils.compile_cache``).

Covers the key's sensitivity (geometry, filter config, backend, shape —
any mismatch is a miss, never a wrong program), round-tripping a real
compiled executable, corrupt/truncated entries being evicted and silently
recompiled, the ``TEXTBLAST_NO_COMPILE_CACHE=1`` bypass, LRU eviction under
the size cap, and the warmup integration (cold run populates, a fresh
pipeline warm-starts entirely from the store with identical outcomes).
"""

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402

from textblaster_tpu.utils import compile_cache as cc


def _tiny_compiled(scale=3):
    fn = jax.jit(lambda x: x * scale + 1)
    return fn.lower(jax.ShapeDtypeStruct((8,), jnp.int32)).compile()


def _base_key_kwargs():
    return dict(
        config_fp="cfg0",
        geometry_fp="geo0",
        backend="cpu",
        length=512,
        phase=0,
        rows=16,
        wire="uint16",
        n_devices=1,
        mesh=False,
    )


def test_program_cache_key_sensitivity():
    base = cc.program_cache_key(**_base_key_kwargs())
    assert base == cc.program_cache_key(**_base_key_kwargs())  # stable
    for field, value in [
        ("config_fp", "cfg1"),
        ("geometry_fp", "geo1"),
        ("backend", "tpu"),
        ("length", 1024),
        ("phase", 1),
        ("rows", 8),
        ("wire", "int32"),
        ("n_devices", 4),
        ("mesh", True),
    ]:
        kw = _base_key_kwargs()
        kw[field] = value
        assert cc.program_cache_key(**kw) != base, field


def test_key_tracks_trace_env_knobs(monkeypatch):
    monkeypatch.delenv("TEXTBLAST_PALLAS", raising=False)
    base = cc.program_cache_key(**_base_key_kwargs())
    monkeypatch.setenv("TEXTBLAST_PALLAS", "off")
    assert cc.program_cache_key(**_base_key_kwargs()) != base


def test_config_fingerprint_tracks_params():
    from textblaster_tpu.config.pipeline import parse_pipeline_config

    yaml_a = """
pipeline:
  - type: GopherQualityFilter
    min_doc_words: 4
"""
    yaml_b = yaml_a.replace("min_doc_words: 4", "min_doc_words: 5")
    fp_a = cc.config_fingerprint(parse_pipeline_config(yaml_a))
    assert fp_a == cc.config_fingerprint(parse_pipeline_config(yaml_a))
    assert fp_a != cc.config_fingerprint(parse_pipeline_config(yaml_b))


def test_store_load_round_trip(tmp_path):
    cache = cc.AOTExecutableCache(cache_dir=str(tmp_path))
    compiled = _tiny_compiled()
    key = "a" * 32
    assert cache.load(key) is None  # absent -> miss
    assert cache.store(key, compiled)
    assert os.path.exists(os.path.join(str(tmp_path), key + ".aotx"))
    loaded = cache.load(key)
    assert loaded is not None
    assert not hasattr(loaded, "lower")  # a finished executable, not a jit
    x = jnp.arange(8, dtype=jnp.int32)
    np.testing.assert_array_equal(np.asarray(loaded(x)), np.asarray(compiled(x)))


def test_corrupt_and_truncated_entries_evicted(tmp_path):
    cache = cc.AOTExecutableCache(cache_dir=str(tmp_path))
    key = "b" * 32
    path = os.path.join(str(tmp_path), key + ".aotx")

    # Garbage bytes: load is a miss and the entry is evicted, never a crash.
    with open(path, "wb") as f:
        f.write(b"not a pickle at all")
    assert cache.load(key) is None
    assert not os.path.exists(path)

    # Truncated real entry: same treatment.
    assert cache.store(key, _tiny_compiled())
    blob = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(blob[: len(blob) // 2])
    assert cache.load(key) is None
    assert not os.path.exists(path)

    # Recompile-and-store after eviction works (the warmup path's recovery).
    assert cache.store(key, _tiny_compiled())
    assert cache.load(key) is not None


def test_bypass_env(tmp_path, monkeypatch):
    cache = cc.AOTExecutableCache(cache_dir=str(tmp_path))
    key = "c" * 32
    assert cache.store(key, _tiny_compiled())
    monkeypatch.setenv("TEXTBLAST_NO_COMPILE_CACHE", "1")
    assert not cc.aot_cache_enabled()
    assert cache.load(key) is None  # present on disk, but bypassed
    assert not cache.store("d" * 32, _tiny_compiled())
    assert not os.path.exists(os.path.join(str(tmp_path), "d" * 32 + ".aotx"))
    assert cc.enable_compilation_cache(str(tmp_path / "xla")) == ""
    monkeypatch.delenv("TEXTBLAST_NO_COMPILE_CACHE")
    assert cache.load(key) is not None


def test_lru_eviction_under_size_cap(tmp_path):
    cache = cc.AOTExecutableCache(cache_dir=str(tmp_path), max_bytes=10**9)
    for i, key in enumerate(["e" * 32, "f" * 32, "g" * 32]):
        assert cache.store(key, _tiny_compiled(scale=i + 2))
        # Distinct mtimes regardless of filesystem timestamp granularity.
        os.utime(cache._path(key), (1_000_000 + i, 1_000_000 + i))
    entry = os.path.getsize(cache._path("e" * 32))
    # A load refreshes recency: the oldest-by-mtime entry is now 'f'.
    assert cache.load("e" * 32) is not None
    cache.max_bytes = 2 * entry + entry // 2
    assert cache._evict_lru() == 1
    assert not os.path.exists(cache._path("f" * 32))
    assert os.path.exists(cache._path("e" * 32))
    assert os.path.exists(cache._path("g" * 32))
    assert cache.size_bytes() <= cache.max_bytes


def test_warmup_populates_then_warm_starts(tmp_path):
    from textblaster_tpu.config.pipeline import parse_pipeline_config
    from textblaster_tpu.data_model import TextDocument
    from textblaster_tpu.ops.pipeline import (
        CompiledPipeline,
        process_documents_device,
    )

    yaml = """
pipeline:
  - type: GopherQualityFilter
    min_doc_words: 3
    min_stop_words: 1
    stop_words: [ "the", "and", "is" ]
"""
    config = parse_pipeline_config(yaml)
    cache = cc.AOTExecutableCache(cache_dir=str(tmp_path))
    docs = [
        TextDocument(
            id=f"d{i}",
            source="s",
            content="the quick brown fox is jumping and running here",
        )
        for i in range(6)
    ]

    cold = CompiledPipeline(config, buckets=(256,), batch_size=16)
    cold_stats = cold.warmup_parallel(aot_cache=cache)
    assert cold_stats.cache_hits == 0
    assert cold_stats.cache_stores == cold_stats.programs > 0
    cold_out = {
        o.document.id: (o.kind, o.reason)
        for o in process_documents_device(config, iter(docs), pipeline=cold)
    }

    warm = CompiledPipeline(config, buckets=(256,), batch_size=16)
    warm_stats = warm.warmup_parallel(aot_cache=cache)
    assert warm_stats.cache_hits == warm_stats.programs == cold_stats.programs
    assert warm_stats.cache_misses == 0
    assert warm_stats.trace_s == 0.0 and warm_stats.compile_s == 0.0
    assert all(not hasattr(f, "lower") for f in warm._jitted.values())
    warm_out = {
        o.document.id: (o.kind, o.reason)
        for o in process_documents_device(
            config, iter([d.copy() for d in docs]), pipeline=warm
        )
    }
    assert warm_out == cold_out


def test_one_device_executable_runs_on_many_device_host(tmp_path):
    """The suite's host has 8 CPU devices: a stored one-device executable
    must load onto its own device, not onto all 8 (JAX 0.9 loads onto every
    local device unless told), and a fresh store instance must call it."""
    assert len(jax.devices()) > 1
    key = "h" * 32
    assert cc.AOTExecutableCache(cache_dir=str(tmp_path)).store(key, _tiny_compiled(5))
    loaded = cc.AOTExecutableCache(cache_dir=str(tmp_path)).load(key)
    assert loaded is not None
    x = jnp.arange(8, dtype=jnp.int32)
    np.testing.assert_array_equal(np.asarray(loaded(x)), np.arange(8) * 5 + 1)


def test_mesh_executable_round_trip(tmp_path):
    from jax.sharding import NamedSharding, PartitionSpec

    from textblaster_tpu.parallel.mesh import data_mesh

    mesh = data_mesh(jax.devices()[:4])
    sharding = NamedSharding(mesh, PartitionSpec("data"))
    compiled = (
        jax.jit(lambda x: x * 2, in_shardings=sharding, out_shardings=sharding)
        .lower(jax.ShapeDtypeStruct((8,), jnp.int32))
        .compile()
    )
    cache = cc.AOTExecutableCache(cache_dir=str(tmp_path))
    assert cache.store("i" * 32, compiled)
    loaded = cache.load("i" * 32)
    x = jax.device_put(jnp.arange(8, dtype=jnp.int32), sharding)
    out = loaded(x)
    np.testing.assert_array_equal(np.asarray(out), np.arange(8) * 2)
    assert {s.device for s in out.addressable_shards} == set(jax.devices()[:4])


def test_cache_dir_placed_from_outside(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, holds every compiled artifact:
    JAX's cache setting is left as JAX read it, and the executable store
    moves beside it."""
    monkeypatch.delenv("TEXTBLAST_NO_COMPILE_CACHE", raising=False)
    monkeypatch.delenv("TEXTBLAST_AOT_CACHE_DIR", raising=False)
    placed = tmp_path / "placed"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(placed))
    before = jax.config.jax_compilation_cache_dir
    assert cc.enable_compilation_cache() == str(placed)
    assert jax.config.jax_compilation_cache_dir == before  # left alone
    assert placed.is_dir()
    store = cc.AOTExecutableCache()
    assert store.cache_dir == os.path.join(str(placed), "textblast-aot")
    assert store.store("j" * 32, _tiny_compiled())
    assert os.listdir(store.cache_dir)

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert cc.default_aot_dir() == cc.DEFAULT_AOT_DIR
    try:
        assert cc.enable_compilation_cache() == cc.DEFAULT_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == cc.DEFAULT_CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
