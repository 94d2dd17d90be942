"""Bit-exact parity for the fused filter megakernel (``ops.pallas_scan
fused_scan``) and the shard_map'd mesh dispatch.

Same contract as ``test_pallas_scan.py``: the exact kernel program runs
under Pallas interpret mode on CPU, every op is int32 ALU with exact
wraparound, so every comparison is bit-exact — fused kernel vs the staged
path the chip takes above the fused kernels' lane ceiling (per-scan kernels,
reached here by lowering ``pallas_scan._FUSED_MAX_LANES`` below the test's
width) vs the pure-Python host oracle, across every in-kernel block width,
multi-block carries, and the edge documents.
The mesh tests assert the shard_map'd kernels match single-device output
bit-for-bit on the 8 virtual CPU devices conftest forces.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
pytest.importorskip("jax.experimental.pallas")

import jax.numpy as jnp  # noqa: E402

try:
    from textblaster_tpu.ops import pallas_scan as psc
    from textblaster_tpu.ops import pallas_sort as pso
    from textblaster_tpu.ops.stats import fineweb_stats, structure
    from textblaster_tpu.parallel.mesh import batch_sharding, data_mesh
except Exception as e:  # pragma: no cover - partial jax builds
    pytest.skip(f"pallas scan stack unavailable: {e}", allow_module_level=True)

pytestmark = [pytest.mark.pallas, pytest.mark.fused]


@pytest.fixture
def interp(monkeypatch):
    """Force the interpret-mode kernel path; clear the disabling hatch."""
    monkeypatch.delenv("TEXTBLAST_PALLAS", raising=False)
    monkeypatch.setenv("TEXTBLAST_PALLAS_INTERPRET", "1")


def _staged(m):
    """Inside ``monkeypatch.context() as m``: the staged path with the
    per-scan kernels, as the chip runs it above the fused lane ceiling."""
    m.setattr(psc, "_FUSED_MAX_LANES", 128)


def _full_range_int32(rng, shape):
    return rng.integers(-(2**31), 2**31, size=shape, dtype=np.int64).astype(
        np.int32
    )


# Edge documents the fuzz must cover: empty, all-whitespace, multilingual
# BMP text, astral-plane codepoints, and a row exactly at bucket length.
EDGE_TEXTS = [
    "",
    " \t\n  \r\t ",
    "The quick brown fox jumps over the lazy dog, twice.",
    "Ætt blåbærsyltetøy — grød på ærø, ÆØÅ æøå.",
    "数据处理流水线的奇偶校验测试文本，包含中文。",
    "𝔘𝔫𝔦𝔠𝔬𝔡𝔢 𝕋𝕖𝕩𝕥 🚀🔥𐍈𒀀 and some ascii",
    "a" * 256,
    "word.\nword her.\n…\n- bullet\n### h\n" + "linje og tekst er det. " * 8,
]


def _rows_from_texts(texts, length):
    cps = np.zeros((len(texts), length), np.int32)
    lens = np.zeros((len(texts),), np.int32)
    for i, t in enumerate(texts):
        cp = [ord(c) for c in t][:length]
        cps[i, : len(cp)] = cp
        lens[i] = len(cp)
    return cps, lens


def _valid_dfa_maps(rng, shape, n_states):
    fns = np.zeros(shape, np.int64)
    for s in range(n_states):
        fns |= rng.integers(0, n_states, size=shape) << (4 * s)
    return jnp.asarray(fns.astype(np.int32))


# --- raw fused kernel vs the lax twins ---------------------------------------


@pytest.mark.parametrize(
    "shape", [(8, 128), (16, 256), (8, 512), (24, 1024), (8, 1280)]
)
def test_fused_groups_match_lax_fuzz(interp, shape):
    # Shapes cover every in-kernel block width (128/256/512) and multi-block
    # carry folding; full-range int32 inputs exercise exact wraparound.
    rng = np.random.default_rng(shape[0] * 7919 + shape[1])
    m, a1, a2, v = (jnp.asarray(_full_range_int32(rng, shape)) for _ in range(4))
    fns = _valid_dfa_maps(rng, shape, 6)
    assert psc.fused_scan_ok(*shape)
    res = psc.fused_scan(
        [
            psc.affine_group(m, (a1, a2)),
            psc.add_group((v,)),
            psc.dfa_group(fns, 6),
            psc.add_group((v, a1), emit="last"),
        ]
    )
    want_aff = jax.lax.associative_scan(psc._affine_op, (m, a1, a2), axis=1)[1:]
    np.testing.assert_array_equal(np.asarray(res[0][0]), np.asarray(want_aff[0]))
    np.testing.assert_array_equal(np.asarray(res[0][1]), np.asarray(want_aff[1]))
    np.testing.assert_array_equal(
        np.asarray(res[1][0]), np.asarray(jnp.cumsum(v, axis=1))
    )
    (want_dfa,) = jax.lax.associative_scan(psc._dfa_op(6), (fns,), axis=1)
    np.testing.assert_array_equal(np.asarray(res[2][0]), np.asarray(want_dfa))
    # emit="last" groups carry only the final [B, 1] totals.
    assert res[3][0].shape == (shape[0], 1)
    # dtype pinned: the kernel accumulates with int32 wraparound, while a
    # bare jnp.sum would promote under x64.
    np.testing.assert_array_equal(
        np.asarray(res[3][0][:, 0]),
        np.asarray(jnp.sum(v, axis=1, dtype=jnp.int32)),
    )
    np.testing.assert_array_equal(
        np.asarray(res[3][1][:, 0]),
        np.asarray(jnp.sum(a1, axis=1, dtype=jnp.int32)),
    )


def test_fused_matches_per_scan_kernels(interp):
    rng = np.random.default_rng(11)
    shape = (16, 640)
    m, a = (jnp.asarray(_full_range_int32(rng, shape)) for _ in range(2))
    fns = _valid_dfa_maps(rng, shape, 8)
    res = psc.fused_scan([psc.affine_group(m, (a,)), psc.dfa_group(fns, 8)])
    np.testing.assert_array_equal(
        np.asarray(res[0][0]), np.asarray(psc.affine_hash_scan(m, (a,))[0])
    )
    np.testing.assert_array_equal(
        np.asarray(res[1][0]), np.asarray(psc.dfa_compose_scan(fns, 8))
    )


def test_fused_is_one_dispatch(interp):
    rng = np.random.default_rng(2)
    m, a, v = (jnp.asarray(_full_range_int32(rng, (8, 256))) for _ in range(3))
    with psc.count_scan_dispatches() as counts:
        psc.fused_scan(
            [
                psc.affine_group(m, (a,)),
                psc.add_group((v,)),
                psc.add_group((v,), emit="last"),
            ]
        )
    assert counts == {"fused": 1}


# --- gates and hatches ------------------------------------------------------


def test_fused_gate(interp):
    assert psc.fused_scan_ok(8, 256)
    assert not psc.fused_scan_ok(12, 256)  # rows not a multiple of 8
    assert not psc.fused_scan_ok(8, 100)  # length not a multiple of 128
    assert not psc.fused_scan_ok(8, 2 * psc._FUSED_MAX_LANES)  # VMEM ceiling
    assert psc.pallas_scan_ok(8, 2 * psc._FUSED_MAX_LANES)  # per-scan still ok


def test_probe_cache_keys_on_env_hatches(monkeypatch):
    """Satellite: the backend probe verdict must not be served stale across
    env-hatch flips — the cache keys on (env hatches, backend)."""
    for mod in (psc, pso):
        mod._probe_cached.cache_clear()
        monkeypatch.delenv("TEXTBLAST_PALLAS", raising=False)
        monkeypatch.setenv("TEXTBLAST_PALLAS_INTERPRET", "1")
        e1 = mod._env_hatches()
        mod._probe_backend()
        mod._probe_backend()
        assert mod._probe_cached.cache_info().misses == 1  # cached within env
        monkeypatch.delenv("TEXTBLAST_PALLAS_INTERPRET")
        assert mod._env_hatches() != e1
        mod._probe_backend()  # flipped hatch -> a fresh probe, not stale
        assert mod._probe_cached.cache_info().misses == 2


@pytest.mark.parametrize(
    "probe",
    ["sort", "scan", "fused", "depfuse"],
)
def test_failed_probe_on_tpu_raises(probe):
    """A kernel that fails its probe on a TPU is an error, never a quiet
    switch to the lax schedule.  Asked to probe "tpu" from this CPU-only
    process, the Mosaic lowering fails, which must raise; on the CPU the
    probes answer False (the lax schedules are the CPU's path)."""
    fn = {
        "sort": pso._probe_cached,
        "scan": psc._probe_cached,
        "fused": psc._probe_fused_cached,
        "depfuse": psc._probe_depfuse_cached,
    }[probe]
    assert fn(("test",), "cpu") is False
    with pytest.raises(RuntimeError, match="probe"):
        fn(("test",), "tpu")


@pytest.mark.parametrize("probe", ["sort", "scan", "fused", "depfuse"])
def test_pipeline_probes_before_tracing(probe, monkeypatch):
    """A probe compiles and runs a kernel, which it cannot do inside the
    trace of a pipeline program: CompiledPipeline resolves every gate when
    it is built, and tracing reads the cached verdicts."""
    from textblaster_tpu.config.pipeline import load_pipeline_config
    from textblaster_tpu.ops.pipeline import CompiledPipeline

    for var in ("TEXTBLAST_PALLAS", "TEXTBLAST_PALLAS_INTERPRET"):
        monkeypatch.delenv(var, raising=False)
    asked = []
    for name, mod, attr in (("sort", pso, "_probe_backend"),
                            ("scan", psc, "_probe_backend"),
                            ("fused", psc, "_probe_fused"),
                            ("depfuse", psc, "_probe_depfuse")):
        monkeypatch.setattr(mod, attr, lambda name=name: asked.append(name) or True)
    CompiledPipeline(load_pipeline_config("configs/pipeline_config_offline.yaml"))
    assert probe in asked


def test_mesh_tracing_with_mesh_keeps_kernels(interp):
    """mesh_tracing(mesh) means shard_map, not decline; the legacy marker
    forms keep their PR 7 semantics (covered in test_pallas_scan too)."""
    mesh = data_mesh()
    n_dev = mesh.devices.size
    with psc.mesh_tracing(mesh):
        assert psc.pallas_scan_supported()
        # Rows must split into ROWS-aligned per-device shards.
        assert psc.pallas_scan_ok(8 * n_dev, 256)
        if n_dev > 1:
            assert not psc.pallas_scan_ok(8, 256)
    with psc.mesh_tracing():
        assert not psc.pallas_scan_supported()


# --- stats fused path vs staged lax path vs host oracle ----------------------


def _edge_batch(length=256, reps=1):
    cps, lens = _rows_from_texts(EDGE_TEXTS * reps, length)
    return jnp.asarray(cps), jnp.asarray(lens)


def _structure_fields(st):
    return {
        k: np.asarray(v)
        for k, v in st._asdict().items()
        if v is not None and k not in ("cps", "lengths")
    }


@pytest.mark.parametrize("with_hashes", [True, False])
def test_structure_fused_vs_staged(interp, monkeypatch, with_hashes):
    cps, lens = _edge_batch()
    assert psc.fused_scan_ok(*cps.shape)
    with psc.count_scan_dispatches() as counts:
        fused = structure(cps, lens, with_hashes=with_hashes)
    assert counts.get("fused") == 1
    with monkeypatch.context() as m:
        _staged(m)
        staged = structure(cps, lens, with_hashes=with_hashes)
    for k, v in _structure_fields(fused).items():
        np.testing.assert_array_equal(v, _structure_fields(staged)[k], err_msg=k)


def test_fineweb_fused_vs_staged(interp, monkeypatch):
    cps, lens = _edge_batch()
    fused = fineweb_stats(structure(cps, lens), (".", "!", "?"), 64, 30)
    with monkeypatch.context() as m:
        _staged(m)
        staged = fineweb_stats(structure(cps, lens), (".", "!", "?"), 64, 30)
    assert set(fused) == set(staged)
    for k in fused:
        np.testing.assert_array_equal(
            np.asarray(fused[k]), np.asarray(staged[k]), err_msg=k
        )


def test_full_pipeline_three_way_parity(interp, monkeypatch):
    """Whole-pipeline decisions: fused kernels vs staged (per-scan kernels)
    vs the pure-Python host oracle must agree on kind/reason/content."""
    from textblaster_tpu.config.pipeline import parse_pipeline_config
    from textblaster_tpu.data_model import TextDocument
    from textblaster_tpu.ops.pipeline import process_documents_device
    from textblaster_tpu.orchestration import process_documents_host
    from textblaster_tpu.pipeline_builder import build_pipeline_from_config

    yaml_str = """
pipeline:
  - type: GopherQualityFilter
    min_doc_words: 3
    max_doc_words: 100000
    min_avg_word_length: 1.0
    max_avg_word_length: 12.0
    max_symbol_word_ratio: 0.5
    max_bullet_lines_ratio: 0.9
    max_ellipsis_lines_ratio: 0.3
    max_non_alpha_words_ratio: 0.8
    min_stop_words: 1
    stop_words: [ "og", "er", "det", "the", "and" ]
  - type: C4QualityFilter
    split_paragraph: true
    remove_citations: true
    filter_no_terminal_punct: true
    min_num_sentences: 1
    min_words_per_line: 2
    max_word_length: 1000
    filter_lorem_ipsum: true
    filter_javascript: true
    filter_curly_bracket: true
    filter_policy: true
  - type: FineWebQualityFilter
    line_punct_thr: 0.12
    line_punct_exclude_zero: false
    short_line_thr: 0.67
    short_line_length: 30
    char_duplicates_ratio: 0.1
    new_line_ratio: 0.3
"""
    texts = EDGE_TEXTS + [
        "Det er en god dag og vejret er fint. Vi går en tur i skoven nu.",
        "Samme linje er her i dag.\n" * 6,
        "Citat her [1]. Mere tekst [2, 3]. Det er en god dag og det er fint.",
    ]
    config = parse_pipeline_config(yaml_str)

    def docs():
        return [
            TextDocument(id=f"d{i}", source="s", content=t)
            for i, t in enumerate(texts)
        ]

    host = {
        o.document.id: o
        for o in process_documents_host(build_pipeline_from_config(config), docs())
    }
    fused = {
        o.document.id: o
        for o in process_documents_device(config, iter(docs()), device_batch=8)
    }
    with monkeypatch.context() as m:
        _staged(m)
        staged = {
            o.document.id: o
            for o in process_documents_device(config, iter(docs()), device_batch=8)
        }
    assert set(host) == set(fused) == set(staged)
    for did, h in sorted(host.items()):
        for name, o in (("fused", fused[did]), ("staged", staged[did])):
            assert o.kind == h.kind, f"{did} {name}: {o.kind} != {h.kind}"
            assert o.reason == h.reason, f"{did} {name}: {o.reason!r}"
            assert o.document.content == h.document.content, f"{did} {name}"


# --- mesh: shard_map'd kernels vs single-device, bit-exact -------------------


def test_mesh_fused_scan_parity(interp):
    mesh = data_mesh()
    n_dev = mesh.devices.size
    if n_dev < 2:
        pytest.skip("needs the multi-device CPU mesh from conftest")
    rng = np.random.default_rng(5)
    shape = (8 * n_dev, 512)
    m, a, v = (jnp.asarray(_full_range_int32(rng, shape)) for _ in range(3))
    ref = psc.fused_scan(
        [psc.affine_group(m, (a,)), psc.add_group((v,), emit="last")]
    )
    ref_h = psc.affine_hash_scan(m, (a,))

    def prog(m, a, v):
        with psc.mesh_tracing(mesh):
            assert psc.fused_scan_ok(*m.shape)
            r = psc.fused_scan(
                [psc.affine_group(m, (a,)), psc.add_group((v,), emit="last")]
            )
            (h,) = psc.affine_hash_scan(m, (a,))
            return r[0][0], r[1][0], h

    sh = batch_sharding(mesh, 2)
    got = jax.jit(prog, in_shardings=(sh, sh, sh))(m, a, v)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(ref[0][0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(ref[1][0]))
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(ref_h[0]))


def test_mesh_structure_parity(interp):
    mesh = data_mesh()
    n_dev = mesh.devices.size
    if n_dev < 2:
        pytest.skip("needs the multi-device CPU mesh from conftest")
    reps = max(1, (8 * n_dev) // len(EDGE_TEXTS))
    cps, lens = _edge_batch(reps=reps)
    assert cps.shape[0] % (8 * n_dev) == 0
    ref = _structure_fields(structure(cps, lens))

    def prog(c, l):
        with psc.mesh_tracing(mesh):
            return structure(c, l)

    got = jax.jit(
        prog, in_shardings=(batch_sharding(mesh, 2), batch_sharding(mesh, 1))
    )(cps, lens)
    for k, v in _structure_fields(got).items():
        np.testing.assert_array_equal(v, ref[k], err_msg=k)


# --- pipeline plumbing: split rows, warmup pre-seed, dispatch counts ---------


_MINI_YAML = """
pipeline:
  - type: FineWebQualityFilter
    line_punct_thr: 0.12
    line_punct_exclude_zero: false
    short_line_thr: 0.67
    short_line_length: 30
    char_duplicates_ratio: 0.1
    new_line_ratio: 0.3
"""


def _pipeline():
    from textblaster_tpu.config.pipeline import parse_pipeline_config
    from textblaster_tpu.ops.pipeline import CompiledPipeline

    return CompiledPipeline(
        parse_pipeline_config(_MINI_YAML), buckets=[256], batch_size=16
    )


def test_split_rows_keeps_sublane_alignment():
    from textblaster_tpu.ops.pipeline import CompiledPipeline

    # Half-splits round UP to the 8-row tile so split retries keep the
    # (fused) kernels; never above the full batch.
    assert CompiledPipeline._split_rows(16) == 8
    assert CompiledPipeline._split_rows(24) == 16
    assert CompiledPipeline._split_rows(8) == 8
    assert CompiledPipeline._split_rows(6) == 6  # sub == full stays unsplit
    assert CompiledPipeline._split_rows(256) == 128


def test_warmup_jobs_preseed_fused_split_variants(interp):
    p = _pipeline()
    jobs = p._warmup_jobs()
    rows = sorted({r for (_, _, _, r) in jobs})
    assert rows == [8, 16]  # full and the ROWS-aligned half split
    assert all(r % 8 == 0 for r in rows)  # every variant stays fused-eligible


def test_scan_dispatch_counts_fused_vs_staged(interp, monkeypatch):
    p = _pipeline()
    fused = p.scan_dispatch_counts(256)
    assert fused.get("fused", 0) >= 1
    with monkeypatch.context() as m:
        _staged(m)
        staged = _pipeline().scan_dispatch_counts(256)
    assert staged.get("fused", 0) == 0
    assert staged.get("pallas_scan", 0) >= 1
    total_fused = sum(fused.values())
    total_staged = sum(staged.values())
    assert total_fused < total_staged  # the megakernel removed dispatches
