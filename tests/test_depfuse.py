"""Dependency-chain fusion (``ops.pallas_scan chain_scan``) + batched
verdict exchange (PR 11).

Kernel layer: a multi-pass chain program — ordered passes whose groups tap
earlier passes' streams without leaving the kernel — must be bit-exact
against the staged lax schedules for every group kind (affine, add, dfa,
segmax, copy), forward and reverse passes, shift taps, and multi-block
carries, over full-range int32 inputs at 128–1280 lanes.  Consumer layer:
``structure``/``gopher_rep_stats``/``gopher_quality_stats``/``c4_stage``/
``sentence_counts`` with the chain kernels vs the staged lax path
(``TEXTBLAST_PALLAS=off``) vs the host oracle must agree on
kind/reason/content over the edge documents, and the per-(bucket, phase)
dispatch counts are pinned as a regression gate.

Exchange layer: ``NegotiatedGuard.negotiate_batch`` posts ONE allgather
vector for a window's worth of verdicts — depth-1 wire traffic must stay
byte-identical, the batched-fault drain must replay to the same ordered
outcome stream as serial, and the overlapped arm must spend fewer
``host_allgather`` posts than serial.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
pytest.importorskip("jax.experimental.pallas")

import jax.numpy as jnp  # noqa: E402

try:
    from textblaster_tpu.ops import pallas_scan as psc
    from textblaster_tpu.ops.dfa import dfa_packed_fns
    from textblaster_tpu.ops.stats import (
        c4_stage,
        C4Params,
        gopher_quality_stats,
        gopher_rep_stats,
        sentence_counts,
        structure,
    )
except Exception as e:  # pragma: no cover - partial jax builds
    pytest.skip(f"pallas scan stack unavailable: {e}", allow_module_level=True)

pytestmark = [pytest.mark.depfuse]


@pytest.fixture
def interp(monkeypatch):
    """Force the interpret-mode kernel path; clear the disabling hatch."""
    monkeypatch.delenv("TEXTBLAST_PALLAS", raising=False)
    monkeypatch.setenv("TEXTBLAST_PALLAS_INTERPRET", "1")


def _full_range_int32(rng, shape):
    return rng.integers(-(2**31), 2**31, size=shape, dtype=np.int64).astype(
        np.int32
    )


# Edge documents: empty, all-whitespace, multilingual BMP, astral-plane
# codepoints, and a row exactly at bucket length.
EDGE_TEXTS = [
    "",
    " \t\n  \r\t ",
    "The quick brown fox jumps over the lazy dog, twice. And again!",
    "Ætt blåbærsyltetøy — grød på ærø, ÆØÅ æøå.",
    "数据处理流水线的奇偶校验测试文本，包含中文。第二句在这里！",
    "𝔘𝔫𝔦𝔠𝔬𝔡𝔢 𝕋𝕖𝕩𝕥 🚀🔥𐍈𒀀 and some ascii",
    "Samme linje her igen.\n" * 6,
    "lorem ipsum dolor sit amet. uses cookies and javascript here.",
    "a" * 256,
]


def _rows_from_texts(texts, length):
    cps = np.zeros((max(8, ((len(texts) + 7) // 8) * 8), length), np.int32)
    lens = np.zeros((cps.shape[0],), np.int32)
    for i, t in enumerate(texts):
        arr = np.array([ord(c) for c in t[:length]], np.int32)
        cps[i, : len(arr)] = arr
        lens[i] = len(arr)
    return jnp.asarray(cps), jnp.asarray(lens)


# --- raw multi-pass chain vs staged lax --------------------------------------


def _seg_add_lax(v, r):
    m = jnp.where(r != 0, 0, 1)
    return jax.lax.associative_scan(psc._affine_op, (m, v), axis=1)[1]


def _segmax_lax(v, r):
    return jax.lax.associative_scan(psc._segmax_op, (v, r), axis=1)[0]


@pytest.mark.pallas
@pytest.mark.parametrize(
    "length",
    [128, pytest.param(384, marks=pytest.mark.slow), pytest.param(1280, marks=pytest.mark.slow)],
)
def test_chain_multipass_groups_vs_staged(interp, length):
    """Four passes chained through taps — seg-add feeding a reverse segmax,
    whose run totals feed a forward copy (with a shift tap) and a whole-row
    total, whose stream feeds a final cumsum — all in ONE dispatch, bit
    equal to the staged lax schedules on full-range int32."""
    rng = np.random.default_rng(length)
    B = 16
    vals = jnp.asarray(_full_range_int32(rng, (B, length)))
    reset = jnp.asarray((rng.random((B, length)) < 0.05).astype(np.int32))
    reset = reset.at[:, 0].set(1)
    nonneg = jnp.abs(vals) % 1000

    seg = _seg_add_lax(nonneg, reset)
    rt = jnp.flip(
        _segmax_lax(
            jnp.flip(jnp.where(reset != 0, seg, 0), 1), jnp.flip(reset, 1)
        ),
        1,
    )
    raw_max = jnp.flip(
        _segmax_lax(jnp.flip(vals, 1), jnp.flip(reset, 1)), 1
    )
    prev_seg = jnp.concatenate([jnp.zeros((B, 1), jnp.int32), seg[:, :-1]], 1)
    copy_ref = rt + prev_seg
    m_h = jnp.where(reset != 0, 0, 31)
    hash_ref = jax.lax.associative_scan(psc._affine_op, (m_h, vals), axis=1)[1]
    wrap_ref = jnp.cumsum(vals, axis=1, dtype=jnp.int32)
    total_ref = jnp.sum(jnp.where(rt > 500, 1, 0), axis=1, keepdims=True)
    cs_ref = jnp.cumsum((copy_ref & 1), axis=1, dtype=jnp.int32)

    with psc.count_scan_dispatches() as counts:
        got = psc.chain_scan([
            psc.chain_pass([
                {"kind": "affine",
                 "xs": (jnp.where(reset != 0, 0, 1), nonneg),
                 "emit": "none"},
                {"kind": "affine", "xs": (m_h, vals), "emit": "scan"},
                {"kind": "add", "xs": (vals,), "emit": "scan"},
            ]),
            psc.chain_pass([
                psc.chain_group(
                    "segmax", (psc.Tap(0, 0), reset),
                    prep=lambda s, r: (jnp.where(r != 0, s, 0), r), n_ops=2,
                ),
                {"kind": "segmax", "xs": (vals, reset), "emit": "scan"},
            ], reverse=True),
            psc.chain_pass([
                psc.chain_group(
                    "copy", (psc.Tap(1, 0), psc.Tap(0, 0, shift=1, fill=0)),
                    prep=lambda a, b: (a + b,), n_ops=1, emit="scan",
                ),
                psc.chain_group(
                    "add", (psc.Tap(1, 0),),
                    prep=lambda a: (jnp.where(a > 500, 1, 0),), n_ops=1,
                    emit="last",
                ),
            ]),
            psc.chain_pass([
                psc.chain_group(
                    "add", (psc.Tap(2, 0),),
                    prep=lambda c: (c & 1,), n_ops=1, emit="scan",
                ),
            ]),
        ])
    assert counts.get("fused") == 1 and "lax_scan" not in counts
    np.testing.assert_array_equal(np.asarray(got[0][1][0]), hash_ref)
    np.testing.assert_array_equal(np.asarray(got[0][2][0]), wrap_ref)
    np.testing.assert_array_equal(np.asarray(got[1][0][0]), rt)
    np.testing.assert_array_equal(np.asarray(got[1][1][0]), raw_max)
    np.testing.assert_array_equal(np.asarray(got[2][0][0]), copy_ref)
    np.testing.assert_array_equal(np.asarray(got[2][1][0]), total_ref)
    np.testing.assert_array_equal(np.asarray(got[3][0][0]), cs_ref)


@pytest.mark.pallas
def test_chain_reverse_shift_tap(interp):
    """A reverse pass's shift tap reads the NEXT natural position of the
    tapped stream (walk-previous in the mirrored frame)."""
    rng = np.random.default_rng(9)
    B, L = 8, 384
    x = jnp.asarray(rng.integers(0, 100, size=(B, L)).astype(np.int32))
    got = psc.chain_scan([
        psc.chain_pass([{"kind": "add", "xs": (x,), "emit": "scan"}]),
        psc.chain_pass([
            psc.chain_group(
                "copy", (psc.Tap(0, 0, shift=1, fill=-7),),
                prep=lambda nxt: (nxt * 2,), n_ops=1, emit="scan",
            ),
        ], reverse=True),
    ])
    cs = jnp.cumsum(x, axis=1, dtype=jnp.int32)
    nxt = jnp.concatenate([cs[:, 1:], jnp.full((B, 1), -7, jnp.int32)], 1)
    np.testing.assert_array_equal(np.asarray(got[1][0][0]), np.asarray(nxt * 2))


@pytest.mark.pallas
@pytest.mark.parametrize("length", [128, 1280])
def test_chain_dfa_pass_feeds_counter(interp, length):
    """A dfa pass's packed-state stream tapped by a later add group — the
    DFA -> boundary-counter handoff shape — vs a per-row host automaton."""
    rng = np.random.default_rng(length + 1)
    B, n_states = 8, 4
    transition = rng.integers(0, n_states, size=(4, n_states)).astype(np.int32)
    transition[:, 0] = rng.integers(0, n_states, size=4)
    cls = rng.integers(0, 4, size=(B, length)).astype(np.int32)
    fns = dfa_packed_fns(jnp.asarray(cls), jnp.asarray(transition))

    got = psc.chain_scan([
        psc.chain_pass([
            {"kind": "dfa", "xs": (fns,), "n_states": n_states,
             "emit": "scan"},
        ]),
        psc.chain_pass([
            psc.chain_group(
                "add", (psc.Tap(0, 0),),
                prep=lambda pk: ((pk & 15) == 1, ), n_ops=1, emit="scan",
            ),
        ]),
    ])
    packed = np.asarray(got[0][0][0])
    counts = np.asarray(got[1][0][0])
    for b in range(B):
        s, hits = 0, 0
        for i in range(length):
            s = int(transition[cls[b, i], s])
            assert (packed[b, i] & 15) == s
            hits += int(s == 1)
            assert counts[b, i] == hits


def test_chain_gate_respects_hatch(interp, monkeypatch):
    assert psc.chain_scan_ok(16, 512)
    monkeypatch.setenv("TEXTBLAST_PALLAS", "off")
    assert not psc.chain_scan_ok(16, 512)


# --- consumer parity: chain kernels vs staged over edge docs -----------------


def _arrays(d):
    return {k: np.asarray(v) for k, v in d.items()}


@pytest.mark.pallas
@pytest.mark.slow
def test_gopher_rep_depfuse_vs_staged(interp, monkeypatch):
    cps, lens = _rows_from_texts(EDGE_TEXTS, 256)
    st = structure(cps, lens, with_hashes=True)
    with psc.count_scan_dispatches() as counts:
        on = gopher_rep_stats(st, (2, 3), (5, 6), 128, 256)
    assert set(counts) == {"fused", "pallas_sort"}, dict(counts)
    with monkeypatch.context() as m:
        m.setenv("TEXTBLAST_PALLAS", "off")
        st2 = structure(cps, lens, with_hashes=True)
        off = gopher_rep_stats(st2, (2, 3), (5, 6), 128, 256)
    assert set(on) == set(off)
    for k in on:
        np.testing.assert_array_equal(
            np.asarray(on[k]), np.asarray(off[k]), err_msg=k
        )


@pytest.mark.pallas
@pytest.mark.parametrize("staged", ["lax", "per_scan_kernels"])
def test_gopher_quality_depfuse_vs_staged(interp, monkeypatch, staged):
    """Chain kernel against the staged path: all lax (``TEXTBLAST_PALLAS=off``)
    or with the per-scan kernels, as the chip runs it above the fused
    kernels' lane ceiling."""
    cps, lens = _rows_from_texts(EDGE_TEXTS, 256)
    hashes = tuple(range(-5, 5))
    with psc.count_scan_dispatches() as counts:
        on = gopher_quality_stats(structure(cps, lens), hashes)
    assert set(counts) == {"fused"}, dict(counts)
    with monkeypatch.context() as m:
        if staged == "lax":
            m.setenv("TEXTBLAST_PALLAS", "off")
        else:
            m.setattr(psc, "_FUSED_MAX_LANES", 128)
        with psc.count_scan_dispatches() as counts:
            off = gopher_quality_stats(structure(cps, lens), hashes)
    assert "fused" not in counts, dict(counts)
    assert set(on) == set(off)
    for k in on:
        np.testing.assert_array_equal(
            np.asarray(on[k]), np.asarray(off[k]), err_msg=k
        )


@pytest.mark.pallas
@pytest.mark.parametrize(
    "split_paragraph", [True, pytest.param(False, marks=pytest.mark.slow)]
)
def test_c4_and_sentences_depfuse_vs_staged(interp, monkeypatch,
                                            split_paragraph):
    cps, lens = _rows_from_texts(EDGE_TEXTS, 256)
    params = C4Params(
        split_paragraph=split_paragraph,
        remove_citations=True,
        filter_no_terminal_punct=True,
        min_num_sentences=1,
        min_words_per_line=2,
        max_word_length=1000,
        filter_lorem_ipsum=True,
        filter_javascript=True,
        filter_curly_bracket=True,
        filter_policy=True,
    )

    def run():
        st, c_cps, c_len = c4_stage(cps, lens, params, max_lines=64)
        out = _arrays(st)
        out["__cps"] = np.asarray(c_cps)
        out["__len"] = np.asarray(c_len)
        out["__nsent"] = np.asarray(sentence_counts(cps, lens))
        return out

    on = run()
    with monkeypatch.context() as m:
        m.setenv("TEXTBLAST_PALLAS", "off")
        off = run()
    assert set(on) == set(off)
    for k in on:
        np.testing.assert_array_equal(on[k], off[k], err_msg=k)


@pytest.mark.pallas
@pytest.mark.slow
def test_full_pipeline_three_way_parity(interp, monkeypatch):
    """Whole-pipeline decisions: chain kernels vs staged lax
    (TEXTBLAST_PALLAS=off) vs the pure-Python host oracle must agree on
    kind/reason/content over the edge docs."""
    from textblaster_tpu.config.pipeline import parse_pipeline_config
    from textblaster_tpu.data_model import TextDocument
    from textblaster_tpu.ops.pipeline import process_documents_device
    from textblaster_tpu.orchestration import process_documents_host
    from textblaster_tpu.pipeline_builder import build_pipeline_from_config

    yaml_str = """
pipeline:
  - type: GopherRepetitionFilter
    dup_line_frac: 0.3
    top_n_grams: [[2, 0.25]]
    dup_n_grams: [[5, 0.15]]
  - type: GopherQualityFilter
    min_doc_words: 3
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
"""
    texts = EDGE_TEXTS + [
        "Det er en god dag og vejret er fint. Vi går en tur i skoven nu.",
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
        for o in process_documents_host(
            build_pipeline_from_config(config), docs()
        )
    }
    on = {
        o.document.id: o
        for o in process_documents_device(config, iter(docs()), device_batch=8)
    }
    with monkeypatch.context() as m:
        m.setenv("TEXTBLAST_PALLAS", "off")
        off = {
            o.document.id: o
            for o in process_documents_device(
                config, iter(docs()), device_batch=8
            )
        }
    assert set(host) == set(on) == set(off)
    for did, h in sorted(host.items()):
        for name, o in (("depfuse", on[did]), ("staged", off[did])):
            assert o.kind == h.kind, f"{did} {name}: {o.kind} != {h.kind}"
            assert o.document.content == h.document.content, f"{did} {name}"
            assert (
                o.document.metadata.get("drop_reason")
                == h.document.metadata.get("drop_reason")
            ), f"{did} {name}"


# --- dispatch-count regression gate ------------------------------------------


_GATE_YAML = """
pipeline:
  - type: GopherRepetitionFilter
    dup_line_frac: 0.3
    top_n_grams: [[2, 0.25], [3, 0.28]]
    dup_n_grams: [[5, 0.15], [6, 0.16]]
  - type: GopherQualityFilter
    min_doc_words: 4
    min_stop_words: 1
    stop_words: [ "og", "the", "er", "i" ]
  - type: C4QualityFilter
    split_paragraph: false
    remove_citations: true
    filter_no_terminal_punct: true
    min_num_sentences: 1
    min_words_per_line: 2
    max_word_length: 1000
    filter_lorem_ipsum: true
    filter_javascript: true
    filter_curly_bracket: true
    filter_policy: true
"""

# Pinned per-(bucket, phase) dispatch counts for _GATE_YAML with the
# kernels on.  A regression that splits a chain back into staged dispatches
# (or silently drops a path out of chain_scan_ok) moves these numbers —
# update them only with a parity-verified kernel change.  The sorts and the
# phase-1 lax scans include the sort-built tables: the rank sorts of the
# line, paragraph and word tables, the window un-sort, C4's two compactions
# and its per-line segmented/latch scans.
_GATE_EXPECT_ON = {
    0: {"fused": 5, "pallas_sort": 7},
    1: {"fused": 4, "lax_scan": 8, "pallas_scan": 1, "pallas_sort": 3},
}


@pytest.mark.pallas
def test_dispatch_count_regression_gate(interp, monkeypatch):
    from textblaster_tpu.config.pipeline import parse_pipeline_config
    from textblaster_tpu.ops.pipeline import CompiledPipeline

    config = parse_pipeline_config(_GATE_YAML)
    pipeline = CompiledPipeline(config, buckets=(256, 512), batch_size=16)
    assert len(pipeline.phases) == len(_GATE_EXPECT_ON)
    for length in (256, 512):
        tot_on = tot_off = 0
        for phase in range(len(pipeline.phases)):
            on_c = pipeline.scan_dispatch_counts(length, phase)
            assert on_c == _GATE_EXPECT_ON[phase], (
                f"bucket {length} phase {phase}: {on_c}"
            )
            tot_on += sum(on_c.values())
            with monkeypatch.context() as m:
                m.setenv("TEXTBLAST_PALLAS", "off")
                off_c = pipeline.scan_dispatch_counts(length, phase)
            tot_off += sum(off_c.values())
        assert tot_on < tot_off, (length, tot_on, tot_off)


# --- batched verdict exchange ------------------------------------------------


def _mk_guard(max_retries=2):
    from textblaster_tpu.config.pipeline import ResilienceConfig
    from textblaster_tpu.resilience import NegotiatedGuard

    rc = ResilienceConfig(
        max_retries=max_retries,
        backoff_base_s=0.01,
        backoff_max_s=1.0,
        backoff_multiplier=2.0,
        breaker_threshold=3,
    )
    return NegotiatedGuard(rc, buckets=(512,), sleep=lambda s: None)


def test_negotiate_batch_depth1_wire_identity(monkeypatch):
    """A 1-element batch posts the exact vector the per-round exchange
    posted — depth-1 wire traffic is unchanged by the batching seam."""
    from textblaster_tpu.parallel import multihost as mh

    posted = []

    def fake_allgather(vec):
        posted.append(np.asarray(vec, dtype=np.int64).ravel().copy())
        return posted[-1].reshape(1, -1)

    monkeypatch.setattr(mh, "host_allgather", fake_allgather)
    guard = _mk_guard()
    assert guard._negotiate(False) is False
    assert guard.negotiate_batch([False]) == [False]
    assert guard._negotiate(True) is True
    np.testing.assert_array_equal(posted[0], posted[1])
    assert posted[2].tolist() == [1]


def test_negotiate_batch_verdict_vector(monkeypatch):
    """Per-round joint verdicts: any host's flag trips that round only."""
    from textblaster_tpu.parallel import multihost as mh

    rows = np.array([[0, 1, 0], [0, 0, 1]], dtype=np.int64)
    monkeypatch.setattr(mh, "host_allgather", lambda vec: rows)
    guard = _mk_guard()
    assert guard.negotiate_batch([False, True, False]) == [False, True, True]


def test_run_round_prior_fault_skips_first_exchange(monkeypatch):
    """With ``prior_fault`` the first joint verdict came from the batch
    post: run_round must fire the drain hook and retry WITHOUT re-posting
    that verdict, then negotiate later attempts normally."""
    from textblaster_tpu.parallel import multihost as mh

    posts = []
    monkeypatch.setattr(
        mh, "host_allgather",
        lambda vec: (posts.append(np.asarray(vec).ravel().tolist()),
                     np.zeros((1, len(np.asarray(vec).ravel())),
                              dtype=np.int64))[1],
    )
    guard = _mk_guard()
    events = []
    stats = guard.run_round(
        512,
        dispatch=lambda: events.append("dispatch") or "out",
        fetch=lambda out: {"ok": np.ones(1)},
        on_fault=lambda: events.append("drain"),
        prior_fault=True,
        prior_local_fault=True,
    )
    assert stats is not None
    # Drain before the retry dispatch; exactly ONE exchange (the retry's
    # verdict) — the pre-resolved batch verdict is never re-posted.
    assert events == ["drain", "dispatch"]
    assert posts == [[0]]


def _overlap_config_and_docs():
    from textblaster_tpu.config.pipeline import parse_pipeline_config
    from textblaster_tpu.data_model import TextDocument

    yaml_str = """
pipeline:
  - type: GopherRepetitionFilter
    dup_line_frac: 0.3
    top_n_grams: [[2, 0.25]]
    dup_n_grams: [[5, 0.15]]
  - type: GopherQualityFilter
    min_doc_words: 4
    min_stop_words: 1
    stop_words: [ "og", "the", "er", "i" ]
"""
    base = [
        "Det er en god dag i dag, og vi skal ud at gå en lang tur i skoven.",
        "The quick brown fox jumps over the lazy dog and the stone bridge.",
        "Samme linje her igen.\n" * 6,
        "kort.",
        "Endnu en dansk tekst om vejret, og den er ganske lang og fin.",
        "Vi mødes nede ved havnen i morgen, og så sejler vi ud på vandet.",
    ]
    docs = [
        TextDocument(id=f"df-{i}", source="s", content=base[i % len(base)])
        for i in range(24)
    ]
    return parse_pipeline_config(yaml_str), docs


def _run_shard(config, docs, pipeline):
    from textblaster_tpu.parallel import multihost as mh

    outs = mh.run_local_shard(
        config, [d.copy() for d in docs], buckets=(512,), pipeline=pipeline
    )
    return [
        (o.kind, o.document.id, o.document.content, o.document.metadata)
        for o in outs
    ]


def test_batched_drain_parity_and_fewer_posts():
    """Overlapped (depth 3, batched tail drain) vs serial on the real
    single-process lockstep path: ordered outcomes byte-identical, with
    strictly fewer host_allgather posts (the window's verdicts ride one
    vector), fault-free AND under an injected transient round fault."""
    from textblaster_tpu.ops.pipeline import CompiledPipeline
    from textblaster_tpu.resilience.faults import FAULTS
    from textblaster_tpu.utils.metrics import METRICS

    config, docs = _overlap_config_and_docs()
    pipeline = CompiledPipeline(config, buckets=(512,), batch_size=8)

    config.overlap.enabled = False
    serial = _run_shard(config, docs, pipeline)  # warm (compiles)
    before = METRICS.get("multihost_exchange_posts_total")
    serial = _run_shard(config, docs, pipeline)
    serial_posts = METRICS.get("multihost_exchange_posts_total") - before
    assert len(serial) == len(docs)

    config.overlap.enabled = True
    config.overlap.pipeline_depth = 3
    before = METRICS.get("multihost_exchange_posts_total")
    overlapped = _run_shard(config, docs, pipeline)
    ov_posts = METRICS.get("multihost_exchange_posts_total") - before
    assert overlapped == serial
    assert ov_posts < serial_posts, (ov_posts, serial_posts)
    assert METRICS.get("resilience_negotiated_batched_verdicts_total") > 0

    # Transient fault on the first launch: its verdict arrives via the
    # batched vector, the younger launched-ahead rounds drain and replay,
    # and the ordered stream still matches serial byte-for-byte.
    FAULTS.inject("multihost.round", OSError("injected blip"))
    try:
        faulted = _run_shard(config, docs, pipeline)
    finally:
        FAULTS.reset()
    assert faulted == serial
