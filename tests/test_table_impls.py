"""Scatter vs sort table-construction parity.

The device kernels build their per-line/per-segment/per-word tables two ways
(:func:`textblaster_tpu.ops.device.use_sort_tables`): XLA scatters (the CPU
default) and a sorted compaction + gathers (the TPU default — XLA:TPU
serializes scatters into per-element loops).  The TPU
path cannot run on TPU in CI, but its *semantics* are backend-independent:
this suite pins both implementations to identical outputs on the nasty-case
corpus (blank lines, trailing newlines, all-whitespace lines, citations,
empty docs, dense repetition), so a silicon window only has to validate
performance, not correctness.
"""

import os

import numpy as np
import pytest

import jax

from textblaster_tpu.data_model import TextDocument
from textblaster_tpu.ops import compact as C
from textblaster_tpu.ops import langid_tpu as LT
from textblaster_tpu.ops import stats as S
from textblaster_tpu.ops.packing import pack_documents

from test_device_parity import CORPUS

EXTRA = [
    "a\n\n\nb\nc\n",
    "line one.\nline two.\n\n\nline one.\n",
    "   \nword here.\n   trailing   \n.",
    "x [1] y [2, 3] z [4]\nplain line here.",
    "[broken [5] citation] more",
    "a.\n\nb!\n\nc?",
    "\n\nonly blanks\n\n",
    "ends with newline\n",
    "solo",
    "." * 40,
    ("tok " * 120) + "\n" + ("tok " * 120),
    "æøå πολύ 北京 😀 mixed\nscripts here.",
]

ML, MW = 128, 256

C4P = S.C4Params(
    split_paragraph=True,
    remove_citations=True,
    filter_no_terminal_punct=True,
    min_num_sentences=3,
    min_words_per_line=2,
    max_word_length=20,
    filter_lorem_ipsum=True,
    filter_javascript=True,
    filter_curly_bracket=True,
    filter_policy=True,
)


def _batch():
    docs = [
        TextDocument(id=str(i), content=c, source="s")
        for i, c in enumerate(CORPUS + EXTRA)
        if len(c) <= 500
    ]
    docs += [
        TextDocument(id=f"p{i}", content="pad doc.", source="s")
        for i in range((-len(docs)) % 8)
    ]
    return pack_documents(docs, len(docs), 512)


def _k_rep(cps, lengths):
    st = S.structure(cps, lengths)
    return dict(S.gopher_rep_stats(st, (2, 3, 4), (5, 6, 10), ML, MW))


def _k_fw(cps, lengths):
    st = S.structure(cps, lengths)
    out = dict(S.fineweb_stats(st, ('"', "'", ".", "!", "?", "”"), ML, 30))
    out.update(
        S.gopher_quality_stats(
            st, tuple(S.hash_string(w) for w in ("og", "er", "det", "the"))
        )
    )
    return out


def _k_c4(cps, lengths):
    c4s, c4c, c4l = S.c4_stage(cps, lengths, C4P, ML)
    out = dict(c4s)
    out["cps"], out["len"] = c4c, c4l
    sp, sc, sl = S.c4_stage(
        cps, lengths, C4P._replace(split_paragraph=False), ML
    )
    out.update({f"sent:{k}": v for k, v in sp.items()})
    out["sent:cps"], out["sent:len"] = sc, sl
    return out


def _k_misc(cps, lengths):
    import jax.numpy as jnp

    keep = (cps % 3 != 0) & (jnp.arange(cps.shape[1])[None, :] < lengths[:, None])
    cc, clen = C.compact(cps, keep)
    sc, ng = LT.langid_scores(cps, lengths)
    return {"c_cps": cc, "c_len": clen, "scores": sc, "n": ng}


def _run(kernel, impl, cps, lengths, monkeypatch):
    monkeypatch.setenv("TEXTBLAST_TABLE_IMPL", impl)

    # A FRESH function object per run: jax.jit caches compiled executables
    # keyed on the underlying function, so re-wrapping the same module-level
    # kernel after an env flip would silently return the previous impl's
    # cached result and make the comparison vacuous (caught by review).
    def fresh(c, l):
        return kernel(c, l)

    return jax.device_get(jax.jit(fresh)(cps, lengths))


@pytest.mark.parametrize("kernel", [_k_rep, _k_fw, _k_c4, _k_misc])
def test_sort_tables_match_scatter(kernel, monkeypatch):
    batch = _batch()
    ref = _run(kernel, "scatter", batch.cps, batch.lengths, monkeypatch)
    got = _run(kernel, "sort", batch.cps, batch.lengths, monkeypatch)
    assert set(ref) == set(got)
    for k in ref:
        np.testing.assert_array_equal(
            np.asarray(ref[k]), np.asarray(got[k]), err_msg=k
        )


@pytest.mark.parametrize("kernel", [_k_rep, _k_fw, _k_c4, _k_misc])
def test_chunk_scan_matches_default(kernel, monkeypatch):
    """The blocked `chunk` scan schedule (TEXTBLAST_SCAN_IMPL=chunk) must be
    bit-identical to the default schedule across every kernel — any scan
    schedule computes the same values for associative monoids, and this pins
    the implementation to that promise (incl. padding of non-multiple
    lengths and segmented resets)."""
    batch = _batch()

    def fresh_ref(c, l):  # fresh fn objects per impl — see _run
        return kernel(c, l)

    def fresh_chunk(c, l):
        return kernel(c, l)

    monkeypatch.delenv("TEXTBLAST_SCAN_IMPL", raising=False)
    ref = jax.device_get(jax.jit(fresh_ref)(batch.cps, batch.lengths))
    monkeypatch.setenv("TEXTBLAST_SCAN_IMPL", "chunk")
    # Odd chunk size forces in-chunk padding; 48 < 512/2 engages the path.
    monkeypatch.setenv("TEXTBLAST_SCAN_CHUNK", "48")
    got = jax.device_get(jax.jit(fresh_chunk)(batch.cps, batch.lengths))
    assert set(ref) == set(got)
    for k in ref:
        np.testing.assert_array_equal(
            np.asarray(ref[k]), np.asarray(got[k]), err_msg=k
        )


def test_chunk_scan_tuple_direct():
    """Direct unit pin of chunk_scan_tuple against the shift schedule:
    random segmented add/max/latch streams (scalar identities) and a
    function-composition scan with an iota array identity + trailing dims —
    odd lengths force the padding path."""
    import jax.numpy as jnp

    from textblaster_tpu.ops.device import (
        _latch_op,
        _seg_add_op,
        _seg_max_op,
        chunk_scan_tuple,
        shift_scan_tuple,
    )

    rng = np.random.default_rng(3)
    for length in (7, 48, 96, 131, 513):
        vals = jnp.asarray(rng.integers(0, 100, (4, length), dtype=np.int32))
        reset = jnp.asarray(rng.random((4, length)) < 0.15)
        for op, ident in ((_seg_add_op, 0), (_seg_max_op, -(2**31)), (_latch_op, 0)):
            want = shift_scan_tuple(op, (ident, False), (vals, reset))
            got = chunk_scan_tuple(op, (ident, False), (vals, reset), chunk_size=16)
            for w, g in zip(want, got):
                np.testing.assert_array_equal(np.asarray(w), np.asarray(g))

    # Function composition with trailing state dim: f_i : [N] -> [N] maps,
    # composed left-to-right (the dfa_states >8-states shape).
    n_states = 5
    fns = jnp.asarray(rng.integers(0, n_states, (3, 67, n_states), dtype=np.int32))
    iota = jnp.arange(n_states, dtype=jnp.int32)

    def compose(a, b):
        # take_along_axis needs equal ranks; chunk broadcasts operands first.
        a0, b0 = jnp.broadcast_arrays(a[0], b[0])
        return (jnp.take_along_axis(b0, a0, axis=-1),)

    want = shift_scan_tuple(compose, (iota,), (fns,))[0]
    got = chunk_scan_tuple(compose, (iota,), (fns,), chunk_size=8)[0]
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
