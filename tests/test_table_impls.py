"""The device program's scan and table primitives against plain references.

Every backend runs one program: segmented scans on the contiguous-shift
schedule (:func:`textblaster_tpu.ops.device.shift_scan_tuple`) and
per-segment tables built by sorted compaction, never by XLA scatter.  This
suite pins each primitive to an independent reference written here — a
sequential numpy loop per scan, a numpy stable partition for ``compact``, a
Python span walk for the citation fill — at widths that cross the doubling
steps, and runs the nasty-case corpus (blank lines, trailing newlines,
all-whitespace lines, citations, empty docs, dense repetition) through
device-vs-host-oracle parity for the filters whose tables these build.
"""

import zlib

import numpy as np
import pytest

import jax.numpy as jnp

from textblaster_tpu.ops import device as D
from textblaster_tpu.ops.compact import compact
from textblaster_tpu.ops.dfa import citation_spans
from textblaster_tpu.ops.stats import _poly_hash_many

from test_device_parity import CORPUS, assert_outcomes_equal, run_both

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

_I32_MIN = int(np.iinfo(np.int32).min)

# Widths below, at and across the doubling steps (1 lane, one past 128, 512).
WIDTHS = (1, 129, 512)


@pytest.fixture(autouse=True)
def lax_path(monkeypatch):
    """The lax schedule, not the interpret-mode kernels."""
    monkeypatch.delenv("TEXTBLAST_PALLAS_INTERPRET", raising=False)


def _seq(values, flags, step, first):
    """Row-wise sequential scan: ``out[i] = step(out[i-1], v[i], f[i])``,
    ``out[0] = first(v[0], f[0])``, in int64 then wrapped to int32."""
    out = np.zeros(values.shape, np.int64)
    for r in range(values.shape[0]):
        acc = None
        for i in range(values.shape[1]):
            v, f = int(values[r, i]), bool(flags[r, i])
            acc = first(v, f) if acc is None else step(acc, v, f)
            out[r, i] = acc
    return out.astype(np.int32)


def _ref_poly_hash(values, in_seg, seg_start):
    # h = h*31 + v inside a segment, restarting at each segment start;
    # positions outside segments pass the running hash through.
    out = np.zeros(values.shape, np.int64)
    for r in range(values.shape[0]):
        h = 0
        for i in range(values.shape[1]):
            v = int(values[r, i])
            if seg_start[r, i]:
                h = v
            elif in_seg[r, i]:
                h = h * 31 + v
            out[r, i] = h
            h = int(np.int64(h).astype(np.int32))
    return out.astype(np.int32)


def _scan_case(name, vals, flags):
    v, f = jnp.asarray(vals), jnp.asarray(flags)
    if name == "seg_scan_add":
        got = D.seg_scan_add(v, f)
        want = _seq(vals, flags, lambda a, x, r: x if r else a + x, lambda x, r: x)
    elif name == "seg_scan_or":
        got = D.seg_scan_or(v, f)
        want = _seq(vals, flags, lambda a, x, r: x if r else a | x, lambda x, r: x)
    elif name == "seg_scan_max":
        got = D.seg_scan_max(v, f)
        want = _seq(vals, flags, lambda a, x, r: x if r else max(a, x), lambda x, r: x)
    elif name == "latch_scan":
        # Every caller holds its values at 0 off the set positions.
        vals = np.where(flags, vals, 0).astype(np.int32)
        got = D.latch_scan(jnp.asarray(vals), f)
        want = _seq(
            vals, flags, lambda a, x, s: x if s else a, lambda x, s: x if s else 0
        )
    elif name == "assoc_scan1_max":
        got = D.assoc_scan1(jnp.maximum, np.int32(_I32_MIN), v)
        want = _seq(vals, flags, lambda a, x, _: max(a, x), lambda x, _: x)
    else:  # _poly_hash_many: two streams sharing one segmentation
        rng = np.random.default_rng(vals.shape[1])
        in_seg = rng.random(vals.shape) < 0.8
        seg_start = flags & in_seg
        vals2 = rng.integers(0, 0x110000, vals.shape, dtype=np.int32)
        got_a, got_b = _poly_hash_many(
            (v, jnp.asarray(vals2)), jnp.asarray(in_seg), jnp.asarray(seg_start)
        )
        np.testing.assert_array_equal(
            np.asarray(got_b), _ref_poly_hash(vals2, in_seg, seg_start)
        )
        got, want = got_a, _ref_poly_hash(vals, in_seg, seg_start)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize(
    "name",
    [
        "seg_scan_add",
        "seg_scan_or",
        "seg_scan_max",
        "latch_scan",
        "assoc_scan1_max",
        "poly_hash_many",
    ],
)
def test_shift_scan_matches_sequential(name, width):
    rng = np.random.default_rng(zlib.crc32(f"{name}/{width}".encode()))
    shape = (3, width)
    if name in ("seg_scan_add", "poly_hash_many"):
        # Full-range int32: the int32 wraparound must match exactly.
        vals = rng.integers(_I32_MIN, 2**31, shape, dtype=np.int64).astype(np.int32)
    elif name == "seg_scan_or":
        vals = rng.integers(0, 2**20, shape, dtype=np.int32)
    else:
        vals = rng.integers(-1000, 1000, shape, dtype=np.int32)
    # Dense, sparse and near-empty reset rows: long segments reach the
    # last doubling levels.
    flags = rng.random(shape) < np.array([[0.1], [0.01], [0.002]])
    _scan_case(name, vals, flags)


@pytest.mark.parametrize("width", [200, 256])
def test_compact_matches_stable_partition(width):
    rng = np.random.default_rng(width)
    cps = rng.integers(0, 0x110000, (8, width), dtype=np.int32)
    keep = rng.random((8, width)) < 0.6
    keep[0] = False  # nothing kept
    keep[1] = True  # everything kept
    got_cps, got_len = compact(jnp.asarray(cps), jnp.asarray(keep))
    want = np.zeros_like(cps)
    for r in range(cps.shape[0]):
        kept = cps[r][keep[r]]
        want[r, : len(kept)] = kept
    np.testing.assert_array_equal(np.asarray(got_cps), want)
    np.testing.assert_array_equal(np.asarray(got_len), keep.sum(axis=1))


def _ref_citations(text):
    """Python span walk for ``\\[\\d+(?:,\\s*\\d+)*\\]``, leftmost first,
    non-overlapping, with the same digit and whitespace predicates as the
    masks handed to the kernel."""
    inside = [False] * len(text)
    i = 0
    while i < len(text):
        if text[i] != "[":
            i += 1
            continue
        j = i + 1
        ok = False
        while True:
            start = j
            while j < len(text) and text[j].isdigit():
                j += 1
            if j == start:
                break
            if j < len(text) and text[j] == "]":
                ok = True
                break
            if j < len(text) and text[j] == ",":
                j += 1
                while j < len(text) and text[j].isspace():
                    j += 1
                continue
            break
        if ok:
            for k in range(i, j + 1):
                inside[k] = True
            i = j + 1
        else:
            i += 1
    return inside


def test_citation_spans_match_span_walk():
    texts = [
        "x [1] y [2, 3] z [4]",
        "[broken [5] citation] more",
        "[[1]] [1,2,  3] [1 ,2] [,1] [] [12a] [7]",
        "no citations here",
        "[1][2][3]",
        "tail [9",
        "[3,\t4]\n[5]",
        "",
    ]
    width = 64
    cps = np.zeros((len(texts), width), np.int32)
    for r, t in enumerate(texts):
        cps[r, : len(t)] = [ord(c) for c in t]
    chars = [[chr(c) if c else "\0" for c in row] for row in cps]
    digit = np.array([[c.isdigit() for c in row] for row in chars])
    ws = np.array([[c.isspace() for c in row] for row in chars])
    got = np.asarray(citation_spans(jnp.asarray(cps), jnp.asarray(digit), jnp.asarray(ws)))
    for r, t in enumerate(texts):
        want = _ref_citations(t) + [False] * (width - len(t))
        assert got[r].tolist() == want, t


_STEP_YAML = {
    "gopher_repetition": """
  - type: GopherRepetitionFilter
    dup_line_frac: 0.2
    dup_para_frac: 0.2
    dup_line_char_frac: 0.15
    dup_para_char_frac: 0.15
    top_n_grams: [[2, 0.1], [3, 0.1]]
    dup_n_grams: [[4, 0.1], [5, 0.1]]
""",
    "fineweb_gopher_quality": """
  - type: FineWebQualityFilter
    line_punct_thr: 0.12
    line_punct_exclude_zero: false
    short_line_thr: 0.67
    short_line_length: 30
    char_duplicates_ratio: 0.1
    new_line_ratio: 0.3
  - type: GopherQualityFilter
    min_doc_words: 2
    max_doc_words: 1000
    min_avg_word_length: 1.0
    max_avg_word_length: 12.0
    max_symbol_word_ratio: 0.5
    max_bullet_lines_ratio: 0.9
    max_ellipsis_lines_ratio: 0.5
    max_non_alpha_words_ratio: 0.9
    min_stop_words: 0
    stop_words: [ "og", "er", "det", "the" ]
""",
    "c4_paragraph": """
  - type: C4QualityFilter
    split_paragraph: true
    remove_citations: true
    filter_no_terminal_punct: true
    min_num_sentences: 1
    min_words_per_line: 2
    max_word_length: 20
    filter_lorem_ipsum: true
    filter_javascript: true
    filter_curly_bracket: true
    filter_policy: true
""",
    "c4_sentence": """
  - type: C4QualityFilter
    split_paragraph: false
    remove_citations: true
    filter_no_terminal_punct: true
    min_num_sentences: 1
    min_words_per_line: 2
    max_word_length: 20
    filter_lorem_ipsum: true
    filter_javascript: true
    filter_curly_bracket: true
    filter_policy: true
""",
}


@pytest.mark.parametrize("step", sorted(_STEP_YAML))
def test_nasty_corpus_device_matches_oracle(step):
    host_by_id, dev_by_id = run_both("pipeline:" + _STEP_YAML[step], CORPUS + EXTRA)
    assert_outcomes_equal(host_by_id, dev_by_id)
