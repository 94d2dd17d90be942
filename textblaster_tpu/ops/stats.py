"""Per-filter statistic kernels over packed codepoint batches.

Each kernel maps ``(cps [B, L], lengths [B])`` to per-document **integer**
statistics.  Ratios, thresholds, and reason strings are computed host-side in
float64 from these integers — identical to the oracle's arithmetic — so
device/host parity cannot be broken by accumulation order (SURVEY.md §7
stage 2: "segmented reductions ... then scalar threshold logic" — the scalar
logic stays on the host).

Structure recovery is scan-based: word/line/paragraph segmentation via
segmented associative scans (:mod:`.device`), citation matching and sentence
boundaries via DFA composition (:mod:`.dfa`), duplicate detection via in-row
sorts of (hash, length) keys.  Per-segment tables are built by sorted
compaction of segment-end positions, never by XLA scatter (see "Per-segment
tables" below).

Known device/oracle divergences (each measured by the parity suite,
tests/test_device_parity.py):
* duplicate detection compares 32-bit content hashes, not strings —
  cross-content collisions are ~2^-32 per pair.

(``find_all_duplicate``'s visited-set dynamics — the oracle's ``seen`` only
holds windows the greedy scan actually *visited*, so a window whose only
earlier twins were skipped over is NOT a duplicate — is reproduced exactly
by ``_find_all_dup_bytes_batched``'s lockstep walk; an earlier static
"any earlier twin" approximation diverged on dense repetitions and was
caught by tests/test_fuzz_parity.py.)
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.text import _CLOSE, _PARA_SEP, _SP, _STERM
from .compact import compact
from .device import (
    ALNUM,
    ALPHA,
    DIGIT,
    EXTEND,
    LOWER,
    PUNCT,
    WS,
    classify,
    isin_sorted,
    latch_scan,
    lower_table,
    rev,
    seg_scan_add,
    seg_scan_max,
    seg_scan_or,
    shift_scan_tuple,
    utf8_width,
    word_mask,
)
from .dfa import citation_spans, dfa_states
from .pallas_sort import sort2

__all__ = [
    "TextStructure",
    "structure",
    "gopher_quality_stats",
    "fineweb_stats",
    "gopher_rep_stats",
    "c4_stage",
    "C4Params",
    "sentence_counts",
    "hash_string",
]

NL = ord("\n")
CR = ord("\r")


def _shift_r(x: jax.Array, fill=0) -> jax.Array:
    """x[i-1] along axis 1 (``fill`` at position 0)."""
    return jnp.concatenate([jnp.full_like(x[:, :1], fill), x[:, :-1]], axis=1)


def _shift_l(x: jax.Array, fill=0) -> jax.Array:
    """x[i+1] along axis 1 (``fill`` at last position)."""
    return jnp.concatenate([x[:, 1:], jnp.full_like(x[:, :1], fill)], axis=1)


def _bool_select(c: jax.Array, a: jax.Array, b: jax.Array) -> jax.Array:
    """``jnp.where(c, a, b)`` over bools, spelled as i1 logic so it lowers
    inside Mosaic kernels (chain preps)."""
    return (c & a) | (~c & b)


def _first_col(mask: jax.Array) -> jax.Array:
    out = jnp.zeros_like(mask, dtype=bool)
    return out.at[:, 0].set(True)


def hash_string(s: str) -> int:
    """Host twin of the device polynomial hash (int32 wraparound, mul 31)."""
    h = 0
    for ch in s:
        h = (h * 31 + ord(ch)) & 0xFFFFFFFF
    return h - (1 << 32) if h >= (1 << 31) else h


def _poly_hash_many(
    values: Tuple[jax.Array, ...],
    in_seg: jax.Array,
    seg_start: jax.Array,
    mul: int = 31,
) -> Tuple[jax.Array, ...]:
    """Segmented polynomial hashes h = h*mul + v via ONE affine scan shared
    by all ``values`` streams (they share the multiplier pattern, so fusing
    them shares the carry-multiply work and the scan's memory passes).

    Positions outside segments are pass-through; ``seg_start`` restarts.
    The value at each position is the hash of its segment's prefix.
    """
    m = jnp.where(seg_start, 0, jnp.where(in_seg, mul, 1)).astype(jnp.int32)
    accs = tuple(jnp.where(in_seg, v, 0).astype(jnp.int32) for v in values)

    def compose(x, y):
        mx, axs = x[0], x[1:]
        my, ays = y[0], y[1:]
        return (mx * my,) + tuple(ay + my * ax for ax, ay in zip(axs, ays))

    from .pallas_scan import affine_hash_scan, pallas_scan_ok

    if pallas_scan_ok(*m.shape):
        # Blocked VMEM kernel — same int32 affine composition, bit-identical
        # to the lax schedule below (parity fuzzed in tests).
        return affine_hash_scan(m, accs)

    # Affine identity is (m=1, a=0, ...).
    identities = (1,) + tuple(0 for _ in accs)
    return shift_scan_tuple(compose, identities, (m,) + accs, axis=1)[1:]


def _poly_hash(
    cps: jax.Array, in_seg: jax.Array, seg_start: jax.Array, mul: int = 31
) -> jax.Array:
    return _poly_hash_many((cps,), in_seg, seg_start, mul=mul)[0]


# --- fused megakernel group builders -----------------------------------------
# Twins of the staged scans above, expressed as pallas_scan.fused_scan groups
# so several independent scans lower into ONE kernel pass over the row tile.
# Each builder re-states the staged path's recurrence exactly:
#
# * segmented sum (device.seg_scan_add, monoid _seg_add_op) is the affine
#   recurrence h = m*h_prev + v with m = 0 at segment resets, 1 elsewhere;
# * the segmented polynomial hash is the same recurrence with m = mul inside
#   segments (identical to _poly_hash_many's operand construction above).
#
# Both are int32 recurrences whose every schedule (lax shift, per-scan
# kernel, fused kernel) computes the same function exactly, so the
# fused path is bit-identical by integer associativity.  Callers gate on
# pallas_scan.fused_scan_ok first.


def _seg_add_group(values: Tuple[jax.Array, ...], reset: jax.Array) -> dict:
    """Fused-group twin of ``seg_scan_add`` over shared ``reset`` streams."""
    from .pallas_scan import affine_group

    m = jnp.where(reset, 0, 1).astype(jnp.int32)
    return affine_group(m, tuple(v.astype(jnp.int32) for v in values))


def _poly_hash_group(
    values: Tuple[jax.Array, ...],
    in_seg: jax.Array,
    seg_start: jax.Array,
    mul: int = 31,
) -> dict:
    """Fused-group twin of ``_poly_hash_many`` (same m/acc construction)."""
    from .pallas_scan import affine_group

    m = jnp.where(seg_start, 0, jnp.where(in_seg, mul, 1)).astype(jnp.int32)
    accs = tuple(jnp.where(in_seg, v, 0).astype(jnp.int32) for v in values)
    return affine_group(m, accs)


def _sum_group(values: Tuple[jax.Array, ...]) -> dict:
    """Fused-group twin of ``jnp.sum(v, axis=1)`` per stream: an add scan
    emitting only the final carry, so the totals never widen to [B, L]."""
    from .pallas_scan import add_group

    return add_group(tuple(v.astype(jnp.int32) for v in values), emit="last")


def _pattern_hash_group(src: jax.Array, mask: jax.Array) -> dict:
    """Chain-group twin of ``_pattern_union_starts``' candidate prefix hash
    (same m/acc construction as its ``_poly_hash`` call), so the candidate
    pass can ride another kernel's dispatch via the ``h_inc`` parameter."""
    first = jnp.zeros_like(mask).at[:, 0].set(True)
    return {
        "kind": "affine",
        "xs": (
            jnp.where(first, 0, 31).astype(jnp.int32),
            jnp.where(mask, src, 0).astype(jnp.int32),
        ),
    }


# --- Per-segment tables ------------------------------------------------------
# XLA:TPU lowers scatters to serialized per-element loops (an early on-chip
# profile measured ~13 s per batch), so no table here is scattered.  Each is
# built by ONE sorted compaction of the active positions (the VMEM bitonic
# network on TPU, ``lax.sort`` elsewhere) plus a small ``take_along_axis``
# gather per value stream.  This requires the active positions' slot keys to
# enumerate 0..n-1 in row order (gapless) — every call site satisfies it by
# construction and says how.


def _stack_rows(xs, mesh=None):
    """Same-shaped ``[B, ...]`` arrays as the rows of one ``[k*B, ...]``
    array; rows are independent in every consumer.  One device takes them
    one array after another.  Under a ``mesh`` they interleave, row ``r``
    of ``xs[i]`` at ``r*k + i``, so each chip's shard of the stack holds
    exactly its own rows: a concatenation along the sharded batch axis
    moves rows between chips (all-to-alls going in, collective-permutes
    coming out of every stacked sort)."""
    if mesh is None:
        return xs[0] if len(xs) == 1 else jnp.concatenate(xs, axis=0)
    return jnp.stack(xs, axis=1).reshape((-1,) + xs[0].shape[1:])


def _unstack_rows(x, k, mesh=None):
    """The ``k`` arrays :func:`_stack_rows` stacked into ``x``."""
    if mesh is None:
        b = x.shape[0] // k
        return [x[i * b : (i + 1) * b] for i in range(k)]
    x = x.reshape((-1, k) + x.shape[1:])
    return [x[:, i] for i in range(k)]


def _rank_positions_many(actives, m, mesh=None):
    """For each ``[B, L]`` bool mask in ``actives``: positions of its 1st,
    2nd, ... active element per row, as ``(pos [B, m] int32, real [B, m]
    bool)``.  All masks share one stacked device sort (rows are independent,
    exactly like :func:`_sort_runs_many`)."""
    b, length = actives[0].shape
    pos = jnp.broadcast_to(jnp.arange(length, dtype=jnp.int32)[None, :], (b, length))
    keys = [jnp.where(a, pos, _I32_MAX) for a in actives]
    key = _stack_rows(keys, mesh)
    # Pad the row length to a power of two for the Pallas network; padding
    # carries the invalid key and a safe gather index.
    padded = 1 << (length - 1).bit_length()
    if padded != length:
        key = jnp.pad(key, ((0, 0), (0, padded - length)), constant_values=_I32_MAX)
    s_key, s_pos = sort2(key, jnp.where(key == _I32_MAX, 0, key), mesh=mesh)
    if m > s_key.shape[1]:  # more slots than row positions: right-pad invalid
        extra = m - s_key.shape[1]
        s_key = jnp.pad(s_key, ((0, 0), (0, extra)), constant_values=_I32_MAX)
        s_pos = jnp.pad(s_pos, ((0, 0), (0, extra)))
    return [
        (blk_pos[:, :m], blk_key[:, :m] != _I32_MAX)
        for blk_key, blk_pos in zip(
            _unstack_rows(s_key, len(actives), mesh),
            _unstack_rows(s_pos, len(actives), mesh),
        )
    ]


def _gather_table(values, pos, real, fill=0):
    v = jnp.take_along_axis(values, pos, axis=1)
    return jnp.where(real, v, jnp.asarray(fill, dtype=values.dtype))


class TextStructure(NamedTuple):
    """Shared word-unit structure extracted once per packed batch."""

    cps: jax.Array  # [B, L] int32
    lengths: jax.Array  # [B]
    cls: jax.Array  # [B, L] uint8 class bits
    mask: jax.Array  # [B, L] bool — char belongs to the doc
    unit_end: jax.Array  # [B, L] bool — last char of each unit
    unit_valid: jax.Array  # [B, L] bool at unit_end — unit counts as a word
    unit_len: jax.Array  # [B, L] int32 at unit_end — chars in unit
    unit_bytes: jax.Array  # [B, L] int32 at unit_end — UTF-8 bytes of unit
    unit_hash: jax.Array  # [B, L] int32 at unit_end — content hash
    unit_lhash: jax.Array  # [B, L] int32 at unit_end — lowercased hash
    unit_alpha: jax.Array  # [B, L] bool at unit_end — has alphabetic char
    n_words: jax.Array  # [B] int32 — valid unit count
    word_idx: jax.Array  # [B, L] int32 at valid unit_end — word ordinal


def structure(
    cps: jax.Array, lengths: jax.Array, with_hashes: bool = True
) -> TextStructure:
    """``with_hashes=False`` skips the two polynomial-hash scans (the unit
    hash fields come back ``None``) — only GopherQuality (stop-word lhash)
    and GopherRepetition (dup-table hash/bytes) consume them, and the hash
    scans are a large share of this kernel's memory passes."""
    _, length = cps.shape
    mask = jnp.arange(length, dtype=jnp.int32)[None, :] < lengths[:, None]
    cls = classify(cps)
    cls = jnp.where(mask, cls, 0).astype(cls.dtype)

    from .pallas_scan import (
        Tap,
        chain_group,
        chain_pass,
        chain_scan,
        chain_scan_ok,
        fused_scan,
        fused_scan_ok,
    )

    if with_hashes:
        lt = lower_table()
        low = lt[jnp.minimum(cps, lt.shape[0] - 1)]

    ws = (cls & WS) != 0
    punct = (cls & PUNCT) != 0
    ext = ((cls & EXTEND) != 0) & mask

    if chain_scan_ok(*cps.shape) and length <= 8192:
        # Dependency-fused path: the whole unit-segmentation chain — the WB4
        # word hold scan, the symbol hold scan it feeds, the per-unit
        # aggregate/hash scans those masks gate, the unit_end/valid_end
        # derivation (a reverse pass: "next" lane values are walk-previous
        # taps), and the word-cumsum -> n_words consumers — runs as ONE
        # multi-pass kernel dispatch.  Every recurrence below restates the
        # staged branch's op exactly (segmented OR of {0,1} streams is a
        # segmented SUM compared > 0), so the streams are bit-identical.
        from .device import word_base

        word_raw, _ = word_base(cps, cls)
        ext_i = ext.astype(jnp.int32)
        wm = (word_raw & mask).astype(jnp.int32)
        base_raw = (~ws & ~punct & mask & (cps != 0x200B) & ~ext).astype(jnp.int32)
        sh_ext = _shift_r(ext_i)
        sh_wm = _shift_r(wm)
        widths_raw = utf8_width(cps)
        np_raw = (~punct).astype(jnp.int32)
        alpha_raw = ((cls & ALPHA) != 0).astype(jnp.int32)

        def _derive(held, hs, shh, e, w, br, she, shw):
            # in_word / in_unit / unit_start from the held scans (staged
            # twin formulas; XLA CSE dedups across the preps sharing them).
            # Bool selects as i1 logic: Mosaic cannot lower select_n with
            # bool operands (no i8 -> i1 truncation), so no jnp.where here.
            iw = _bool_select(e != 0, held > 0, w != 0)
            bs = ~iw & (br != 0)
            sym = bs | ((e != 0) & ~iw & (hs > 0))
            iu = iw | sym
            piw = _bool_select(she != 0, shh > 0, shw != 0)
            us = (iw & ~piw) | bs
            return iu, us

        core = (
            Tap(0, 0),  # held (WB4 word hold)
            Tap(1, 0),  # held_sym
            Tap(0, 0, shift=1, fill=0),  # held at the previous lane
            ext_i,
            wm,
            base_raw,
            sh_ext,
            sh_wm,
        )

        def prep_sym(held, e, w, br):
            iw = _bool_select(e != 0, held > 0, w != 0)
            return e, (~iw & (br != 0)).astype(jnp.int32)

        def prep_agg(held, hs, shh, e, w, br, she, shw, wd, np_, al):
            iu, us = _derive(held, hs, shh, e, w, br, she, shw)
            m = jnp.where(us, 0, 1)
            acc1 = iu.astype(jnp.int32) * jnp.int32(1 << 17) + jnp.where(iu, wd, 0)
            acc2 = jnp.where(iu, np_, 0) * jnp.int32(1 << 16) + jnp.where(iu, al, 0)
            return m, acc1, acc2

        def prep_hash(held, hs, shh, e, w, br, she, shw, c, lo):
            iu, us = _derive(held, hs, shh, e, w, br, she, shw)
            m = jnp.where(us, 0, jnp.where(iu, 31, 1))
            return m, jnp.where(iu, c, 0), jnp.where(iu, lo, 0)

        def prep_copy(held, hs, shh, e, w, br, she, shw):
            iu, us = _derive(held, hs, shh, e, w, br, she, shw)
            return iu.astype(jnp.int32), us.astype(jnp.int32)

        p2_groups = [
            chain_group("affine", core + (widths_raw, np_raw, alpha_raw),
                        prep=prep_agg, n_ops=3),
        ]
        if with_hashes:
            p2_groups.append(
                chain_group("affine", core + (cps, low), prep=prep_hash, n_ops=3)
            )
        p2_groups.append(chain_group("copy", core, prep=prep_copy, n_ops=2))
        s_iu = 4 if with_hashes else 2  # flat stream index of in_unit in pass 2
        s_us = s_iu + 1

        def prep_vend(iu, iu_next, us_next, pb):
            ue = (iu != 0) & ((iu_next == 0) | (us_next != 0))
            return (jnp.where(ue & ((pb >> 16) > 0), 1, 0),)

        res = chain_scan(
            [
                chain_pass(
                    [{"kind": "affine", "xs": (ext_i, wm), "emit": "none"}]
                ),
                chain_pass(
                    [chain_group("affine", (Tap(0, 0), ext_i, wm, base_raw),
                                 prep=prep_sym, n_ops=2, emit="none")]
                ),
                chain_pass(p2_groups),
                chain_pass(
                    [chain_group(
                        "copy",
                        (Tap(2, s_iu), Tap(2, s_iu, shift=1, fill=0),
                         Tap(2, s_us, shift=1, fill=0), Tap(2, 1)),
                        prep=prep_vend, n_ops=1, emit="none",
                    )],
                    reverse=True,
                ),
                chain_pass(
                    [chain_group("add", (Tap(3, 0),), emit="scan")]
                ),
            ]
        )
        packed_a, packed_b = res[2][0]
        unit_len = packed_a >> 17
        unit_bytes = packed_a & jnp.int32((1 << 17) - 1)
        unit_valid = (packed_b >> 16) > 0
        unit_alpha = (packed_b & jnp.int32((1 << 16) - 1)) > 0
        unit_hash, unit_lhash = res[2][1] if with_hashes else (None, None)
        iu_s, us_s = res[2][-1]
        in_unit = iu_s != 0
        unit_start = us_s != 0
        unit_end = in_unit & (~_shift_l(in_unit, False) | _shift_l(unit_start, False))
        cs = res[4][0][0]
        word_idx = cs - 1
        n_words = cs[:, -1]

        return TextStructure(
            cps=cps,
            lengths=lengths,
            cls=cls,
            mask=mask,
            unit_end=unit_end,
            unit_valid=unit_valid,
            unit_len=unit_len,
            unit_bytes=unit_bytes,
            unit_hash=unit_hash,
            unit_lhash=unit_lhash,
            unit_alpha=unit_alpha,
            n_words=n_words,
            word_idx=word_idx,
        )

    in_word = word_mask(cps, cls) & mask
    # Symbols: not word/ws/punct; ZWSP yields no token (WordBreak=Other and
    # not word-like in ICU), bare Extend chars yield no token, and an Extend
    # run after a symbol CONTINUES that symbol's unit (WB4) — mirror of
    # utils.text.word_spans.
    base_symbol = ~in_word & ~ws & ~punct & mask & (cps != 0x200B) & ~ext
    held_sym = seg_scan_or(base_symbol.astype(jnp.int32), ~ext) > 0
    symbol = base_symbol | (ext & ~in_word & held_sym)

    in_unit = in_word | symbol
    prev_in_word = _shift_r(in_word, False)
    unit_start = (in_word & ~prev_in_word) | base_symbol
    next_start = _shift_l(unit_start, False)
    next_in_unit = _shift_l(in_unit, False)
    unit_end = in_unit & (~next_in_unit | next_start)

    ones = jnp.where(in_unit, 1, 0).astype(jnp.int32)
    widths = jnp.where(in_unit, utf8_width(cps), 0)
    nonpunct = jnp.where(in_unit, (~punct).astype(jnp.int32), 0)
    alpha = jnp.where(in_unit, ((cls & ALPHA) != 0).astype(jnp.int32), 0)

    if fused_scan_ok(*cps.shape):
        # One kernel pass for every per-unit scan of this kernel: the packed
        # aggregates and (when requested) both polynomial hash streams share
        # the tile walk, so this replaces 2-3 scan dispatches with one and no
        # intermediate stream round-trips HBM.  Same packed-field reasoning
        # as the staged branch below; fused lengths are <= 16384, within the
        # <= 8192-style field bounds only when length <= 8192, so the longer
        # buckets take the unpacked 4-stream group (still one dispatch).
        if length <= 8192:
            groups = [
                _seg_add_group(
                    (
                        ones * jnp.int32(1 << 17) + widths,
                        nonpunct * jnp.int32(1 << 16) + alpha,
                    ),
                    unit_start,
                )
            ]
        else:
            groups = [_seg_add_group((ones, widths, nonpunct, alpha), unit_start)]
        if with_hashes:
            groups.append(_poly_hash_group((cps, low), in_unit, unit_start))
        res = fused_scan(groups)
        if length <= 8192:
            packed_a, packed_b = res[0]
            unit_len = packed_a >> 17
            unit_bytes = packed_a & jnp.int32((1 << 17) - 1)
            unit_valid = (packed_b >> 16) > 0
            unit_alpha = (packed_b & jnp.int32((1 << 16) - 1)) > 0
        else:
            # Counts of {0,1} streams: "> 0" on a segmented SUM equals the
            # staged branch's segmented OR bit-for-bit.
            u_len, u_bytes, u_np, u_al = res[0]
            unit_len, unit_bytes = u_len, u_bytes
            unit_valid = u_np > 0
            unit_alpha = u_al > 0
        unit_hash, unit_lhash = res[1] if with_hashes else (None, None)
    else:
        if length <= 8192:
            # Fuse the four per-unit aggregates into two packed add-scans:
            # within a unit, chars <= 8192 (14 bits used: counts <= 2^13) and
            # UTF-8 bytes <= 4*8192 (field below bit 17), so len<<17|bytes
            # and nonpunct<<16|alpha add without cross-field carries.
            packed_a = seg_scan_add(ones * jnp.int32(1 << 17) + widths, unit_start)
            packed_b = seg_scan_add(nonpunct * jnp.int32(1 << 16) + alpha, unit_start)
            unit_len = packed_a >> 17
            unit_bytes = packed_a & jnp.int32((1 << 17) - 1)
            unit_valid = (packed_b >> 16) > 0
            unit_alpha = (packed_b & jnp.int32((1 << 16) - 1)) > 0
        else:
            unit_len = seg_scan_add(ones, unit_start)
            unit_bytes = seg_scan_add(widths, unit_start)
            unit_valid = seg_scan_or(nonpunct, unit_start) > 0
            unit_alpha = seg_scan_or(alpha, unit_start) > 0

        if with_hashes:
            unit_hash, unit_lhash = _poly_hash_many((cps, low), in_unit, unit_start)
        else:
            unit_hash = unit_lhash = None

    valid_end = unit_end & unit_valid
    word_idx = jnp.cumsum(valid_end.astype(jnp.int32), axis=1) - 1
    n_words = jnp.sum(valid_end, axis=1).astype(jnp.int32)

    return TextStructure(
        cps=cps,
        lengths=lengths,
        cls=cls,
        mask=mask,
        unit_end=unit_end,
        unit_valid=unit_valid,
        unit_len=unit_len,
        unit_bytes=unit_bytes,
        unit_hash=unit_hash,
        unit_lhash=unit_lhash,
        unit_alpha=unit_alpha,
        n_words=n_words,
        word_idx=word_idx,
    )


def _lowered(cps: jax.Array, mask: jax.Array) -> jax.Array:
    lt = lower_table()
    return jnp.where(mask, lt[jnp.minimum(cps, lt.shape[0] - 1)], 0)


def _match_pattern(src: jax.Array, mask: jax.Array, pattern: str) -> jax.Array:
    """[B, L] bool: fixed string ``pattern`` starts at each position."""
    hit = mask
    for k, ch in enumerate(pattern):
        shifted = jnp.pad(src[:, k:], ((0, 0), (0, k)), constant_values=-1)
        mk = jnp.pad(mask[:, k:], ((0, 0), (0, k)), constant_values=False)
        hit = hit & (shifted == ord(ch)) & mk
    return hit


def _pattern_union_starts(
    src: jax.Array, mask: jax.Array, patterns: Tuple[str, ...], h_inc=None
) -> jax.Array:
    """[B, L] bool: some pattern in ``patterns`` starts at each position.

    Two-phase: rolling-hash window candidates (one affine scan + one
    gather/multiply/compare per pattern), then the exact shifted-compare
    match under a batch-global ``lax.cond`` taken only when a candidate
    exists.  Clean batches — the common case for lorem-ipsum / javascript /
    policy text — pay only the hash pass; decisions always come from the
    exact compare, so hash collisions cannot alter semantics.

    ``h_inc`` optionally supplies the inclusive prefix hash precomputed by a
    caller's chain kernel (operands per :func:`_pattern_hash_group`) so the
    candidate pass rides an existing dispatch.
    """
    vals = jnp.where(mask, src, 0)
    first = jnp.zeros_like(mask).at[:, 0].set(True)
    if h_inc is None:
        h_inc = _poly_hash(vals, jnp.ones_like(mask), first)  # inclusive prefix hash
    h_exc = _shift_r(h_inc, 0)  # hash of chars [0, i)

    def to_i32(u: int) -> np.int32:
        u &= 0xFFFFFFFF
        return np.int32(u - (1 << 32)) if u >= (1 << 31) else np.int32(u)

    cand = jnp.zeros_like(mask)
    for pat in patterns:
        n = len(pat)
        target = np.int32(hash_string(pat))
        pw = to_i32(pow(31, n, 1 << 32))
        # Window [i, i+n): hash = h_inc[i+n-1] - h_exc[i] * 31^n (int32 wrap).
        h_end = jnp.pad(h_inc[:, n - 1 :], ((0, 0), (0, n - 1)))
        cand = cand | ((h_end - h_exc * pw == target) & mask)

    def verify():
        hit = jnp.zeros_like(mask)
        for pat in patterns:
            hit = hit | _match_pattern(src, mask, pat)
        return hit

    return jax.lax.cond(jnp.any(cand), verify, lambda: jnp.zeros_like(mask))


# --- Line structure ----------------------------------------------------------


class LineInfo(NamedTuple):
    line_id: jax.Array  # [B, L] int32 — rust_lines index per char
    line_start: jax.Array  # [B, L] bool — first char of each line (or its \n)
    content: jax.Array  # [B, L] bool — not \n, not \r-before-\n
    is_nl: jax.Array  # [B, L] bool
    n_lines: jax.Array  # [B] int32 — rust_lines count
    last_content: jax.Array  # [B, L] bool — last content char of its line


def line_info(cps: jax.Array, mask: jax.Array) -> LineInfo:
    is_nl = (cps == NL) & mask
    next_is_nl = _shift_l(is_nl, False)
    stripped_cr = (cps == CR) & next_is_nl & mask
    content = mask & ~is_nl & ~stripped_cr

    line_id = jnp.cumsum(is_nl.astype(jnp.int32), axis=1) - is_nl.astype(jnp.int32)

    prev_nl = _shift_r(is_nl, False)
    line_start = mask & (prev_nl | _first_col(mask))

    # last content char of its line: next non-content or row end.
    last_content = content & ~_shift_l(content, False)

    n_newlines = jnp.sum(is_nl, axis=1).astype(jnp.int32)
    lengths = jnp.sum(mask, axis=1).astype(jnp.int32)
    pos = jnp.arange(cps.shape[1], dtype=jnp.int32)[None, :]
    last_char_nl = jnp.any((pos == lengths[:, None] - 1) & is_nl, axis=1)
    n_lines = jnp.where(
        lengths == 0, 0, n_newlines + jnp.where(last_char_nl, 0, 1)
    ).astype(jnp.int32)
    return LineInfo(line_id, line_start, content, is_nl, n_lines, last_content)


def _line_reset(li: LineInfo, mask: jax.Array) -> jax.Array:
    """Scan-reset mask starting a fresh segment at each line's first char
    (resets placed on the char after each \\n, and at column 0)."""
    return _first_col(mask) | _shift_r(li.is_nl, False)


def _first_nonws_in_line(nonws: jax.Array, li: LineInfo, mask: jax.Array) -> jax.Array:
    cnt = seg_scan_add(nonws.astype(jnp.int32), _line_reset(li, mask))
    return nonws & (cnt == 1)


def _last_nonws_in_line(nonws: jax.Array, li: LineInfo, mask: jax.Array) -> jax.Array:
    r_reset = _first_col(mask) | _shift_r(rev(li.is_nl), False)
    cnt_r = seg_scan_add(rev(nonws).astype(jnp.int32), r_reset)
    return rev(rev(nonws) & (cnt_r == 1))


# --- Duplicate counting over (hash, bytes) tables ----------------------------
# Lexicographic (validity, hash, payload) sort: the VMEM-resident Pallas
# bitonic network on TPU, lax.sort elsewhere (:mod:`.pallas_sort`).  Every key
# stays int32 (JAX x64 mode is off, and int32 sorts are faster on TPU anyway).
# Invalid slots carry a leading 1 key, sorting them past all real segments.


_I32_MAX = np.int32(2**31 - 1)


def _sort_runs_many(jobs, mesh=None):
    """Sort many same-shaped ``(hash, payload, valid)`` jobs in ONE device
    sort, returning ``(is_real, s_hash, s_payload)`` per job.

    Two structural tricks keep this cheap (it was the pipeline's dominant
    cost when emitted as one 3-key sort per n-gram size):

    * jobs stack along the batch axis — rows are independent, so k jobs of
      shape ``[B, m]`` cost one ``[kB, m]`` sort network / lax.sort call;
    * the sort uses a SINGLE int32 key: invalid slots are biased to
      ``INT32_MAX`` and valid hashes clamped to ``INT32_MAX - 1`` (one more
      2^-32-per-pair collision class on top of hashing itself, see module
      docstring), so validity needs no second key and ``is_real`` is just a
      position-vs-count compare after the sort.  Runs are keyed by hash
      alone; the payload rides as a sort value (stable off-TPU, full-pair
      bitonic on TPU — within-run payload order differs, which no consumer
      depends on for iota/byte payloads under the no-collision assumption).
    """
    b, m = jobs[0][0].shape
    keys, n_valid = [], []
    for h, _, v in jobs:
        keys.append(jnp.where(v, jnp.minimum(h, _I32_MAX - 1), _I32_MAX))
        n_valid.append(jnp.sum(v, axis=1).astype(jnp.int32))
    s_key, s_payload = sort2(
        _stack_rows(keys, mesh), _stack_rows([j[1] for j in jobs], mesh), mesh=mesh
    )
    iota = jnp.arange(m, dtype=jnp.int32)[None, :]
    return [
        (iota < nv[:, None], k, v)
        for nv, k, v in zip(
            n_valid,
            _unstack_rows(s_key, len(jobs), mesh),
            _unstack_rows(s_payload, len(jobs), mesh),
        )
    ]


def _dup_counts_sorted(sorted_triple) -> Tuple[jax.Array, jax.Array]:
    """find_duplicates semantics over hashed segments: every occurrence after
    the first counts (text.rs:197-208)."""
    is_real, s_hash, s_bytes = sorted_triple
    same_prev = (
        jnp.concatenate(
            [
                jnp.zeros_like(is_real[:, :1]),
                s_hash[:, 1:] == s_hash[:, :-1],
            ],
            axis=1,
        )
        & is_real
    )
    dup_elems = jnp.sum(same_prev, axis=1).astype(jnp.int32)
    dup_bytes = jnp.sum(jnp.where(same_prev, s_bytes, 0), axis=1).astype(jnp.int32)
    return dup_elems, dup_bytes


def _dup_counts(seg_hash, seg_bytes, seg_valid, mesh=None) -> Tuple[jax.Array, jax.Array]:
    return _dup_counts_sorted(
        _sort_runs_many([(seg_hash, seg_bytes, seg_valid)], mesh=mesh)[0]
    )


def _run_starts(s_hash: jax.Array) -> jax.Array:
    """Run-start mask over a hash-sorted table (hash change or slot 0)."""
    return jnp.concatenate(
        [
            jnp.ones_like(s_hash[:, :1], dtype=bool),
            s_hash[:, 1:] != s_hash[:, :-1],
        ],
        axis=1,
    )


def _sorted_table_streams(tagged_triples, mesh=None):
    """ONE chain dispatch for every per-run scan over the sorted tables:
    run lengths for "top" jobs, first-window-index-in-run for "dup" jobs
    (the staged scans inside _top_duplicate_sorted / _dup_run_info_sorted).

    Returns a per-job list of precomputed streams, or ``None`` when the
    table shape fails the chain gate — callers fall back to the staged
    per-scan path, which computes the identical int32 recurrences.
    """
    from .pallas_scan import chain_pass, chain_scan, chain_scan_ok

    if not tagged_triples:
        return None
    b, m = tagged_triples[0][1][1].shape
    if not chain_scan_ok(b, m):
        return None
    groups = []
    for kind, (is_real, s_hash, sidx) in tagged_triples:
        rs = _run_starts(s_hash)
        if kind == "top":
            groups.append(
                {
                    "kind": "affine",
                    "xs": (jnp.where(rs, 0, 1), jnp.ones_like(s_hash)),
                }
            )
        else:
            groups.append(
                {
                    "kind": "segmax",
                    "xs": (jnp.where(rs, sidx, -(2**30)), rs.astype(jnp.int32)),
                }
            )
    res = chain_scan([chain_pass(groups)])
    return [g[0] for g in res[0]]


def _top_duplicate_sorted(sorted_triple, run_len=None) -> jax.Array:
    """find_top_duplicate semantics: bytes*count of the most frequent item,
    ties by larger contribution, 0 when nothing repeats (text.rs:211-238)."""
    is_real, s_hash, s_bytes = sorted_triple
    run_start = _run_starts(s_hash)
    if run_len is None:
        run_len = seg_scan_add(jnp.ones_like(s_hash), run_start)
    run_end = _shift_l(run_start, True)
    counts = jnp.where(run_end & is_real, run_len, 0)
    max_count = jnp.max(counts, axis=1, keepdims=True)
    contrib = jnp.where(
        run_end & is_real & (run_len == max_count), s_bytes * run_len, 0
    )
    top = jnp.max(contrib, axis=1)
    return jnp.where(max_count[:, 0] > 1, top, 0).astype(jnp.int32)


# --- GopherQuality -----------------------------------------------------------


def gopher_quality_stats(
    st: TextStructure, stop_word_hashes: Sequence[int]
) -> Dict[str, jax.Array]:
    """Integer stats for GopherQualityFilter (gopher_quality.rs:69-295)."""
    cps, cls, mask = st.cps, st.cls, st.mask
    valid_end = st.unit_end & st.unit_valid

    n_words = st.n_words

    # Non-overlapping "..." count: dot-run lengths // 3 (str::matches parity).
    is_dot = (cps == ord(".")) & mask
    dot_start = is_dot & ~_shift_r(is_dot, False)

    li = line_info(cps, mask)
    ws = (cls & WS) != 0
    nonws = li.content & ~ws

    if stop_word_hashes:
        is_stop = isin_sorted(st.unit_lhash, stop_word_hashes)
    else:
        is_stop = None

    from .pallas_scan import (
        Tap,
        chain_group,
        chain_pass,
        chain_scan,
        chain_scan_ok,
    )

    if chain_scan_ok(*cps.shape):
        # Dependency-chain kernel: the staged path runs the three line scans,
        # then derives bullet/ellipsis line flags from their outputs on the
        # host and sums them — two more full-width [B, L] round-trips.  Here
        # a third pass consumes the counter streams in-register and emits
        # only the [B, 1] totals; the dot-run stream is the single full-width
        # output (its //3 consumer stays host-side: int32 division).
        totals = [
            ((cps == ord("#")) & mask).astype(jnp.int32),
            ((cps == 0x2026) & mask).astype(jnp.int32),
            jnp.where(valid_end, st.unit_len, 0).astype(jnp.int32),
            (valid_end & st.unit_alpha).astype(jnp.int32),
        ]
        if is_stop is not None:
            totals.append((valid_end & is_stop).astype(jnp.int32))
        r_reset = _first_col(mask) | _shift_r(rev(li.is_nl), False)
        nonws_i = nonws.astype(jnp.int32)
        is_bullet_i = ((cps == 0x2022) | (cps == ord("-"))).astype(jnp.int32)
        ell_cp_i = ((cps == 0x2026)).astype(jnp.int32)
        is_dot_i = is_dot.astype(jnp.int32)

        def _prep_line_flags(lead_cnt, cnt_r, dot_run_t, nw, bul, ell, dt):
            leader_ = (nw != 0) & (lead_cnt == 1)
            last_ = (nw != 0) & (cnt_r == 1)
            return (
                (leader_ & (bul != 0)).astype(jnp.int32),
                (last_ & ((ell != 0) | ((dt != 0) & (dot_run_t >= 3)))).astype(
                    jnp.int32
                ),
            )

        res = chain_scan(
            [
                chain_pass(
                    [
                        _seg_add_group((is_dot_i,), dot_start),
                        {
                            "kind": "affine",
                            "xs": (
                                jnp.where(_line_reset(li, mask), 0, 1),
                                nonws_i,
                            ),
                            "emit": "none",
                        },
                        _sum_group(tuple(totals)),
                    ]
                ),
                chain_pass(
                    [
                        # Reversed per-line counter: operands in natural
                        # orientation (the reverse pass walks them flipped),
                        # so rev() of the staged reversed-frame operands.
                        {
                            "kind": "affine",
                            "xs": (rev(jnp.where(r_reset, 0, 1)), nonws_i),
                            "emit": "none",
                        }
                    ],
                    reverse=True,
                ),
                chain_pass(
                    [
                        chain_group(
                            "add",
                            (
                                Tap(0, 1),
                                Tap(1, 0),
                                Tap(0, 0),
                                nonws_i,
                                is_bullet_i,
                                ell_cp_i,
                                is_dot_i,
                            ),
                            prep=_prep_line_flags,
                            n_ops=2,
                            emit="last",
                        )
                    ]
                ),
            ]
        )
        (dot_run,) = res[0][0]
        t = res[0][2]
        hash_count = t[0][:, 0]
        ellipsis_uni = t[1][:, 0]
        sum_len = t[2][:, 0]
        alpha_words = t[3][:, 0]
        stop_words = t[4][:, 0] if is_stop is not None else jnp.zeros_like(n_words)
        bullet_lines = res[2][0][0][:, 0]
        ellipsis_lines = res[2][0][1][:, 0]
    else:
        dot_run = seg_scan_add(is_dot.astype(jnp.int32), dot_start)
        leader = _first_nonws_in_line(nonws, li, mask)
        last_nonws = _last_nonws_in_line(nonws, li, mask)
        hash_count = jnp.sum((cps == ord("#")) & mask, axis=1).astype(jnp.int32)
        ellipsis_uni = jnp.sum((cps == 0x2026) & mask, axis=1).astype(jnp.int32)
        sum_len = jnp.sum(
            jnp.where(valid_end, st.unit_len, 0), axis=1
        ).astype(jnp.int32)
        alpha_words = jnp.sum(valid_end & st.unit_alpha, axis=1).astype(jnp.int32)
        stop_words = (
            jnp.sum(valid_end & is_stop, axis=1).astype(jnp.int32)
            if is_stop is not None
            else jnp.zeros_like(n_words)
        )
        # Bullet lines: first non-ws char is '•' or '-' (trim_start semantics).
        is_bullet_char = (cps == 0x2022) | (cps == ord("-"))
        bullet_lines = jnp.sum(leader & is_bullet_char, axis=1).astype(jnp.int32)
        # Ellipsis-ended lines: last non-ws char is '…' or closes a >=3 dot run.
        ell_line = last_nonws & ((cps == 0x2026) | (is_dot & (dot_run >= 3)))
        ellipsis_lines = jnp.sum(ell_line, axis=1).astype(jnp.int32)

    dot_end = is_dot & ~_shift_l(is_dot, False)
    ellipsis_ascii = jnp.sum(jnp.where(dot_end, dot_run // 3, 0), axis=1)
    ellipsis_units = (ellipsis_ascii + ellipsis_uni).astype(jnp.int32)

    return {
        "n_words": n_words,
        # All valid units contain a non-PUNCT char, so non_symbol == words.
        "n_non_symbol": n_words,
        "sum_word_len": sum_len,
        "hash_count": hash_count,
        "ellipsis_units": ellipsis_units,
        "n_lines": li.n_lines,
        "bullet_lines": bullet_lines,
        "ellipsis_lines": ellipsis_lines,
        "alpha_words": alpha_words,
        "stop_words": stop_words,
    }


# --- FineWeb -----------------------------------------------------------------


def fineweb_stats(
    st: TextStructure,
    stop_chars: Sequence[str],
    max_lines: int,
    short_line_length: int,
    mesh=None,
) -> Dict[str, jax.Array]:
    """Integer stats for FineWebQualityFilter (fineweb_quality.rs:71-225)."""
    from .pallas_scan import fused_scan, fused_scan_ok

    cps, cls, mask = st.cps, st.cls, st.mask
    li = line_info(cps, mask)
    ws = (cls & WS) != 0
    nonws = li.content & ~ws
    reset = _line_reset(li, mask)

    if fused_scan_ok(*cps.shape):
        # One kernel for this filter's four line scans, the reversed
        # last-non-ws counter, and the two whole-row totals.  has_nonws
        # becomes a segmented SUM of the {0,1} stream — every consumer tests
        # "> 0", where sum and or agree bit-for-bit.
        r_reset = _first_col(mask) | _shift_r(rev(li.is_nl), False)
        res = fused_scan(
            [
                _seg_add_group(
                    (
                        li.content.astype(jnp.int32),
                        jnp.where(li.content, utf8_width(cps), 0),
                        nonws.astype(jnp.int32),
                    ),
                    reset,
                ),
                _poly_hash_group((cps,), li.content, reset),
                _seg_add_group((rev(nonws).astype(jnp.int32),), r_reset),
                _sum_group(
                    (
                        (mask & ~li.is_nl).astype(jnp.int32),
                        li.is_nl.astype(jnp.int32),
                    )
                ),
            ]
        )
        char_cnt, byte_cnt, has_nonws = res[0]
        (line_hash,) = res[1]
        last_nonws = rev(rev(nonws) & (res[2][0] == 1))
        total_chars_no_nl = res[3][0][:, 0]
        newline_count = res[3][1][:, 0]
    else:
        # Per-line cumulative values, read at the line's last content char.
        char_cnt = seg_scan_add(li.content.astype(jnp.int32), reset)
        byte_cnt = seg_scan_add(jnp.where(li.content, utf8_width(cps), 0), reset)
        has_nonws = seg_scan_or(nonws.astype(jnp.int32), reset)
        line_hash = _poly_hash(cps, li.content, reset)
        last_nonws = _last_nonws_in_line(nonws, li, mask)
        total_chars_no_nl = jnp.sum(mask & ~li.is_nl, axis=1).astype(jnp.int32)
        newline_count = jnp.sum(li.is_nl, axis=1).astype(jnp.int32)

    # Slot j = the j-th line WITH content (a blank line holds no values,
    # and no consumer below reads slots positionally: validity masks, sums,
    # and the dup sort are all permutation/gap insensitive).
    [(tpos, treal)] = _rank_positions_many([li.last_content], max_lines, mesh)
    line_chars = _gather_table(char_cnt, tpos, treal)
    line_bytes = _gather_table(byte_cnt, tpos, treal)
    line_has_content = _gather_table(has_nonws, tpos, treal) > 0
    line_hash_t = _gather_table(line_hash, tpos, treal)
    # Byte-length mixing, as in gopher_rep's tables (collision discrimination).
    line_hash_t = line_hash_t * jnp.int32(31) + line_bytes

    n_nonblank = jnp.sum(line_has_content, axis=1).astype(jnp.int32)

    ends_stop_char = last_nonws & isin_sorted(cps, [ord(c) for c in stop_chars])
    ends_stop = jnp.sum(ends_stop_char, axis=1).astype(jnp.int32)

    dup_elems, dup_bytes = _dup_counts(line_hash_t, line_bytes, line_has_content, mesh)

    # Short-line count on device (the threshold is config-static), so the
    # [B, ML] line tables never leave the chip (fineweb_quality.rs:126-146).
    short_lines = jnp.sum(
        line_has_content & (line_chars <= short_line_length), axis=1
    ).astype(jnp.int32)

    return {
        "n_nonblank_lines": n_nonblank,
        "lines_ending_stop": ends_stop,
        "short_lines": short_lines,
        "dup_line_bytes": dup_bytes,
        "total_chars_no_newline": total_chars_no_nl,
        "n_words": st.n_words,
        "newline_count": newline_count,
        "line_overflow": li.n_lines > max_lines,
    }


# --- GopherRepetition --------------------------------------------------------


def gopher_rep_stats(
    st: TextStructure,
    top_ns: Sequence[int],
    dup_ns: Sequence[int],
    max_segs: int,
    max_words: int,
    mesh=None,
) -> Dict[str, jax.Array]:
    """Integer stats for GopherRepetitionFilter (gopher_rep.rs:52-219)."""
    cps, cls, mask = st.cps, st.cls, st.mask
    ws = (cls & WS) != 0
    _, length = cps.shape
    pos = jnp.arange(length, dtype=jnp.int32)[None, :]

    # Trim bounds (gopher_rep.rs:57).
    nonws = mask & ~ws
    any_nonws = jnp.any(nonws, axis=1)
    t0 = jnp.min(jnp.where(nonws, pos, length), axis=1)
    t1 = jnp.max(jnp.where(nonws, pos, -1), axis=1)
    in_trim = (pos >= t0[:, None]) & (pos <= t1[:, None]) & mask
    trimmed_len = jnp.where(any_nonws, t1 - t0 + 1, 0).astype(jnp.int32)

    is_nl = (cps == NL) & in_trim
    prev_nl = _shift_r(is_nl, False)
    at_t0 = pos == t0[:, None]

    # Line segments: split on \n+.
    l_content = in_trim & ~is_nl
    l_start = l_content & (prev_nl | at_t0)

    # Paragraph separators: \n chars inside runs of >= 2.
    nl_start = is_nl & ~prev_nl
    nl_run_end = is_nl & ~_shift_l(is_nl, False)
    widths = utf8_width(cps)

    from .pallas_scan import Tap, chain_group, chain_pass, chain_scan, chain_scan_ok

    if chain_scan_ok(*cps.shape):
        # Dependency-chain megakernel: the nl-run counter, the reversed
        # run-total broadcast, and the four line/paragraph segment scans (the
        # paragraph pair depends on run_total through is_sep/p_start) walk
        # the row tile in ONE dispatch instead of six.  Pass 0 counts
        # newline runs; pass 1 (reverse) broadcasts each run's total back
        # over its run; pass 2 derives the paragraph frame from run_total
        # taps in-register and runs all four segment hash/byte scans.  Every
        # operand restates the staged recurrence exactly (_seg_add_group
        # note) — bit-identical by int32 associativity.
        is_nl_i = is_nl.astype(jnp.int32)

        def _prep_run_total(nl_run_t, ne):
            return jnp.where(ne != 0, nl_run_t, 0), ne

        def _para_frame(rt, sh_rt, nl, sh_nl, it, t0f):
            sep = (nl != 0) & (rt >= 2)
            sh_sep = (sh_nl != 0) & (sh_rt >= 2)
            p_c = (it != 0) & ~sep
            p_s = p_c & (sh_sep | (t0f != 0))
            return p_c, p_s

        def _prep_p_hash(rt, sh_rt, nl, sh_nl, it, t0f, c):
            p_c, p_s = _para_frame(rt, sh_rt, nl, sh_nl, it, t0f)
            return (
                jnp.where(p_s, 0, jnp.where(p_c, 31, 1)),
                jnp.where(p_c, c, 0),
            )

        def _prep_p_bytes(rt, sh_rt, nl, sh_nl, it, t0f, w):
            p_c, p_s = _para_frame(rt, sh_rt, nl, sh_nl, it, t0f)
            return jnp.where(p_s, 0, 1), jnp.where(p_c, w, 0)

        para_deps = (
            Tap(1, 0),
            Tap(1, 0, shift=1, fill=0),
            is_nl_i,
            prev_nl.astype(jnp.int32),
            in_trim.astype(jnp.int32),
            at_t0.astype(jnp.int32),
        )
        res = chain_scan(
            [
                chain_pass(
                    [
                        {
                            "kind": "affine",
                            "xs": (jnp.where(nl_start, 0, 1), is_nl_i),
                            "emit": "none",
                        }
                    ]
                ),
                chain_pass(
                    [
                        chain_group(
                            "segmax",
                            (Tap(0, 0), nl_run_end.astype(jnp.int32)),
                            prep=_prep_run_total,
                            n_ops=2,
                        )
                    ],
                    reverse=True,
                ),
                chain_pass(
                    [
                        {
                            "kind": "affine",
                            "xs": (
                                jnp.where(l_start, 0, jnp.where(l_content, 31, 1)),
                                jnp.where(l_content, cps, 0),
                            ),
                        },
                        {
                            "kind": "affine",
                            "xs": (
                                jnp.where(l_start, 0, 1),
                                jnp.where(l_content, widths, 0),
                            ),
                        },
                        chain_group(
                            "affine", para_deps + (cps,), prep=_prep_p_hash, n_ops=2
                        ),
                        chain_group(
                            "affine", para_deps + (widths,), prep=_prep_p_bytes, n_ops=2
                        ),
                    ]
                ),
            ]
        )
        run_total = res[1][0][0]
        l_pre = (res[2][0][0], res[2][1][0])
        p_pre = (res[2][2][0], res[2][3][0])
    else:
        nl_run = seg_scan_add(is_nl.astype(jnp.int32), nl_start)
        run_total = rev(
            seg_scan_max(rev(jnp.where(nl_run_end, nl_run, 0)), rev(nl_run_end))
        )
        l_pre = p_pre = None

    is_sep = is_nl & (run_total >= 2)
    p_content = in_trim & ~is_sep
    p_start = p_content & (_shift_r(is_sep, False) | at_t0)

    def seg_values(content, start, pre=None):
        end = content & ~_shift_l(content, False)
        if pre is not None:
            h, by = pre
        else:
            h = _poly_hash(cps, content, start)
            by = seg_scan_add(jnp.where(content, widths, 0), start)
        n = jnp.sum(start, axis=1).astype(jnp.int32)
        return end, h, by, n

    def seg_finish(tbl_h, tbl_b, n):
        # Mix the byte length into the run key: equal strings keep equal
        # keys, while hash-colliding unequal strings of different lengths
        # no longer count as duplicates (ADVICE r2 discrimination note).
        tbl_h = tbl_h * jnp.int32(31) + tbl_b
        tbl_valid = jnp.arange(max_segs, dtype=jnp.int32)[None, :] < n[:, None]
        return tbl_h, tbl_b, tbl_valid, n

    l_end, l_h, l_by, n_l = seg_values(l_content, l_start, l_pre)
    p_end, p_h, p_by, n_p = seg_values(p_content, p_start, p_pre)
    # Segments are non-empty char runs, so seg ids are gapless 0..n-1 and
    # slot j is the j-th segment end.
    (lr, pr) = _rank_positions_many([l_end, p_end], max_segs, mesh)
    lh, lb, lv, n_l = seg_finish(
        _gather_table(l_h, *lr), _gather_table(l_by, *lr), n_l
    )
    ph, pb, pv, n_p = seg_finish(
        _gather_table(p_h, *pr), _gather_table(p_by, *pr), n_p
    )
    l_sorted, p_sorted = _sort_runs_many([(lh, lb, lv), (ph, pb, pv)], mesh=mesh)
    l_dup_elems, l_dup_bytes = _dup_counts_sorted(l_sorted)
    p_dup_elems, p_dup_bytes = _dup_counts_sorted(p_sorted)

    # Word tables for n-grams (valid ends enumerate words gaplessly, so the
    # sorted compaction lands word j at slot j).
    valid_end = st.unit_end & st.unit_valid
    [(wpos, wreal)] = _rank_positions_many([valid_end], max_words, mesh)
    whash = _gather_table(st.unit_hash, wpos, wreal)
    wbytes = _gather_table(st.unit_bytes, wpos, wreal)
    n_words = st.n_words
    widx = jnp.arange(max_words, dtype=jnp.int32)[None, :]

    out: Dict[str, jax.Array] = {
        "trimmed_len": trimmed_len,
        "n_paragraphs": n_p,
        "para_dup_elems": p_dup_elems,
        "para_dup_bytes": p_dup_bytes,
        "n_lines": n_l,
        "line_dup_elems": l_dup_elems,
        "line_dup_bytes": l_dup_bytes,
        "seg_overflow": (n_l > max_segs) | (n_p > max_segs),
        "word_overflow": n_words > max_words,
    }

    # Build all n-gram tables, then run every dup-detection sort as ONE
    # batched device sort and every greedy-selection DFA as ONE batched scan
    # (per-n emission dominated compile time and HLO size).
    ns = sorted(set(list(top_ns) + list(dup_ns)))
    grams = {}
    for n in ns:
        gh = jnp.zeros_like(whash)
        gb = jnp.zeros_like(wbytes)
        for k in range(n):
            gh = gh * jnp.int32(1000003) + jnp.pad(whash[:, k:], ((0, 0), (0, k)))
            gb = gb + jnp.pad(wbytes[:, k:], ((0, 0), (0, k)))
        # Byte-length mixing, as for the line/para tables above.
        gh = gh * jnp.int32(31) + gb
        win_valid = (widx + n) <= n_words[:, None]
        grams[n] = (gh, gb, win_valid)

    b, m = whash.shape
    idx = jnp.broadcast_to(jnp.arange(m, dtype=jnp.int32)[None, :], (b, m))
    dup_sizes = sorted(set(dup_ns))
    min_dup = dup_sizes[0] if dup_sizes else None

    # Ungated jobs: every top-n job plus the SMALLEST dup-n job.  A truly
    # duplicated n-gram contains a duplicated (n-1)-gram at the same offset,
    # so "no dup min_dup-grams" implies no dup larger-n-grams either — the
    # expensive larger-n sorts and the greedy-selection machinery run under a
    # lax.cond taken only when the cheap gate fires.  (Hash-collision-only
    # "dups" at larger n without a min_dup dup are suppressed by the gate —
    # a strict reduction of the documented collision divergence.)
    #
    # The gate is batch-global (one dirty row runs the branch for the whole
    # batch); it pays off for clean or small batches — parity suites, shards
    # of already-deduped text — while dirty web-scale batches cost one extra
    # sort dispatch over the ungated form.
    jobs, tags = [], []
    for n in ns:
        gh, gb, win_valid = grams[n]
        if n in top_ns:
            # " "-joined n-grams: byte length includes n-1 single-byte spaces.
            jobs.append((gh, gb + (n - 1), win_valid))
            tags.append(("top", n))
        if n == min_dup:
            jobs.append((gh, idx, win_valid))
            tags.append(("dup", n))

    dup_min_flags = dup_min_rid = None
    srts = _sort_runs_many(jobs, mesh=mesh) if jobs else []
    # All post-sort per-run scans (top-n run lengths + min-dup run ids) fuse
    # into one chain dispatch over the stacked tables when the table shape
    # passes the gate; None falls back to the identical staged scans.
    pre = _sorted_table_streams(
        [(kind, srt) for (kind, _), srt in zip(tags, srts)], mesh=mesh
    )
    for i, ((kind, n), srt) in enumerate(zip(tags, srts)):
        if kind == "top":
            out[f"top_{n}"] = _top_duplicate_sorted(
                srt, run_len=pre[i] if pre else None
            )
        else:
            dup_min_flags, dup_min_rid = _dup_run_info_sorted(
                srt, grams[n][2], idx, mesh=mesh,
                first_in_run=pre[i] if pre else None,
            )

    if dup_sizes:
        rest = dup_sizes[1:]

        def _dup_work(operand):
            _, min_rid = operand
            walk = [(min_dup, min_rid, grams[min_dup][2], grams[min_dup][1])]
            if rest:
                rjobs = [(grams[n][0], idx, grams[n][2]) for n in rest]
                rsrts = _sort_runs_many(rjobs, mesh=mesh)
                rpre = _sorted_table_streams(
                    [("dup", srt) for srt in rsrts], mesh=mesh
                )
                for i, (n, srt) in enumerate(zip(rest, rsrts)):
                    _, rid_n = _dup_run_info_sorted(
                        srt, grams[n][2], idx, mesh=mesh,
                        first_in_run=rpre[i] if rpre else None,
                    )
                    walk.append((n, rid_n, grams[n][2], grams[n][1]))
            res = _find_all_dup_bytes_batched(walk, mesh)
            return tuple(res[f"dup_{n}"] for n in dup_sizes)

        def _dup_zero(operand):
            zero = jnp.zeros_like(n_words)
            return tuple(zero for _ in dup_sizes)

        dup_outs = jax.lax.cond(
            jnp.any(dup_min_flags), _dup_work, _dup_zero, (dup_min_flags, dup_min_rid)
        )
        for n, v in zip(dup_sizes, dup_outs):
            out[f"dup_{n}"] = v
    return out


def _dup_run_info_sorted(
    sorted_triple, win_valid, idx, mesh=None, first_in_run=None
) -> Tuple[jax.Array, jax.Array]:
    """``(flags, run_first)`` from a ``(hash, idx)``-sorted window table:
    ``flags`` — "an earlier identical window exists" (a superset of
    find_all_duplicate's dynamic dup test, used as the rarity gate);
    ``run_first`` — each window's run id (the minimum window index sharing
    its hash), the canonical slot for the walk's visited table."""
    is_real, s_hash, sidx = sorted_triple
    b, m = s_hash.shape
    run_start = _run_starts(s_hash)
    if first_in_run is None:
        # Sorted by (hash, idx): the run's first slot holds the minimum index.
        first_in_run = seg_scan_max(jnp.where(run_start, sidx, -(2**30)), run_start)
    # Un-sort by window index: the real entries' sidx values are exactly
    # 0..n_valid-1 (win_valid is a prefix mask), so sorting
    # (sidx, first_in_run) restores window order with slot j holding window
    # j's run id, and 0 past the real entries.  Pad m to a power of two
    # first (ADVICE r4): sort2's Pallas bitonic network requires it, and a
    # non-pow2 width here silently fell back to lax.sort — correct but off
    # the tuned VMEM path.  Pad keys are _I32_MAX, sorting to the end; the
    # real entries occupy slots 0..n_valid-1 either way, so slicing back is
    # exact.
    k0 = jnp.where(is_real, sidx, _I32_MAX)
    k1 = jnp.where(is_real, first_in_run, 0)
    m_pow2 = 1 << (max(m - 1, 1)).bit_length()
    if m_pow2 != m:
        pad = ((0, 0), (0, m_pow2 - m))
        k0 = jnp.pad(k0, pad, constant_values=_I32_MAX)
        k1 = jnp.pad(k1, pad)
    first_occ = sort2(k0, k1, mesh=mesh)[1][:, :m]
    return win_valid & (first_occ < idx), first_occ


def _find_all_dup_bytes_batched(jobs, mesh=None) -> Dict[str, jax.Array]:
    """find_all_duplicate, EXACT: the oracle's greedy scan with its
    visited-set dynamics (text.rs:241-259) — ``seen`` holds only windows the
    scan actually visited, a hit counts the window's bytes and jumps ``n``
    (the jumped-over windows are never inserted), a miss inserts and steps 1.

    Every job ``(n, run_first, win_valid, gb)`` stacks along the batch axis
    and one ``lax.scan`` over the ``m`` window positions walks all rows in
    lockstep: the carry is a per-row visited table indexed by ``run_first``
    (each window's canonical run id — equal hash == equal gram under the
    module's no-collision assumption), a skip counter, and the byte
    accumulator.  Position dynamics can't be pointer-jumped ahead of time —
    whether a window is a duplicate depends on which of its twins were
    themselves skipped — which is why this is a sequential scan and not the
    earlier (approximate) binary-lifting chain.  It only runs under the
    min-dup rarity gate, so clean batches never pay for it.
    """
    out: Dict[str, jax.Array] = {}
    if not jobs:
        return out
    b, m = jobs[0][1].shape
    n_vec = _stack_rows(
        [jnp.full((b,), n, jnp.int32) for n, _, _, _ in jobs], mesh
    )  # [kB]
    rid = _stack_rows([j[1] for j in jobs], mesh)  # [kB, m]
    val = _stack_rows([j[2] for j in jobs], mesh)
    gbs = _stack_rows([j[3] for j in jobs], mesh)
    lane = jnp.arange(m, dtype=jnp.int32)[None, :]

    def step(carry, xs):
        visited, skip, acc = carry
        rid_c, gb_c, val_c = xs  # [kB] each
        can = (skip == 0) & val_c
        # One-hot compare instead of row gather/scatter: O(kB*m) VPU work
        # per step, but no serialized dynamic addressing on TPU.
        oh = lane == rid_c[:, None]
        seen = jnp.sum(jnp.where(oh, visited, 0), axis=1) > 0
        hit = can & seen
        visited = jnp.maximum(
            visited, (oh & (can & ~seen)[:, None]).astype(jnp.int32)
        )
        acc = acc + jnp.where(hit, gb_c, 0)
        skip = jnp.where(hit, n_vec - 1, jnp.maximum(skip - 1, 0))
        return (visited, skip, acc), None

    init = (
        jnp.zeros(rid.shape, jnp.int32),
        jnp.zeros(rid.shape[0], jnp.int32),
        jnp.zeros(rid.shape[0], jnp.int32),
    )
    (_, _, acc), _ = jax.lax.scan(step, init, (rid.T, gbs.T, val.T))
    for (n, _, _, _), a in zip(jobs, _unstack_rows(acc, len(jobs), mesh)):
        out[f"dup_{n}"] = a
    return out


# --- Sentence counting (device twin of split_into_sentences) -----------------

_TERM_SET = np.sort(np.array([ord(c) for c in ("." + _STERM)], dtype=np.int32))
_STERM_SET = np.sort(np.array([ord(c) for c in _STERM], dtype=np.int32))
_CLOSE_SET = np.sort(np.array([ord(c) for c in _CLOSE], dtype=np.int32))
_SP_SET = np.sort(np.array([ord(c) for c in _SP], dtype=np.int32))
_PSEP_SET = np.sort(np.array([ord(c) for c in _PARA_SEP], dtype=np.int32))

# Match DFA over symbols 0=other, 1=TERM, 2=CLOSE, 3=SP.
# States: 0 outside, 1 in terms, 2 in closes, 3 in spaces.
_SENT_T = np.zeros((4, 4), dtype=np.int32)
_SENT_T[0, :] = 0
_SENT_T[1, :] = 1
_SENT_T[2, :] = [0, 2, 2, 0]
_SENT_T[3, :] = [0, 3, 3, 3]


def sentence_boundaries(cps: jax.Array, mask: jax.Array, cls: jax.Array) -> jax.Array:
    """[B, L] bool — a sentence boundary falls immediately BEFORE each True
    position (the device twin of utils.text._sentence_boundaries, applied to
    the chars selected by ``mask``)."""
    term = isin_sorted(cps, _TERM_SET) & mask
    sterm = isin_sorted(cps, _STERM_SET) & mask
    close = isin_sorted(cps, _CLOSE_SET) & mask
    sp = isin_sorted(cps, _SP_SET) & mask
    psep = isin_sorted(cps, _PSEP_SET) & mask

    sym = jnp.zeros_like(cps)
    sym = jnp.where(term, 1, sym)
    sym = jnp.where(close & ~term, 2, sym)
    sym = jnp.where(sp & ~close & ~term, 3, sym)
    state = dfa_states(sym, _SENT_T)
    prev_state = _shift_r(state, 0)

    # Match-start: a terminator not already inside a terminator run.
    match_start = term & (prev_state != 1)
    has_sterm = (
        seg_scan_or(jnp.where(state > 0, sterm.astype(jnp.int32), 0), match_start) > 0
    )
    prev_has_sterm = _shift_r(has_sterm.astype(jnp.int32), 0) > 0
    dot_last = (
        _shift_r((cps == ord(".")) & mask, False) & (prev_state == 1)
    )

    lower = (cls & LOWER) != 0
    alnum_ = ((cls & ALNUM) != 0) | (cps == ord("_"))

    # Boundary candidate: previous char inside a match; current char either
    # exits the match or starts a fresh terminator run after closes/spaces.
    fresh_term = term & ((prev_state == 2) | (prev_state == 3))
    candidate = mask & (prev_state > 0) & ((state == 0) | fresh_term)

    no_break = ~prev_has_sterm & ((dot_last & alnum_) | lower)
    return (candidate & ~no_break) | (_shift_r(psep, False) & mask)


def _sentence_frame(cps: jax.Array, mask: jax.Array, cls: jax.Array) -> dict:
    """Elementwise operands of :func:`sentence_boundaries`, shared between
    the staged path and the chain kernel (all int32, kernel-ready)."""
    from .dfa import dfa_packed_fns

    term = isin_sorted(cps, _TERM_SET) & mask
    sterm = isin_sorted(cps, _STERM_SET) & mask
    close = isin_sorted(cps, _CLOSE_SET) & mask
    sp = isin_sorted(cps, _SP_SET) & mask
    psep = isin_sorted(cps, _PSEP_SET) & mask

    sym = jnp.zeros_like(cps)
    sym = jnp.where(term, 1, sym)
    sym = jnp.where(close & ~term, 2, sym)
    sym = jnp.where(sp & ~close & ~term, 3, sym)
    return {
        "fns": dfa_packed_fns(sym, _SENT_T),
        "term": term.astype(jnp.int32),
        "sterm": sterm.astype(jnp.int32),
        "lower": ((cls & LOWER) != 0).astype(jnp.int32),
        "alnum": (((cls & ALNUM) != 0) | (cps == ord("_"))).astype(jnp.int32),
        "sh_dot": _shift_r((cps == ord(".")) & mask, False).astype(jnp.int32),
        "sh_psep": (_shift_r(psep, False) & mask).astype(jnp.int32),
        "mask": mask.astype(jnp.int32),
    }


def _sentence_passes(fr: dict, begin_extra: jax.Array, nonws: jax.Array, emit: str):
    """Passes 0-2 of the sentence chain: DFA map composition → sterm run
    counter → per-segment non-ws counter.  The boundary rule is derived from
    packed-state taps in-register — the same int32 formulas as
    :func:`sentence_boundaries` (prev_* via shift taps with fill 0, matching
    the staged ``_shift_r(..., 0)``; the sterm OR becomes a segmented SUM
    tested ``> 0``, which agrees bit-for-bit on {0,1} streams)."""
    from .pallas_scan import Tap, chain_group, chain_pass

    def _prep_hst(pk, pk_prev, t, s):
        st = pk & 15
        return (
            jnp.where((t != 0) & ((pk_prev & 15) != 1), 0, 1),
            jnp.where(st > 0, s, 0),
        )

    def _prep_cnt(pk, pk_prev, hst_prev, t, shd, lo, al, shp, mk, ex, nw):
        st = pk & 15
        pst = pk_prev & 15
        fresh = (t != 0) & ((pst == 2) | (pst == 3))
        cand = (mk != 0) & (pst > 0) & ((st == 0) | fresh)
        dot_last = (shd != 0) & (pst == 1)
        nb = ~(hst_prev > 0) & ((dot_last & (al != 0)) | (lo != 0))
        boundary = (cand & ~nb) | ((shp != 0) & (mk != 0))
        return jnp.where(boundary | (ex != 0), 0, 1), nw

    return [
        chain_pass(
            [{"kind": "dfa", "xs": (fr["fns"],), "emit": emit, "n_states": 4}]
        ),
        chain_pass(
            [
                chain_group(
                    "affine",
                    (Tap(0, 0), Tap(0, 0, shift=1, fill=0), fr["term"], fr["sterm"]),
                    prep=_prep_hst,
                    n_ops=2,
                    emit=emit,
                )
            ]
        ),
        chain_pass(
            [
                chain_group(
                    "affine",
                    (
                        Tap(0, 0),
                        Tap(0, 0, shift=1, fill=0),
                        Tap(1, 0, shift=1, fill=0),
                        fr["term"],
                        fr["sh_dot"],
                        fr["lower"],
                        fr["alnum"],
                        fr["sh_psep"],
                        fr["mask"],
                        begin_extra.astype(jnp.int32),
                        nonws.astype(jnp.int32),
                    ),
                    prep=_prep_cnt,
                    n_ops=2,
                    emit="scan" if emit == "scan" else emit,
                )
            ]
        ),
    ]


def sentence_counts(cps: jax.Array, lengths: jax.Array) -> jax.Array:
    """Sentences per row — ``len(split_into_sentences(text))`` for rows whose
    content is already globally trimmed (C4's rewritten batches are)."""
    from .pallas_scan import Tap, chain_group, chain_pass, chain_scan, chain_scan_ok

    _, length = cps.shape
    mask = jnp.arange(length, dtype=jnp.int32)[None, :] < lengths[:, None]
    cls = classify(cps)
    cls = jnp.where(mask, cls, 0).astype(cls.dtype)
    ws = (cls & WS) != 0
    nonws = mask & ~ws

    if chain_scan_ok(*cps.shape):
        # DFA → sterm counter → segment counter → total, ONE dispatch: every
        # intermediate (three staged dispatches' worth) stays in scratch and
        # only the [B, 1] sentence count reaches HBM.
        fr = _sentence_frame(cps, mask, cls)
        passes = _sentence_passes(fr, _first_col(mask), nonws, emit="none")
        passes.append(
            chain_pass(
                [
                    chain_group(
                        "add",
                        (Tap(2, 0), nonws.astype(jnp.int32)),
                        prep=lambda c, nw: ((nw != 0) & (c == 1),),
                        n_ops=1,
                        emit="last",
                    )
                ]
            )
        )
        res = chain_scan(passes)
        return res[3][0][0][:, 0].astype(jnp.int32)

    boundary = sentence_boundaries(cps, mask, cls)

    # Count segments containing >= 1 non-ws char.
    seg_begin = boundary | _first_col(mask)
    cnt = seg_scan_add(nonws.astype(jnp.int32), seg_begin)
    first_nonws = nonws & (cnt == 1)
    return jnp.sum(first_nonws, axis=1).astype(jnp.int32)


# --- C4 stage ----------------------------------------------------------------


class C4Params(NamedTuple):
    split_paragraph: bool
    remove_citations: bool
    filter_no_terminal_punct: bool
    min_num_sentences: int
    min_words_per_line: int
    max_word_length: int
    filter_lorem_ipsum: bool
    filter_javascript: bool
    filter_curly_bracket: bool
    filter_policy: bool


_END_PUNCT_SET = np.sort(
    np.array([ord(c) for c in (".", "!", "?", '"', "'", "”")], dtype=np.int32)
)

_POLICY = (
    "terms of use",
    "privacy policy",
    "cookie policy",
    "uses cookies",
    "use of cookies",
    "use cookies",
)


def c4_stage(
    cps: jax.Array,
    lengths: jax.Array,
    params: C4Params,
    max_lines: int,
    mesh=None,
) -> Tuple[Dict[str, jax.Array], jax.Array, jax.Array]:
    """The C4 quality filter as a device stage (c4_filters.rs:147-295).

    Returns ``(stats, new_cps, new_lengths)``: the new batch is the rewritten
    content (kept lines joined by ``\\n``) for every row.

    ``split_paragraph=True`` segments on newlines (``content.lines()``,
    c4_filters.rs:150-156); ``False`` segments on sentence boundaries via the
    shared sentence DFA (:func:`sentence_boundaries`), synthesizing one
    ``\\n`` separator per kept-sentence join from the inter-sentence
    whitespace.  A sentence boundary with NO whitespace after it (rare:
    terminator directly followed by the next sentence's first char) cannot
    host a separator — those rows set ``line_overflow`` and take the counted
    bit-exact host fallback.
    """
    _, length = cps.shape
    mask = jnp.arange(length, dtype=jnp.int32)[None, :] < lengths[:, None]
    cls = classify(cps)
    cls = jnp.where(mask, cls, 0).astype(cls.dtype)
    ws = (cls & WS) != 0
    low = _lowered(cps, mask)
    pos = jnp.arange(length, dtype=jnp.int32)[None, :]

    # Doc-level early rejects (c4_filters.rs:166-187).  The lorem-ipsum
    # candidate prefix hash rides the segmentation chain kernel below when
    # the chain gate holds (lorem_h), so has_lorem finalizes after the split.
    lorem_h = None
    has_curly = jnp.any(((cps == ord("{")) | (cps == ord("}"))) & mask, axis=1)

    def _citation_deleted(unit_content):
        if not params.remove_citations:
            return jnp.zeros_like(mask)
        # Citation machinery only runs on batches that contain a '[' at all
        # (rare in clean text — the same skip the oracle's regex scan gets
        # from its first-byte check).
        return jax.lax.cond(
            jnp.any((cps == ord("[")) & mask),
            lambda: citation_spans(
                jnp.where(unit_content, cps, 0),
                ((cls & DIGIT) != 0) & unit_content,
                ws & unit_content,
            ),
            lambda: jnp.zeros_like(mask),
        )

    gap_overflow = jnp.zeros(cps.shape[0], dtype=bool)
    if params.split_paragraph:
        li = line_info(cps, mask)
        nonws = li.content & ~ws
        reset = _line_reset(li, mask)

        # Per-line trim: chars at/after the first non-ws, at/before the last.
        from .pallas_scan import chain_pass, chain_scan, chain_scan_ok

        r_reset = _first_col(mask) | _shift_r(rev(li.is_nl), False)
        if chain_scan_ok(*cps.shape):
            # Forward line counter (+ the doc-level lorem-ipsum candidate
            # hash riding along) and the reversed counter as a second pass —
            # reverse-pass operands are given in natural orientation (the
            # kernel walks them flipped), i.e. rev() of the staged
            # reversed-frame operands.
            g0 = [_seg_add_group((nonws.astype(jnp.int32),), reset)]
            if params.filter_lorem_ipsum:
                g0.append(_pattern_hash_group(low, mask))
            res = chain_scan(
                [
                    chain_pass(g0),
                    chain_pass(
                        [
                            {
                                "kind": "affine",
                                "xs": (
                                    rev(jnp.where(r_reset, 0, 1)),
                                    nonws.astype(jnp.int32),
                                ),
                            }
                        ],
                        reverse=True,
                    ),
                ]
            )
            after_first = res[0][0][0] >= 1
            if params.filter_lorem_ipsum:
                lorem_h = res[0][1][0]
            before_last = res[1][0][0] >= 1
        else:
            after_first = seg_scan_add(nonws.astype(jnp.int32), reset) >= 1
            before_last = rev(
                seg_scan_add(rev(nonws).astype(jnp.int32), r_reset) >= 1
            )
        in_line_trim = li.content & after_first & before_last

        deleted = _citation_deleted(li.content)
        keep1 = (in_line_trim & ~deleted) | li.is_nl
        c1_src = cps
        n_units = li.n_lines
    else:
        # Sentence mode: global trim (split_into_sentences trims the input,
        # utils/text.py), boundaries from the shared DFA, segments between
        # boundaries, each trimmed; blank segments are not sentences.
        nonws_all = mask & ~ws
        any_nonws = jnp.any(nonws_all, axis=1)
        t0 = jnp.min(jnp.where(nonws_all, pos, length), axis=1)
        t1 = jnp.max(jnp.where(nonws_all, pos, -1), axis=1)
        in_trim = (pos >= t0[:, None]) & (pos <= t1[:, None]) & mask

        nonws = in_trim & ~ws
        from .pallas_scan import chain_scan, chain_scan_ok

        if chain_scan_ok(*cps.shape):
            # Sentence DFA → sterm counter → segment counter in one kernel
            # (+ the lorem candidate hash riding pass 0); the boundary mask
            # the compaction handoff needs is recomputed elementwise from
            # the emitted packed-state/sterm streams — the exact staged
            # formulas from sentence_boundaries, so bit-identical.
            fr = _sentence_frame(cps, in_trim, cls)
            at_t0x = (pos == t0[:, None]) & in_trim
            passes = _sentence_passes(fr, at_t0x, nonws, emit="scan")
            if params.filter_lorem_ipsum:
                passes[0]["groups"].append(_pattern_hash_group(low, mask))
            res = chain_scan(passes)
            state = res[0][0][0] & 15
            if params.filter_lorem_ipsum:
                lorem_h = res[0][1][0]
            hst = res[1][0][0]
            cnt = res[2][0][0]
            prev_state = _shift_r(state, 0)
            prev_has_sterm = _shift_r(hst, 0) > 0
            term = fr["term"] != 0
            fresh_term = term & ((prev_state == 2) | (prev_state == 3))
            candidate = in_trim & (prev_state > 0) & ((state == 0) | fresh_term)
            dot_last = (fr["sh_dot"] != 0) & (prev_state == 1)
            no_break = ~prev_has_sterm & (
                (dot_last & (fr["alnum"] != 0)) | (fr["lower"] != 0)
            )
            boundary = (candidate & ~no_break) | ((fr["sh_psep"] != 0) & in_trim)
            seg_begin = (boundary | (pos == t0[:, None])) & in_trim
        else:
            boundary = sentence_boundaries(cps, in_trim, cls)
            seg_begin = (boundary | (pos == t0[:, None])) & in_trim
            cnt = seg_scan_add(nonws.astype(jnp.int32), seg_begin)
        first_nonws_seg = nonws & (cnt == 1)
        n_units = jnp.sum(first_nonws_seg, axis=1).astype(jnp.int32)

        # Segment ends: last char of each segment (next char starts a new
        # one or leaves the trim).
        seg_end = in_trim & (_shift_l(seg_begin, False) | ~_shift_l(in_trim, False))
        r_reset = _first_col(mask) | rev(seg_end)
        cnt_r = seg_scan_add(rev(nonws).astype(jnp.int32), r_reset)
        before_last = rev(cnt_r >= 1)
        in_sent_trim = in_trim & (cnt >= 1) & before_last
        sent_last_nonws = rev(rev(nonws) & (cnt_r == 1))

        # One synthesized '\n' per kept-sentence join: the first char after
        # each sentence's trimmed end (inter-sentence gaps are pure ws), if
        # any sentence follows.
        suffix_nonws = _shift_l(
            rev(jnp.cumsum(rev(nonws).astype(jnp.int32), axis=1)) > 0, False
        )
        sep_keep = _shift_r(sent_last_nonws, False) & ~nonws & in_trim & suffix_nonws
        gap_overflow = jnp.any(sent_last_nonws & _shift_l(nonws, False), axis=1)

        deleted = _citation_deleted(in_trim)
        keep1 = (in_sent_trim & ~deleted) | sep_keep
        c1_src = jnp.where(sep_keep, jnp.int32(NL), cps)
        del any_nonws  # rows without content have empty keep1 already

    if params.filter_lorem_ipsum:
        has_lorem = jnp.any(
            _pattern_union_starts(low, mask, ("lorem ipsum",), h_inc=lorem_h), axis=1
        )
    else:
        has_lorem = jnp.zeros(cps.shape[0], dtype=bool)

    c1_cps, c1_len = compact(c1_src, keep1, mesh=mesh)

    # --- per-line checks on the compacted batch ---
    m1 = jnp.arange(length, dtype=jnp.int32)[None, :] < c1_len[:, None]
    st1 = structure(c1_cps, c1_len, with_hashes=False)
    li1 = line_info(c1_cps, m1)
    low1 = _lowered(c1_cps, m1)

    valid_end1 = st1.unit_end & st1.unit_valid
    is_dot1 = (c1_cps == ord(".")) & m1
    dot_start1 = is_dot1 & ~_shift_r(is_dot1, False)

    # Only the UNION of javascript/policy line flags affects line_keep (no
    # per-cause stats are reported), so all patterns share one candidate
    # pass (_pattern_union_starts).
    line_patterns: Tuple[str, ...] = ()
    if params.filter_javascript:
        line_patterns += ("javascript",)
    if params.filter_policy:
        line_patterns += _POLICY

    from .pallas_scan import chain_pass as _cpass, chain_scan as _cscan
    from .pallas_scan import chain_scan_ok as _cok

    starts_h = None
    if _cok(*cps.shape):
        # Post-compaction pass: the dot-run counter and the line-pattern
        # candidate hash share one dispatch over the rewritten batch.
        g1 = [_seg_add_group((is_dot1.astype(jnp.int32),), dot_start1)]
        if line_patterns:
            g1.append(_pattern_hash_group(low1, m1))
        res1 = _cscan([_cpass(g1)])
        dot_run1 = res1[0][0][0]
        if line_patterns:
            starts_h = res1[0][1][0]
    else:
        dot_run1 = seg_scan_add(is_dot1.astype(jnp.int32), dot_start1)
    starts = (
        _pattern_union_starts(low1, m1, line_patterns, h_inc=starts_h)
        if line_patterns
        else None
    )

    # Slot j = line id j: every line present in the compacted batch has
    # exactly one representative char — its '\n', or the row's final char —
    # in line order (a final line whose chars all trimmed away has no slot;
    # its verdict comes from the fills via ``line_exists`` below).  Per-line
    # values are segmented scans read at the representative.
    reset1 = _line_reset(li1, m1)
    row_last1 = m1 & ~_shift_l(m1, False)
    rep1 = (li1.is_nl | row_last1) & m1
    [(lpos1, lreal1)] = _rank_positions_many([rep1], max_lines, mesh)
    content_set1 = li1.content | reset1

    line_words = _gather_table(
        seg_scan_add(valid_end1.astype(jnp.int32), reset1), lpos1, lreal1
    )
    line_max_word = _gather_table(
        seg_scan_max(jnp.where(valid_end1, st1.unit_len, 0), reset1),
        lpos1,
        lreal1,
    )
    # "Value at the line's last content char" via a latch over content
    # positions (a blank line's representative reads the latch cleared at
    # its line start: 0).
    line_last_char = _gather_table(
        latch_scan(jnp.where(li1.content, c1_cps, 0), content_set1),
        lpos1,
        lreal1,
    )
    line_end_dots = _gather_table(
        latch_scan(jnp.where(li1.content & is_dot1, dot_run1, 0), content_set1),
        lpos1,
        lreal1,
    )
    if starts is not None:
        bad_pattern_line = (
            _gather_table(
                seg_scan_or(starts.astype(jnp.int32), reset1), lpos1, lreal1
            )
            > 0
        )
    else:
        bad_pattern_line = jnp.zeros_like(line_words, dtype=bool)

    ends_terminal = isin_sorted(line_last_char, _END_PUNCT_SET) & (
        line_last_char > 0
    )
    ends_ellipsis = line_end_dots >= 3

    # Unit count comes from the ORIGINAL batch: a final line whose content
    # trimmed away entirely has no chars and no trailing \n in the compacted
    # batch, so li1 under-counts it — but it still exists as a (droppable)
    # line in the oracle's rust_lines view.  (Sentence mode has no such
    # invisible units: every sentence contains a non-ws char.)
    n_lines1 = n_units
    line_exists = jnp.arange(max_lines, dtype=jnp.int32)[None, :] < n_lines1[:, None]

    if params.max_word_length > 0:
        drop_too_long = line_exists & (line_max_word > params.max_word_length)
    else:
        drop_too_long = jnp.zeros_like(line_exists)
    remaining = line_exists & ~drop_too_long
    if params.filter_no_terminal_punct:
        drop_no_term = remaining & ~(ends_terminal & ~ends_ellipsis)
    else:
        drop_no_term = jnp.zeros_like(remaining)
    remaining = remaining & ~drop_no_term
    if params.min_words_per_line > 0:
        drop_few_words = remaining & (line_words < params.min_words_per_line)
    else:
        drop_few_words = jnp.zeros_like(remaining)
    remaining = remaining & ~drop_few_words
    line_keep = remaining & ~bad_pattern_line

    # --- compact kept lines into the rewritten batch ---
    later = rev(jnp.cumsum(rev(line_keep.astype(jnp.int32)), axis=1), axis=1)
    keep_later = _shift_l(later, 0) > 0  # a kept line exists after slot l

    lid1 = jnp.minimum(li1.line_id, max_lines - 1)
    char_line_keep = jnp.take_along_axis(line_keep, lid1, axis=1)
    char_keep_later = jnp.take_along_axis(keep_later, lid1, axis=1)
    keep2 = (li1.content & char_line_keep & m1) | (
        li1.is_nl & char_line_keep & char_keep_later
    )
    c2_cps, c2_len = compact(c1_cps, keep2, mesh=mesh)

    n_sent = sentence_counts(c2_cps, c2_len)

    # Rewrite-identity flag: the rewritten batch equals this stage's input
    # (both zero-padded), so the host can skip its per-document Python
    # string rebuild — the common case on clean text, where every line is
    # kept and already trimmed.
    rewrite_identity = (c2_len == lengths) & jnp.all(c2_cps == cps, axis=1)

    false_b = jnp.zeros_like(has_lorem)
    stats = {
        "has_lorem": has_lorem if params.filter_lorem_ipsum else false_b,
        "has_curly": has_curly if params.filter_curly_bracket else false_b,
        "n_sentences": n_sent,
        "rewrite_identity": rewrite_identity,  # [B]
        "line_keep": line_keep,  # [B, ML]
        "n_lines": jnp.minimum(n_lines1, jnp.int32(max_lines)),
        "drop_too_long": jnp.sum(drop_too_long, axis=1).astype(jnp.int32),
        "drop_no_term": jnp.sum(drop_no_term, axis=1).astype(jnp.int32),
        "drop_few_words": jnp.sum(drop_few_words, axis=1).astype(jnp.int32),
        "line_overflow": (n_lines1 > max_lines) | gap_overflow,
    }
    return stats, c2_cps, c2_len
