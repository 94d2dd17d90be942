"""Shared device primitives: class tables, segmented scans, hashing.

These are the building blocks of every filter kernel (SURVEY.md §7 stage 2):
a byte-class precompute (here: codepoint-class gather over the same table the
host oracle uses, so host and device classify identically), segmented
associative scans for per-word / per-line / per-paragraph aggregates, and
rolling hashes for duplicate detection.

All kernels operate on ``[B, L]`` codepoint tensors with a validity mask;
reductions are along axis 1.  Scans run on one lax schedule, the
contiguous-shift doubling below, on every backend; per-segment tables are
built by a sorted compaction (:mod:`.stats`, :mod:`.compact`), never by an
XLA scatter.  Membership in a constant set is a chain of compares, never a
search: on a TPU every gather is a full-batch memory pass.
"""

from __future__ import annotations

from functools import lru_cache
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import chartables as ct
from ..utils.text import _MID_ALL, _MID_LETTER, _MID_NUM, _MID_NUM_LET

__all__ = [
    "class_table",
    "lower_table",
    "classify",
    "utf8_width",
    "isin_sorted",
    "seg_scan_add",
    "seg_scan_or",
    "seg_scan_max",
    "latch_scan",
    "rev",
    "ALNUM",
    "ALPHA",
    "DIGIT",
    "WS",
    "PUNCT",
    "LOWER",
    "UPPER",
    "EXTEND",
    "MID_LETTER_CPS",
    "MID_NUM_CPS",
    "MID_ALL_CPS",
    "word_base",
    "word_mask",
    "HASH_MUL",
]

ALNUM = ct.ALNUM
ALPHA = ct.ALPHA
DIGIT = ct.DIGIT
WS = ct.WS
PUNCT = ct.PUNCT
LOWER = ct.LOWER
UPPER = ct.UPPER
EXTEND = ct.EXTEND

HASH_MUL = np.int32(31)  # polynomial rolling-hash multiplier (int32 wraparound)


def tpu_refusal() -> str:
    """Why the compiled pipeline must not start on ``--backend tpu`` here,
    or "".

    It runs on JAX's default platform, so without this check a machine
    whose TPU did not come up would run it on the CPU without saying so.
    ``JAX_PLATFORMS=cpu`` is the explicit request for the CPU (the tests,
    CPU rehearsals and ``--backend cpu`` set it)."""
    if str(jax.config.jax_platforms or "").strip().lower() == "cpu":
        return ""
    try:
        platform = jax.default_backend()
    except RuntimeError as e:
        return f"--backend tpu: JAX could not bring up a backend ({e})"
    if platform == "tpu":
        return ""
    return (
        f"--backend tpu needs a TPU, but JAX's default platform is "
        f"{platform!r}.  Use --backend cpu (or set JAX_PLATFORMS=cpu) to run "
        "the compiled pipeline on the CPU on purpose."
    )


@lru_cache(maxsize=1)
def _class_table_np() -> np.ndarray:
    return ct.char_table()


@lru_cache(maxsize=1)
def _lower_table_np() -> np.ndarray:
    table = np.arange(ct._MAX_CP, dtype=np.int32)
    for cp in range(ct._MAX_CP):
        low = chr(cp).lower()
        if len(low) == 1 and ord(low) < ct._MAX_CP:
            table[cp] = ord(low)
    return table


def class_table() -> jax.Array:
    """The host classification table (``[0x40000] uint8``).  Materialized per
    trace as an XLA constant (cached host-side; never cache traced arrays)."""
    return jnp.asarray(_class_table_np())


def lower_table() -> jax.Array:
    """Codepoint -> lowercase codepoint (identity where ``str.lower`` is not
    a single char).  ``[0x40000] int32``."""
    return jnp.asarray(_lower_table_np())


def classify(cps: jax.Array) -> jax.Array:
    """Gather char classes; indices clipped like the host ``classify``,
    with the same plane-14 EXTEND range check."""
    cls = class_table()[jnp.minimum(cps, ct._MAX_CP - 1)]
    plane14 = (cps >= ct._PLANE14_LO) & (cps < ct._PLANE14_HI)
    return jnp.where(plane14, jnp.uint8(EXTEND), cls)


def utf8_width(cps: jax.Array) -> jax.Array:
    """UTF-8 encoded byte width of each codepoint (1/2/3/4) — recovers the
    reference's byte-length semantics (text.rs:203,230,252) from codepoints."""
    w = jnp.where(cps < 0x80, 1, jnp.where(cps < 0x800, 2, jnp.where(cps < 0x10000, 3, 4)))
    return w.astype(jnp.int32)


def isin_sorted(x: jax.Array, values) -> jax.Array:
    """``np.isin(x, values)`` for a host-constant set (numpy array or tuple,
    known at trace time): an OR of ``x == v`` compares in int32, which XLA
    fuses into one elementwise loop, because on a TPU each probe of a binary
    search would be a full-batch gather, a memory pass of its own."""
    x32 = x.astype(jnp.int32)
    hit = jnp.zeros(x.shape, dtype=bool)
    for v in np.unique(np.asarray(values, dtype=np.int32)):
        hit = hit | (x32 == v)
    return hit


# Plain numpy at module scope: a jnp.asarray here would initialize a JAX
# backend at import time, and with it claim the chip for whichever process
# merely imported the module.  jnp converts these per trace.
MID_LETTER_CPS = np.sort(
    np.array([ord(c) for c in (_MID_LETTER | _MID_NUM_LET)], dtype=np.int32)
)
MID_NUM_CPS = np.sort(
    np.array([ord(c) for c in (_MID_NUM | _MID_NUM_LET)], dtype=np.int32)
)
MID_ALL_CPS = np.sort(np.array([ord(c) for c in _MID_ALL], dtype=np.int32))


# --- Segmented scans ---------------------------------------------------------
# State (v, r): r = "resets here".  Composition is the standard segmented-scan
# monoid, so any scan schedule computes the same values; the lax path has one.
#
# ``shift`` — Hillis-Steele doubling: level ``d`` combines position ``i``
# with ``i - d`` via a pad+slice shift.  O(L log L) work instead of the
# work-efficient O(L), but every step is a contiguous, layout-preserving
# move.  ``lax.associative_scan``'s odd/even recursion takes stride-2 slices
# instead, which relayout on TPU's tiled [sublane, lane] layouts and make
# each of its log L levels far dearer than its FLOPs suggest; shift is the
# schedule the chip was measured on.  The same program runs on every
# backend, so the CPU test suite checks what the chip runs.  Where
# ``pallas_scan.pallas_scan_ok`` admits a shape, the Pallas kernels take the
# scan instead (same ops, bit-identical by integer associativity).


def _seg_add_op(a, b):
    av, ar = a
    bv, br = b
    return jnp.where(br, bv, av + bv), ar | br


def _seg_or_op(a, b):
    av, ar = a
    bv, br = b
    return jnp.where(br, bv, av | bv), ar | br


def _seg_max_op(a, b):
    av, ar = a
    bv, br = b
    return jnp.where(br, bv, jnp.maximum(av, bv)), ar | br


def _latch_op(a, b):
    # "Rightmost set value" monoid: b wins where it is set.
    av, ar = a
    bv, br = b
    return jnp.where(br, bv, av), ar | br


def shift_scan_tuple(op, identities, xs, axis: int = 1):
    """Inclusive scan of a TUPLE state under associative ``op`` via the
    contiguous-shift (Hillis-Steele) schedule.

    ``op`` maps ``(left_state, right_state)`` tuples to a state tuple, where
    the left operand is the earlier prefix.  ``identities`` gives ``op``'s
    identity per component: a scalar, or an array broadcastable to a
    ``[B, d, ...]`` pad block.  The one lax scan schedule, shared by the
    segmented scans, :func:`assoc_scan1`, and the fused polynomial hashes
    (stats._poly_hash_many).
    """
    if axis != 1:
        xs = tuple(jnp.moveaxis(x, axis, 1) for x in xs)
    length = xs[0].shape[1]

    def pad_block(x, ident, d):
        blk = x[:, :d]
        if isinstance(ident, (int, bool, np.integer, np.bool_)):
            pad = jnp.full_like(blk, ident)
        else:
            pad = jnp.broadcast_to(ident, blk.shape).astype(x.dtype)
        return jnp.concatenate([pad, x[:, :-d]], axis=1)

    d = 1
    while d < length:
        shifted = tuple(
            pad_block(x, ident, d) for x, ident in zip(xs, identities)
        )
        xs = op(shifted, xs)
        d *= 2
    if axis != 1:
        xs = tuple(jnp.moveaxis(x, 1, axis) for x in xs)
    return xs


def _seg_scan(op, identity, values: jax.Array, reset: jax.Array, axis: int):
    # Dispatch accounting for the dispatch-count gates (no-op unless a
    # count_scan_dispatches scope is active).  Imported lazily: device is
    # imported by pallas_scan's consumers, never the other way around.
    from .pallas_scan import record_scan_dispatch

    record_scan_dispatch("lax_scan")
    # Virtual elements left of position 0 are (op identity, reset=True):
    # the identity keeps in-range prefixes exact, the True seals the
    # boundary for later levels.
    v, _ = shift_scan_tuple(op, (identity, True), (values, reset), axis)
    return v


def assoc_scan1(op, identity, x: jax.Array, axis: int = 1) -> jax.Array:
    """Inclusive scan of a single array under an arbitrary associative ``op``
    on the shift schedule (see scan notes above).

    ``identity`` is ``op``'s identity: a scalar, or an array broadcastable to
    a ``[B, d, ...]`` pad block (e.g. an iota for function-composition scans).
    """
    from .pallas_scan import record_scan_dispatch

    record_scan_dispatch("lax_scan")

    def tuple_op(a, b):
        return (op(a[0], b[0]),)

    return shift_scan_tuple(tuple_op, (identity,), (x,), axis)[0]


def seg_scan_add(values: jax.Array, reset: jax.Array, axis: int = 1) -> jax.Array:
    """Inclusive segmented sum along ``axis``; ``reset[i]`` starts a segment."""
    return _seg_scan(_seg_add_op, 0, values, reset, axis)


def seg_scan_or(values: jax.Array, reset: jax.Array, axis: int = 1) -> jax.Array:
    return _seg_scan(_seg_or_op, 0, values, reset, axis)


def seg_scan_max(values: jax.Array, reset: jax.Array, axis: int = 1) -> jax.Array:
    return _seg_scan(_seg_max_op, np.iinfo(np.int32).min, values, reset, axis)


def latch_scan(values: jax.Array, set_mask: jax.Array, axis: int = 1) -> jax.Array:
    """Inclusive "hold" scan: at each position, the value of the most recent
    position where ``set_mask`` is True (0 before any set position).  A reset
    is expressed by a set position carrying the fill value."""
    return _seg_scan(_latch_op, 0, values, set_mask, axis)


def rev(x: jax.Array, axis: int = 1) -> jax.Array:
    return jnp.flip(x, axis=axis)


def word_base(cps: jax.Array, cls: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Raw pre-WB4 wordness plus the Extend mask — the elementwise half of
    :func:`word_mask`, exposed so the dependency-fused chain kernel can run
    the WB4 hold scan in-kernel (stats.structure's depfuse path).

    A char is word-raw if alphanumeric/underscore, or a UAX#29-lite mid
    character flanked by the right neighbor classes.
    """
    word = ((cls & ALNUM) != 0) | (cps == ord("_"))
    prev_cls = jnp.pad(cls[:, :-1], ((0, 0), (1, 0)))
    next_cls = jnp.pad(cls[:, 1:], ((0, 0), (0, 1)))
    letter_ok = (
        isin_sorted(cps, MID_LETTER_CPS)
        & ((prev_cls & ALPHA) != 0)
        & ((next_cls & ALPHA) != 0)
    )
    num_ok = (
        isin_sorted(cps, MID_NUM_CPS)
        & ((prev_cls & DIGIT) != 0)
        & ((next_cls & DIGIT) != 0)
    )
    word = word | letter_ok | num_ok
    ext = (cls & EXTEND) != 0
    return word, ext


def word_mask(cps: jax.Array, cls: jax.Array) -> jax.Array:
    """In-word mask — the device twin of ``utils.text._word_mask``.

    UAX#29 WB4 (lite): Extend/Format chars inherit the wordness of the
    nearest preceding non-Extend char (utils.text._attach_extend twin).
    ``word`` is always False at Extend positions, so a segmented or-scan
    that RESETS at non-Extend positions holds each word flag through the
    following Extend run (leading Extend runs hold 0).
    """
    word, ext = word_base(cps, cls)
    held = seg_scan_or(word.astype(jnp.int32), ~ext)
    return jnp.where(ext, held > 0, word)
