"""Small DFAs over codepoint rows via associative function composition.

The reference's regexes on the hot path (the citation pattern
``\\[\\d+(?:,\\s*\\d+)*\\]``, c4_filters.rs:33; the sentence-boundary rules)
become tiny DFAs here.  A DFA step is a gather through a per-char transition
row; runs of steps compose associatively (``t_ab = t_b[t_a]``), so the whole
row is evaluated in log depth — the Pallas scan kernel where
``pallas_scan_ok`` admits the shape, else the shift schedule of
:func:`.device.assoc_scan1` — with no sequential scan (SURVEY.md §7 "regexes
on device").
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .device import assoc_scan1, latch_scan
from .pallas_scan import dfa_compose_scan, pallas_scan_ok

__all__ = ["dfa_packed_fns", "dfa_states", "citation_spans"]


def dfa_packed_fns(char_classes: jax.Array, transition: np.ndarray) -> jax.Array:
    """Nibble-packed per-char transition maps for a <= 8-state DFA.

    This is exactly the operand stream :func:`dfa_states` composes (state
    ``s``'s successor in bits ``4s..4s+3``), exposed so multi-pass chain
    programs (pallas_scan.chain_scan) can run the DFA composition as one
    group of a larger kernel and derive downstream operands from the packed
    state in-register.  ``(packed >> (4 * start_state)) & 15`` recovers the
    inclusive state stream.
    """
    n_states = transition.shape[1]
    if n_states > 8:
        raise ValueError("packed DFA maps require <= 8 states")
    packed_rows = np.zeros(transition.shape[0], dtype=np.int64)
    for s in range(n_states):
        packed_rows |= transition[:, s].astype(np.int64) << (4 * s)
    table = jnp.asarray(packed_rows.astype(np.int32))
    return table[char_classes]


def dfa_states(
    char_classes: jax.Array, transition: np.ndarray, start_state: int = 0
) -> jax.Array:
    """Inclusive per-position DFA state along axis 1.

    Args:
      char_classes: ``[B, L] int32`` — per-char input symbol in ``[0, S)``.
      transition:   ``[S, N] -> N`` numpy table: next state per (symbol, state).
      start_state:  initial state before position 0.

    Returns:
      ``[B, L] int32`` — state *after* consuming each char.

    The per-char state maps are nibble-packed into one int32 (state ``s``'s
    successor in bits ``4s..4s+3``, so at most 8 states) and composed with
    elementwise shifts — no gathers, which cost far more than ALU on both
    XLA:CPU and TPU.
    """
    n_states = transition.shape[1]
    fns = dfa_packed_fns(char_classes, transition)  # [B, L] packed maps

    def compose(a, b):
        # (b . a)(s) = b[a[s]]: route each of a's nibbles through b.
        out = jnp.zeros_like(a)
        for s in range(n_states):
            nib = (a >> (4 * s)) & 15
            out = out | (((b >> (nib << 2)) & 15) << (4 * s))
        return out

    # Identity function map: nibble s holds s.
    ident = 0
    for s in range(n_states):
        ident |= s << (4 * s)
    if pallas_scan_ok(*fns.shape):
        # Blocked VMEM kernel — same int32 composition, bit-identical
        # (pallas_scan module docstring; parity fuzzed in tests).  Under
        # mesh_tracing(mesh) the kernel dispatch shard_maps itself over the
        # data axis, so mesh programs keep this path too.
        packed = dfa_compose_scan(fns, n_states)
    else:
        packed = assoc_scan1(compose, np.int32(ident), fns, axis=1)
    return (packed >> (4 * start_state)) & 15


# Citation DFA symbols: 0=other, 1='[', 2=digit, 3=',', 4=space, 5=']'.
# States: 0=dead/outside, 1=after '[', 2=in digits, 3=after comma (spaces ok),
# 4=accept (just consumed ']' after digits).
_CIT_N = 5
_CIT_T = np.zeros((6, _CIT_N), dtype=np.int32)
# other: kill any progress
_CIT_T[0, :] = 0
# '[': always (re)start a candidate
_CIT_T[1, :] = 1
# digit: valid after '[', digit, comma-space; else dead
_CIT_T[2, :] = [0, 2, 2, 2, 0]
# ',': valid within digits
_CIT_T[3, :] = [0, 0, 3, 0, 0]
# space: valid after comma (\s* between comma and digits)
_CIT_T[4, :] = [0, 0, 0, 3, 0]
# ']': accept after >=1 digit
_CIT_T[5, :] = [0, 0, 4, 0, 0]


def citation_spans(cps: jax.Array, digit_mask: jax.Array, ws_mask: jax.Array) -> jax.Array:
    """Deletion mask for Wikipedia-style citations ``[1]``, ``[2, 3]``.

    Matches the reference regex ``\\[\\d+(?:,\\s*\\d+)*\\]`` over each row and
    returns a ``[B, L] bool`` mask marking every char inside a match
    (brackets included).

    ``\\s`` here is the regex-semantics whitespace of the reference engine
    (Unicode White_Space), supplied by ``ws_mask``.
    """
    sym = jnp.zeros_like(cps)
    sym = jnp.where(digit_mask, 2, sym)
    sym = jnp.where(cps == ord("["), 1, sym)
    sym = jnp.where(cps == ord(","), 3, sym)
    sym = jnp.where(ws_mask & (sym == 0), 4, sym)
    sym = jnp.where(cps == ord("]"), 5, sym)

    states = dfa_states(sym, _CIT_T)
    accept = states == 4  # position of each closing ']'

    # Span start = the most recent '[' (inside a match no other '[' occurs,
    # because '[' resets the candidate — so the nearest preceding '[' is the
    # match opener).  Mark spans with a +1/-1 difference array and a cumsum.
    positions = jnp.arange(cps.shape[1], dtype=jnp.int32)[None, :]
    lb_pos = jnp.where(cps == ord("["), positions, -1)
    last_lb = assoc_scan1(jnp.maximum, np.int32(-1), lb_pos, axis=1)

    # Scatter-free span fill: spans never overlap ('[' resets the
    # candidate), so position p is inside a span iff the NEAREST accept
    # at/after p opened at or before p.  A reversed latch scan carries each
    # accept's span start (biased +1 so 0 = "no accept follows") back over
    # the positions it covers.
    start1 = jnp.where(accept, last_lb + 1, 0)
    na = jnp.flip(latch_scan(jnp.flip(start1, 1), jnp.flip(accept, 1)), 1)
    return (na > 0) & (positions >= na - 1)
