"""Device-side compaction: masked gather into a fresh packed tensor.

The reference's C4 filter physically rewrites document strings (drops lines,
removes citation spans, rejoins — c4_filters.rs:195-258).  On device the same
effect is a *compaction*: given a keep-mask over ``[B, L]`` codepoints,
move the kept chars to the front of a new ``[B, L]`` tensor and recompute
lengths.  Downstream filter kernels then run on the compacted batch exactly as
they would on any packed batch — sequential pipeline semantics preserved
without leaving the device (SURVEY.md §7 "content rewriting" hard part).

The kept chars are moved by a stable partition: one row sort keyed by
original position (kept) or ``INT32_MAX`` (dropped), on the VMEM bitonic
network on TPU and ``lax.sort`` elsewhere — no XLA scatter, which XLA:TPU
serializes into per-element loops.  Also used by the language-ID kernel to
build its normalized letters-and-boundaries stream.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .pallas_sort import sort2

__all__ = ["compact"]

_I32_MAX = np.int32(2**31 - 1)


def compact(
    cps: jax.Array, keep: jax.Array, mesh=None
) -> Tuple[jax.Array, jax.Array]:
    """Pack kept chars to the row starts.

    Args:
      cps:  ``[B, L]`` int32 codepoints.
      keep: ``[B, L]`` bool; True chars survive, order preserved.
      mesh: data-axis mesh for the sort (the Pallas kernel under shard_map).

    Returns:
      ``(new_cps [B, L] int32 zero-padded, new_lengths [B] int32)``.
    """
    b, length = cps.shape

    new_lengths = jnp.sum(keep, axis=1).astype(jnp.int32)
    # Stable partition by sort: key = original position for kept chars,
    # INT32_MAX for dropped — kept chars land at the row start in order.
    # Codepoints are non-negative, satisfying sort2's payload contract.
    pos = jnp.broadcast_to(jnp.arange(length, dtype=jnp.int32)[None, :], (b, length))
    key = jnp.where(keep, pos, _I32_MAX)
    val = jnp.where(keep, cps, 0)
    padded = 1 << (length - 1).bit_length()
    if padded != length:
        pad = ((0, 0), (0, padded - length))
        key = jnp.pad(key, pad, constant_values=_I32_MAX)
        val = jnp.pad(val, pad)
    s_key, s_val = sort2(key, val, mesh=mesh)
    new_cps = jnp.where(s_key[:, :length] != _I32_MAX, s_val[:, :length], 0)
    return new_cps, new_lengths
