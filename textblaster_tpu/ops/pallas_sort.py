"""VMEM-resident lexicographic sort — the pipeline's hottest device primitive.

Every duplicate-detection statistic (GopherRepetition line/paragraph/n-gram
dups, FineWeb duplicate lines — gopher_rep.rs:86-196, fineweb_quality.rs:
149-185 equivalents) reduces to "sort per-row (validity, hash, payload)
triples along the row".  XLA's ``lax.sort`` runs its compare-exchange network
with HBM round-trips between passes; this Pallas kernel keeps each block of
rows resident in VMEM for the entire bitonic network, so the ~log²(m)/2
stages cost lane-shuffles (``pltpu.roll``) and VPU selects instead of HBM
bandwidth.

The network is a standard bitonic sorter: two nested ``fori_loop`` calls over
the ``(size, stride)`` stages, the keys held in the output refs between
stages, all shapes static, no gathers (partner access is a pair of circular
lane shifts selected by a per-stage parity mask), which keeps the kernel
inside Mosaic's supported op set.  The loops keep Mosaic's compile time flat
in the row length: unrolled, the ~log2(m)^2/2 stages took 41 s to compile at
8192 lanes and grew ~4x per doubling; looped, 65536 lanes compile in ~7 s.

Rows are independent; the grid tiles the batch dimension.  Row length must be
a power of two (all duplicate tables in :mod:`.stats` are sized to powers of
two by ``pipeline._table_sizes``).

Multi-device: Mosaic ``pallas_call`` custom calls carry no GSPMD partitioning
rule, so a program jitted with multi-device ``in_shardings`` cannot contain a
bare one.  ``sort2``/``sort3`` therefore take the target ``mesh`` explicitly
and wrap the kernel in ``shard_map`` over the data axis — each device sorts
its own row shard in VMEM; rows never cross devices, so no collective beyond
the resharding (if any) is inserted.  Off-TPU or for shapes the kernel cannot
tile, both fall back to ``lax.sort``, which partitions fine under GSPMD.

``TEXTBLAST_PALLAS_INTERPRET=1`` forces the Pallas *interpret* path on any
backend — used by the CPU-mesh tests to exercise the exact shard_map +
pallas_call program the TPU runs, minus the Mosaic lowering.
"""

from __future__ import annotations

import contextlib
import functools
import os
import threading
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # lowering is TPU-only
from jax.sharding import Mesh, PartitionSpec as P

__all__ = [
    "sort2",
    "sort3",
    "pallas_sort2",
    "pallas_sort3",
    "pallas_sort_supported",
    # Shared Pallas helpers (used by ops.pallas_scan as well).
    "ROWS",
    "interpret_forced",
    "pallas_enabled",
    "roll_lanes",
    "COMPILER_PARAMS",
    "count_scan_dispatches",
    "record_scan_dispatch",
    "shard_map",
]

_ROWS = 8  # sublane tile for int32
ROWS = _ROWS

#: Scoped VMEM every kernel may use.  The compiler's default is 16 MiB,
#: which the widest buckets' row tiles overflow (a 3-stream scan at 65536
#: lanes asks for 24 MiB, a 3-key sort for ~54 MiB); v5e has 128 MiB.
COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 * 2**20)

_tls = threading.local()


# --- dispatch accounting ----------------------------------------------------
#
# The dispatch-count gates (tests, the profiler's sentinel) and chip_smoke.py
# count the kernels one traced (bucket, phase) program launches.  Recording
# is thread-local and a no-op unless a count_scan_dispatches() scope is
# active, so the hot path pays one getattr.


def record_scan_dispatch(kind: str) -> None:
    """Count one dispatch of ``kind`` ("fused", "pallas_scan", "lax_scan",
    "pallas_sort", "lax_sort") if a :func:`count_scan_dispatches` scope is
    active."""
    counts = getattr(_tls, "dispatch_counts", None)
    if counts is not None:
        counts[kind] = counts.get(kind, 0) + 1


@contextlib.contextmanager
def count_scan_dispatches():
    """Collect per-kind dispatch counts issued while tracing under this
    scope (trace-time accounting: each recorded dispatch is one device
    kernel/scan/sort in the lowered program)."""
    prev = getattr(_tls, "dispatch_counts", None)
    counts: Dict[str, int] = {}
    _tls.dispatch_counts = counts
    try:
        yield counts
    finally:
        _tls.dispatch_counts = prev

#: Mesh axis the batch dimension is sharded over (parallel.mesh.DATA_AXIS;
#: duplicated here to keep this module importable standalone).
_DATA_AXIS = "data"


def pallas_enabled() -> bool:
    """Global Pallas escape hatch shared by every kernel (sort + scan):
    ``TEXTBLAST_PALLAS=off`` (or ``0``/``false``) forces the lax fallbacks
    everywhere.  Re-read per call so tests can toggle it."""
    return os.environ.get("TEXTBLAST_PALLAS", "").lower() not in ("off", "0", "false")


def interpret_forced() -> bool:
    return bool(os.environ.get("TEXTBLAST_PALLAS_INTERPRET"))


# Back-compat internal alias (older call sites / tests).
_interpret_forced = interpret_forced


def _lex_gt(a: Tuple[jax.Array, ...], b: Tuple[jax.Array, ...]) -> jax.Array:
    """Elementwise lexicographic ``a > b`` over equal-length key tuples."""
    gt = a[-1] > b[-1]
    for x, y in zip(reversed(a[:-1]), reversed(b[:-1])):
        gt = (x > y) | ((x == y) & gt)
    return gt


def roll_lanes(k: jax.Array, shift: int) -> jax.Array:
    """Circular right-roll along the lane axis.  ``pltpu.roll`` requires
    non-negative shifts; callers spell a left-roll by ``s`` as a right-roll
    by ``lanes - s``.  Works under interpret mode too (generic lowering ==
    ``jnp.roll``), so CPU tests run the exact kernel program the TPU lowers."""
    return pltpu.roll(k, shift=shift, axis=1)


_roll = roll_lanes


def _bitonic_kernel(*refs):
    n = len(refs) // 2
    in_refs, out_refs = refs[:n], refs[n:]
    m = in_refs[0].shape[-1]
    for i, o in zip(in_refs, out_refs):
        o[:] = i[:]

    # In-kernel lane index (Pallas kernels cannot capture host constants).
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, m), 1)

    def stage(j, size):
        # One compare-exchange stage of the size-``size`` merge; the keys
        # live in the output refs between stages.
        stride = jnp.right_shift(size, j + 1)
        ks = tuple(o[:] for o in out_refs)
        is_lower = (lane & stride) == 0  # partner is at i+stride
        asc = (lane & size) == 0
        # pltpu.roll requires non-negative shifts; left-roll by `stride`
        # is a right-roll by `m - stride`.
        partners = tuple(
            jnp.where(is_lower, _roll(k, m - stride), _roll(k, stride))
            for k in ks
        )
        lower = tuple(jnp.where(is_lower, k, p) for k, p in zip(ks, partners))
        upper = tuple(jnp.where(is_lower, p, k) for k, p in zip(ks, partners))
        # Select between the two bool comparisons with i1 bitwise logic:
        # Mosaic cannot lower `select_n` with bool *operands* at >1 lane
        # tile (arith.trunci vector<i8> -> vector<i1> is unsupported).
        swap = (asc & _lex_gt(lower, upper)) | (
            jnp.logical_not(asc) & _lex_gt(upper, lower)
        )
        for o, k, p in zip(out_refs, ks, partners):
            o[:] = jnp.where(swap, p, k)
        return size

    def merge(log_size, carry):
        size = jnp.left_shift(jnp.int32(1), log_size)
        jax.lax.fori_loop(0, log_size, stage, size)
        return carry

    jax.lax.fori_loop(1, m.bit_length(), merge, 0)


def _pallas_sort_n(ks: Tuple[jax.Array, ...], interpret: bool = False):
    """Row-wise ascending lexicographic sort of int32 ``[B, m]`` key arrays
    (``m`` a power of two, ``B`` a multiple of 8)."""
    b, m = ks[0].shape
    if m & (m - 1):
        raise ValueError(f"row length {m} is not a power of two")
    if b % _ROWS:
        raise ValueError(f"batch {b} is not a multiple of {_ROWS}")
    spec = pl.BlockSpec((_ROWS, m), lambda i: (i, 0))
    shape = jax.ShapeDtypeStruct((b, m), jnp.int32)
    return pl.pallas_call(
        _bitonic_kernel,
        grid=(b // _ROWS,),
        in_specs=[spec] * len(ks),
        out_specs=[spec] * len(ks),
        out_shape=[shape] * len(ks),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(*(k.astype(jnp.int32) for k in ks))


@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_sort3(
    k1: jax.Array, k2: jax.Array, k3: jax.Array, interpret: bool = False
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    return tuple(_pallas_sort_n((k1, k2, k3), interpret=interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_sort2(
    k1: jax.Array, k2: jax.Array, interpret: bool = False
) -> Tuple[jax.Array, jax.Array]:
    return tuple(_pallas_sort_n((k1, k2), interpret=interpret))


def _env_hatches() -> Tuple[str, ...]:
    """Env hatches that shape a probe verdict — the probe cache keys on
    these so flipping a hatch mid-process (as tests do) re-probes instead
    of serving the verdict cached under the old env."""
    return (
        os.environ.get("TEXTBLAST_PALLAS", ""),
        os.environ.get("TEXTBLAST_PALLAS_INTERPRET", ""),
    )


def _sort_probe(interpret: bool = False) -> bool:
    """One tiny sort against ``jnp.sort``."""
    k = (jax.lax.broadcasted_iota(jnp.int32, (_ROWS, 128), 1) * 37) % 101
    got = pallas_sort2(k, k, interpret=interpret)
    return bool(jnp.array_equal(got[0], jnp.sort(k, axis=1)))


@functools.lru_cache(maxsize=32)
def _probe_cached(env: Tuple[str, ...], backend: str) -> bool:
    """Compile and run one tiny sort on the live backend.  Only a TPU
    lowers Mosaic kernels: other backends answer False (callers take
    ``lax.sort``), and a TPU on which the kernel fails raises — the
    ``TEXTBLAST_PALLAS=off`` hatch is the explicit way to run without it."""
    del env  # participates only in the cache key
    if backend != "tpu":
        return False
    try:
        ok = _sort_probe()
    except Exception as e:
        raise RuntimeError(
            f"Pallas sort kernel failed its probe on TPU: {type(e).__name__}: "
            f"{e}.  Set TEXTBLAST_PALLAS=off to run without it"
        ) from e
    if not ok:
        raise RuntimeError("Pallas sort kernel probe differs from lax.sort on TPU")
    return True


def _probe_backend() -> bool:
    return _probe_cached(_env_hatches(), jax.default_backend())


def pallas_sort_supported() -> bool:
    """Whether the Pallas kernel can run here.  Env-dependent decisions are
    re-read on every call (only the backend lowering probe is cached), so a
    test or embedder toggling the env vars cannot be poisoned by a stale
    cached answer."""
    if not pallas_enabled():
        return False
    if _interpret_forced():
        return True
    return _probe_backend()


#: Widest row the bitonic kernel sorts (the widest bucket); wider rows take
#: ``lax.sort``.  Bounded by VMEM: a 3-key row tile at 65536 lanes asks for
#: ~54 MiB of :data:`COMPILER_PARAMS`' 64 MiB.
_MAX_SORT_LANES = 65536


def _pallas_ok(b: int, m: int) -> bool:
    return (
        pallas_sort_supported()
        and 128 <= m <= _MAX_SORT_LANES
        and not (m & (m - 1))
        and b % _ROWS == 0
        and b > 0
    )


def _data_axis_size(mesh: Optional[Mesh]) -> Optional[int]:
    """Size of the ``data`` mesh axis rows are sharded over; 1 only when the
    whole program is single-device.  None when the mesh has no data axis or
    has other >1 axes alongside data=1 (shard_map over ``data`` would be
    ill-formed / a bare pallas call would need a GSPMD rule; callers then
    use ``lax.sort``, which partitions fine under GSPMD)."""
    if mesh is None:
        return 1
    size = dict(mesh.shape).get(_DATA_AXIS)
    if size == 1 and mesh.devices.size > 1:
        return None
    return size


def _sharded_sort(fn, mesh: Mesh, ks):
    """Run ``fn`` (a pallas sort over the local shard) under shard_map, rows
    sharded along the data axis, each device's shard VMEM-resident."""
    spec = P(_DATA_AXIS, None)
    n = len(ks)
    kwargs = dict(mesh=mesh, in_specs=(spec,) * n, out_specs=(spec,) * n)
    # Replication checking needs vma annotations pallas outputs don't carry;
    # rows are fully sharded, nothing is replicated — disable it.
    return shard_map(fn, check_vma=False, **kwargs)(*ks)


def _dispatch(*ks) -> Tuple[jax.Array, ...]:
    interpret = _interpret_forced()
    return tuple(_pallas_sort_n(ks, interpret=interpret))


def _kernel_sort(ks, mesh: Optional[Mesh]) -> Optional[Tuple[jax.Array, ...]]:
    """The Pallas sort of ``ks`` (shard_mapped over ``mesh``'s data axis
    when it has more than one device), or None when the gates decline it;
    books the dispatch either way."""
    b, m = ks[0].shape
    n_dev = _data_axis_size(mesh)
    if n_dev is not None and n_dev > 1:
        if b % n_dev == 0 and _pallas_ok(b // n_dev, m):
            record_scan_dispatch("pallas_sort")
            return _sharded_sort(_dispatch, mesh, ks)
    elif n_dev == 1 and _pallas_ok(b, m):
        record_scan_dispatch("pallas_sort")
        return _dispatch(*ks)
    record_scan_dispatch("lax_sort")
    return None


def sort3(
    k1: jax.Array,
    k2: jax.Array,
    k3: jax.Array,
    mesh: Optional[Mesh] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Lexicographic row sort: Pallas bitonic network on TPU (shard_mapped
    over ``mesh`` when given), ``lax.sort`` elsewhere."""
    out = _kernel_sort((k1, k2, k3), mesh)
    if out is not None:
        return out
    return jax.lax.sort(
        (k1.astype(jnp.int32), k2.astype(jnp.int32), k3.astype(jnp.int32)),
        dimension=1,
        num_keys=3,
    )


def sort2(
    k1: jax.Array, k2: jax.Array, mesh: Optional[Mesh] = None
) -> Tuple[jax.Array, jax.Array]:
    """Row sort by key ``k1`` carrying ``k2``, deterministic within equal
    keys: ascending ``k2`` order.

    On TPU this is the VMEM bitonic network sorting the full ``(k1, k2)``
    pair.  Elsewhere, when int64 is live (``jax_enable_x64`` — the CPU
    backend enables it for exactly this), the pair is packed into ONE
    ``(k1 << 32) | k2`` int64 operand and sorted with the single-operand
    ``lax.sort``, which XLA:CPU runs ~4.4x faster than the two-operand
    comparator form (measured [9216, 512]: 188ms vs 837ms); unpacked order
    is (k1, then k2) — identical to the stable form for non-negative
    payloads, which every caller passes (iotas or byte lengths).  With x64
    off, the 1-key *stable* two-operand ``lax.sort`` is used."""
    out = _kernel_sort((k1, k2), mesh)
    if out is not None:
        return out
    if jax.config.jax_enable_x64:
        z = (k1.astype(jnp.int64) << 32) | k2.astype(jnp.int64)
        s = jax.lax.sort(z, dimension=1)
        return (
            (s >> 32).astype(jnp.int32),
            (s & jnp.int64(0xFFFFFFFF)).astype(jnp.int32),
        )
    return jax.lax.sort(
        (k1.astype(jnp.int32), k2.astype(jnp.int32)),
        dimension=1,
        num_keys=1,
        is_stable=True,
    )
