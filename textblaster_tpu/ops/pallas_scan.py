"""VMEM-resident blocked associative scans (DFA composition, rolling hashes)
and the fused per-bucket filter megakernel.

The per-row hot scans — DFA matching over nibble-packed transition maps
(:mod:`.dfa`) and the segmented polynomial-hash streams feeding the
repetition/duplicate statistics (:mod:`.stats`) — run under XLA as the
log-depth shift schedule of :mod:`.device`, which materializes every
doubling level's ``[B, L]`` intermediate in HBM.  This module runs the *same
associative ops* as a blocked sequential scan instead: the grid tiles rows
(8-row sublane tiles), each tile stays resident in VMEM while an in-kernel
``fori_loop`` walks fixed-width lane blocks, scanning each block with
Hillis–Steele doubling (circular lane rolls masked to the op identity) and
folding a per-row carry across blocks — intermediate state never
round-trips HBM.

:func:`fused_scan` goes one step further: it lowers *several* independent
scan groups (affine hash streams, segmented adds, DFA compositions, and
whole-row reductions) into ONE ``pallas_call`` that walks the packed
codepoint tile once — each lane block is loaded once and every group's
doubling runs on it in-register, so a phase's worth of filter statistics
costs one kernel dispatch per (bucket, phase) instead of one per scan, and
no intermediate mask or stat stream touches HBM between filters.  Groups
marked ``emit="last"`` write only their final ``[B, 1]`` carry (a per-row
total), never the full scanned stream.

Every op here is int32 ALU with exact wraparound semantics, so the kernels
are **bit-identical** to the lax schedule by integer associativity; the
decision parity vs the host oracle is preserved exactly (the parity fuzz
suites in ``tests/test_pallas_scan.py`` and ``tests/test_fused_scan.py``
stamp this, not approximate it).

Which form computes a statistic is decided here, by shape and capability
only — the same decision on every backend:

* :func:`chain_scan` (the dependency-chained multi-pass kernel) wherever
  :func:`chain_scan_ok` admits the shape; :func:`fused_scan` where a caller
  has no chain form (``fineweb_stats``, and ``structure`` above 8,192
  lanes); both stop at ``_FUSED_MAX_LANES``.  Past that, the staged lax
  path, whose single scans take the per-scan kernels up to ``_MAX_LANES``
  (:func:`pallas_scan_ok`) and the shift schedule beyond.
* ``TEXTBLAST_PALLAS=off`` disables every Pallas kernel — callers take the
  staged lax path.  It is the one way round a kernel: on a TPU a kernel
  that fails its probe raises.
* Non-TPU backends lower no Mosaic kernel and take the staged lax path.
  ``TEXTBLAST_PALLAS_INTERPRET=1`` forces the interpret-mode kernels
  anywhere — how the test suite runs the exact kernel programs on CPU.
* Mosaic ``pallas_call`` custom calls carry no GSPMD partitioning rule, so
  a program jitted with multi-device shardings cannot contain a bare one.
  ``CompiledPipeline`` traces mesh programs under ``mesh_tracing(mesh)``,
  which makes every scan here dispatch through ``shard_map`` over the data
  axis instead (mirroring ``pallas_sort.sort2``) — rows are independent, so
  each device scans its own row shard in VMEM and mesh-sharded programs no
  longer fall back to the lax scans.  The legacy ``mesh_tracing()`` form
  (no mesh object) still declines the kernels outright.
"""

from __future__ import annotations

import contextlib
import functools
import os
import threading
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import Mesh, PartitionSpec as P

from .pallas_sort import (
    COMPILER_PARAMS,
    ROWS,
    count_scan_dispatches,
    interpret_forced,
    pallas_enabled,
    pallas_sort_supported,
    pltpu,
    record_scan_dispatch,
    roll_lanes,
    shard_map,
)

__all__ = [
    "Tap",
    "add_group",
    "affine_group",
    "affine_hash_scan",
    "chain_group",
    "chain_pass",
    "chain_scan",
    "chain_scan_ok",
    "chain_scan_supported",
    "copy_group",
    "count_scan_dispatches",
    "dfa_compose_scan",
    "dfa_group",
    "fused_scan",
    "fused_scan_ok",
    "fused_scan_supported",
    "mesh_tracing",
    "pallas_scan_ok",
    "pallas_scan_supported",
    "probe_kernels",
    "record_scan_dispatch",
    "segmax_group",
]

#: Lanes per in-kernel scan block.  Blocked doubling costs
#: ``L/BLK * (log2(BLK)+1)`` roll+compose levels vs ``L * log2(L)`` for a
#: whole-row scan — 512 keeps the working set one register-friendly tile
#: while shaving the upper doubling levels of long buckets.
_BLK = 512

#: Widest row the per-scan kernels take (the widest bucket).  Each stream's
#: [8, L] int32 block is double-buffered in VMEM: a 3-stream scan at 65536
#: lanes asks for 24 MiB, within :data:`COMPILER_PARAMS`' limit.
_MAX_LANES = 65536

#: The fused kernel holds every group's input *and* output tiles resident at
#: once, so its lane ceiling is tighter than the 2–4-stream per-scan kernels.
_FUSED_MAX_LANES = 16384

#: Mesh axis the batch dimension is sharded over (parallel.mesh.DATA_AXIS;
#: duplicated here to keep this module importable standalone).
_DATA_AXIS = "data"

_tls = threading.local()


@contextlib.contextmanager
def mesh_tracing(mesh=True):
    """Mark the current (thread-local) trace as targeting a multi-device
    sharded program, where a bare ``pallas_call`` is illegal (no GSPMD
    rule).

    Pass the program's :class:`~jax.sharding.Mesh` and every scan kernel in
    this module dispatches through ``shard_map`` over the data axis — each
    device scans its own row shard in VMEM (the ``pallas_sort.sort2``
    pattern).  The legacy forms keep working: ``mesh_tracing()`` / ``True``
    declines the kernels for the scope (no mesh to shard_map over), and
    ``mesh_tracing(False)`` re-enables bare kernels inside an active scope.
    """
    prev = getattr(_tls, "mesh", False)
    _tls.mesh = mesh
    try:
        yield
    finally:
        _tls.mesh = prev


def _mesh_shards() -> Optional[int]:
    """How many data-axis shards the current trace's rows split into.

    1 outside ``mesh_tracing`` (bare kernels are fine); the data-axis size
    under ``mesh_tracing(mesh)``; None when kernels must decline — the
    legacy ``mesh_tracing()`` marker, or a mesh without a usable data axis
    (callers then take the lax scans, which partition fine under GSPMD)."""
    state = getattr(_tls, "mesh", False)
    if state is False or state is None:
        return 1
    if state is True:
        return None
    size = dict(state.shape).get(_DATA_AXIS)
    if size == 1 and state.devices.size > 1:
        return None
    return size


def _current_mesh() -> Optional[Mesh]:
    """The mesh to shard_map kernels over, or None for a bare kernel."""
    state = getattr(_tls, "mesh", False)
    if isinstance(state, Mesh):
        shards = _mesh_shards()
        if shards is not None and shards > 1:
            return state
    return None


def _blk_for(length: int) -> int:
    for blk in (_BLK, 256, 128):
        if length % blk == 0:
            return blk
    raise ValueError(f"row length {length} is not a multiple of 128")


def _scan_body(op: Callable, identities: Sequence[int], refs) -> None:
    """Kernel body: blocked inclusive scan of an n-stream int32 tuple state
    along the lane axis, one VMEM-resident row tile per grid step."""
    n = len(refs) // 2
    in_refs, out_refs = refs[:n], refs[n:]
    rows, length = in_refs[0].shape
    blk = _blk_for(length)
    # In-kernel lane index (Pallas kernels cannot capture host constants).
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, blk), 1)
    idents = tuple(jnp.int32(i) for i in identities)

    def body(i, carry):
        start = i * blk
        xs = tuple(r[:, pl.ds(start, blk)] for r in in_refs)
        d = 1
        while d < blk:
            # Hillis–Steele level: acc[j] = op(acc[j-d], acc[j]).  The roll
            # is circular; wrapped lanes are masked to the op identity.
            shifted = tuple(
                jnp.where(lane >= d, roll_lanes(x, d), ident)
                for x, ident in zip(xs, idents)
            )
            xs = op(shifted, xs)
            d *= 2
        # Fold the running prefix of all earlier blocks ([rows, 1],
        # broadcast) in front of this block's inclusive scan.
        xs = op(carry, xs)
        for r, x in zip(out_refs, xs):
            r[:, pl.ds(start, blk)] = x
        return tuple(x[:, blk - 1 : blk] for x in xs)

    init = tuple(jnp.full((rows, 1), i, jnp.int32) for i in identities)
    jax.lax.fori_loop(0, length // blk, body, init)


def _pallas_scan_tuple(
    op: Callable,
    identities: Sequence[int],
    xs: Tuple[jax.Array, ...],
    interpret: bool,
) -> Tuple[jax.Array, ...]:
    """Row-wise inclusive associative scan of int32 ``[B, L]`` streams.
    ``op`` maps (earlier-tuple, later-tuple) -> tuple with elementwise jnp
    ops only (operands may broadcast ``[B, 1]`` against ``[B, blk]``)."""
    n = len(xs)
    b, length = xs[0].shape

    def kernel(*refs):
        _scan_body(op, identities, refs)

    spec = pl.BlockSpec((ROWS, length), lambda i: (i, 0))
    shape = jax.ShapeDtypeStruct((b, length), jnp.int32)
    return tuple(
        pl.pallas_call(
            kernel,
            grid=(b // ROWS,),
            in_specs=[spec] * n,
            out_specs=[spec] * n,
            out_shape=[shape] * n,
            compiler_params=COMPILER_PARAMS,
            interpret=interpret,
        )(*(x.astype(jnp.int32) for x in xs))
    )


# --- associative ops (must match the lax twins bit-for-bit) -----------------


def _affine_op(xs, ys):
    # Segmented polynomial hash: affine maps h -> m*h + a, composed
    # earlier-then-later; identical to stats._poly_hash_many's compose.
    mx, axs = xs[0], xs[1:]
    my, ays = ys[0], ys[1:]
    return (mx * my,) + tuple(ay + my * ax for ax, ay in zip(axs, ays))


def _add_op(xs, ys):
    # Plain elementwise sum streams — exact by integer associativity, used
    # both for cumulative counts and (emit="last") whole-row totals.
    return tuple(x + y for x, y in zip(xs, ys))


def _dfa_op(n_states: int) -> Callable:
    def op(xs, ys):
        # (b . a)(s) = b[a[s]]: route each of a's nibbles through b —
        # identical to dfa.dfa_states's compose.
        a, b = xs[0], ys[0]
        out = None
        for s in range(n_states):
            nib = (a >> (4 * s)) & 15
            term = ((b >> (nib << 2)) & 15) << (4 * s)
            out = term if out is None else out | term
        return (out,)

    return op


def _dfa_ident(n_states: int) -> int:
    ident = 0
    for s in range(n_states):
        ident |= s << (4 * s)
    return ident


#: Identity for the segmented-max value stream: max(_I32_MIN, x) == x.
_I32_MIN = -(2**31)


def _segmax_op(xs, ys):
    # Segmented running max over (value, reset) pairs — the kernel twin of
    # device._seg_max_op (reset-as-int32, same select/or formulation).
    av, ar = xs
    bv, br = ys
    return (jnp.where(br != 0, bv, jnp.maximum(av, bv)), ar | br)


# --- fused multi-group megakernel -------------------------------------------
#
# A "group" is one independent associative scan over one or more int32
# [B, L] streams.  fused_scan() lowers a list of groups into a single
# pallas_call whose body walks each lane block once and runs every group's
# Hillis–Steele doubling on the in-register tile — so a phase's statistics
# cost one dispatch, and streams a caller only needs reduced (emit="last")
# never touch HBM at full width.


def affine_group(
    m: jax.Array, accs: Sequence[jax.Array], emit: str = "scan"
) -> dict:
    """Shared-multiplier segmented affine-hash group (the fused twin of
    :func:`affine_hash_scan`).  Emits only the accumulator streams — the
    scanned multiplier stays in-register."""
    return {"kind": "affine", "xs": (m,) + tuple(accs), "emit": emit}


def add_group(vals: Sequence[jax.Array], emit: str = "scan") -> dict:
    """Elementwise running-sum group.  ``emit="last"`` yields ``[B, 1]``
    whole-row totals (the fused twin of ``jnp.sum(..., axis=1)``)."""
    return {"kind": "add", "xs": tuple(vals), "emit": emit}


def dfa_group(fns: jax.Array, n_states: int, emit: str = "scan") -> dict:
    """Nibble-packed DFA transition-map composition group (the fused twin of
    :func:`dfa_compose_scan`)."""
    return {"kind": "dfa", "xs": (fns,), "emit": emit, "n_states": n_states}


def _group_spec(g: dict) -> Tuple[Optional[Callable], Tuple[int, ...], int, Tuple[int, ...], bool]:
    """(op, identities, n_operands, emitted stream indices, emit_last).

    ``n_operands`` counts the streams the associative op runs over — for
    chain groups with a ``prep`` this is ``g["n_ops"]`` (what prep returns),
    not the dep count.  ``emit="none"`` behaves like "scan" in-kernel but
    the chain layer stages the stream through scratch instead of HBM."""
    kind = g["kind"]
    n_in = g.get("n_ops", len(g["xs"]))
    emit = g.get("emit", "scan")
    if emit not in ("scan", "last", "none"):
        raise ValueError(f"unknown emit mode {emit!r}")
    emit_last = emit == "last"
    if kind == "affine":
        return _affine_op, (1,) + (0,) * (n_in - 1), n_in, tuple(range(1, n_in)), emit_last
    if kind == "add":
        return _add_op, (0,) * n_in, n_in, tuple(range(n_in)), emit_last
    if kind == "dfa":
        n_states = g["n_states"]
        return _dfa_op(n_states), (_dfa_ident(n_states),), 1, (0,), emit_last
    if kind == "segmax":
        return _segmax_op, (_I32_MIN, 0), 2, (0,), emit_last
    if kind == "copy":
        # Elementwise pass-through (no doubling, no carry): materializes a
        # prep-derived stream so later passes can tap it.
        if emit_last:
            raise ValueError("copy groups cannot emit='last'")
        return None, (0,) * n_in, n_in, tuple(range(n_in)), False
    raise ValueError(f"unknown fused group kind {kind!r}")


def _fused_body(specs, refs) -> None:
    """Kernel body: one pass over the row tile's lane blocks, every group's
    blocked doubling + carry fold run on each in-register block."""
    n_in_total = sum(s[2] for s in specs)
    in_refs, out_refs = refs[:n_in_total], refs[n_in_total:]
    rows, length = in_refs[0].shape
    blk = _blk_for(length)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, blk), 1)

    # Static partition of the flat ref lists back into per-group views.
    group_in, group_out = [], []
    i = j = 0
    for _, _, n_in, emit_idx, _ in specs:
        group_in.append(in_refs[i : i + n_in])
        i += n_in
        group_out.append(out_refs[j : j + len(emit_idx)])
        j += len(emit_idx)

    def body(b_i, carry):
        start = b_i * blk
        new_carry = []
        for g, (op, identities, _, emit_idx, emit_last) in enumerate(specs):
            xs = tuple(r[:, pl.ds(start, blk)] for r in group_in[g])
            idents = tuple(jnp.int32(v) for v in identities)
            d = 1
            while d < blk:
                shifted = tuple(
                    jnp.where(lane >= d, roll_lanes(x, d), ident)
                    for x, ident in zip(xs, idents)
                )
                xs = op(shifted, xs)
                d *= 2
            xs = op(carry[g], xs)
            if not emit_last:
                for r, x_idx in zip(group_out[g], emit_idx):
                    r[:, pl.ds(start, blk)] = xs[x_idx]
            new_carry.append(tuple(x[:, blk - 1 : blk] for x in xs))
        return tuple(new_carry)

    init = tuple(
        tuple(jnp.full((rows, 1), v, jnp.int32) for v in s[1]) for s in specs
    )
    final = jax.lax.fori_loop(0, length // blk, body, init)
    for g, (_, _, _, emit_idx, emit_last) in enumerate(specs):
        if emit_last:
            for r, x_idx in zip(group_out[g], emit_idx):
                r[:, :] = final[g][x_idx]


def _fused_call(groups: Sequence[dict], interpret: bool) -> Tuple[jax.Array, ...]:
    """One pallas_call evaluating every group; returns the flat tuple of
    emitted streams in group order."""
    specs = tuple(_group_spec(g) for g in groups)
    xs = tuple(x for g in groups for x in g["xs"])
    b, length = xs[0].shape

    def kernel(*refs):
        _fused_body(specs, refs)

    row_spec = pl.BlockSpec((ROWS, length), lambda i: (i, 0))
    last_spec = pl.BlockSpec((ROWS, 1), lambda i: (i, 0))
    out_specs: List[pl.BlockSpec] = []
    out_shapes: List[jax.ShapeDtypeStruct] = []
    for _, _, _, emit_idx, emit_last in specs:
        for _ in emit_idx:
            out_specs.append(last_spec if emit_last else row_spec)
            out_shapes.append(
                jax.ShapeDtypeStruct((b, 1) if emit_last else (b, length), jnp.int32)
            )
    return tuple(
        pl.pallas_call(
            kernel,
            grid=(b // ROWS,),
            in_specs=[row_spec] * len(xs),
            out_specs=out_specs,
            out_shape=out_shapes,
            compiler_params=COMPILER_PARAMS,
            interpret=interpret,
        )(*(x.astype(jnp.int32) for x in xs))
    )


def _regroup(groups: Sequence[dict], flat: Sequence[jax.Array]):
    """Split a flat emitted-stream tuple back into per-group tuples."""
    out, i = [], 0
    for g in groups:
        k = len(_group_spec(g)[3])
        out.append(tuple(flat[i : i + k]))
        i += k
    return out


# --- shard_map dispatch -----------------------------------------------------


def _shard_mapped(fn: Callable, mesh: Mesh, xs: Tuple[jax.Array, ...], n_out: int):
    """Run ``fn`` (a bare pallas scan over the local row shard) under
    shard_map, rows sharded along the data axis — the pallas_sort._sharded_sort
    pattern.  Rows are independent, so no collective is inserted."""
    spec = P(_DATA_AXIS, None)
    kwargs = dict(mesh=mesh, in_specs=(spec,) * len(xs), out_specs=(spec,) * n_out)
    # Replication checking needs vma annotations pallas outputs don't carry;
    # rows are fully sharded, nothing is replicated — disable it.
    return shard_map(fn, check_vma=False, **kwargs)(*xs)


def _dispatch_scan_tuple(
    op: Callable, identities: Sequence[int], xs: Tuple[jax.Array, ...]
) -> Tuple[jax.Array, ...]:
    """Mesh-aware dispatch for the per-scan kernels: bare pallas_call on a
    single device, shard_map'd over the data axis under ``mesh_tracing(mesh)``.
    Callers gate on :func:`pallas_scan_ok` first."""
    record_scan_dispatch("pallas_scan")
    interpret = interpret_forced()
    mesh = _current_mesh()
    if mesh is not None:
        def fn(*ks):
            return _pallas_scan_tuple(op, identities, ks, interpret)

        return tuple(_shard_mapped(fn, mesh, tuple(xs), len(xs)))
    return _pallas_scan_tuple(op, identities, tuple(xs), interpret)


# --- support gates ----------------------------------------------------------


def _env_hatches() -> Tuple[str, ...]:
    """The env hatches that shape a probe verdict.  Probe caches key on
    these so flipping a hatch mid-process (as tests do) re-probes instead of
    serving the verdict cached under the old env."""
    return (
        os.environ.get("TEXTBLAST_PALLAS", ""),
        os.environ.get("TEXTBLAST_PALLAS_INTERPRET", ""),
    )


def _probe_error(what: str, detail: str) -> RuntimeError:
    """A probe that fails on a TPU is a fault, not a reason to switch to the
    lax schedule behind the user's back: callers raise this.
    ``TEXTBLAST_PALLAS=off`` is the explicit way to run without the
    kernels."""
    return RuntimeError(
        f"{what} failed its probe on TPU: {detail}.  Set "
        "TEXTBLAST_PALLAS=off to run without the Pallas kernels"
    )


def _scan_probe(interpret: bool = False) -> bool:
    """One tiny per-scan kernel against ``lax.associative_scan``."""
    m = jnp.full((ROWS, 128), 31, jnp.int32)
    a = (jax.lax.broadcasted_iota(jnp.int32, (ROWS, 128), 1) * 7) % 97
    got = _pallas_scan_tuple(_affine_op, (1, 0), (m, a), interpret=interpret)
    want = jax.lax.associative_scan(_affine_op, (m, a), axis=1)
    return all(bool(jnp.array_equal(g, w)) for g, w in zip(got, want))


@functools.lru_cache(maxsize=32)
def _probe_cached(env: Tuple[str, ...], backend: str) -> bool:
    """Compile and run one tiny kernel on the live backend and check it
    against the lax result.  Only a TPU lowers Mosaic kernels: other
    backends answer False (their callers take the lax scans), and a TPU on
    which the probe fails raises."""
    del env  # participates only in the cache key
    if backend != "tpu":
        return False
    try:
        ok = _scan_probe()
    except Exception as e:
        raise _probe_error("Pallas scan kernel", f"{type(e).__name__}: {e}") from e
    if not ok:
        raise _probe_error("Pallas scan kernel", "result differs from lax")
    return True


def _probe_backend() -> bool:
    return _probe_cached(_env_hatches(), jax.default_backend())


def _fused_probe(interpret: bool = False) -> bool:
    """One tiny fused kernel, an emit="last" group included."""
    m = jnp.full((ROWS, 128), 31, jnp.int32)
    a = (jax.lax.broadcasted_iota(jnp.int32, (ROWS, 128), 1) * 7) % 97
    ones = jnp.ones((ROWS, 128), jnp.int32)
    got = _fused_call(
        [affine_group(m, (a,)), add_group((ones,), emit="last")],
        interpret=interpret,
    )
    want_h = jax.lax.associative_scan(_affine_op, (m, a), axis=1)[1]
    return bool(jnp.array_equal(got[0], want_h)) and bool(
        jnp.array_equal(got[1], jnp.full((ROWS, 1), 128, jnp.int32))
    )


@functools.lru_cache(maxsize=32)
def _probe_fused_cached(env: Tuple[str, ...], backend: str) -> bool:
    """Probe the fused megakernel specifically: its emit="last" outputs use
    a narrower BlockSpec the per-scan probe never exercises."""
    del env
    if backend != "tpu":
        return False
    try:
        ok = _fused_probe()
    except Exception as e:
        raise _probe_error("fused scan kernel", f"{type(e).__name__}: {e}") from e
    if not ok:
        raise _probe_error("fused scan kernel", "result differs from lax")
    return True


def _probe_fused() -> bool:
    return _probe_fused_cached(_env_hatches(), jax.default_backend())


def pallas_scan_supported() -> bool:
    """Whether the scan kernels can run here.  Env decisions are re-read per
    call (the backend probe is cached keyed on env hatches + backend);
    False under the legacy mesh-marker trace or a mesh with no usable data
    axis (see :func:`mesh_tracing` — a real mesh shard_maps instead)."""
    if not pallas_enabled():
        return False
    if _mesh_shards() is None:
        return False
    if interpret_forced():
        return True
    return _probe_backend()


def pallas_scan_ok(b: int, length: int) -> bool:
    """Shape + support gate callers use before dispatching to a kernel.
    Under ``mesh_tracing(mesh)`` the row count must split evenly into
    ROWS-aligned per-device shards (the shard_map'd kernel sees ``b/shards``
    rows)."""
    if not pallas_scan_supported():
        return False
    shards = _mesh_shards()
    if shards is None or b <= 0 or b % shards:
        return False
    return (
        (b // shards) % ROWS == 0
        and 128 <= length <= _MAX_LANES
        and length % 128 == 0
    )


def fused_scan_supported() -> bool:
    """Whether the fused megakernel can run here: the scan kernels and its
    own probe."""
    if not pallas_scan_supported():
        return False
    return interpret_forced() or _probe_fused()


def fused_scan_ok(b: int, length: int) -> bool:
    """Gate for :func:`fused_scan` — the per-scan gate plus the fused
    kernel's own probe and tighter VMEM lane ceiling."""
    if not pallas_scan_ok(b, length) or length > _FUSED_MAX_LANES:
        return False
    return fused_scan_supported()


# --- public kernels ---------------------------------------------------------


def dfa_compose_scan(fns: jax.Array, n_states: int) -> jax.Array:
    """Inclusive scan of nibble-packed DFA transition maps along axis 1 —
    the kernel twin of ``dfa.dfa_states``'s <=8-state composition.  Callers
    gate on :func:`pallas_scan_ok` first."""
    (out,) = _dispatch_scan_tuple(
        _dfa_op(n_states), (_dfa_ident(n_states),), (fns,)
    )
    return out


def affine_hash_scan(
    m: jax.Array, accs: Tuple[jax.Array, ...]
) -> Tuple[jax.Array, ...]:
    """Inclusive scan of the shared-multiplier affine hash op — the kernel
    twin of ``stats._poly_hash_many``.  Returns the scanned accumulator
    streams (the scanned multiplier is internal).  Callers gate on
    :func:`pallas_scan_ok` first."""
    identities = (1,) + (0,) * len(accs)
    out = _dispatch_scan_tuple(_affine_op, identities, (m,) + tuple(accs))
    return out[1:]


def fused_scan(groups: Sequence[dict]) -> List[Tuple[jax.Array, ...]]:
    """Evaluate several independent scan groups in ONE kernel pass over the
    row tile — see the module docstring.  Returns one tuple of emitted
    int32 streams per group, in order: ``[B, L]`` scans for ``emit="scan"``
    groups, ``[B, 1]`` per-row totals for ``emit="last"`` groups.  Callers
    gate on :func:`fused_scan_ok` first."""
    record_scan_dispatch("fused")
    interpret = interpret_forced()
    mesh = _current_mesh()
    if mesh is not None:
        xs = tuple(x for g in groups for x in g["xs"])
        sizes = [len(g["xs"]) for g in groups]
        n_out = sum(len(_group_spec(g)[3]) for g in groups)

        def fn(*flat_xs):
            local, i = [], 0
            for g, n in zip(groups, sizes):
                local.append(dict(g, xs=tuple(flat_xs[i : i + n])))
                i += n
            return _fused_call(local, interpret)

        flat = tuple(_shard_mapped(fn, mesh, xs, n_out))
    else:
        flat = _fused_call(groups, interpret)
    return _regroup(groups, flat)


# --- dependency-chained multi-pass megakernel --------------------------------
#
# fused_scan only fuses *independent* groups: a scan whose operands derive
# from another scan's output still pays a separate dispatch with an HBM
# round-trip between the two.  chain_scan lifts that restriction: a chain is
# an ordered list of passes, and a pass's groups may consume earlier passes'
# emitted streams through Tap references — resolved in-kernel against the
# output (or VMEM scratch) row tile, which the earlier pass has fully
# written by the time the later pass's fori_loop starts.  The whole chain is
# ONE pallas_call: the GopherRepetition hash -> n-gram dedup feeders, the
# word-cumsum -> n_words consumers, and the sentence-DFA -> compaction
# handoff each walk the packed tile once instead of 2-4 staged dispatches.
#
# Orientation: every stream (external or emitted) is stored in natural lane
# order.  A pass with reverse=True *walks* the row tile back-to-front: its
# lane blocks are visited last to first and scanned right-to-left in place
# (the doubling reads lane j+d instead of j-d, the carry is lane 0), which
# computes the staged ``rev(scan(rev(x)))`` idiom bit-exactly while emitting
# the result already in natural orientation.  Nothing is flipped inside the
# kernel — Mosaic has no lane reversal.  Prep callables are elementwise, so
# block orientation does not matter to them.
#
# Tap(pass_idx, out_idx, shift, fill) addresses the ``out_idx``-th emitted
# stream (flattened over that pass's groups, all emit modes counted) of an
# earlier pass.  shift=1 reads the stream at the *previous walk position*
# (the staged ``_shift_r`` in a forward pass, ``_shift_l`` in a reverse
# pass), with ``fill`` injected at walk position 0.  Shifted *external*
# operands never need kernel support — callers pre-shift them on the host
# (elementwise, exact).  emit="none" streams are tap-only: they live in VMEM
# scratch (``pltpu.VMEM``) and never touch HBM.


class Tap(NamedTuple):
    """Reference to an earlier chain pass's emitted stream (see above)."""

    pass_idx: int
    out_idx: int
    shift: int = 0
    fill: int = 0


def chain_group(
    kind: str,
    deps: Sequence,
    prep: Optional[Callable] = None,
    n_ops: Optional[int] = None,
    emit: str = "scan",
    n_states: Optional[int] = None,
) -> dict:
    """A chain-pass scan group.  ``deps`` mixes ``[B, L]`` arrays (external
    operands) and :class:`Tap` references; ``prep`` (elementwise, walk-frame)
    maps the loaded dep blocks to the op's ``n_ops`` operand streams —
    omitted, the deps are the operands directly."""
    g = {"kind": kind, "xs": tuple(deps), "emit": emit}
    if n_states is not None:
        g["n_states"] = n_states
    if prep is not None:
        if n_ops is None:
            raise ValueError("chain_group with prep= requires n_ops=")
        g["prep"] = prep
        g["n_ops"] = n_ops
    return g


def segmax_group(v, r, emit: str = "scan") -> dict:
    """Segmented running-max group over (value, reset) — the fused twin of
    ``device.seg_scan_max``."""
    return {"kind": "segmax", "xs": (v, r), "emit": emit}


def copy_group(vals: Sequence, emit: str = "none") -> dict:
    """Elementwise materialization group (no scan): stages prep-derived
    streams so later passes can tap them."""
    return {"kind": "copy", "xs": tuple(vals), "emit": emit}


def chain_pass(groups: Sequence[dict], reverse: bool = False) -> dict:
    """One pass of a :func:`chain_scan` program."""
    return {"groups": list(groups), "reverse": bool(reverse)}


def _chain_plan(passes: Sequence[dict]):
    """Resolve a chain program statically: dedup external arrays (by object
    identity), assign every emitted stream to an output or scratch slot, and
    produce the kernel plan plus the caller-facing result layout."""
    ext_arrays: List[jax.Array] = []
    ext_index: Dict[int, int] = {}
    stream_table: List[List[Tuple[Tuple[str, int], str]]] = []
    out_modes: List[str] = []  # per out slot: "scan" | "last"
    n_scratch = 0
    plan = []
    layout: List[List[List[int]]] = []
    for p_idx, pss in enumerate(passes):
        groups_plan = []
        pass_streams: List[Tuple[Tuple[str, int], str]] = []
        pass_layout: List[List[int]] = []
        for g in pss["groups"]:
            spec = _group_spec(g)
            emit = g.get("emit", "scan")
            deps = []
            for d in g["xs"]:
                if isinstance(d, Tap):
                    if not 0 <= d.pass_idx < p_idx:
                        raise ValueError(
                            f"Tap(pass_idx={d.pass_idx}) must reference an "
                            f"earlier pass (current pass {p_idx})"
                        )
                    if d.shift not in (0, 1):
                        raise ValueError("Tap.shift must be 0 or 1")
                    storage, s_emit = stream_table[d.pass_idx][d.out_idx]
                    if s_emit == "last":
                        raise ValueError("cannot tap an emit='last' stream")
                    deps.append(("s", storage, d.shift, int(d.fill)))
                else:
                    key = id(d)
                    if key not in ext_index:
                        ext_index[key] = len(ext_arrays)
                        ext_arrays.append(d)
                    deps.append(("e", ext_index[key]))
            streams: List[Tuple[str, int]] = []
            g_layout: List[int] = []
            for _ in spec[3]:
                if emit == "none":
                    storage = ("scratch", n_scratch)
                    n_scratch += 1
                else:
                    storage = ("out", len(out_modes))
                    out_modes.append(emit)
                    g_layout.append(storage[1])
                streams.append(storage)
                pass_streams.append((storage, emit))
            groups_plan.append(
                {"spec": spec, "prep": g.get("prep"), "deps": deps, "streams": streams}
            )
            pass_layout.append(g_layout)
        stream_table.append(pass_streams)
        plan.append({"reverse": bool(pss.get("reverse", False)), "groups": groups_plan})
        layout.append(pass_layout)
    if not ext_arrays:
        raise ValueError("chain_scan needs at least one external operand")
    return plan, ext_arrays, out_modes, n_scratch, layout


def _chain_body(plan, refs, n_ext: int, n_out: int) -> None:
    """Kernel body: sequential per-pass fori_loops over one VMEM-resident
    row tile.  Pass p fully writes its emitted streams (output or scratch
    refs) before pass p+1's loop starts, so taps — including block-crossing
    shift taps — read settled data without leaving the kernel."""
    in_refs = refs[:n_ext]
    out_refs = refs[n_ext : n_ext + n_out]
    scratch_refs = refs[n_ext + n_out :]
    rows, length = in_refs[0].shape
    blk = _blk_for(length)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, blk), 1)

    def ref_for(storage):
        return out_refs[storage[1]] if storage[0] == "out" else scratch_refs[storage[1]]

    for pss in plan:
        reverse = pss["reverse"]
        groups = pss["groups"]
        # Walk-order neighbour: the lane one step earlier in the walk sits
        # at natural offset -1 (forward) or +1 (reverse).  A circular right
        # roll by ``step`` brings it into place; ``edge`` is the lane whose
        # neighbour lies outside the block.
        step, edge = (blk - 1, blk - 1) if reverse else (1, 0)

        def block_start(b_i):
            # A reverse pass walks the blocks back to front, each block kept
            # in natural lane order (Mosaic has no lane reversal).
            start = length - (b_i + 1) * blk if reverse else b_i * blk
            return pl.multiple_of(start, blk)

        def load(ref, b_i, shift, fill):
            start = block_start(b_i)
            x = ref[:, pl.ds(start, blk)]
            if shift:
                # Previous-walk-position value across the block edge: the
                # natural lane just before (forward) or after (reverse) this
                # block, read from the 128-lane tile holding it (Mosaic loads
                # only whole tiles at dynamic offsets).  Clamped; unused when
                # b_i == 0, where ``fill`` is injected instead.
                if reverse:
                    tile = jnp.minimum(start + blk, length - 128)
                    pick = slice(0, 1)
                else:
                    tile = jnp.maximum(start - 128, 0)
                    pick = slice(127, 128)
                tile = pl.multiple_of(tile, 128)
                prev = jnp.where(
                    b_i == 0,
                    jnp.full((rows, 1), fill, jnp.int32),
                    ref[:, pl.ds(tile, 128)][:, pick],
                )
                x = jnp.where(lane == edge, prev, roll_lanes(x, step))
            return x

        def body(b_i, carry):
            start = block_start(b_i)
            new_carry = []
            for gi, g in enumerate(groups):
                op, identities, _, emit_idx, emit_last = g["spec"]
                blocks = []
                for d in g["deps"]:
                    if d[0] == "e":
                        blocks.append(load(in_refs[d[1]], b_i, 0, 0))
                    else:
                        blocks.append(load(ref_for(d[1]), b_i, d[2], d[3]))
                prep = g["prep"]
                xs = tuple(prep(*blocks)) if prep is not None else tuple(blocks)
                xs = tuple(jnp.asarray(x).astype(jnp.int32) for x in xs)
                if op is not None:
                    idents = tuple(jnp.int32(v) for v in identities)
                    d2 = 1
                    while d2 < blk:
                        # Walk-earlier lane d2 steps back: j-d2 forward,
                        # j+d2 (a right roll by blk-d2) in reverse.
                        if reverse:
                            valid, sh = lane < blk - d2, blk - d2
                        else:
                            valid, sh = lane >= d2, d2
                        shifted = tuple(
                            jnp.where(valid, roll_lanes(x, sh), ident)
                            for x, ident in zip(xs, idents)
                        )
                        xs = op(shifted, xs)
                        d2 *= 2
                    xs = op(carry[gi], xs)
                if not emit_last:
                    for storage, x_idx in zip(g["streams"], emit_idx):
                        ref_for(storage)[:, pl.ds(start, blk)] = xs[x_idx]
                last = 0 if reverse else blk - 1  # the block's last walk lane
                new_carry.append(
                    tuple(x[:, last : last + 1] for x in xs) if op is not None else ()
                )
            return tuple(new_carry)

        init = tuple(
            tuple(jnp.full((rows, 1), v, jnp.int32) for v in g["spec"][1])
            if g["spec"][0] is not None
            else ()
            for g in groups
        )
        final = jax.lax.fori_loop(0, length // blk, body, init)
        for gi, g in enumerate(groups):
            _, _, _, emit_idx, emit_last = g["spec"]
            if emit_last:
                for storage, x_idx in zip(g["streams"], emit_idx):
                    ref_for(storage)[:, :] = final[gi][x_idx]


def _chain_call(plan, ext_arrays, out_modes, n_scratch: int, interpret: bool):
    b, length = ext_arrays[0].shape
    n_ext = len(ext_arrays)
    n_out = len(out_modes)

    def kernel(*refs):
        _chain_body(plan, refs, n_ext, n_out)

    row_spec = pl.BlockSpec((ROWS, length), lambda i: (i, 0))
    last_spec = pl.BlockSpec((ROWS, 1), lambda i: (i, 0))
    out_specs = [last_spec if m == "last" else row_spec for m in out_modes]
    out_shapes = [
        jax.ShapeDtypeStruct((b, 1) if m == "last" else (b, length), jnp.int32)
        for m in out_modes
    ]
    kwargs = {}
    if n_scratch:
        kwargs["scratch_shapes"] = [pltpu.VMEM((ROWS, length), jnp.int32)] * n_scratch
    return tuple(
        pl.pallas_call(
            kernel,
            grid=(b // ROWS,),
            in_specs=[row_spec] * n_ext,
            out_specs=out_specs,
            out_shape=out_shapes,
            compiler_params=COMPILER_PARAMS,
            interpret=interpret,
            **kwargs,
        )(*(x.astype(jnp.int32) for x in ext_arrays))
    )


def chain_scan(passes: Sequence[dict]) -> List[List[Tuple[jax.Array, ...]]]:
    """Evaluate a dependency-chained multi-pass program in ONE kernel
    dispatch — see the section comment above.  Returns, per pass, one tuple
    of emitted int32 arrays per group (``[B, L]`` for emit="scan", ``[B, 1]``
    for emit="last"; emit="none" streams are tap-only and omitted).  Every
    external operand must be ``[B, L]``.  Callers gate on
    :func:`chain_scan_ok` first."""
    record_scan_dispatch("fused")
    plan, ext_arrays, out_modes, n_scratch, layout = _chain_plan(passes)
    interpret = interpret_forced()
    mesh = _current_mesh()
    if mesh is not None:
        def fn(*xs):
            return _chain_call(plan, tuple(xs), out_modes, n_scratch, interpret)

        flat = tuple(_shard_mapped(fn, mesh, tuple(ext_arrays), len(out_modes)))
    else:
        flat = _chain_call(plan, tuple(ext_arrays), out_modes, n_scratch, interpret)
    return [
        [tuple(flat[s] for s in g_slots) for g_slots in p_layout]
        for p_layout in layout
    ]


def _chain_probe(interpret: bool = False) -> bool:
    """A two-block chain: reverse walks, cross-pass and shift taps, VMEM
    scratch and the segmented-max op, against the staged lax schedule."""
    L = 1024
    iota = jax.lax.broadcasted_iota(jnp.int32, (ROWS, L), 1)
    vals = (iota * 7 + 3) % 97
    reset = ((iota % 64) == 0).astype(jnp.int32)
    m = jnp.where(reset != 0, 0, 1)
    probe_passes = [
            chain_pass([{"kind": "affine", "xs": (m, vals), "emit": "none"}]),
            chain_pass(
                [
                    chain_group(
                        "segmax",
                        (Tap(0, 0), reset),
                        prep=lambda seg, r: (jnp.where(r != 0, seg, 0), r),
                        n_ops=2,
                    )
                ],
                reverse=True,
            ),
            chain_pass(
                [
                    chain_group(
                        "copy",
                        (Tap(1, 0), Tap(0, 0, shift=1, fill=0)),
                        prep=lambda rt, prev: (rt + prev,),
                        n_ops=1,
                        emit="scan",
                    ),
                    chain_group(
                        "add",
                        (Tap(1, 0),),
                        prep=lambda rt: (jnp.where(rt > 50, 1, 0),),
                        n_ops=1,
                        emit="last",
                    ),
                ]
            ),
        ]
    plan, ext, modes, n_scr, layout = _chain_plan(probe_passes)
    flat = _chain_call(plan, tuple(ext), modes, n_scr, interpret=interpret)
    got = [
        [tuple(flat[s] for s in g_slots) for g_slots in p_layout]
        for p_layout in layout
    ]
    seg = jax.lax.associative_scan(_affine_op, (m, vals), axis=1)[1]
    rt = jnp.flip(
        jax.lax.associative_scan(
            _segmax_op,
            (
                jnp.flip(jnp.where(reset != 0, seg, 0), 1),
                jnp.flip(reset, 1),
            ),
            axis=1,
        )[0],
        1,
    )
    prev = jnp.concatenate([jnp.zeros((ROWS, 1), jnp.int32), seg[:, :-1]], 1)
    ok = (
        bool(jnp.array_equal(got[2][0][0], rt + prev))
        and bool(
            jnp.array_equal(
                got[2][1][0],
                jnp.sum(jnp.where(rt > 50, 1, 0), axis=1, keepdims=True),
            )
        )
        and bool(jnp.array_equal(got[1][0][0], rt))
    )
    return ok


@functools.lru_cache(maxsize=32)
def _probe_depfuse_cached(env: Tuple[str, ...], backend: str) -> bool:
    """Probe the chain kernel specifically: reverse-walk passes, cross-pass
    taps, shift taps, VMEM scratch staging, and the
    segmented-max op exercise Mosaic surface the fused probe never
    touches."""
    del env
    if backend != "tpu":
        return False
    try:
        ok = _chain_probe()
    except Exception as e:
        raise _probe_error("chain scan kernel", f"{type(e).__name__}: {e}") from e
    if not ok:
        raise _probe_error("chain scan kernel", "result differs from lax")
    return True


def _probe_depfuse() -> bool:
    return _probe_depfuse_cached(_env_hatches(), jax.default_backend())


def chain_scan_supported() -> bool:
    """Whether the dependency-chain kernel can run here: the fused kernel
    and its own probe."""
    if not fused_scan_supported():
        return False
    return interpret_forced() or _probe_depfuse()


def chain_scan_ok(b: int, length: int) -> bool:
    """Gate for :func:`chain_scan` — the fused gate (its mesh, shape and
    lane rules) plus the chain kernel's own backend probe."""
    return fused_scan_ok(b, length) and chain_scan_supported()


def probe_kernels() -> Dict[str, bool]:
    """Resolve every kernel gate now and return what each says.

    A probe compiles and runs a tiny kernel, which it cannot do from inside
    another program's trace, so callers that trace kernels ask this first
    (``CompiledPipeline`` does on construction); the gates asked later while
    tracing read the cached verdicts."""
    return {
        "pallas_sort": pallas_sort_supported(),
        "pallas_scan": pallas_scan_supported(),
        "fused_scan": fused_scan_supported(),
        "chain_scan": chain_scan_supported(),
    }
