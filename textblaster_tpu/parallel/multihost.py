"""Multi-host execution: per-host document feed over a global device mesh.

The reference scales across machines by pointing more worker processes at one
RabbitMQ broker (SURVEY.md §2.5); the TPU-native equivalent is a
``jax.distributed`` SPMD job.  Every process joins one coordinator, the
``data`` mesh spans all hosts' devices, each host packs and feeds only its
*local* shard of the document stream
(``jax.make_array_from_process_local_data``), the compiled pipeline executes
once globally per round — cross-host traffic rides DCN exactly where XLA
places it — and each host assembles outcomes for its own documents from its
addressable output shards (the results-queue analogue: outputs land where
the documents came from, ready for per-host Parquet shards).

Lockstep contract: multi-host SPMD requires every process to dispatch the
same programs in the same order.  The per-(bucket) round counts are therefore
**negotiated**: every process allgathers how many rounds each bucket needs for
its local documents, and all processes run the columnwise maximum — hosts
with fewer documents pad with empty batches.  No operator-supplied round
budget is needed (the round-3 ``rounds`` argument survives as an optional
assertion).  ``textblast run --coordinator ... --num-processes N
--process-id i`` is the production entry (:func:`run_multihost`): each
process reads its row stripe of the input Parquet, writes a per-host shard
pair, and host 0 merges the shards into the final kept/excluded files after
a global barrier — the "resharded static fan-out" SURVEY.md §2.5 maps the
reference's competing consumers onto.

On real pods the same code runs unchanged: ``initialize()`` picks up the TPU
coordinator, the mesh spans the slice, and ICI/DCN routing is XLA's choice —
no NCCL/MPI analogue to manage (SURVEY.md §2.5's north-star mapping).

Kernels (PR 8): mesh-sharded programs no longer fall back to the lax scans.
``CompiledPipeline._build_fn`` traces them under ``mesh_tracing(mesh)``
(:mod:`textblaster_tpu.ops.pallas_scan`), which makes every scan kernel —
including the fused per-(bucket, phase) megakernel — dispatch through
``shard_map`` over the ``data`` axis, the same pattern ``pallas_sort.sort2``
has always used: each host's devices scan their own row shards in VMEM, and
rows never cross devices so no collective is inserted.  The host-oracle
degradation rung still runs pure Python and never sees Pallas code.

Resilience (PR 4): each lockstep round resolves under the negotiated guard
(:mod:`textblaster_tpu.resilience.negotiated`) — a retryable fault on any
host triggers a jointly-negotiated retry/degradation so transient device
faults no longer kill the job; per-host dead-letter shards merge like
kept/excluded; and the host-0 merge commits every final atomically
(tmp + fsync + rename via :func:`merge_shard_files`), deleting shards only
after every rename lands.

Elastic membership (PR 6): every KV exchange is deadline-bounded
(``--exchange-deadline-s``) and raises a typed
:class:`~textblaster_tpu.errors.PeerFailure` naming the unposted ranks —
dead-versus-slow resolved against renewable KV liveness leases
(``--lease-ttl-s``) — instead of blocking on the old hardcoded 300 s get;
exchange keys are namespaced by epoch and deleted once drained.  With
``--elastic`` the run leaves the lockstep contract entirely
(:func:`_run_elastic`): membership lives in shared-filesystem leases,
survivors adopt a dead rank's input stripe at the membership-epoch bump,
and a SIGKILLed rank can be relaunched to rejoin in place from its
committed cursor — replaying zero completed chunks, outcomes
byte-identical to a fault-free run.

Overlap (PR 9): lockstep rounds ride a K-deep in-flight window where K is
the **min** over every host's ``OverlapConfig.pipeline_depth``, allgathered
once at shard start (:func:`_negotiate_depth`) — depth is lockstep state,
so it cannot be a per-host choice.  Packing runs ahead on the shared
pack-worker pool (including the next phase's survivor chunks, packed while
the current phase's tail rounds still resolve), launches run up to K ahead
of unresolved verdicts, resolves stay strict FIFO, and a negotiated fault
verdict drains the window so every host re-dispatches the younger rounds
in the identical order — serial and overlapped runs stay byte-identical.
"""

from __future__ import annotations

import json
import math
import time
from typing import Iterable, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..config.pipeline import PipelineConfig
from ..data_model import ProcessingOutcome, TextDocument
from ..errors import GangReformed, PeerFailure, ReformationFailed
from ..resilience.membership import (
    DEFAULT_EXCHANGE_DEADLINE_S,
    DEFAULT_LEASE_TTL_S,
    EpochTracker,
    FileMembershipStore,
    KVLeaseStore,
    LeaseHeartbeat,
    _kv_set,
    elect_members,
)
from ..resilience.watchdog import WATCHDOG
from ..utils.events import EVENTS
from ..utils.trace import TRACER
from .mesh import DATA_AXIS, batch_sharding

__all__ = [
    "initialize",
    "global_data_mesh",
    "host_allgather",
    "configure_exchange",
    "bump_exchange_epoch",
    "current_exchange_epoch",
    "ExchangeTransport",
    "KVExchangeTransport",
    "FileLeaseTransport",
    "resolve_exchange_transport",
    "PeerFailure",
    "GangReformed",
    "ReformationFailed",
    "detect_stale_shards",
    "merge_shard_files",
    "run_local_shard",
    "run_multihost",
]


def detect_stale_shards(
    finals: Sequence[str], num_processes: int
) -> List[str]:
    """``*.shard*`` siblings of ``finals`` that THIS run will not produce.

    A prior crashed run with a larger ``--num-processes`` leaves orphan
    ``<final>.shard{j}`` files (j >= num_processes); the old merge silently
    ignored them next to fresh outputs — data loss masquerading as success.
    Returns the sorted offenders so callers can fail fast naming them
    (``--force`` removes them instead).  Expected shards
    (``.shard0..shard{n-1}``) are NOT stale: this run overwrites them.
    """
    import glob

    expected = {
        f"{final}.shard{i}" for final in finals for i in range(num_processes)
    }
    stale = {
        path
        for final in finals
        for path in glob.glob(glob.escape(final) + ".shard*")
        if path not in expected
    }
    return sorted(stale)


def _commit_merged(final: str, shards: Sequence[str]) -> None:
    """Stream the shards' row groups into ``<final>.tmp``, then commit it
    atomically: fsync the tmp, rename over ``final``, fsync the directory —
    the checkpoint-commit discipline (checkpoint.py), so a crash at any
    instant leaves ``final`` either absent or complete, never truncated."""
    import os

    import pyarrow.parquet as pq

    from ..utils.metrics import METRICS

    tmp = final + ".tmp"
    writer = None
    try:
        for s in shards:
            pf = pq.ParquetFile(s)
            if writer is None:
                writer = pq.ParquetWriter(tmp, pf.schema_arrow)
            # Row-group streaming keeps the merge O(row-group) memory
            # however large the global corpus is.
            for g in range(pf.metadata.num_row_groups):
                writer.write_table(pf.read_row_group(g))
    finally:
        if writer is not None:
            writer.close()
    fd = os.open(tmp, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)
    os.replace(tmp, final)
    dfd = os.open(os.path.dirname(os.path.abspath(final)), os.O_RDONLY)
    try:
        os.fsync(dfd)
    finally:
        os.close(dfd)
    METRICS.inc("multihost_merge_commits_total")


def _commit_concat(final: str, part_paths: Sequence[str], schema) -> None:
    """Concatenate Parquet parts into ``final`` atomically, with an
    **explicit schema**: unlike :func:`_commit_merged` (which infers the
    schema from the first shard), zero parts still commit a well-formed
    empty file — the elastic merge must produce valid finals even when
    every row was filtered or a stripe is empty."""
    import os

    import pyarrow.parquet as pq

    from ..utils.metrics import METRICS

    tmp = final + ".tmp"
    writer = pq.ParquetWriter(tmp, schema)
    try:
        for p in part_paths:
            writer.write_table(pq.read_table(p).cast(schema))
    finally:
        writer.close()
    fd = os.open(tmp, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)
    os.replace(tmp, final)
    dfd = os.open(os.path.dirname(os.path.abspath(final)), os.O_RDONLY)
    try:
        os.fsync(dfd)
    finally:
        os.close(dfd)
    METRICS.inc("multihost_merge_commits_total")


def merge_shard_files(
    pairs: Sequence[Tuple[str, Sequence[str]]]
) -> None:
    """Commit every ``(final, shards)`` merge atomically, THEN delete shards.

    Deletion only starts after the last rename has landed: a kill anywhere
    mid-merge leaves every input shard intact, so a re-run (with ``--force``
    to clear the re-produced finals' leftover shards if needed) loses
    nothing.  The old in-place merge consumed shards into a final that a
    crash left truncated — unrecoverable."""
    import os

    for final, shards in pairs:
        _commit_merged(final, shards)
    for _final, shards in pairs:
        for s in shards:
            os.remove(s)


def initialize(
    coordinator: str, num_processes: int, process_id: int
) -> None:
    """Join the distributed job (no-op if this process already joined).

    ``coordinator`` is ``host:port`` of process 0 — the moral equivalent of
    the reference's ``--amqp-addr`` (utils/common.rs:15), except the
    connection carries collectives instead of JSON tasks."""
    if _distributed_initialized():
        return
    jax.distributed.initialize(
        coordinator, num_processes=num_processes, process_id=process_id
    )


def _distributed_initialized() -> bool:
    """True once this process joined a ``jax.distributed`` job."""
    return bool(jax.distributed.is_initialized())


def global_data_mesh() -> "jax.sharding.Mesh":
    """1-D ``data`` mesh over every device of every process.

    Exception: on a multi-process **CPU** job the mesh covers only this
    process's local devices.  XLA:CPU refuses to execute a computation that
    spans processes (INVALID_ARGUMENT "Multiprocess computations aren't
    implemented on the CPU backend"), and the compiled pipeline programs move
    no row between devices, so per-host execution under the negotiated lockstep
    schedule — whose exchanges ride :func:`host_allgather` — is semantically
    identical: each host's "global" batch is simply its own stripe.  On
    accelerator backends the mesh spans the whole job as before and XLA
    routes cross-host traffic over ICI/DCN."""
    from jax.sharding import Mesh

    devices = (
        jax.local_devices()
        if jax.process_count() > 1 and jax.default_backend() == "cpu"
        else jax.devices()
    )
    return Mesh(np.array(devices), (DATA_AXIS,))


class _ExchangeState:
    """Shared round state for the KV-transport lockstep exchanges.

    The old implementation keyed each exchange by a process-local
    ``itertools.count`` — fine while every process lives forever, but a
    relaunched process restarts its counter at 0 and can never re-enter.
    Keys are now namespaced by an **exchange epoch** with the sequence
    number restarting at every epoch boundary, and the epoch advances only
    at points derived from shared round state (:func:`bump_exchange_epoch`
    at each negotiated phase boundary in :func:`run_local_shard`), so any
    process that re-enters at an epoch boundary computes the same key names
    as its peers.  Drained epochs are deleted (see :func:`host_allgather`'s
    hygiene note), so the KV store holds O(1) allgather keys per rank
    instead of growing for the life of the coordinator.
    """

    def __init__(self) -> None:
        self.deadline_s: float = DEFAULT_EXCHANGE_DEADLINE_S
        self.epoch: int = 0
        self.seq: int = 0
        self.lease_store = None  # KVLeaseStore or FileMembershipStore
        # Own (epoch, seq) keys whose epoch drained but whose read-proof
        # (a peer completing a later exchange) hadn't landed yet.
        self.pending_delete: List[Tuple[int, int]] = []
        # Active transport override: ``None`` means the default XLA/KV
        # funnel (:class:`KVExchangeTransport`); :func:`run_multihost`
        # installs a :class:`FileLeaseTransport` for ``--exchange-transport
        # file`` runs.
        self.transport: Optional["ExchangeTransport"] = None


_EXCHANGE = _ExchangeState()

#: Timeout for the post-deadline sweep that names EVERY laggard (not just
#: the first): once the budget is spent, each remaining rank gets one short
#: probe instead of the full deadline again.
_PROBE_TIMEOUT_MS = 1000


def configure_exchange(
    deadline_s: Optional[float] = None,
    lease_store=None,
    reset: bool = True,
    transport: Optional["ExchangeTransport"] = None,
) -> None:
    """Configure the exchange deadline / lease table / transport for this
    process and (by default) restart the epoch/sequence counters — called
    by :func:`run_multihost` on every process at run start, so the shared
    round state begins aligned.  ``transport=None`` selects the default
    XLA/KV funnel (:class:`KVExchangeTransport`)."""
    if deadline_s is not None:
        _EXCHANGE.deadline_s = float(deadline_s)
    _EXCHANGE.lease_store = lease_store
    _EXCHANGE.transport = transport
    if reset:
        _EXCHANGE.epoch = 0
        _EXCHANGE.seq = 0
        _EXCHANGE.pending_delete = []


def current_exchange_epoch() -> int:
    """The epoch namespace current exchanges are keyed under (trace/metrics
    labeling; every process in lockstep reports the same value)."""
    return _EXCHANGE.epoch


def bump_exchange_epoch() -> int:
    """Open the next exchange epoch: the sequence restarts at 0 and the
    drained epoch's last own key is queued for deletion (it is removed once
    a completed exchange in the new epoch proves every peer has read it).
    Must be called in lockstep — :func:`run_local_shard` does so at every
    negotiated phase boundary, the shared round state all processes agree
    on without communicating."""
    if _EXCHANGE.seq > 0:
        _EXCHANGE.pending_delete.append((_EXCHANGE.epoch, _EXCHANGE.seq - 1))
    _EXCHANGE.epoch += 1
    _EXCHANGE.seq = 0
    return _EXCHANGE.epoch


def _ag_key(epoch: int, seq: int, rank: int) -> str:
    return f"textblast/allgather/e{epoch}/s{seq}/{rank}"


def _validate_rows(
    rows: Sequence[Sequence[int]], width: int, *, seq: int, epoch: int
) -> None:
    """Ragged-row guard: every peer's row must match this process's lane
    count.  A shorter/empty row previously fed a ragged list-of-lists to
    ``np.asarray`` (an object-dtype array that crashed far from the cause);
    now the offending rank is named in a typed :exc:`PeerFailure`."""
    for r, row in enumerate(rows):
        if len(row) != width:
            from ..utils.metrics import METRICS

            METRICS.inc("multihost_peer_failures_total")
            raise PeerFailure(
                f"exchange e{epoch}/s{seq}: rank {r} posted {len(row)} "
                f"lane(s) where {width} were expected — a desynchronized "
                "or corrupted peer (ragged allgather row)",
                missing_ranks=(r,),
                seq=seq,
                epoch=epoch,
            )


def _raise_peer_failure(
    missing: Sequence[int],
    *,
    seq: int,
    epoch: int,
    deadline_s: float,
    transport_error: str = "",
) -> None:
    """Deadline expired with peers unposted: resolve dead-vs-slow against
    the lease table and raise the typed error naming both lists.
    ``transport_error`` carries the coordination service's own words (a
    heartbeat/UNAVAILABLE teardown reads very differently from a plain
    DEADLINE_EXCEEDED, and operators grep for it)."""
    from ..utils.metrics import METRICS

    dead: List[int] = []
    store = _EXCHANGE.lease_store
    if store is not None:
        try:
            dead, _slow = store.resolve_liveness(missing)
        except Exception:  # pragma: no cover - lease table best-effort
            dead = []
    METRICS.inc("multihost_peer_failures_total")
    TRACER.instant(
        "peer_failure",
        {"seq": seq, "epoch": epoch, "missing": list(missing),
         "dead": list(dead)},
    )
    if EVENTS.enabled:
        EVENTS.emit("peer_failure", missing_ranks=list(missing),
                    dead_ranks=list(dead), seq=seq, epoch=epoch)
    detail = (
        f"; liveness leases mark rank(s) {list(dead)} dead "
        f"(lease older than {store.ttl_s:g}s)"
        if dead and store is not None
        else "; every missing rank still holds a fresh liveness lease "
        "(slow or wedged, not dead)"
        if store is not None
        else ""
    )
    transport = (
        f"; last transport error: {transport_error[:300]}"
        if transport_error
        else ""
    )
    raise PeerFailure(
        f"exchange e{epoch}/s{seq} deadline ({deadline_s:g}s) expired; "
        f"rank(s) {list(missing)} never posted{detail}{transport}",
        missing_ranks=missing,
        dead_ranks=dead,
        seq=seq,
        epoch=epoch,
    )


class ExchangeTransport:
    """Pluggable carrier for the lockstep exchanges (:func:`host_allgather`).

    Two implementations:

    * :class:`KVExchangeTransport` (``kv``, the default) — the XLA
      collective / ``jax.distributed`` coordination-service KV funnel,
      byte-for-byte the pre-seam behavior.  Diagnoses a peer death fast
      (typed :exc:`PeerFailure`) but cannot outlive it: the coordination
      service force-terminates every healthy task ~90-100 s after a peer
      stops heartbeating, regardless of what the survivor does.
    * :class:`FileLeaseTransport` (``file``) — exchange slots on the shared
      filesystem next to :class:`FileMembershipStore`'s liveness leases.
      The gang is not coupled through ``jax.distributed`` at all, so under
      ``--survive-peer-loss`` a peer death triggers gang *reformation*
      (fence → elect → adopt) instead of gang death.
    """

    name: str = "?"

    def members(self) -> Tuple[int, ...]:
        """Current member ranks, in exchange row order."""
        raise NotImplementedError

    def allgather(self, arr: np.ndarray) -> np.ndarray:
        """Exchange one flat int64 row per member; returns
        ``[n_members, len(arr)]`` in :meth:`members` order."""
        raise NotImplementedError


class KVExchangeTransport(ExchangeTransport):
    """The default transport: XLA collective on accelerator backends, the
    ``jax.distributed`` coordination-service key-value store on multi-process
    CPU jobs (where XLA cannot run the collective at all) — the transport
    that already carries barriers and heartbeats.

    KV-path failure semantics (the exchange *deadline*, PR 6): the whole
    exchange gets ``configure_exchange``'s budget (default
    ``DEFAULT_EXCHANGE_DEADLINE_S``; ``--exchange-deadline-s``) instead of
    the old hardcoded 300 s per rank.  On expiry, the remaining ranks are
    each probed briefly so every laggard is identified, peer liveness is
    resolved against the KV lease table, and a typed :exc:`PeerFailure`
    names the exchange coordinates, the missing ranks, and which of them
    hold expired leases (dead) versus fresh ones (slow).  Rows are also
    validated for raggedness (:func:`_validate_rows`).  The accelerator
    path is XLA's collective and carries no host-side deadline — there the
    coordination-service heartbeat teardown remains the backstop.

    Hygiene: completing exchange ``s`` proves every peer has read exchange
    ``s-1`` (each peer posts ``s`` only after fully reading ``s-1``), so
    this process's ``s-1`` key — and any queued keys from drained epochs —
    are deleted after each completed exchange.  The KV table stays O(1) per
    rank for the life of the coordinator."""

    name = "kv"

    def members(self) -> Tuple[int, ...]:
        return tuple(range(jax.process_count()))

    def allgather(self, arr: np.ndarray) -> np.ndarray:
        n = jax.process_count()
        if n == 1:
            return arr.reshape(1, -1)
        if jax.default_backend() != "cpu":
            from jax.experimental import multihost_utils

            return np.asarray(
                multihost_utils.process_allgather(arr), dtype=np.int64
            ).reshape(n, -1)
        from jax._src import distributed

        client = distributed.global_state.client
        me = jax.process_index()
        epoch, seq = _EXCHANGE.epoch, _EXCHANGE.seq
        _EXCHANGE.seq += 1
        _kv_set(
            client,
            _ag_key(epoch, seq, me),
            ",".join(str(int(x)) for x in arr),
        )
        deadline_s = _EXCHANGE.deadline_s
        t0 = time.monotonic()
        own_row = [int(x) for x in arr]
        rows: List[List[int]] = []
        missing: List[int] = []
        transport_error = ""
        for r in range(n):
            if r == me:
                rows.append(own_row)
                continue
            remaining_ms = int((deadline_s - (time.monotonic() - t0)) * 1000)
            timeout_ms = (
                remaining_ms if remaining_ms > 0 else _PROBE_TIMEOUT_MS
            )
            try:
                raw = client.blocking_key_value_get(
                    _ag_key(epoch, seq, r), timeout_ms
                )
            except Exception as e:  # DEADLINE_EXCEEDED / service teardown
                missing.append(r)
                rows.append([])
                transport_error = str(e)
                continue
            rows.append([int(x) for x in raw.split(",")] if raw else [])
        if missing:
            _raise_peer_failure(
                missing, seq=seq, epoch=epoch, deadline_s=deadline_s,
                transport_error=transport_error,
            )
        _validate_rows(rows, len(own_row), seq=seq, epoch=epoch)
        drained = [_ag_key(e, s, me) for e, s in _EXCHANGE.pending_delete]
        _EXCHANGE.pending_delete.clear()
        if seq > 0:
            drained.append(_ag_key(epoch, seq - 1, me))
        for key in drained:
            try:
                client.key_value_delete(key)
            except Exception:  # pragma: no cover - hygiene is best-effort
                pass
        return np.asarray(rows, dtype=np.int64)


_KV_TRANSPORT = KVExchangeTransport()


class FileLeaseTransport(ExchangeTransport):
    """File-lease exchange transport: slots on the shared filesystem.

    Each exchange ``(epoch, seq)`` is a directory of per-rank slot files
    under the membership root (``exchange/e{E}/s{S}/rank{r}.json``), posted
    with the same atomic tmp+rename discipline as the liveness leases and
    naming the poster's incarnation so a fenced zombie's late post is
    ignored.  Reads are deadline-bounded polls over the member set; hygiene
    mirrors the KV rules — completing exchange ``s`` proves every member
    read ``s-1``, so the own ``s-1`` slot and any queued drained-epoch
    slots are deleted after each completed exchange.

    With ``survive=True``, a deadline expiry runs the reformation protocol
    (fence the missing ranks' incarnations, elect the survivor set via
    shared-filesystem proposals, bump the membership and exchange epochs)
    and raises :exc:`GangReformed` for the driver to replay the interrupted
    exchange over the survivors; without it, the expiry raises the same
    typed :exc:`PeerFailure` the KV transport does.

    Unlike the KV transport this one never touches ``jax.distributed`` —
    that is the point: the coordination service force-terminates healthy
    tasks ~90-100 s after a peer death, so survivability requires a carrier
    the dead rank cannot take down."""

    name = "file"

    def __init__(
        self,
        store: FileMembershipStore,
        rank: int,
        num_processes: int,
        *,
        survive: bool = False,
        heartbeat: Optional[LeaseHeartbeat] = None,
        poll_s: float = 0.02,
    ) -> None:
        self.store = store
        self.rank = int(rank)
        self._members: Tuple[int, ...] = tuple(range(int(num_processes)))
        self.survive = bool(survive)
        self.heartbeat = heartbeat
        self.poll_s = float(poll_s)
        self.dead_ranks: List[int] = []
        self.reformations = 0
        self.tracker = EpochTracker(rank)
        self.tracker.observe(self._members)

    def members(self) -> Tuple[int, ...]:
        return self._members

    def _self_check(self, epoch: int, seq: int) -> None:
        """Zombie/solo guard, run at every exchange: a rank whose own
        incarnation got fenced (a peer reformed without it), or whose lease
        went stale (heartbeat dead, filesystem gone), must terminate typed —
        on a shrunk gang there may be no peer left to notice, so hanging on
        slots that can never fill is the alternative."""
        if self.store.self_fenced():
            raise ReformationFailed(
                f"rank {self.rank} (incarnation {self.store.incarnation}) "
                f"found itself fenced at exchange e{epoch}/s{seq}: a peer "
                "reformed the gang without it",
                rank=self.rank,
            )
        hb_dead = self.heartbeat is not None and self.heartbeat.failed
        if not hb_dead and not self.store.my_lease_fresh():
            # Stale-but-present lease of this very incarnation: a long
            # GIL hold (an XLA compile) can starve the heartbeat thread
            # past the TTL, and on wake the main thread may reach this
            # check before the overdue renewal lands.  That is a
            # scheduling artifact, not a death — nobody fenced us (checked
            # above) — so renew in place.  Gone, or overwritten by a
            # successor incarnation, stays fatal below; and if a peer
            # fenced us in the same gap, the next exchange's fence check
            # terminates this rank typed.
            d = self.store.read_leases().get(self.rank)
            if (
                d is not None
                and d.get("incarnation") == self.store.incarnation
            ):
                try:
                    self.store.post()
                except OSError:
                    pass  # renewal refused: fall through to the fatal raise
        if hb_dead or not self.store.my_lease_fresh():
            raise ReformationFailed(
                f"rank {self.rank} failed its liveness self-check at "
                f"exchange e{epoch}/s{seq}: "
                + (
                    "the lease heartbeat died"
                    if hb_dead
                    else "its own lease file is stale or gone "
                    f"(ttl {self.store.ttl_s:g}s)"
                )
                + " — no quorum can include this process",
                rank=self.rank,
            )

    def allgather(self, arr: np.ndarray) -> np.ndarray:
        epoch, seq = _EXCHANGE.epoch, _EXCHANGE.seq
        _EXCHANGE.seq += 1
        self._self_check(epoch, seq)
        own_row = [int(x) for x in arr]
        mem = self._members
        if len(mem) == 1:
            # Solo gang: nothing to exchange, but the self-check above still
            # ran — a double-death (reform down to one member, then lose the
            # filesystem lease) fails typed instead of hanging on peers that
            # can never post.
            return np.asarray([own_row], dtype=np.int64)
        self.store.post_exchange_slot(
            epoch, seq, ",".join(str(int(x)) for x in arr)
        )
        deadline_s = _EXCHANGE.deadline_s
        t0 = time.monotonic()
        got = {self.rank: own_row}
        while len(got) < len(mem):
            for r in mem:
                if r in got:
                    continue
                slot = self.store.read_exchange_slot(epoch, seq, r)
                if slot is None:
                    continue
                if self.store.is_fenced(
                    int(slot.get("rank", r)), str(slot.get("incarnation", ""))
                ):
                    continue  # a fenced zombie's late post
                raw = str(slot.get("data", ""))
                got[r] = [int(x) for x in raw.split(",")] if raw else []
            if len(got) == len(mem):
                break
            if time.monotonic() - t0 >= deadline_s:
                missing = [r for r in mem if r not in got]
                if self.survive:
                    self._reform(missing, epoch, seq)  # raises GangReformed
                _raise_peer_failure(
                    missing, seq=seq, epoch=epoch, deadline_s=deadline_s,
                    transport_error=(
                        "file-lease exchange slot(s) never appeared"
                    ),
                )
            self._self_check(epoch, seq)
            time.sleep(self.poll_s)
        rows = [got[r] for r in mem]
        _validate_rows(rows, len(own_row), seq=seq, epoch=epoch)
        for e, s in _EXCHANGE.pending_delete:
            self.store.delete_exchange_slot(e, s)
        _EXCHANGE.pending_delete.clear()
        if seq > 0:
            self.store.delete_exchange_slot(epoch, seq - 1)
        return np.asarray(rows, dtype=np.int64)

    def _reform(self, missing: Sequence[int], epoch: int, seq: int) -> None:
        """The reformation protocol, run by every survivor blocked at the
        same ``(epoch, seq)``: fence the missing ranks' incarnations, elect
        the new member set through shared-filesystem proposals
        (:func:`elect_members`), bump the membership epoch (eviction
        accounting) and the exchange epoch (slot-namespace hygiene — the
        failed exchange's own slot is queued for deletion by the bump), and
        raise :exc:`GangReformed` so the driver replays the interrupted
        exchange over the survivors."""
        from ..utils.metrics import METRICS

        dead: List[int] = []
        try:
            dead, _slow = self.store.resolve_liveness(missing)
        except Exception:  # pragma: no cover - lease table best-effort
            dead = []
        TRACER.instant(
            "gang_reform_start",
            {"epoch": epoch, "seq": seq, "missing": list(missing),
             "dead": list(dead)},
        )
        if EVENTS.enabled:
            # The detection is a peer failure whether or not the gang
            # survives it; the journal names it first so the causal chain
            # reads peer_failure -> gang_reform_start -> gang_reformation.
            EVENTS.emit("peer_failure", missing_ranks=list(missing),
                        dead_ranks=list(dead), epoch=epoch, seq=seq)
            EVENTS.emit("gang_reform_start", epoch=epoch, seq=seq,
                        missing=list(missing), dead=list(dead))
        members, newly_dead = elect_members(
            self.store,
            self._members,
            missing,
            tag=f"e{epoch}s{seq}",
            deadline_s=_EXCHANGE.deadline_s,
        )
        self._members = members
        self.dead_ranks.extend(
            r for r in newly_dead if r not in self.dead_ranks
        )
        self.reformations += 1
        self.tracker.observe(members)
        new_exchange_epoch = bump_exchange_epoch()
        self.store.write_roster(
            members, self.tracker.epoch, new_exchange_epoch
        )
        METRICS.inc("multihost_gang_reformations_total")
        METRICS.set("multihost_reformation_epoch", float(self.tracker.epoch))
        if EVENTS.enabled:
            # Records emitted from here on carry the new gang generation.
            EVENTS.set_incarnation(self.reformations)
        TRACER.instant(
            "gang_reformation",
            {"membership_epoch": self.tracker.epoch,
             "exchange_epoch": new_exchange_epoch,
             "members": list(members), "dead": list(newly_dead)},
        )
        if EVENTS.enabled:
            EVENTS.emit("gang_reformation", epoch=self.tracker.epoch,
                        world_size=len(members), members=list(members),
                        dead=list(newly_dead))
        print(
            f"reform[{self.rank}]: exchange e{epoch}/s{seq} deadline "
            f"({_EXCHANGE.deadline_s:g}s) expired; fenced rank(s) "
            f"{list(newly_dead)} (lease table marked {list(dead)} dead); "
            f"reformed to members {list(members)} at membership epoch "
            f"{self.tracker.epoch}",
            flush=True,
        )
        raise GangReformed(
            f"rank(s) {list(newly_dead)} fenced at exchange e{epoch}/s{seq};"
            f" members now {list(members)} (membership epoch "
            f"{self.tracker.epoch})",
            members=members,
            dead_ranks=newly_dead,
            epoch=self.tracker.epoch,
        )

    def maybe_admit(self) -> None:
        """Phase-boundary admission sweep: observe posted join requests
        and grow the gang through the reformation machinery.

        Called at every negotiated phase boundary (via
        :func:`maybe_admit_joiners` from :func:`run_local_shard`) — the one
        point where no rounds are in flight, so growing the member set
        cannot strand a launched chunk.  The sweep is collective: a
        joiner's request file may be visible to some members before others
        (shared-filesystem propagation), so members first allgather the
        join ranks each observed and act on the **union** — either every
        member runs the admission election or none does.  Success bumps
        the membership and exchange epochs, publishes the grown roster
        (``roster.json`` — how the joiner learns it is in), clears the
        handled requests, and raises :exc:`GangReformed` so the driver
        replays from the phase boundary with the window depth re-negotiated
        over the grown gang.  A joiner that died mid-admission is fenced by
        the election and the gang proceeds un-grown (no raise); a *member*
        death during the sweep folds into the ordinary reformation retry
        inside :func:`elect_members`."""
        lanes = self.collect_join_lanes()
        if lanes is None:
            return
        if len(self._members) == 1:
            # Solo gang: nobody to agree with, the local view is the union.
            union = [r for r in lanes if r >= 0]
        else:
            merged = self.allgather(np.asarray(lanes, dtype=np.int64))
            union = [int(x) for x in np.asarray(merged).ravel()]
        self.admit_union(union)

    def collect_join_lanes(self) -> Optional[List[int]]:
        """Local half of the admission sweep: the fixed-width join-lane row
        this rank would post (observed joiner ranks, ``-1`` padding to
        ``_JOIN_LANES``), split out of :meth:`maybe_admit` so the
        speculative phase barrier can piggyback it on the combined
        barrier exchange instead of spending a dedicated allgather.
        Returns ``None`` when admission is off (no ``--survive-peer-loss``
        — the caller then posts no admission lanes at all, keeping the
        vector width identical on every host)."""
        if not self.survive:
            return None
        local = sorted(
            r for r in self.store.read_join_requests()
            if r not in self._members
        )[:_JOIN_LANES]
        return local + [-1] * (_JOIN_LANES - len(local))

    def admit_union(self, ranks) -> None:
        """Gang half of the admission sweep: act on the agreed joiner set.

        ``ranks`` is the flattened merge of every member's join lanes
        (``-1`` padding and already-member ranks are filtered here, so
        callers hand over raw allgather rows).  Every member reaches this
        with the identical union — from :meth:`maybe_admit`'s own
        allgather or from lanes piggybacked on the barrier exchange — so
        either every member runs the admission election or none does.
        Raises :exc:`GangReformed` on successful admission, exactly as
        :meth:`maybe_admit` always did."""
        if not self.survive:
            return
        from ..resilience.faults import FAULTS
        from ..utils.metrics import METRICS

        epoch = _EXCHANGE.epoch
        union = sorted(
            {int(x) for x in ranks if int(x) >= 0} - set(self._members)
        )
        if not union:
            return
        FAULTS.fire("multihost.join.admit")
        TRACER.instant(
            "gang_admission_start",
            {"exchange_epoch": epoch, "joiners": list(union)},
        )
        if EVENTS.enabled:
            EVENTS.emit("gang_admission_start", epoch=epoch,
                        joiners=list(union))
        members, newly_dead = elect_members(
            self.store,
            self._members,
            (),
            tag=f"join.e{epoch}",
            deadline_s=_EXCHANGE.deadline_s,
            joiners=union,
        )
        admitted = [r for r in members if r not in self._members]
        for r in union:
            # Handled either way: the roster supersedes an admitted
            # request, and a fenced joiner's request must not re-trigger
            # the sweep at every subsequent boundary.
            self.store.clear_join_request(r)
        if not admitted and not newly_dead:
            print(
                f"admit[{self.rank}]: joiner(s) {list(union)} fenced "
                "mid-admission; gang proceeds un-grown",
                flush=True,
            )
            return
        self._members = members
        self.dead_ranks.extend(
            r for r in newly_dead if r not in self.dead_ranks
        )
        if newly_dead:
            # A member died during the admission sweep: that is a
            # reformation folded into the same election.
            self.reformations += 1
            METRICS.inc("multihost_gang_reformations_total")
        self.tracker.observe(members)
        new_exchange_epoch = bump_exchange_epoch()
        METRICS.set("multihost_reformation_epoch", float(self.tracker.epoch))
        self.store.write_roster(
            members, self.tracker.epoch, new_exchange_epoch
        )
        TRACER.instant(
            "gang_admission",
            {"membership_epoch": self.tracker.epoch,
             "exchange_epoch": new_exchange_epoch,
             "members": list(members), "admitted": admitted,
             "dead": list(newly_dead)},
        )
        if EVENTS.enabled:
            EVENTS.emit("gang_admission", epoch=self.tracker.epoch,
                        world_size=len(members), admitted=list(admitted),
                        dead=list(newly_dead))
        print(
            f"admit[{self.rank}]: admitted rank(s) {admitted} at phase "
            f"boundary (exchange epoch {epoch}); members now "
            f"{list(members)} at membership epoch {self.tracker.epoch}",
            flush=True,
        )
        raise GangReformed(
            f"rank(s) {admitted} admitted at exchange epoch {epoch}; "
            f"members now {list(members)} (membership epoch "
            f"{self.tracker.epoch})",
            members=members,
            dead_ranks=tuple(newly_dead),
            epoch=self.tracker.epoch,
        )


#: Admission fan-in per phase boundary: the union allgather carries a
#: fixed-width vector of observed joiner ranks (-1 padding), so at most
#: this many joiners are admitted per boundary — later requests simply
#: wait for the next one.
_JOIN_LANES = 4


def maybe_admit_joiners() -> None:
    """Phase-boundary hook for :func:`run_local_shard`: run the admission
    sweep when the active exchange transport supports one (the file-lease
    transport under ``--survive-peer-loss``); a no-op everywhere else, so
    the KV path's exchange sequence is untouched."""
    admit = getattr(_EXCHANGE.transport, "maybe_admit", None)
    if admit is not None:
        admit()


def request_admission(
    store: FileMembershipStore,
    *,
    deadline_s: float = DEFAULT_EXCHANGE_DEADLINE_S,
    poll_s: float = 0.05,
) -> dict:
    """Joiner-side half of the admission protocol (file-lease transport).

    Renews this rank's liveness lease, posts an incarnation-stamped join
    request next to it, and waits for the running gang to admit it at a
    phase boundary.  The joiner deliberately does NOT drive the election
    (:func:`elect_members` fences silent candidates — a joiner running the
    full driver could fence healthy members on its own deadline); it
    **echoes**: whenever a gang member's ``join.*`` proposal includes this
    rank, the joiner posts the identical proposal, making itself a
    unanimous candidate without ever suspecting anyone.  Admission is
    learned from ``roster.json`` (published by every admitting member
    after the epoch bump); the returned roster dict carries ``members``,
    ``membership_epoch`` and ``exchange_epoch``, so the caller can align
    its exchange state with the gang before its first collective.

    Raises :exc:`ReformationFailed` when the gang fenced this incarnation
    (the died-mid-admission verdict, seen from the inside: the gang
    proceeded un-grown) or when nothing admits it within ``deadline_s``.
    """
    store.post()
    store.post_join_request()
    t0 = time.monotonic()
    while True:
        roster = store.read_roster()
        if roster is not None and store.rank in {
            int(r) for r in roster.get("members", ())
        }:
            return roster
        if store.self_fenced():
            raise ReformationFailed(
                f"rank {store.rank} (incarnation {store.incarnation}) was "
                "fenced while awaiting admission: the gang proceeded "
                "un-grown",
                rank=store.rank,
            )
        for tag, proposed in store.peer_proposals("join.").items():
            if store.rank in proposed and (
                store.read_proposal(tag, store.rank) is None
            ):
                store.post_proposal(tag, proposed)
        if time.monotonic() - t0 >= deadline_s:
            raise ReformationFailed(
                f"rank {store.rank}'s join request was not admitted within "
                f"{deadline_s:g}s (no phase boundary reached, or the gang "
                "is gone)",
                rank=store.rank,
            )
        store.post()  # keep the lease fresh: a stale joiner is invisible
        time.sleep(poll_s)


def resolve_exchange_transport(choice: str, survive_peer_loss: bool) -> str:
    """Resolve ``--exchange-transport {auto,kv,file}`` to a concrete name.

    ``auto`` picks ``file`` when ``--survive-peer-loss`` is set (reformation
    needs a carrier that outlives the coordination service) and ``kv``
    otherwise (lowest exchange latency; XLA collective on accelerators).
    Explicit ``kv`` + survive is a contradiction and fails fast."""
    from ..errors import PipelineError

    c = str(choice or "auto").lower()
    if c not in ("auto", "kv", "file"):
        raise PipelineError(
            f"exchange transport must be one of auto/kv/file, got {choice!r}"
        )
    if c == "auto":
        c = "file" if survive_peer_loss else "kv"
    if survive_peer_loss and c != "file":
        raise PipelineError(
            "--survive-peer-loss requires the file-lease exchange transport"
            " (the kv transport rides the jax coordination service, which "
            "force-terminates survivors ~90-100s after a peer death); pass "
            "--exchange-transport file or auto"
        )
    return c


def host_allgather(vec: np.ndarray) -> np.ndarray:
    """Allgather one small int vector per process; returns ``[n_proc, len]``.

    Every lockstep exchange in this module (round schedules, fault verdicts,
    merged histograms, the totals barrier) funnels through here, and from
    here through the configured :class:`ExchangeTransport` — the XLA/KV
    funnel by default (:class:`KVExchangeTransport`, byte-for-byte the
    pre-seam behavior), or :class:`FileLeaseTransport` when
    :func:`run_multihost` installed one via :func:`configure_exchange`.
    Callers must invoke it in lockstep (the contract this module enforces
    anyway): slots are ``(epoch, seq, rank)`` tuples from the shared round
    state (:class:`_ExchangeState`), and the blocking read doubles as the
    barrier — no process proceeds until every member has posted its row."""
    arr = np.asarray(vec, dtype=np.int64).ravel()
    transport = _EXCHANGE.transport
    if transport is None:
        transport = _KV_TRANSPORT
    # One post per process per call, whatever the vector width — the
    # counter the batched verdict exchange drives down (a piggybacked
    # K-flag verdict vector is ONE post where K per-round flags were K).
    from ..utils.metrics import METRICS
    from ..utils.telemetry import TELEMETRY

    METRICS.inc("multihost_exchange_posts_total")
    t0 = time.perf_counter()
    try:
        return transport.allgather(arr)
    finally:
        dt = time.perf_counter() - t0
        METRICS.inc("multihost_exchange_post_seconds_total", dt)
        if TELEMETRY.enabled:
            METRICS.observe_hdr(
                "exchange_post_latency_seconds", int(dt * 1e6)
            )


def host_allgather_obj(obj) -> list:
    """Allgather one small JSON-serializable object per process.

    Rides :func:`host_allgather` (the only transport this module trusts):
    the object is JSON-encoded to UTF-8 bytes, lengths are exchanged first
    so every process can pad its byte vector to the common width, then the
    padded vectors are exchanged and each row decoded back.  Two collectives
    per call — callers must invoke it in lockstep, like every other
    exchange here.  Sized for metrics snapshots (a few KiB), not bulk data:
    each byte travels as an int64 lane.  The row count follows the active
    transport's member set, not ``jax.process_count()`` — on a reformed
    file-transport gang only survivors contribute rows (a reformation
    *between* the two collectives raises :exc:`GangReformed` from the
    second, so callers replay the whole closure, never decode with stale
    lengths)."""
    data = json.dumps(obj, sort_keys=True).encode("utf-8")
    lens = host_allgather(np.array([len(data)]))[:, 0]
    width = max(1, int(lens.max()))
    buf = np.zeros(width, dtype=np.int64)
    if data:
        buf[: len(data)] = np.frombuffer(data, dtype=np.uint8)
    rows = host_allgather(buf)
    return [
        json.loads(
            bytes(rows[i, : int(lens[i])].astype(np.uint8)).decode("utf-8")
        )
        for i in range(rows.shape[0])
    ]


def _local_stats(out: dict) -> dict:
    """This process's rows of every ``data``-sharded output, in row order,
    moved in ONE bundled transfer (per-key np.asarray is a synchronous
    device round trip each — see assemble_batch)."""
    shard_tree = {
        k: [
            s.data
            for s in sorted(
                v.addressable_shards, key=lambda s: s.index[0].start or 0
            )
        ]
        for k, v in out.items()
    }
    if WATCHDOG.enabled:
        # Deadline-bounded readiness poll before the blocking transfer: a
        # wedged lockstep dispatch raises StallError here, which the
        # negotiated guard converts to a local fault verdict — the gang
        # jointly drains/retries instead of riding the exchange deadline.
        WATCHDOG.wait_device_ready(
            "device_fetch",
            (s for parts in shard_tree.values() for s in parts),
        )
    host_tree = jax.device_get(shard_tree)
    return {
        k: (np.concatenate(parts, axis=0) if parts else np.empty((0,)))
        for k, parts in host_tree.items()
    }


def _timed_stats(out: dict, bucket: int, phase: int, rows: int) -> dict:
    """``_local_stats`` with device-wait attribution.

    The lockstep path fetches shard trees directly — it never goes through
    the single-host ``_device_fetch`` seam — so this wrapper is where its
    blocked-on-device time lands in ``stage_device_wait_seconds`` (the
    counter the window decomposition subtracts from window stall) and,
    when profiling is on, in the per-(bucket, phase) device-time
    histograms.  A faulted fetch still books the wait (matching
    ``_device_fetch``'s ``finally``) but records no dispatch sample."""
    from ..utils.metrics import METRICS
    from ..utils.profiler import PROFILER

    t0 = time.perf_counter()
    ok = False
    try:
        stats = _local_stats(out)
        ok = True
    finally:
        dt = time.perf_counter() - t0
        METRICS.inc("stage_device_wait_seconds", dt)
        if ok and PROFILER.enabled:
            PROFILER.record_dispatch(bucket, phase, rows, dt)
    return stats


def _negotiate_max(needed_local: np.ndarray) -> np.ndarray:
    """Columnwise max of every process's per-bucket round counts.

    Lockstep safety: EVERY process must run the same number of rounds per
    bucket — a unilateral decision while peers enter ``fn()`` would hang the
    job until the coordinator heartbeat tears it down.  One small allgather
    makes the schedule global and deterministic."""
    return host_allgather(needed_local).max(axis=0).astype(np.int32)


def _negotiate_depth(local_depth: int, local_spec_depth: Optional[int] = None):
    """Joint in-flight window depth: the MIN over every host's configured
    ``OverlapConfig.pipeline_depth`` (one extra startup allgather, zero
    per-round exchanges).

    Depth is lockstep state: every host must launch and resolve the
    identical round sequence with the identical interleave, so a host
    configured shallower than its peers pulls the whole gang down to what
    it can sustain — min, not max, because depth K means K launches may
    run ahead of unresolved verdicts and the most conservative host bounds
    what all hosts may assume about each other's dispatch order.  A
    mismatch is legal (hosts merely negotiate down) but surfaced in the
    trace so an operator can see which rank capped the window.

    With ``local_spec_depth`` the post carries a second lane — the
    speculative cross-phase dispatch depth — negotiated by the same min
    rule in the same allgather, and the return becomes ``(depth, spec)``.
    Speculation is lockstep state for the same reason depth is: the
    combined barrier exchange replaces the classic three-post phase
    boundary, so every host must agree whether the protocol is on (joint
    spec > 0) before the first barrier.  One host running with
    ``TEXTBLAST_SPECULATE=off`` (local spec 0) therefore pins the whole
    gang to the classic barrier.  The 1-arg form stays a 1-lane post
    returning a bare int — existing call sites and their wire traffic are
    untouched."""
    from ..utils.metrics import METRICS

    lanes = [max(1, int(local_depth))]
    if local_spec_depth is not None:
        lanes.append(max(0, int(local_spec_depth)))
    merged = host_allgather(np.array(lanes, dtype=np.int32))
    depths = merged[:, 0]
    joint = max(1, int(depths.min()))
    METRICS.set("multihost_negotiated_depth", float(joint))
    if int(depths.max()) != joint:
        TRACER.instant(
            "window_depth_mismatch",
            {"host_depths": [int(d) for d in depths], "joint": joint},
        )
        if EVENTS.enabled:
            EVENTS.emit("window_depth_mismatch", joint=joint,
                        host_depths=[int(d) for d in depths])
    if local_spec_depth is None:
        return joint
    spec = max(0, int(merged[:, 1].min()))
    METRICS.set("multihost_speculate_depth", float(spec))
    return joint, spec


def _align_trace_clocks() -> None:
    """Cross-host trace clock handshake (one allgather at run start).

    Each process's tracer stamps events from a private ``perf_counter``
    origin, so per-host trace files loaded into one Perfetto session show
    hosts skewed by their process start times.  Every process allgathers
    the wall-clock time of its tracer origin; the **minimum** becomes the
    run's shared origin and each tracer shifts its timestamps by
    ``own_wall - min_wall`` (recording the offset and every host's wall in
    a ``trace_clock_offset`` metadata event).  The exchange is
    unconditional — it is a collective, and a host without ``--trace``
    still must participate or the gang desynchronizes; only the local
    ``align`` is gated on tracing being enabled.  Alignment is as good as
    the hosts' wall clocks (NTP-grade), which is what a cross-host
    timeline needs — spans are still *timed* by each host's monotonic
    clock."""
    wall = TRACER.wall_at_origin_us()
    walls = host_allgather(np.array([wall], dtype=np.int64))[:, 0]
    if TRACER.enabled:
        origin = int(walls.min())
        TRACER.align(
            wall - origin,
            args={
                "origin_wall_us": origin,
                "host_walls_us": [int(w) for w in walls],
            },
        )


def run_local_shard(
    config: PipelineConfig,
    docs: Sequence[TextDocument],
    bucket: Optional[int] = None,
    rounds: Optional[int] = None,
    mesh=None,
    pipeline=None,
    buckets: Optional[Sequence[int]] = None,
    fault_guard: bool = True,
) -> List[ProcessingOutcome]:
    """Run this host's documents through the globally-sharded pipeline.

    Every participating process must call this with the same ``config`` and
    bucket set (lockstep).  The number of rounds per bucket is negotiated by
    allgather (:func:`_negotiate_max`), so hosts never need a pre-agreed
    budget; passing ``rounds`` turns it into an assertion (ValueError if the
    negotiated schedule exceeds it — the round-3 interface).  Documents
    longer than every bucket run the host oracle locally (the usual counted
    fallback).

    Returns outcomes for **this host's** documents only.

    Phased short-circuit, lockstep-safe (VERDICT r3 item 3): for EVERY phase
    the per-bucket round counts are renegotiated over allgather from the
    hosts' surviving document counts, so all processes dispatch the identical
    program sequence while later phases run on shrinking, repacked survivor
    batches — the device analogue of the executor short-circuit that the
    single-controller path already had.

    With ``fault_guard`` (default) every round resolves under the
    :class:`~textblaster_tpu.resilience.negotiated.NegotiatedGuard`: a
    retryable fault on ANY host triggers a jointly-negotiated retry of the
    round on EVERY host (shared zero-jitter backoff), then a
    jointly-negotiated degradation of the round's documents to the host
    oracle; a per-bucket breaker latches persistently bad buckets onto the
    oracle for the rest of the run.  The guard's only lockstep addition is
    one 1-int allgather per round resolution — the fault-free program
    sequence is unchanged.

    Overlap (PR 9): rounds ride a K-deep in-flight window, where K is the
    min over every host's ``OverlapConfig.pipeline_depth``, allgathered
    once at shard start (:func:`_negotiate_depth` — depth is lockstep
    state, so it cannot be a per-host choice).  Packing runs ahead on the
    shared pack pool (rounds r+1..r+K pack while round r executes, and the
    next phase's full survivor chunks pack while this phase's tail rounds
    still resolve), launches run up to K ahead of unresolved verdicts, and
    resolves stay strict FIFO — so serial (depth 1 / ``--no-overlap``) and
    overlapped runs produce byte-identical outcome streams.  A negotiated
    fault verdict drains the window: every host discards its launched-ahead
    results and the younger rounds re-dispatch fresh at their own resolve,
    keeping the post-verdict global program order identical on every host.

    Speculative cross-phase dispatch (this PR): at each non-final phase
    barrier, up to ``spec_depth`` next-phase rounds launch before the tail
    verdicts resolve (``launch_speculative``), and the tail verdict batch,
    join-admission sweep, and next-phase schedule negotiation collapse
    into ONE exchange post (``resolve_barrier`` — two on phases a badwords
    step keeps from previewing).  The joint speculation depth is the min
    over every host's local value (``--speculate-depth``, default the
    window depth; ``TEXTBLAST_SPECULATE=off`` posts 0 and pins the whole
    gang to the classic barrier).  Any joint fault voids the speculated
    launches and the piggybacked freight identically on every host —
    speculation moves launches, never outcomes, so on/off runs stay
    byte-identical.
    """
    import os
    from collections import deque

    from ..ops.pipeline import CompiledPipeline, maybe_warmup, record_occupancy
    from ..orchestration import execute_processing_pipeline
    from ..resilience.negotiated import NegotiatedGuard
    from ..resilience.retry import classify_error
    from ..utils.metrics import METRICS
    from ..utils.overlap import shared_pack_pool

    from ..ops.packing import PACK_MARGIN

    if buckets is None:
        buckets = (bucket,) if bucket is not None else (2048,)
    buckets = tuple(sorted(buckets))
    mesh = mesh if mesh is not None else global_data_mesh()
    # How many processes the program's mesh spans: jax.process_count() on
    # accelerators, 1 under the multi-process-CPU local-mesh fallback
    # (global_data_mesh) where each host runs its own full-width program.
    n_proc = len({d.process_index for d in mesh.devices.flat})
    if pipeline is None:
        pipeline = CompiledPipeline(
            config, buckets=buckets, mesh=mesh, multihost=True
        )
        # Warm before the first lockstep round: every host compiles (or AOT-
        # cache-loads) the identical program set up front, so no host hits a
        # first-dispatch compile stall mid-round while its peers wait at the
        # allgather.
        maybe_warmup(pipeline)
    # Per-bucket local row counts: each host feeds its 1/n_proc stripe of the
    # bucket's global batch.  Under uniform geometry every bucket resolves to
    # the old single ``pipeline.batch_size // n_proc``.
    geo = pipeline.geometry
    local_for = {
        b: max(1, geo.batch_for(b) // n_proc) if b in geo.buckets
        else max(1, pipeline.batch_size // n_proc)
        for b in buckets
    }

    def partition(ds: Sequence[TextDocument]):
        by_bucket: dict = {b: [] for b in buckets}
        over: List[TextDocument] = []
        for d in ds:
            for b in buckets:
                if len(d.content) <= b - PACK_MARGIN:
                    by_bucket[b].append(d)
                    break
            else:
                over.append(d)
        return by_bucket, over

    if pipeline._route_dict_scripts:
        # Dictionary-script docs take the host oracle (ops/pipeline.py
        # __init__ note); they join the local fallback list, which runs
        # outside the lockstep schedule and so needs no negotiation.
        # Single pass: ``docs`` may be any iterable, and one content scan
        # per document suffices.
        from ..utils.cjk import has_dict_script

        routed, kept = [], []
        for d in docs:
            (routed if has_dict_script(d.content) else kept).append(d)
        docs = kept
    else:
        routed = []
    current, fallback = partition(docs)
    fallback.extend(routed)

    sh2 = batch_sharding(mesh, 2)
    sh1 = batch_sharding(mesh, 1)

    guard = NegotiatedGuard(config.resilience, buckets=buckets) if fault_guard else None
    degraded: List[TextDocument] = []

    # Joint window depth: a collective, so EVERY host negotiates it even
    # when its own overlap is off (its local depth is then 1, pulling the
    # whole gang to serial — min rule).
    overlap_cfg = getattr(config, "overlap", None)
    overlapped = (
        overlap_cfg is not None
        and overlap_cfg.enabled
        and os.environ.get("TEXTBLAST_NO_OVERLAP") != "1"
    )
    local_depth = max(1, overlap_cfg.pipeline_depth) if overlapped else 1
    # Local speculative cross-phase dispatch depth: how many next-phase
    # rounds this host is willing to launch at a phase barrier before the
    # tail verdicts resolve.  Defaults to the window depth; capped per-host
    # by --speculate-depth and killed by TEXTBLAST_SPECULATE=off (or a
    # single-phase pipeline, where there is no barrier to speculate
    # across).  The joint value is min-negotiated alongside the window
    # depth — one host opting out pins the whole gang to the classic
    # three-post barrier, because the barrier protocol itself is lockstep
    # state.
    spec_env = os.environ.get("TEXTBLAST_SPECULATE", "").strip().lower()
    spec_cfg = getattr(overlap_cfg, "speculate_depth", None)
    if (
        not overlapped
        or spec_env in ("off", "0", "false")
        or len(pipeline.phases) < 2
    ):
        local_spec = 0
    elif spec_cfg is None:
        local_spec = local_depth
    else:
        local_spec = max(0, int(spec_cfg))
    while True:
        try:
            depth, spec_depth = _negotiate_depth(local_depth, local_spec)
            break
        except GangReformed:
            # The reformation already bumped the exchange epoch; just
            # replay the negotiation over the survivor set.
            continue
    # Pack off the critical path: the process-wide pool (shared with the
    # single-host packers) packs rounds ahead of the launch cursor and the
    # next phase's survivor chunks behind the resolve cursor.  Serial mode
    # (--no-overlap) packs inline on this thread, exactly as before.
    pool = shared_pack_pool(max(1, overlap_cfg.pack_workers)) if overlapped else None

    def launch(local, ph, speculative=False):
        """Guarded async launch.  Returns ``(out, launch_fault)``: a
        retryable launch failure is captured, not raised — the verdict has
        to convene at resolve time so every host takes the same branch.
        ``speculative`` marks a cross-phase launch fired at a phase
        barrier before the tail verdicts resolved (its own chaos seam,
        ``multihost.speculate``)."""
        from ..resilience.faults import FAULTS

        if guard is None:
            if speculative:
                FAULTS.fire("multihost.speculate")
            return pipeline.dispatch_lockstep(local, ph, sh2, sh1), False
        try:
            if speculative:
                FAULTS.fire("multihost.speculate")
            return pipeline.dispatch_lockstep(local, ph, sh2, sh1), False
        except BaseException as e:  # noqa: BLE001 — classifier decides
            if classify_error(e) != "retryable":
                raise
            WATCHDOG.escalated(e)
            return None, True

    def phase_rewrites(ph: int) -> bool:
        # Only C4QualityFilter rewrites survivor content mid-phase (line
        # drops); every other device step decides and stamps.  Phases
        # without it preserve lengths, so each survivor's bucket is its
        # round's bucket and the re-partition length scan is skipped.
        return any(
            pipeline.device_steps[i].type == "C4QualityFilter"
            for i in pipeline.phases[ph]
        )

    outcomes: List[ProcessingOutcome] = []
    n_phases = len(pipeline.phases)
    lockstep_t0 = time.perf_counter()
    # Cross-phase pre-pack handoff: pack futures for the next phase's full
    # survivor chunks, keyed (bucket, round), built while this phase's tail
    # rounds are still resolving.
    prepack_next: dict = {}
    # Speculative cross-phase dispatch (joint spec_depth > 0): entries
    # ``{"batch", "out", "fault"}`` keyed (bucket, round) for next-phase
    # rounds LAUNCHED at this phase's barrier, before the tail verdicts
    # resolved.  Chunks are only speculated once fully confirmed (a full
    # next_current chunk exists ⇒ its documents' phase membership is
    # resolved); the optimism lives in the piggybacked round COUNTS, which
    # include still-pending tail survivors and are voided with the
    # launches on any joint fault.  ``carried_schedule`` hands the
    # barrier-negotiated next-phase schedule across the phase edge.
    spec_next: dict = {}
    carried_schedule = None
    for phase in range(n_phases):
        # Exchange epochs advance with the negotiated phase sequence — a
        # piece of round state every process derives identically without
        # communicating (phases are negotiated in lockstep), which is what
        # lets KV exchange keys be namespaced deterministically instead of
        # by a process-local counter (see _ExchangeState).
        bump_exchange_epoch()
        last = phase == n_phases - 1
        rewrites = (not last) and phase_rewrites(phase)
        # State that must survive a gang reformation re-entry of this phase:
        # resolved rounds' outcomes/survivors stand (outcomes, next_current,
        # degraded only ever grow), and the pre-pack handoff for the NEXT
        # phase keys on next_current chunk indexes, which are persistent.
        next_current: dict = {b: [] for b in buckets}
        next_over: List[TextDocument] = []
        prepack_done = {b: 0 for b in buckets}
        inherited = prepack_next  # this phase's pre-packed chunks
        prepack_next = {}
        # Speculative launches made FOR this phase at the previous barrier,
        # and the schedule negotiated there (piggybacked on the combined
        # barrier exchange) — both None'd out by a reformation, which
        # replays through the classic negotiation instead.
        spec_inflight = spec_next
        spec_next = {}
        carried = carried_schedule
        carried_schedule = None
        reformed = False
        while True:
            plan: Optional[List[tuple]] = None
            consumed: List[bool] = []
            try:
                # Admission sweep before any round launches: a posted join
                # request is observed here, at the phase boundary — the one
                # point with no rounds in flight — and a successful
                # admission raises GangReformed into the handler below, so
                # the re-entry re-negotiates the window depth over the
                # grown gang exactly as a shrink reformation would.
                if carried is not None:
                    # The previous phase's speculative barrier already
                    # negotiated this phase's schedule (round counts
                    # piggybacked on the tail verdict post) and ran the
                    # admission sweep off the same vector — re-posting
                    # either here would break the lockstep exchange
                    # sequence, since peers carried too.
                    schedule = carried
                    carried = None
                else:
                    maybe_admit_joiners()
                    if reformed:
                        # Survivor re-entry: re-negotiate the window depth
                        # (and speculation depth) over the reformed gang (a
                        # member with a different local depth may have
                        # died).  Fault-free runs never take this branch,
                        # so the exchange sequence they emit is unchanged;
                        # the reformation itself already bumped the
                        # exchange epoch, so no re-bump here.
                        depth, spec_depth = _negotiate_depth(
                            local_depth, local_spec
                        )
                        reformed = False
                    needed_local = np.array(
                        [
                            math.ceil(len(current[b]) / local_for[b])
                            for b in buckets
                        ],
                        dtype=np.int32,
                    )
                    schedule = _negotiate_max(needed_local)
                if (
                    phase == 0
                    and rounds is not None
                    and int(schedule.sum()) > rounds
                ):
                    raise ValueError(
                        f"shard needs {int(schedule.sum())} rounds "
                        f"(local {int(needed_local.sum())}), got {rounds}"
                    )

                # The phase's launch plan, in the negotiated (bucket,
                # round) order every host shares.  The negotiated count
                # covers the local ceil by construction; a violation would
                # silently strand a tail chunk once launches run ahead of
                # resolves, so fail loudly instead.
                plan = []
                for b, n_rounds in zip(buckets, schedule):
                    local_batch = local_for[b]
                    assert int(n_rounds) * local_batch >= len(current[b]), (
                        f"bucket {b}: negotiated {int(n_rounds)} round(s) "
                        f"of {local_batch} rows cannot cover "
                        f"{len(current[b])} local documents — geometry "
                        "round-up stranded a tail chunk"
                    )
                    for r in range(int(n_rounds)):
                        plan.append(
                            (
                                b,
                                r,
                                current[b][
                                    r * local_batch : (r + 1) * local_batch
                                ],
                            )
                        )
                consumed = [False] * len(plan)
                packs: dict = {}  # plan index -> PackedBatch (or future)

                def ensure_packed(j, plan=plan, packs=packs):
                    """Keep rounds j..j+K packed (or packing) ahead of the
                    launch cursor; cross-phase pre-packed chunks are
                    adopted as-is."""
                    for k in range(j, min(j + depth + 1, len(plan))):
                        if k in packs:
                            continue
                        kb, kr, kchunk = plan[k]
                        if (kb, kr) in spec_inflight:
                            # Speculatively launched at the previous
                            # barrier: the packed batch lives in the spec
                            # entry and is adopted at this round's launch
                            # slot — packing it again would be pure waste.
                            continue
                        pre = inherited.pop((kb, kr), None)
                        if pre is not None:
                            packs[k] = pre
                        elif pool is not None:
                            packs[k] = pool.submit(
                                pipeline._timed_pack, kchunk,
                                batch_size=local_for[kb], max_len=kb,
                            )
                        else:
                            packs[k] = pipeline._timed_pack(
                                kchunk, batch_size=local_for[kb], max_len=kb
                            )

                def absorb(src_bucket, alive):
                    """Fold one resolved round's survivors into the next
                    phase — incrementally, in resolve order (== the old
                    flat-list partition order), so full next-phase chunks
                    can pack while this phase still has rounds in flight
                    (the next ``_negotiate_max`` needs only the final
                    counts, exchanged after the drain as before)."""
                    if last:
                        return
                    if rewrites:
                        # Survivor content may have been rewritten (C4) —
                        # re-route by current length.  Growth past every
                        # bucket is impossible (rewrites only drop chars),
                        # but route defensively anyway.
                        for d in alive:
                            for nb in buckets:
                                if len(d.content) <= nb - PACK_MARGIN:
                                    next_current[nb].append(d)
                                    break
                            else:
                                next_over.append(d)
                    else:
                        next_current[src_bucket].extend(alive)
                    if pool is None:
                        return
                    for nb in buckets if rewrites else (src_bucket,):
                        lb = local_for[nb]
                        k = prepack_done[nb]
                        # A full chunk's document prefix is final once
                        # appended (later resolves only extend the list),
                        # so it can pack now.
                        while (k + 1) * lb <= len(next_current[nb]):
                            prepack_next[(nb, k)] = pool.submit(
                                pipeline._timed_pack,
                                next_current[nb][k * lb : (k + 1) * lb],
                                batch_size=lb, max_len=nb,
                            )
                            k += 1
                        prepack_done[nb] = k

                window: deque = deque()

                def void_speculation():
                    """Joint rollback of every speculative launch: this
                    phase's not-yet-adopted entries and the next phase's
                    barrier launches discard their results (the packed
                    batches stay — chunk contents are final) and
                    re-dispatch fresh, on every host identically, because
                    the verdict that triggers the void is allgathered.
                    The cross-barrier extension of the window drain's
                    first-fault-authoritative contract."""
                    n = sum(
                        1
                        for e in list(spec_inflight.values())
                        + list(spec_next.values())
                        if e["out"] is not None or e["fault"]
                    )
                    for e in list(spec_inflight.values()) + list(
                        spec_next.values()
                    ):
                        e["out"] = None
                        e["fault"] = False
                    if n:
                        METRICS.inc("multihost_voided_rounds_total", n)
                        TRACER.instant(
                            "window_drained",
                            {"replayed": 0, "pending": 0, "voided": n,
                             "phase": phase, "cause": "speculation_void"},
                        )
                        if EVENTS.enabled:
                            EVENTS.emit("speculation_void", voided=n,
                                        phase=phase, cause="drain")

                def drain_window():
                    """Joint fault verdict convened at the window front:
                    discard this host's launched-ahead results so every
                    host's program order after the verdict is the same
                    ``[retry(r), r+1, ...]`` — the younger rounds
                    re-dispatch fresh at their own resolve.  Speculative
                    launches are part of the launched-ahead state and void
                    with the window."""
                    n = sum(
                        1 for e in window if e["out"] is not None or e["fault"]
                    )
                    for e in window:
                        e["out"] = None
                        e["fault"] = False
                    if n:
                        METRICS.inc(
                            "multihost_window_replayed_rounds_total", n
                        )
                    TRACER.instant(
                        "window_drained",
                        {"replayed": n, "pending": len(window),
                         "phase": phase, "cause": "fault"},
                    )
                    void_speculation()

                def resolve_front():
                    """Block for the OLDEST in-flight round and assemble it
                    — under the negotiated verdict protocol when the guard
                    is on.  Strict FIFO at every depth: the window moves
                    waits, never sequence."""
                    entry = window.popleft()
                    TRACER.counter("lockstep_window", len(window))
                    local, ph, eb = (
                        entry["batch"], entry["phase"], entry["bucket"]
                    )
                    t0 = time.perf_counter()
                    try:
                        with TRACER.span(
                            "lockstep_resolve", {"bucket": eb, "phase": ph}
                        ):
                            rows = local.batch_size
                            if guard is None:
                                stats = _timed_stats(
                                    entry["out"], eb, ph, rows
                                )
                            else:
                                stats = guard.run_round(
                                    eb,
                                    dispatch=lambda: (
                                        pipeline.dispatch_lockstep(
                                            local, ph, sh2, sh1
                                        )
                                    ),
                                    fetch=lambda out: _timed_stats(
                                        out, eb, ph, rows
                                    ),
                                    inflight=entry["out"],
                                    launch_fault=entry["fault"],
                                    on_fault=drain_window,
                                )
                                if stats is None:
                                    # Jointly degraded: every host routes
                                    # this round's chunk to the host
                                    # oracle; none re-enters the program.
                                    degraded.extend(local.docs)
                                    consumed[entry["plan_idx"]] = True
                                    return
                            po, alive = pipeline.assemble_phase(
                                local, stats, ph
                            )
                            outcomes.extend(po)
                            absorb(eb, alive)
                            consumed[entry["plan_idx"]] = True
                    finally:
                        METRICS.inc(
                            "multihost_window_stall_seconds_total",
                            time.perf_counter() - t0,
                        )

                def resolve_batch(n):
                    """Drain the ``n`` oldest in-flight rounds under ONE
                    batched verdict post (``NegotiatedGuard.
                    negotiate_batch``): every round's local flag is fetched
                    first, then all flags ride a single allgather vector
                    instead of one scalar post each.  ``n`` is derived from
                    the negotiated plan and depth, so every host batches
                    the identical rounds.  With no guard or a single round
                    this IS ``resolve_front`` — depth-1 behavior stays
                    byte-identical by construction.  On the first joint
                    fault the younger rounds' piggybacked flags are void
                    (measured on launched-ahead state the drain discards):
                    they return to the window, the faulted round re-enters
                    the serial retry protocol with its verdict pre-resolved
                    (``prior_fault``), and the remainder resolves
                    round-at-a-time — the exact drain ordering of the
                    unbatched path."""
                    if guard is None or n <= 1:
                        for _ in range(n):
                            resolve_front()
                        return
                    entries = [window.popleft() for _ in range(n)]
                    TRACER.counter("lockstep_window", len(window))
                    t0 = time.perf_counter()
                    faults, stats_list = [], []
                    for entry in entries:
                        fault, st = bool(entry["fault"]), None
                        if not fault:
                            try:
                                if entry["out"] is None:
                                    # Voided by a mid-phase drain: nothing
                                    # is in flight, so re-dispatch fresh at
                                    # the resolve — the batched analogue of
                                    # resolve_front's ``inflight=None``
                                    # path (the voided set is joint, so
                                    # every host re-dispatches the same
                                    # rounds here, in the same order).
                                    entry["out"] = pipeline.dispatch_lockstep(
                                        entry["batch"], entry["phase"],
                                        sh2, sh1,
                                    )
                                st = _timed_stats(
                                    entry["out"],
                                    entry["bucket"],
                                    entry["phase"],
                                    entry["batch"].batch_size,
                                )
                            except BaseException as e:  # noqa: BLE001
                                if classify_error(e) != "retryable":
                                    raise
                                WATCHDOG.escalated(e)
                                fault = True
                        faults.append(fault)
                        stats_list.append(st)
                    verdicts = guard.negotiate_batch(faults)
                    METRICS.inc(
                        "multihost_window_stall_seconds_total",
                        time.perf_counter() - t0,
                    )
                    for i, entry in enumerate(entries):
                        local, ph, eb = (
                            entry["batch"], entry["phase"], entry["bucket"]
                        )
                        if verdicts[i]:
                            # Younger rounds rejoin the window BEFORE the
                            # drain hook fires, so the joint drain clears
                            # exactly the launched-ahead set the unbatched
                            # path would have cleared.
                            for e in reversed(entries[i + 1:]):
                                window.appendleft(e)
                            TRACER.counter("lockstep_window", len(window))
                            with TRACER.span(
                                "lockstep_resolve",
                                {"bucket": eb, "phase": ph},
                            ):
                                stats = guard.run_round(
                                    eb,
                                    dispatch=lambda local=local, ph=ph: (
                                        pipeline.dispatch_lockstep(
                                            local, ph, sh2, sh1
                                        )
                                    ),
                                    fetch=lambda out, eb=eb, ph=ph, rows=(
                                        local.batch_size
                                    ): _timed_stats(out, eb, ph, rows),
                                    on_fault=drain_window,
                                    prior_fault=True,
                                    prior_local_fault=faults[i],
                                )
                                if stats is None:
                                    degraded.extend(local.docs)
                                else:
                                    po, alive = pipeline.assemble_phase(
                                        local, stats, ph
                                    )
                                    outcomes.extend(po)
                                    absorb(eb, alive)
                                consumed[entry["plan_idx"]] = True
                            while window:
                                resolve_front()
                            return
                        with TRACER.span(
                            "lockstep_resolve", {"bucket": eb, "phase": ph}
                        ):
                            guard.record_round_success(eb)
                            po, alive = pipeline.assemble_phase(
                                local, stats_list[i], ph
                            )
                            outcomes.extend(po)
                            absorb(eb, alive)
                            consumed[entry["plan_idx"]] = True

                def launch_speculative():
                    """Launch up to ``spec_depth`` of the NEXT phase's
                    confirmed survivor chunks while this phase's tail
                    verdicts are still unresolved — the device computes
                    phase p+1 rounds across the barrier instead of idling
                    through the drain.

                    Only fully-confirmed chunks launch: a complete
                    ``next_current`` chunk exists only once every document
                    in it resolved its phase-p membership, so the LAUNCHED
                    work is never optimistic — the optimism lives in the
                    piggybacked round counts, which include still-pending
                    tail survivors.  Per-host launch counts may differ
                    (chunk confirmation progress is local); that is sound
                    for programs that move no row between devices,
                    the same residual-risk stance resilience/negotiated.py
                    documents for fetches.  Voided entries (``out=None``)
                    re-launch here on the barrier's next pass, after the
                    joint drain."""
                    if spec_depth <= 0 or pool is None:
                        return
                    in_flight = sum(
                        1 for e in spec_next.values()
                        if e["out"] is not None or e["fault"]
                    )
                    for nb in buckets:
                        if guard is not None and guard.bucket_degraded(nb):
                            continue
                        for k in range(prepack_done[nb]):
                            if in_flight >= spec_depth:
                                return
                            key = (nb, k)
                            e = spec_next.get(key)
                            if e is None:
                                fut = prepack_next.pop(key, None)
                                if fut is None:
                                    continue
                                if hasattr(fut, "result"):
                                    if WATCHDOG.enabled:
                                        WATCHDOG.wait("pack_wait", fut.done)
                                    fut = fut.result()
                                e = {
                                    "batch": fut,
                                    "out": None,
                                    "fault": False,
                                }
                                spec_next[key] = e
                            elif e["out"] is not None or e["fault"]:
                                continue
                            with TRACER.span(
                                "lockstep_speculate",
                                {"bucket": nb, "round": k,
                                 "phase": phase + 1},
                            ):
                                out, fault = launch(
                                    e["batch"], phase + 1, speculative=True
                                )
                            e["out"], e["fault"] = out, fault
                            METRICS.inc(
                                "multihost_speculated_rounds_total"
                            )
                            in_flight += 1

                def resolve_barrier():
                    """Speculative phase barrier: resolve the tail rounds,
                    sweep join admission, and negotiate the next phase's
                    schedule — all on ONE exchange post — with up to
                    ``spec_depth`` next-phase rounds launched before the
                    tail verdicts convene.

                    The combined vector is ``[tail fault flags | join
                    lanes | next-phase round counts]``; every section's
                    presence is derived from shared state (guard
                    configured, transport admission-capable, phase
                    previewable), so the width is identical on every host.
                    The counts are optimistic — each host projects its
                    tail survivors via ``preview_phase_survivors`` — and
                    the first-fault-authoritative contract extends across
                    the barrier: ANY fault verdict voids the speculative
                    launches AND the freight on every host, the faulted
                    round re-enters the serial retry protocol
                    (``prior_fault``), the remainder drains
                    round-at-a-time, and the barrier re-posts fresh.
                    Returns the negotiated next-phase schedule, carried
                    into the next phase instead of its classic
                    ``maybe_admit_joiners`` + ``_negotiate_max`` posts.
                    Phases without a batch verdict mask (badwords) cannot
                    preview: the schedule then posts separately after
                    assembly — two posts instead of one, still never
                    three."""
                    previewable = (
                        not rewrites and pipeline.phase_previewable(phase)
                    )
                    collect = getattr(
                        _EXCHANGE.transport, "collect_join_lanes", None
                    )
                    while True:
                        launch_speculative()
                        n_tail = len(window)
                        entries = [window.popleft() for _ in range(n_tail)]
                        TRACER.counter("lockstep_window", 0)
                        t0 = time.perf_counter()
                        faults, stats_list = [], []
                        for entry in entries:
                            fault, st = bool(entry["fault"]), None
                            if not fault:
                                if guard is None:
                                    st = _timed_stats(
                                        entry["out"], entry["bucket"],
                                        entry["phase"],
                                        entry["batch"].batch_size,
                                    )
                                else:
                                    try:
                                        if entry["out"] is None:
                                            # Voided by a mid-phase drain:
                                            # re-dispatch fresh, jointly
                                            # (see resolve_batch).
                                            entry["out"] = (
                                                pipeline.dispatch_lockstep(
                                                    entry["batch"],
                                                    entry["phase"],
                                                    sh2, sh1,
                                                )
                                            )
                                        st = _timed_stats(
                                            entry["out"], entry["bucket"],
                                            entry["phase"],
                                            entry["batch"].batch_size,
                                        )
                                    except BaseException as e:  # noqa: BLE001
                                        if classify_error(e) != "retryable":
                                            raise
                                        WATCHDOG.escalated(e)
                                        fault = True
                            faults.append(fault)
                            stats_list.append(st)
                        proj = None
                        counts = None
                        if previewable:
                            proj = {
                                b: len(next_current[b]) for b in buckets
                            }
                            for i, entry in enumerate(entries):
                                if not faults[i]:
                                    proj[entry["bucket"]] += (
                                        pipeline.preview_phase_survivors(
                                            entry["batch"],
                                            stats_list[i],
                                            phase,
                                        )
                                    )
                            counts = [
                                math.ceil(proj[b] / local_for[b])
                                for b in buckets
                            ]
                        lanes = collect() if collect is not None else None
                        freight = (
                            list(lanes) if lanes is not None else []
                        ) + (counts if counts is not None else [])
                        if guard is not None:
                            verdicts, rows = guard.negotiate_freight(
                                faults, freight
                            )
                            posts = 1
                        elif freight:
                            rows = host_allgather(
                                np.asarray(freight, dtype=np.int64)
                            )
                            verdicts = [False] * n_tail
                            posts = 1
                        else:
                            rows, verdicts, posts = None, [], 0
                        METRICS.inc(
                            "multihost_window_stall_seconds_total",
                            time.perf_counter() - t0,
                        )
                        first = next(
                            (i for i, v in enumerate(verdicts) if v), None
                        )
                        if first is not None:
                            # Joint rollback: speculative launches and
                            # piggybacked freight void together, on every
                            # host (the counts were measured on tail state
                            # the drain is about to discard).
                            void_speculation()
                            for k in range(first):
                                entry = entries[k]
                                eb = entry["bucket"]
                                with TRACER.span(
                                    "lockstep_resolve",
                                    {"bucket": eb, "phase": phase},
                                ):
                                    guard.record_round_success(eb)
                                    po, alive = pipeline.assemble_phase(
                                        entry["batch"], stats_list[k],
                                        phase,
                                    )
                                    outcomes.extend(po)
                                    absorb(eb, alive)
                                    consumed[entry["plan_idx"]] = True
                            for e in reversed(entries[first + 1:]):
                                window.appendleft(e)
                            TRACER.counter("lockstep_window", len(window))
                            entry = entries[first]
                            local, eb = entry["batch"], entry["bucket"]
                            with TRACER.span(
                                "lockstep_resolve",
                                {"bucket": eb, "phase": phase},
                            ):
                                stats = guard.run_round(
                                    eb,
                                    dispatch=lambda local=local: (
                                        pipeline.dispatch_lockstep(
                                            local, phase, sh2, sh1
                                        )
                                    ),
                                    fetch=lambda out, eb=eb, rows_n=(
                                        local.batch_size
                                    ): _timed_stats(
                                        out, eb, phase, rows_n
                                    ),
                                    on_fault=drain_window,
                                    prior_fault=True,
                                    prior_local_fault=faults[first],
                                )
                                if stats is None:
                                    degraded.extend(local.docs)
                                else:
                                    po, alive = pipeline.assemble_phase(
                                        local, stats, phase
                                    )
                                    outcomes.extend(po)
                                    absorb(eb, alive)
                                consumed[entry["plan_idx"]] = True
                            while window:
                                resolve_front()
                            # Re-post a fresh barrier exchange: voided
                            # speculative launches re-dispatch first, and
                            # lanes/counts re-measure post-drain.
                            continue
                        for k, entry in enumerate(entries):
                            eb = entry["bucket"]
                            with TRACER.span(
                                "lockstep_resolve",
                                {"bucket": eb, "phase": phase},
                            ):
                                if guard is not None:
                                    guard.record_round_success(eb)
                                po, alive = pipeline.assemble_phase(
                                    entry["batch"], stats_list[k], phase
                                )
                                outcomes.extend(po)
                                absorb(eb, alive)
                                consumed[entry["plan_idx"]] = True
                        TRACER.instant(
                            "window_drained",
                            {"replayed": 0, "pending": 0, "phase": phase,
                             "cause": "barrier"},
                        )
                        if proj is not None:
                            for b in buckets:
                                assert len(next_current[b]) == proj[b], (
                                    f"bucket {b}: barrier preview "
                                    f"projected {proj[b]} next-phase "
                                    f"documents, assembly produced "
                                    f"{len(next_current[b])} — "
                                    "preview_phase_survivors drifted from "
                                    "assemble_phase"
                                )
                        off = _JOIN_LANES if lanes is not None else 0
                        if lanes is not None:
                            # May raise GangReformed (admission) into the
                            # phase handler — safe here: every tail round
                            # above is consumed, so the replayed plan is
                            # empty and the barrier re-runs over the grown
                            # gang with fresh lanes.
                            _EXCHANGE.transport.admit_union(
                                [int(x) for x in rows[:, :off].ravel()]
                            )
                        if counts is not None:
                            sched = (
                                rows[:, off:off + len(buckets)]
                                .max(axis=0)
                                .astype(np.int32)
                            )
                        else:
                            # A step without a batch verdict mask
                            # (badwords) blocks the survivor preview: the
                            # schedule needs post-assembly counts — one
                            # extra post, still fewer than the classic
                            # three.
                            sched = _negotiate_max(
                                np.array(
                                    [
                                        math.ceil(
                                            len(next_current[b])
                                            / local_for[b]
                                        )
                                        for b in buckets
                                    ],
                                    dtype=np.int32,
                                )
                            )
                            posts += 1
                        # Posts the classic barrier would have made: the
                        # tail verdict batch, the admission sweep (only
                        # when a multi-member gang runs one), and the
                        # next-phase schedule.
                        baseline = (
                            (1 if guard is not None and n_tail >= 1 else 0)
                            + (
                                1
                                if lanes is not None
                                and rows is not None
                                and rows.shape[0] > 1
                                else 0
                            )
                            + 1
                        )
                        if baseline > posts:
                            METRICS.inc(
                                "multihost_barrier_elisions_total",
                                baseline - posts,
                            )
                        return sched

                for j, (b, r, chunk) in enumerate(plan):
                    if guard is not None and guard.bucket_degraded(b):
                        # Breaker latched on negotiated verdicts, so every
                        # host reaches the same conclusion at the same
                        # round and the dispatch is skipped jointly —
                        # lockstep preserved without touching the device.
                        METRICS.inc(
                            "resilience_negotiated_degraded_rounds_total"
                        )
                        TRACER.instant(
                            "negotiated_bucket_latched",
                            {"bucket": b, "round": r, "phase": phase},
                        )
                        packs.pop(j, None)
                        se = spec_inflight.pop((b, r), None)
                        if se is not None and (
                            se["out"] is not None or se["fault"]
                        ):
                            # Bucket latched between the speculative launch
                            # and its adoption slot (a tail degradation at
                            # the same barrier): the result is discarded
                            # jointly, like any other voided speculation.
                            METRICS.inc("multihost_voided_rounds_total")
                            TRACER.instant(
                                "window_drained",
                                {"replayed": 0, "pending": 0, "voided": 1,
                                 "phase": phase,
                                 "cause": "speculation_void"},
                            )
                            if EVENTS.enabled:
                                EVENTS.emit("speculation_void", voided=1,
                                            phase=phase,
                                            cause="bucket_latch")
                        degraded.extend(chunk)
                        consumed[j] = True
                        continue
                    ensure_packed(j)
                    with TRACER.span(
                        "lockstep_round",
                        {"bucket": b, "round": r, "phase": phase,
                         "rows": len(chunk)},
                    ):
                        se = spec_inflight.pop((b, r), None)
                        if se is not None:
                            # Adopt the speculative launch at its plan
                            # slot: occupancy books here (once per round,
                            # like every round), and a voided entry simply
                            # re-dispatches fresh — byte-identical either
                            # way, the speculation only moved the launch.
                            local = se["batch"]
                            record_occupancy(local)
                            if se["out"] is None and not se["fault"]:
                                out, fault = launch(local, phase)
                            else:
                                out, fault = se["out"], se["fault"]
                        else:
                            item = packs.pop(j)
                            if hasattr(item, "result"):
                                if WATCHDOG.enabled:
                                    WATCHDOG.wait("pack_wait", item.done)
                                local = item.result()
                            else:
                                local = item
                            record_occupancy(local)
                            out, fault = launch(local, phase)
                    window.append({
                        "batch": local, "bucket": b, "phase": phase,
                        "out": out, "fault": fault, "plan_idx": j,
                    })
                    TRACER.counter("lockstep_window", len(window))
                    while len(window) > depth:
                        resolve_front()
                if not last and spec_depth > 0:
                    carried_schedule = resolve_barrier()
                else:
                    resolve_batch(len(window))
                    TRACER.instant(
                        "window_drained",
                        {"replayed": 0, "pending": 0, "phase": phase,
                         "cause": "barrier"},
                    )
                break
            except GangReformed:
                # Resume at the next round boundary over the survivor set:
                # every resolved round stands (its outcomes and survivors
                # are already folded), and the unconsumed plan chunks — in
                # flight, launched ahead, or never launched — are stitched
                # back into ``current`` in plan order, so the replayed plan
                # re-chunks them at identical boundaries (consumed rounds
                # form a plan-order prefix per bucket; breaker-latched
                # skips route to the host oracle either way).
                if plan is not None:
                    for b in buckets:
                        current[b] = []
                    for j, (b, _r, chunk) in enumerate(plan):
                        if not consumed[j]:
                            current[b].extend(chunk)
                # Pre-packs inherited from the previous phase key on the
                # abandoned plan's round numbering — drop them and pack
                # fresh (futures are pure; unused results are garbage).
                inherited = {}
                # Speculative launches do not survive a reformation: the
                # exchange epoch moved and the replayed plan renumbers its
                # rounds.  Entries for THIS phase (keyed on the abandoned
                # plan) drop entirely and re-pack fresh; entries for the
                # next phase (keyed on persistent next_current chunk
                # indexes) keep their packed batches and re-dispatch at
                # the replayed barrier.
                n_void = sum(
                    1
                    for e in list(spec_inflight.values())
                    + list(spec_next.values())
                    if e["out"] is not None or e["fault"]
                )
                if n_void:
                    METRICS.inc("multihost_voided_rounds_total", n_void)
                    TRACER.instant(
                        "window_drained",
                        {"replayed": 0, "pending": 0, "voided": n_void,
                         "phase": phase, "cause": "speculation_void"},
                    )
                    if EVENTS.enabled:
                        EVENTS.emit("speculation_void", voided=n_void,
                                    phase=phase, cause="reformation")
                spec_inflight = {}
                for e in spec_next.values():
                    e["out"] = None
                    e["fault"] = False
                carried = None
                reformed = True
        if last:
            break
        fallback.extend(next_over)
        current = next_current
    METRICS.inc(
        "multihost_lockstep_seconds_total",
        time.perf_counter() - lockstep_t0,
    )

    for d in fallback:
        METRICS.inc("worker_host_fallback_total")
        o = execute_processing_pipeline(pipeline.host_executor, d)
        if o is not None:
            outcomes.append(o)
    if degraded:
        # Degraded rounds re-run start to finish on the bit-exact host
        # oracle (mid-phase re-stamp contract, ops/pipeline.py _host_rerun),
        # so outcomes stay byte-identical to a fault-free run.
        outcomes.extend(pipeline._host_rerun(degraded))
    return outcomes


def run_multihost(
    config: PipelineConfig,
    input_file: str,
    output_file: str,
    excluded_file: str,
    *,
    coordinator: str,
    num_processes: int,
    process_id: int,
    text_column: str = "text",
    id_column: str = "id",
    buckets: Sequence[int] = (512, 2048, 8192),
    read_batch_size: int = 1024,
    device_batch: Optional[int] = None,
    auto_geometry: bool = False,
    errors_file: Optional[str] = None,
    force: bool = False,
    run_report: Optional[str] = None,
    provenance: Optional[dict] = None,
    exchange_deadline_s: float = DEFAULT_EXCHANGE_DEADLINE_S,
    lease_ttl_s: float = DEFAULT_LEASE_TTL_S,
    elastic: bool = False,
    exchange_transport: str = "auto",
    survive_peer_loss: bool = False,
    autoscale: Optional[str] = None,
):
    """Production multi-host entry (``textblast run --coordinator ...``).

    ``run_report`` (must be passed on EVERY process or on none — the
    snapshot exchange is a collective) makes each process contribute its
    metrics-delta snapshot over :func:`host_allgather_obj` after the totals
    barrier; process 0 writes a merged run report to that path with both
    the per-host snapshots and the summed totals.  ``provenance`` is the
    config-provenance dict embedded in the report.

    Each process reads its contiguous row stripe of ``input_file`` (the
    static shard assignment SURVEY.md §2.5 maps the task queue onto), runs
    the negotiated lockstep schedule, and writes a per-host
    ``<output>.shard<i>`` / ``<excluded>.shard<i>`` Parquet pair (plus an
    ``<errors>.shard<i>`` dead-letter shard when ``errors_file`` is given —
    the per-host slice of PR 1's sink).  After a global barrier, process 0
    merges each shard set into its final file **atomically**
    (:func:`merge_shard_files`: tmp + fsync + rename, shards deleted only
    after every rename lands) — the results-queue aggregation analogue,
    producer_logic.rs:109-196.  Stale ``*.shard*`` leftovers from a crashed
    run with different ``--num-processes`` fail the run fast on every
    process unless ``force`` removes them.

    Returns an ``AggregationResult``: global totals on process 0 (after the
    merge), local totals elsewhere.

    Failure behavior (measured, tests/test_multihost.py +
    tests/test_multihost_chaos.py + tests/test_elastic_membership.py): a
    *retryable device fault* on any host no longer kills the job —
    ``run_local_shard``'s negotiated guard retries the round jointly on
    every host and, past the budget, degrades it to the host oracle jointly
    (outcomes stay byte-identical).  If a process *dies* mid-run, survivors
    do not wait forever on the next exchange: every KV-transport allgather
    is bounded by ``exchange_deadline_s`` and on expiry raises a typed
    :exc:`PeerFailure` naming the exchange coordinates and every rank that
    never posted, with dead-versus-slow resolved against the renewable KV
    liveness leases each process maintains (TTL ``lease_ttl_s``, renewed by
    a daemon heartbeat at TTL/3).  The accelerator collective path carries
    no host-side deadline — there, and for deadlines configured beyond it,
    the jax coordination-service heartbeat teardown (~90 s, UNAVAILABLE to
    every healthy task) remains the backstop.  After a ``PeerFailure`` the
    lockstep run is re-launched whole — the lockstep contract cannot
    reshape a live gang.

    ``elastic=True`` trades the lockstep contract for membership that can
    shrink, grow, and restart in place (:func:`_run_elastic`): processes
    coordinate through renewable leases and per-stripe checkpoint cursors
    on the shared filesystem instead of ``jax.distributed`` collectives,
    survivors adopt a dead rank's stripe at the membership-epoch bump, and
    a relaunched rank rejoins mid-run resuming from the committed cursor —
    replaying zero completed chunks, with outcomes byte-identical to a
    fault-free run.  A brand-new rank (``process_id >= num_processes``)
    scales the gang OUT mid-run: it posts a join request next to its
    lease, the members admit it on observation, and
    :func:`~textblaster_tpu.resilience.membership.assign_stripes` moves a
    pending stripe to it (the donor fences at its next committed chunk,
    the joiner adopts the cursor — dead-stripe adoption in reverse).
    ``run_report`` is supported (the merging rank folds per-rank report
    shards into the merged v4 report; an aborted run leaves a partial
    report, like the kv path); ``auto_geometry`` stays incompatible (a
    full-gang collective with no lockstep exchange to ride).
    ``autoscale="MIN:MAX"`` arms the supervisor loop on the lowest live
    home rank: joiners are spawned under sustained backlog and drain
    (fence-and-leave) at idle.

    ``exchange_transport`` / ``survive_peer_loss`` (PR 10): with the
    ``file`` transport (:class:`FileLeaseTransport`; ``auto`` resolves to
    it iff ``survive_peer_loss``) the lockstep exchanges ride shared-
    filesystem slots next to the membership leases instead of
    ``jax.distributed`` — which is never initialized on this path, because
    the coordination service force-terminates every healthy task ~90-100 s
    after a peer death and would undercut survival from below.  Each
    process then runs its full-width local-device mesh (exactly the
    multi-process CPU fallback :func:`global_data_mesh` already takes; the
    compiled programs move no row between devices either way — on accelerator pods
    this trades the cross-host XLA mesh for survivability).  Under
    ``survive_peer_loss`` a peer death mid-exchange triggers gang
    reformation instead of gang death: survivors fence the dead rank's
    incarnation, elect the new member set at a bumped membership epoch,
    replay the interrupted exchange, and the lowest live rank adopts the
    dead rank's stripe through :meth:`CheckpointState.adopt` — the final
    merged outputs stay byte-identical to a fault-free run.  Keeps the
    lockstep contract (unlike ``elastic``) and therefore keeps
    ``run_report``/``auto_geometry``.
    """
    import os
    from itertools import islice

    import pyarrow.parquet as pq

    from ..errors import PipelineError
    from ..ops.device import tpu_refusal
    from ..orchestration import (
        AggregationResult,
        aggregate_results_from_stream,
        read_documents,
    )
    from ..resilience import DeadLetterSink
    from ..resilience.faults import arm_from_env
    from ..utils.metrics import (
        METRICS,
        build_run_report,
        metrics_snapshot,
        write_run_report,
    )

    finals = [output_file, excluded_file]
    if errors_file is not None:
        finals.append(errors_file)
    stale = detect_stale_shards(finals, num_processes)
    if stale:
        if not force:
            # Checked on EVERY process before joining the coordinator, so
            # the whole gang exits fast instead of one host discovering the
            # problem after the run.
            raise PipelineError(
                "stale shard files from a previous run would be ignored by "
                f"the merge: {', '.join(stale)} — remove them or pass "
                "--force to overwrite"
            )
        for s in stale:
            try:
                os.remove(s)
            except FileNotFoundError:
                pass  # a peer on a shared filesystem got there first
            else:
                METRICS.inc("multihost_stale_shards_removed_total")

    transport_name = resolve_exchange_transport(
        exchange_transport, survive_peer_loss
    )
    if elastic and (survive_peer_loss or transport_name == "file"):
        raise PipelineError(
            "--elastic is incompatible with --survive-peer-loss and "
            "--exchange-transport file: elastic membership deliberately has "
            "no lockstep exchanges for the transport to carry"
        )
    if transport_name == "file" and exchange_deadline_s <= lease_ttl_s:
        raise PipelineError(
            f"--exchange-deadline-s ({exchange_deadline_s:g}s) must exceed "
            f"--lease-ttl-s ({lease_ttl_s:g}s): with the exchange deadline "
            "at or under the lease TTL, every slow lease renewal is "
            "misclassified as a peer death"
        )

    if autoscale is not None and not elastic:
        raise PipelineError(
            "--autoscale requires --elastic: the supervisor spawns and "
            "drains joiner ranks through the elastic membership protocol"
        )
    if elastic:
        if auto_geometry:
            raise PipelineError(
                "--elastic is incompatible with --auto-geometry: geometry "
                "negotiation is a full-gang collective, and elastic "
                "membership deliberately has no lockstep exchanges to "
                "carry it"
            )
        refusal = tpu_refusal()  # elastic ranks never join jax.distributed
        if refusal:
            raise PipelineError(refusal)
        return _run_elastic(
            config,
            input_file,
            output_file,
            excluded_file,
            num_processes=num_processes,
            process_id=process_id,
            text_column=text_column,
            id_column=id_column,
            buckets=buckets,
            read_batch_size=read_batch_size,
            device_batch=device_batch,
            errors_file=errors_file,
            lease_ttl_s=lease_ttl_s,
            force=force,
            run_report=run_report,
            provenance=provenance,
            autoscale=autoscale,
        )

    heartbeat = None
    file_transport = None
    membership_store = None
    membership_root = f"{output_file}.membership"
    if transport_name == "file":
        # The file transport deliberately does NOT initialize
        # jax.distributed: the coordination service force-terminates every
        # healthy task ~90-100 s after a peer stops heartbeating (measured
        # on this stack — the motivation for _run_elastic's identical
        # choice), which would undercut --survive-peer-loss from below.
        # The gang is coupled only through the membership dir on the shared
        # filesystem; jax.process_count() stays 1, so global_data_mesh()
        # hands every process its full-width local mesh — exactly the
        # multi-process CPU fallback, each host's collectives its own.
        import shutil

        if force and os.path.isdir(membership_root):
            shutil.rmtree(membership_root, ignore_errors=True)
        membership_store = FileMembershipStore(
            membership_root, process_id, lease_ttl_s
        )
        membership_store.register()
        heartbeat = LeaseHeartbeat(
            membership_store, max(0.05, lease_ttl_s / 3.0)
        )
        heartbeat.start()
        file_transport = FileLeaseTransport(
            membership_store,
            process_id,
            num_processes,
            survive=survive_peer_loss,
            heartbeat=heartbeat,
        )
        arm_from_env(process_id=process_id)
        configure_exchange(
            deadline_s=exchange_deadline_s,
            lease_store=membership_store,
            transport=file_transport,
        )
        # Publish the launch roster (idempotent across ranks — every
        # writer posts identical content atomically): the membership view
        # a prospective joiner echoes in its admission election.
        membership_store.write_roster(
            file_transport.members(),
            file_transport.tracker.epoch,
            current_exchange_epoch(),
        )
        print(
            f"coordinated[{process_id}]: file-lease exchange transport at "
            f"{membership_root} (survive_peer_loss={survive_peer_loss}, "
            f"deadline {exchange_deadline_s:g}s, lease ttl {lease_ttl_s:g}s)",
            flush=True,
        )
    else:
        initialize(coordinator, num_processes, process_id)
        if jax.process_count() != num_processes:
            # Without this, a topology mismatch (typically jax.distributed
            # already initialized with different numbers) surfaces as a
            # hang or a shape error deep inside the first allgather.
            raise PipelineError(
                f"--num-processes {num_processes} does not match the "
                f"initialized distributed runtime "
                f"(jax.process_count()={jax.process_count()}); all "
                "processes must be launched with the same topology, and an "
                "existing jax.distributed initialization cannot be "
                "re-shaped"
            )
        arm_from_env(process_id=process_id)
        configure_exchange(deadline_s=exchange_deadline_s)
        if jax.process_count() > 1 and _distributed_initialized():
            # Liveness leases ride the same coordination-service KV store
            # the exchanges do, so an expired exchange deadline can tell
            # the user WHICH missing ranks are dead (lease expired) vs
            # merely slow.
            from jax._src import distributed

            client = getattr(distributed.global_state, "client", None)
            if client is not None:
                store = KVLeaseStore(client, process_id, lease_ttl_s)
                store.post()
                heartbeat = LeaseHeartbeat(
                    store, max(0.05, lease_ttl_s / 3.0)
                )
                heartbeat.start()
                configure_exchange(
                    deadline_s=exchange_deadline_s,
                    lease_store=store,
                    reset=False,
                )

    def _ride_reformations(fn):
        """Replay a lockstep closure until it completes without a gang
        reformation (at most num_processes-1 replays — each reformation
        permanently shrinks the member set).  On the kv transport
        GangReformed is never raised, so this is a transparent wrapper."""
        while True:
            try:
                return fn()
            except GangReformed:
                continue

    try:
        # The gang has formed (jax.distributed, when used, is up): only now
        # may the backend be touched to refuse a missing TPU.
        refusal = tpu_refusal()
        if refusal:
            raise PipelineError(refusal)
        mesh = global_data_mesh()
        _ride_reformations(_align_trace_clocks)

        import time as _time

        # Run-report scope starts here: everything after distributed init is
        # this run's work, so the snapshot deltas attribute only it.
        values_before = metrics_snapshot() if run_report is not None else {}
        wall_t0 = _time.perf_counter()

        n_rows = pq.ParquetFile(input_file).metadata.num_rows
        stride = math.ceil(n_rows / max(num_processes, 1))
        skip = min(process_id * stride, n_rows)
        take = max(0, min(stride, n_rows - skip))

        # Per-host dead-letter shard, merged by process 0 exactly like
        # kept/excluded.  Created eagerly (DeadLetterSink writes the empty
        # file up front) so the merge never races a host that recorded
        # nothing.
        deadletter = (
            DeadLetterSink(f"{errors_file}.shard{process_id}")
            if errors_file is not None
            else None
        )

        read_errors = 0
        docs: List[TextDocument] = []
        stream = read_documents(
            input_file,
            text_column=text_column,
            id_column=id_column,
            batch_size=read_batch_size,
            skip_rows=skip,
        )
        for item in islice(stream, take):  # one stream item per Parquet row
            if isinstance(item, PipelineError):
                read_errors += 1
                if deadletter is not None:
                    deadletter.record_read_error(item)
            else:
                docs.append(item)

        from ..ops.pipeline import CompiledPipeline

        geometry = None
        if auto_geometry:
            # Geometry negotiation: each host histograms ITS shard's
            # document lengths over the fixed shape-stable bin edges, the
            # histograms are allgathered and summed elementwise, and every
            # host derives the geometry from the identical merged histogram
            # — so the lockstep round schedule (which depends on buckets
            # and batch sizes) stays in agreement without shipping raw
            # lengths across hosts.
            from ..ops.geometry import (
                geometry_from_histogram,
                length_histogram,
            )

            hist = length_histogram([len(d.content) for d in docs])
            folded_stripes: set = set()

            def _merged_hist():
                # Reformation during geometry negotiation: the adopter-to-
                # be (lowest live rank) folds each newly-dead stripe's
                # length histogram into its own before the replay, so the
                # merged histogram — and the geometry derived from it — is
                # identical to the fault-free gang's.
                nonlocal hist
                if file_transport is not None and file_transport.dead_ranks:
                    if process_id == min(file_transport.members()):
                        for r in sorted(set(file_transport.dead_ranks)):
                            if r in folded_stripes:
                                continue
                            folded_stripes.add(r)
                            skip_r = min(r * stride, n_rows)
                            take_r = max(0, min(stride, n_rows - skip_r))
                            lens = [
                                len(d.content)
                                for d in islice(
                                    read_documents(
                                        input_file,
                                        text_column=text_column,
                                        id_column=id_column,
                                        batch_size=read_batch_size,
                                        skip_rows=skip_r,
                                    ),
                                    take_r,
                                )
                                if not isinstance(d, PipelineError)
                            ]
                            hist = hist + length_histogram(lens)
                return host_allgather(hist).sum(axis=0)

            hist = _ride_reformations(_merged_hist)
            if hist.sum() > 0:
                geometry = geometry_from_histogram(
                    hist, backend=jax.default_backend()
                )

        pipeline = CompiledPipeline(
            config, buckets=tuple(sorted(buckets)), batch_size=device_batch,
            mesh=mesh, geometry=geometry, multihost=True,
        )
        from ..ops.pipeline import maybe_warmup

        # Warm ahead of the lockstep rounds (see run_local_shard): compile
        # stalls must not land mid-round where peers wait at the allgather.
        maybe_warmup(pipeline)
        try:
            outcomes = run_local_shard(
                config, docs, buckets=pipeline.geometry.buckets, mesh=mesh,
                pipeline=pipeline,
            )

            shard_out = f"{output_file}.shard{process_id}"
            shard_exc = f"{excluded_file}.shard{process_id}"
            result = aggregate_results_from_stream(
                iter(outcomes), shard_out, shard_exc, deadletter=deadletter
            )
        finally:
            # The shard must be complete on disk before the totals barrier
            # releases process 0 into the merge.
            if deadletter is not None:
                deadletter.close()
        result.read_errors = read_errors

        if file_transport is not None:
            return _finish_file_coordinated(
                config=config,
                input_file=input_file,
                output_file=output_file,
                excluded_file=excluded_file,
                errors_file=errors_file,
                finals=finals,
                text_column=text_column,
                id_column=id_column,
                read_batch_size=read_batch_size,
                num_processes=num_processes,
                process_id=process_id,
                n_rows=n_rows,
                stride=stride,
                mesh=mesh,
                pipeline=pipeline,
                result=result,
                file_transport=file_transport,
                membership_store=membership_store,
                membership_root=membership_root,
                run_report=run_report,
                provenance=provenance,
                values_before=values_before,
                wall_t0=wall_t0,
            )

        totals = np.array(
            [result.received, result.success, result.filtered,
             result.errors, result.read_errors],
            dtype=np.int64,
        )
        # Barrier doubling as the totals exchange: every process must have
        # closed its shard files before process 0 merges (host_allgather's
        # blocking gets release only once every peer has posted).
        all_totals = host_allgather(totals).reshape(-1, 5)

        # Cross-host metrics aggregation: one more lockstep exchange
        # carrying each process's metrics-delta snapshot (a few KiB of
        # JSON), so host 0's report survives the other processes' exit.
        # Runs on EVERY process or on none — see the docstring contract.
        host_reports = None
        if run_report is not None:
            from ..utils.metrics import snapshot_delta

            now = metrics_snapshot()
            local_delta = snapshot_delta(values_before, now)
            host_reports = host_allgather_obj(
                {
                    "process": process_id,
                    "wall_time_s": round(
                        _time.perf_counter() - wall_t0, 3
                    ),
                    "counts": {
                        "received": result.received,
                        "success": result.success,
                        "filtered": result.filtered,
                        "errors": result.errors,
                        "read_errors": result.read_errors,
                    },
                    "metrics": local_delta,
                }
            )

        if process_id == 0:
            merge_shard_files(
                [
                    (
                        final,
                        [f"{final}.shard{i}" for i in range(num_processes)],
                    )
                    for final in finals
                ]
            )
            g = all_totals.sum(axis=0)
            merged = AggregationResult()
            merged.received, merged.success, merged.filtered = (
                int(g[0]), int(g[1]), int(g[2])
            )
            merged.errors, merged.read_errors = int(g[3]), int(g[4])
            if host_reports is not None:
                from ..utils.metrics import is_merge_gauge

                summed: dict = {}
                for h in host_reports:
                    for k, v in h["metrics"].items():
                        # Counters sum across hosts; gauges (gang-agreed
                        # values like the negotiated window depth) merge
                        # by max so the report shows the value, not n x it.
                        if is_merge_gauge(k):
                            summed[k] = max(summed.get(k, v), v)
                        else:
                            summed[k] = summed.get(k, 0.0) + v
                report = build_run_report(
                    values=summed,
                    wall_time_s=max(
                        h["wall_time_s"] for h in host_reports
                    ),
                    counts={
                        "received": merged.received,
                        "success": merged.success,
                        "filtered": merged.filtered,
                        "errors": merged.errors,
                        "read_errors": merged.read_errors,
                    },
                    provenance=provenance,
                    hosts=host_reports,
                )
                write_run_report(run_report, report)
            return merged
        return result
    except PeerFailure:
        # A peer is gone: the coordination service's shutdown barrier can
        # never complete, and jax's atexit hook would hold this process
        # hostage until the service's own heartbeat teardown (~95 s on this
        # stack).  Abandon the distributed client so the survivor's exit is
        # as fast as its diagnosis.
        _abandon_distributed()
        raise
    finally:
        if heartbeat is not None:
            heartbeat.stop()


def _finish_file_coordinated(
    *,
    config,
    input_file: str,
    output_file: str,
    excluded_file: str,
    errors_file: Optional[str],
    finals: Sequence[str],
    text_column: str,
    id_column: str,
    read_batch_size: int,
    num_processes: int,
    process_id: int,
    n_rows: int,
    stride: int,
    mesh,
    pipeline,
    result,
    file_transport: FileLeaseTransport,
    membership_store: FileMembershipStore,
    membership_root: str,
    run_report: Optional[str],
    provenance: Optional[dict],
    values_before: dict,
    wall_t0: float,
):
    """Completion protocol for the file-transport coordinated path: adopt
    dead ranks' stripes, exchange totals/report over the (possibly
    reformed) member set, and have the lowest live rank merge.

    Adoption is a *deferred completion phase*, not mid-stream surgery: a
    dead rank committed nothing durable (shard files are written only after
    its ``run_local_shard`` returned), so the lowest live rank reproduces
    the whole stripe — a collective pass in which the adopter feeds the
    stripe's documents and every other member feeds zero documents, keeping
    the negotiated lockstep schedule identical on all survivors.  The
    adopter then writes ``<final>.shard{r}`` exactly as rank ``r`` would
    have and commits a completed per-stripe cursor
    (:meth:`CheckpointState.adopt` + ``complete=True``), so if the adopter
    itself dies the NEXT adopter skips finished stripes instead of
    repeating them.  Every decision that could diverge (is the stripe done?
    which stripes are dead?) is exchanged, never inferred locally, and the
    whole protocol rides the same GangReformed-replay loop as the run
    itself — a second death during adoption reforms again and resumes.

    The merge and run-report write move from rank 0 to ``min(members)``
    (rank 0 may be the dead one); shard files for ALL of
    ``range(num_processes)`` exist by then — survivors' own plus adopted
    ones — so the merged outputs are byte-identical to a fault-free run."""
    from itertools import islice

    from ..checkpoint import (
        CheckpointState,
        _config_fingerprint,
        _input_fingerprint,
    )
    from ..errors import PipelineError
    from ..orchestration import (
        AggregationResult,
        aggregate_results_from_stream,
        read_documents,
    )
    from ..resilience import DeadLetterSink
    from ..utils.metrics import (
        METRICS,
        build_run_report,
        is_merge_gauge,
        metrics_snapshot,
        write_run_report,
    )

    fingerprint = _input_fingerprint(input_file)
    config_hash = _config_fingerprint(config)
    my_token = {
        "rank": process_id,
        "incarnation": membership_store.incarnation,
    }
    adopted_done: set = set()

    def _adopt_stripe(r: int, adopter: int) -> None:
        skip_r = min(r * stride, n_rows)
        take_r = max(0, min(stride, n_rows - skip_r))
        adopt_docs: List[TextDocument] = []
        dl = None
        st = None
        adopt_read_errors = 0
        if process_id == adopter:
            METRICS.inc("multihost_adopted_stripes_total")
            TRACER.instant(
                "stripe_adopted",
                {"stripe": r, "epoch": file_transport.tracker.epoch},
            )
            if EVENTS.enabled:
                EVENTS.emit("stripe_adopted", stripe=r, adopter=process_id,
                            epoch=file_transport.tracker.epoch)
            print(
                f"reform[{process_id}]: adopting dead rank {r}'s stripe "
                f"({take_r} row(s))",
                flush=True,
            )
            st = CheckpointState.adopt(
                membership_store.stripe_dir(r),
                my_token,
                input_fingerprint=fingerprint,
                config_hash=config_hash,
            )
            dl = (
                DeadLetterSink(f"{errors_file}.shard{r}")
                if errors_file is not None
                else None
            )
            for item in islice(
                read_documents(
                    input_file,
                    text_column=text_column,
                    id_column=id_column,
                    batch_size=read_batch_size,
                    skip_rows=skip_r,
                ),
                take_r,
            ):
                if isinstance(item, PipelineError):
                    adopt_read_errors += 1
                    if dl is not None:
                        dl.record_read_error(item)
                else:
                    adopt_docs.append(item)
        try:
            # Collective: every member runs the pass (non-adopters with
            # zero documents still negotiate/launch the identical padded
            # schedule), so the lockstep contract holds during adoption.
            outcomes_r = run_local_shard(
                config, adopt_docs, buckets=pipeline.geometry.buckets,
                mesh=mesh, pipeline=pipeline,
            )
            if process_id == adopter:
                res_r = aggregate_results_from_stream(
                    iter(outcomes_r),
                    f"{output_file}.shard{r}",
                    f"{excluded_file}.shard{r}",
                    deadletter=dl,
                )
        finally:
            if dl is not None:
                dl.close()
        if process_id == adopter:
            st.rows_consumed = take_r
            st.read_errors = adopt_read_errors
            st.received = res_r.received
            st.success = res_r.success
            st.filtered = res_r.filtered
            st.errors = res_r.errors
            st.complete = True
            st.save(membership_store.stripe_dir(r))

    all_totals = None
    host_reports = None
    while True:
        try:
            members = file_transport.members()
            pending = [
                r
                for r in sorted(set(file_transport.dead_ranks))
                if r not in adopted_done
            ]
            if pending:
                r = pending[0]
                adopter = min(members)
                done = 0
                if process_id == adopter:
                    st = CheckpointState.load(membership_store.stripe_dir(r))
                    done = int(st is not None and bool(st.complete))
                # Joint decision, not a local read: if the adopter saw a
                # completed cursor the commit is durable — every member
                # agrees to skip; otherwise every member joins the pass.
                joint = int(
                    host_allgather(np.array([done], dtype=np.int64)).max()
                )
                if joint:
                    adopted_done.add(r)
                else:
                    _adopt_stripe(r, adopter)
                continue

            # Totals barrier over the (possibly reformed) member set; the
            # current adoption leader folds every dead stripe's committed
            # counts in — recomputed fresh from the cursors on every replay
            # so the fold stays idempotent — and the global sums match a
            # fault-free gang's.
            totals = np.array(
                [result.received, result.success, result.filtered,
                 result.errors, result.read_errors],
                dtype=np.int64,
            )
            if file_transport.dead_ranks and process_id == min(
                file_transport.members()
            ):
                for r in sorted(set(file_transport.dead_ranks)):
                    st = CheckpointState.load(membership_store.stripe_dir(r))
                    if st is not None:
                        totals += np.array(
                            [st.received, st.success, st.filtered,
                             st.errors, st.read_errors],
                            dtype=np.int64,
                        )
            all_totals = host_allgather(totals).reshape(-1, 5)

            if run_report is not None:
                from ..utils.metrics import snapshot_delta

                now = metrics_snapshot()
                local_delta = snapshot_delta(values_before, now)
                host_reports = host_allgather_obj(
                    {
                        "process": process_id,
                        "wall_time_s": round(
                            time.perf_counter() - wall_t0, 3
                        ),
                        "counts": {
                            "received": result.received,
                            "success": result.success,
                            "filtered": result.filtered,
                            "errors": result.errors,
                            "read_errors": result.read_errors,
                        },
                        "metrics": local_delta,
                    }
                )
            break
        except GangReformed:
            continue

    merger = min(file_transport.members())
    if process_id != merger:
        # Heartbeat first, withdraw second: a renewal landing after the
        # withdraw would resurrect the lease file (and the membership dir
        # after the merger's cleanup).  stop() is idempotent — the outer
        # finally's call is then a no-op.
        if file_transport.heartbeat is not None:
            file_transport.heartbeat.stop()
        membership_store.withdraw()
        return result

    merge_shard_files(
        [
            (final, [f"{final}.shard{i}" for i in range(num_processes)])
            for final in finals
        ]
    )
    g = all_totals.sum(axis=0)
    merged = AggregationResult()
    merged.received, merged.success, merged.filtered = (
        int(g[0]), int(g[1]), int(g[2])
    )
    merged.errors, merged.read_errors = int(g[3]), int(g[4])
    if host_reports is not None:
        summed: dict = {}
        for h in host_reports:
            for k, v in h["metrics"].items():
                # Counters sum across hosts; gauges merge by max (same
                # rule as the kv-path report).
                if is_merge_gauge(k):
                    summed[k] = max(summed.get(k, v), v)
                else:
                    summed[k] = summed.get(k, 0.0) + v
        report = build_run_report(
            values=summed,
            wall_time_s=max(h["wall_time_s"] for h in host_reports),
            counts={
                "received": merged.received,
                "success": merged.success,
                "filtered": merged.filtered,
                "errors": merged.errors,
                "read_errors": merged.read_errors,
            },
            provenance=provenance,
            hosts=host_reports,
        )
        write_run_report(run_report, report)
    if file_transport.heartbeat is not None:
        file_transport.heartbeat.stop()
    membership_store.withdraw()
    import shutil

    # Bounded wait for every peer's withdraw before removing the dir: a
    # peer withdraws only AFTER its final exchange read completes, so the
    # leases going away proves nobody is still polling the last report
    # slots.  Removing eagerly races a peer that posted its final row but
    # has not yet read the merger's (a ~10 ms window this merger can win
    # under load): the peer's next liveness self-check then finds its own
    # lease gone and dies typed on an otherwise healthy run.  The timeout
    # covers peers that crashed mid-run and left a stale lease behind.
    peers = [r for r in file_transport.members() if r != process_id]
    deadline = time.monotonic() + min(membership_store.ttl_s, 10.0)
    while peers and time.monotonic() < deadline:
        leases = membership_store.read_leases()
        if not any(r in leases for r in peers):
            break
        time.sleep(0.02)
    shutil.rmtree(membership_root, ignore_errors=True)
    return merged


def _abandon_distributed() -> None:
    """Drop the ``jax.distributed`` client without the shutdown barrier.

    ``DistributedRuntimeClient.shutdown()`` is a full-gang barrier — with a
    dead rank it blocks until the coordination service force-terminates the
    survivors.  After a :class:`PeerFailure` the gang is known-broken, so
    the only useful exit is a non-graceful one: null the client reference
    (jax's atexit ``clean_up`` then skips the barrier) and leave the
    service (if this host runs it) to die with the process."""
    try:
        from jax._src import distributed

        distributed.global_state.client = None
        distributed.global_state.preemption_sync_manager = None
    except Exception as e:  # pragma: no cover - jax internals moved
        import sys

        print(
            f"warning: could not abandon distributed client ({e}); exit may "
            "stall until the coordination service tears the gang down",
            file=sys.stderr,
            flush=True,
        )


def _run_elastic(
    config: PipelineConfig,
    input_file: str,
    output_file: str,
    excluded_file: str,
    *,
    num_processes: int,
    process_id: int,
    text_column: str,
    id_column: str,
    buckets: Sequence[int],
    read_batch_size: int,
    device_batch: Optional[int],
    errors_file: Optional[str],
    lease_ttl_s: float,
    force: bool,
    run_report: Optional[str] = None,
    provenance: Optional[dict] = None,
    autoscale: Optional[str] = None,
):
    """Elastic membership execution (``--elastic``) — no lockstep, no gang.

    Processes are deliberately NOT coupled through ``jax.distributed``:
    on this container's jax the coordination service force-terminates every
    healthy task ~90-100 s after a peer stops heartbeating, which is the
    opposite of elasticity.  Coordination instead lives entirely on the
    shared filesystem under ``<output>.membership/`` (the same filesystem
    the shard merge already assumes): per-rank lease files
    (:class:`FileMembershipStore`), and one checkpoint directory per input
    *stripe* with a fenced, owner-tokened cursor
    (:func:`~textblaster_tpu.checkpoint.run_stripe_checkpointed`).
    ``--coordinator`` is accepted but unused.

    The protocol, per heartbeat interval:

    1. **Self-fence** — a process whose own lease went stale (or was taken
       over by a newer incarnation of its rank) stops committing and dies;
       its last unfenced commit races the adopter only within the lease
       TTL, and lineage-scoped part files + the single atomic cursor
       rename make any interleaving converge (worst case: one chunk is
       reprocessed, committed once).
    2. **Observe membership** — live set changes bump the membership epoch
       (:class:`EpochTracker`), printing eviction/rejoin transitions.
    3. **Own and advance stripes** — stripe ``s`` belongs to live rank
       ``s``, orphans to the lowest live rank (:func:`stripe_owner`).
       Claiming rewrites the cursor's owner token
       (:meth:`CheckpointState.adopt`); committed work transfers verbatim,
       so adoption and restart-in-place replay **zero completed chunks**.
       A relaunched rank simply re-registers a lease under a fresh
       incarnation and reclaims its cursor; its zombie predecessor (if
       any) loses ownership at its next fence.
    4. **Merge** — when every stripe's cursor shows its window consumed,
       the lowest live rank (merge duty fails over exactly like stripe
       ownership) concatenates all stripes' part files — in stripe order,
       so output order is independent of which ranks did the work — into
       the final kept/excluded (and dead-letter) files atomically with an
       explicit schema (:func:`_commit_concat`), then removes the
       membership directory.

    Byte parity: chunk boundaries are device-batch flush barriers and the
    stripe windows are the same contiguous row ranges the lockstep path
    uses, so outputs are byte-identical to an uninterrupted (or
    single-host) run regardless of kills, adoptions, or rejoins.

    Returns an ``AggregationResult``: global totals on the merging rank,
    this rank's local contribution elsewhere.
    """
    import os
    import shutil

    from ..checkpoint import (
        CheckpointState,
        StripeLost,
        _config_fingerprint,
        _input_fingerprint,
        run_stripe_checkpointed,
    )
    from ..errors import PipelineError
    from ..io.parquet_writer import OUTPUT_SCHEMA
    from ..ops.geometry import DeviceGeometry
    from ..ops.pipeline import CompiledPipeline, process_documents_device
    from ..orchestration import AggregationResult
    from ..resilience.deadletter import DEADLETTER_SCHEMA
    from ..resilience.faults import FAULTS, arm_from_env
    from ..resilience.membership import (
        EpochTracker,
        FileMembershipStore,
        assign_stripes,
    )
    from ..utils.metrics import (
        METRICS,
        build_run_report,
        is_merge_gauge,
        metrics_snapshot,
        write_run_report,
    )
    from .mesh import data_mesh

    import pyarrow.parquet as pq

    root = f"{output_file}.membership"

    def say(msg: str) -> None:
        # stdout + flush: the chaos tests stream these lines to time their
        # SIGKILLs, and operators of a 2-terminal run read them live.
        print(f"elastic[{process_id}]: {msg}", flush=True)

    if force and os.path.isdir(root):
        shutil.rmtree(root)
        say(f"removed leftover membership dir {root} (--force)")

    fingerprint = _input_fingerprint(input_file)
    config_hash = _config_fingerprint(config)
    arm_from_env(process_id=process_id)

    # Run-report scope starts here (mirrors the coordinated path): the
    # metrics delta attributes only this run's work.
    values_before = metrics_snapshot() if run_report is not None else {}
    wall_t0 = time.perf_counter()

    store = FileMembershipStore(root, process_id, lease_ttl_s)
    store.register()
    joiner = process_id >= num_processes
    if joiner:
        # A joiner exists to help a RUNNING gang.  Without a live home
        # rank there is nothing to join — most likely the run already
        # finished and the merger tore the membership directory down, in
        # which case claiming work here would silently re-execute the
        # whole job from virgin cursors (and re-merge over the published
        # outputs).  Bounded grace covers a gang that is still starting.
        grace = max(2.0, 2.0 * lease_ttl_s)
        t_grace = time.monotonic() + grace
        while not any(
            r < num_processes for r in store.live_ranks()
        ):
            if time.monotonic() >= t_grace:
                store.withdraw()
                say(
                    f"no live gang to join (no home-rank lease within "
                    f"{grace:g}s); exiting without work"
                )
                return AggregationResult()
            store.post()  # a stale joiner lease is invisible to the gang
            time.sleep(min(0.1, lease_ttl_s / 10.0))
        # A scale-out joiner (rank beyond the stripe count) is admitted on
        # the strength of an incarnation-stamped join request posted next
        # to its lease.  The request is only valid while the lease stays
        # fresh, so a joiner dying right here (the ``multihost.join.post``
        # fault site) is never assigned work — the gang proceeds un-grown.
        store.post_join_request()
        say(f"posted join request (incarnation {store.incarnation})")
    if TRACER.enabled:
        # File-backend analogue of _align_trace_clocks: the first process
        # to register wrote the run's wall-clock origin; every tracer
        # shifts onto it, no collective needed.
        t0 = store.t0_us()
        if t0 is not None:
            TRACER.align(
                TRACER.wall_at_origin_us() - t0,
                args={"origin_wall_us": t0, "backend": "file"},
            )
    interval = max(0.05, lease_ttl_s / 3.0)
    heartbeat = LeaseHeartbeat(store, interval).start()

    mesh = data_mesh() if len(jax.devices()) > 1 else None
    pipeline = CompiledPipeline(
        config, buckets=tuple(sorted(buckets)), batch_size=device_batch,
        mesh=mesh, multihost=True,
    )
    from ..ops.pipeline import maybe_warmup

    # Warm (or AOT-cache-load) the program set before claiming a stripe —
    # a restarted-in-place elastic member re-enters with warm executables
    # instead of re-paying the cold compile inside its adopted stripe.
    maybe_warmup(pipeline)

    n_rows = pq.ParquetFile(input_file).metadata.num_rows
    stride = math.ceil(n_rows / max(num_processes, 1))

    # Overlapped stripe residue (PR 9): reuse the window config so each
    # process keeps pipeline_depth stripe chunks in flight — one being
    # processed/committed, the rest decoding on the prefetch thread.  Reads
    # are side-effect-free, so fence/commit semantics are untouched and
    # chunk boundaries stay at stripe order.
    oc = getattr(config, "overlap", None)
    read_ahead = 0
    if (
        oc is not None
        and oc.enabled
        and os.environ.get("TEXTBLAST_NO_OVERLAP") != "1"
    ):
        read_ahead = max(1, oc.pipeline_depth - 1)

    def window(s: int) -> Tuple[int, int]:
        # Identical striping to the lockstep path, computed from the input
        # alone — every process (and every relaunch) derives the same
        # windows without communicating.
        skip = min(s * stride, n_rows)
        return skip, max(0, min(stride, n_rows - skip))

    def stripe_done(s: int, st: Optional[CheckpointState] = None) -> bool:
        _skip, take = window(s)
        if take <= 0:
            return True
        if st is None:
            st = CheckpointState.load(store.stripe_dir(s))
        return st is not None and st.rows_consumed >= take

    my_token = {"rank": process_id, "incarnation": store.incarnation}
    lineage = f"-r{process_id}x{store.incarnation}"
    tracker = EpochTracker(process_id)
    local = AggregationResult()
    say(
        f"joined membership (incarnation {store.incarnation}, "
        f"{num_processes} stripe(s), lease ttl {lease_ttl_s:g}s)"
    )

    seen_joiners: set = set()

    def assignable(live):
        # A rank beyond the stripe count is assignable only while its join
        # request is valid (request present + fresh lease of the same
        # incarnation, unfenced): a joiner that died before/at its request
        # post never receives a stripe, and one that dies later drops out
        # with its lease exactly like a home rank.
        reqs = store.read_join_requests()
        picked = sorted(r for r in live if r < num_processes or r in reqs)
        for r in picked:
            if r >= num_processes and r not in seen_joiners:
                seen_joiners.add(r)
                if r != process_id:
                    # First observation of a valid join request IS the
                    # admission on this path (``multihost.join.admit``).
                    FAULTS.fire("multihost.join.admit")
                    say(f"admitting joiner rank {r} (epoch {tracker.epoch})")
        return picked

    def owners_now(live):
        pending = [s for s in range(num_processes) if not stripe_done(s)]
        return assign_stripes(pending, assignable(live), num_processes)

    supervisor = None
    if autoscale is not None:
        from .autoscale import AutoscaleSupervisor

        cfg_path = (provenance or {}).get("pipeline_config")
        if cfg_path is None:
            raise PipelineError(
                "--autoscale needs the pipeline-config path in the run "
                "provenance to respawn joiners (both CLI entries provide "
                "it)"
            )

        def backlog_rows() -> int:
            total = 0
            for s in range(num_processes):
                _sk, tk = window(s)
                if tk <= 0:
                    continue
                st = CheckpointState.load(store.stripe_dir(s))
                total += tk - (st.rows_consumed if st is not None else 0)
            return max(0, total)

        def spawn_command(jid: int):
            import sys as _sys

            cmd = [
                _sys.executable, "-m",
                "textblaster_tpu.parallel.multihost",
                "--coordinator", "autoscale:0",
                "--num-processes", str(num_processes),
                "--process-id", str(jid),
                "--pipeline-config", str(cfg_path),
                "-i", input_file,
                "-o", output_file,
                "-e", excluded_file,
                "--elastic",
                "--lease-ttl-s", str(lease_ttl_s),
                "--read-batch-size", str(read_batch_size),
                "--buckets", ",".join(str(b) for b in sorted(buckets)),
                "--text-column", text_column,
                "--id-column", id_column,
            ]
            if device_batch is not None:
                cmd += ["--device-batch", str(device_batch)]
            if errors_file is not None:
                cmd += ["--errors-file", errors_file]
            return cmd

        supervisor = AutoscaleSupervisor(
            autoscale,
            num_stripes=num_processes,
            rank=process_id,
            live_ranks=store.live_ranks,
            backlog_rows=backlog_rows,
            spawn_command=spawn_command,
            say=say,
        )

    def self_fence() -> None:
        if heartbeat.failed or not store.my_lease_fresh():
            raise PipelineError(
                f"rank {process_id} self-fenced: its liveness lease went "
                f"stale (ttl {lease_ttl_s:g}s) or a newer incarnation of "
                "this rank took over; committing now could race the "
                "stripe's adopter, so this process stops instead"
            )

    # A joiner may only START working while a home rank is live (the
    # pre-compile grace check above, re-verified here because the gang can
    # finish and tear down during this process's pipeline compile).  Once
    # latched it is an ordinary member: if the home ranks die later it
    # keeps its adopted work and can even inherit merge duty.
    gang_seen = not joiner
    try:
        while True:
            self_fence()
            live = store.live_ranks()
            if not gang_seen:
                if any(r < num_processes for r in live):
                    gang_seen = True
                else:
                    say(
                        "gang disappeared before this joiner was "
                        "assigned work; exiting without work"
                    )
                    store.clear_join_request(process_id)
                    store.withdraw()
                    return local
            for msg in tracker.observe(live):
                say(msg)
            if supervisor is not None:
                supervisor.tick()
            progressed = False
            owners = owners_now(live)
            for s in range(num_processes):
                _skip, take = window(s)
                if take <= 0 or stripe_done(s):
                    continue
                if owners.get(s) != process_id:
                    continue
                st_dir = store.stripe_dir(s)
                cur = CheckpointState.load(st_dir)
                if cur is None or cur.owner != my_token:
                    st = CheckpointState.adopt(
                        st_dir, my_token,
                        input_fingerprint=fingerprint,
                        config_hash=config_hash,
                    )
                    if s != process_id:
                        METRICS.inc("multihost_adopted_stripes_total")
                        TRACER.instant(
                            "stripe_adopted",
                            {"stripe": s, "epoch": tracker.epoch},
                        )
                        if EVENTS.enabled:
                            EVENTS.emit("stripe_adopted", stripe=s,
                                        adopter=process_id,
                                        epoch=tracker.epoch)
                        say(
                            f"adopted stripe {s} at row {st.rows_consumed}"
                            f"/{take} (epoch {tracker.epoch})"
                        )
                    elif st.rows_consumed > 0:
                        say(
                            f"stripe {s} resume at row {st.rows_consumed}"
                            f"/{take} (epoch {tracker.epoch})"
                        )
                else:
                    st = cur
                recorded = (
                    DeviceGeometry.from_dict(st.geometry)
                    if st.geometry is not None
                    else None
                )
                if recorded is not None:
                    if (
                        recorded.fingerprint()
                        != pipeline.geometry.fingerprint()
                    ):
                        # Chunk boundaries are batch flush barriers; a
                        # different geometry would batch the remainder
                        # differently than the original owner did.
                        raise PipelineError(
                            f"stripe {s} cursor was created with device "
                            f"geometry {recorded.describe()}, but this "
                            "process resolves to "
                            f"{pipeline.geometry.describe()}; every "
                            "elastic participant must run the identical "
                            "--buckets/--device-batch"
                        )
                else:
                    st.geometry = pipeline.geometry.to_dict()

                skip, take = window(s)
                before = (
                    st.received, st.success, st.filtered, st.errors,
                    st.read_errors,
                )

                def fence(s=s, st_dir=st_dir) -> None:
                    self_fence()
                    if owners_now(store.live_ranks()).get(s) != process_id:
                        raise StripeLost(
                            f"stripe {s} ownership moved (membership "
                            "changed)"
                        )
                    reloaded = CheckpointState.load(st_dir)
                    if reloaded is not None and reloaded.owner != my_token:
                        raise StripeLost(
                            f"stripe {s} cursor claimed by "
                            f"{reloaded.owner}"
                        )

                def on_chunk(state: CheckpointState, s=s, take=take) -> None:
                    say(
                        f"stripe {s} committed rows "
                        f"{state.rows_consumed}/{take} "
                        f"(epoch {tracker.epoch})"
                    )
                    if supervisor is not None:
                        # The supervising rank spends most of the run
                        # inside its own stripe; committed chunk
                        # boundaries are its scaling cadence.
                        supervisor.tick()

                done = run_stripe_checkpointed(
                    input_file,
                    st_dir,
                    state=st,
                    skip_rows=skip,
                    take_rows=take,
                    chunk_size=read_batch_size,
                    process_chunk=lambda items, on_err: (
                        process_documents_device(
                            config, items, on_read_error=on_err,
                            pipeline=pipeline,
                        )
                    ),
                    fence=fence,
                    lineage=lineage,
                    text_column=text_column,
                    id_column=id_column,
                    record_dead=errors_file is not None,
                    on_chunk=on_chunk,
                    read_ahead=read_ahead,
                )
                local.received += st.received - before[0]
                local.success += st.success - before[1]
                local.filtered += st.filtered - before[2]
                local.errors += st.errors - before[3]
                local.read_errors += st.read_errors - before[4]
                progressed = True
                if not done:
                    say(f"stripe {s} lost to another owner; moving on")
            if all(stripe_done(s) for s in range(num_processes)):
                break
            if not progressed:
                time.sleep(interval)
    except BaseException as exc:
        # Aborted elastic run: still leave a machine-readable partial
        # report (this rank's contribution, flagged) — the same contract
        # the kv path keeps on a PeerFailure abort.
        if run_report is not None and not isinstance(exc, GeneratorExit):
            from ..utils.metrics import snapshot_delta

            now = metrics_snapshot()
            delta = snapshot_delta(values_before, now)
            partial = build_run_report(
                values=delta,
                wall_time_s=round(time.perf_counter() - wall_t0, 3),
                counts={
                    "received": local.received,
                    "success": local.success,
                    "filtered": local.filtered,
                    "errors": local.errors,
                    "read_errors": local.read_errors,
                },
                provenance=provenance,
            )
            partial["aborted"] = True
            partial["abort_reason"] = f"{type(exc).__name__}: {exc}"
            try:
                write_run_report(run_report, partial)
            except OSError:
                pass  # the abort itself stays the headline
        raise
    finally:
        heartbeat.stop()

    report_dir = os.path.join(root, "report")
    if run_report is not None:
        # Post this rank's report shard before withdrawing: the merging
        # rank folds whatever shards the (possibly churned) membership
        # left behind — counts stay exact either way, they come from the
        # stripe cursors.
        from ..utils.metrics import snapshot_delta

        now = metrics_snapshot()
        delta = snapshot_delta(values_before, now)
        os.makedirs(report_dir, exist_ok=True)
        path = os.path.join(report_dir, f"rank{process_id}.json")
        tmp = f"{path}.tmp.{store.incarnation}"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(
                {
                    "process": process_id,
                    "wall_time_s": round(
                        time.perf_counter() - wall_t0, 3
                    ),
                    "counts": {
                        "received": local.received,
                        "success": local.success,
                        "filtered": local.filtered,
                        "errors": local.errors,
                        "read_errors": local.read_errors,
                    },
                    "metrics": delta,
                },
                f,
            )
        os.replace(tmp, path)

    live = store.live_ranks()
    merger = min(live) if live else process_id
    if process_id != merger:
        store.withdraw()
        say(f"all stripes consumed; rank {merger} merges; local done")
        return local

    host_reports: List[dict] = []
    if run_report is not None:
        # Bounded wait for the other live ranks' report shards: each posts
        # before withdrawing, so every rank either reports or lets its
        # lease lapse.
        deadline = time.monotonic() + max(2.0, 2.0 * lease_ttl_s)
        while time.monotonic() < deadline:
            try:
                posted = {
                    int(n[len("rank"):-len(".json")])
                    for n in os.listdir(report_dir)
                    if n.startswith("rank") and n.endswith(".json")
                }
            except (FileNotFoundError, ValueError):
                posted = set()
            if not [
                r for r in store.live_ranks()
                if r != process_id and r not in posted
            ]:
                break
            time.sleep(0.05)
        try:
            names = sorted(os.listdir(report_dir))
        except FileNotFoundError:
            names = []
        for n in names:
            if not (n.startswith("rank") and n.endswith(".json")):
                continue
            try:
                with open(
                    os.path.join(report_dir, n), encoding="utf-8"
                ) as f:
                    host_reports.append(json.load(f))
            except (OSError, ValueError):
                continue
        host_reports.sort(key=lambda h: int(h.get("process", 0)))

    # Merge duty: lowest live rank (fails over like stripe ownership —
    # if the merger dies here, any relaunched/surviving rank re-enters,
    # finds every stripe done, and repeats this idempotent, atomic merge).
    cursors = [
        CheckpointState.load(store.stripe_dir(s))
        for s in range(num_processes)
    ]

    def parts(attr: str) -> List[str]:
        return [
            os.path.join(store.stripe_dir(s), name)
            for s, cur in enumerate(cursors)
            if cur is not None
            for name in getattr(cur, attr)
        ]

    _commit_concat(output_file, parts("out_parts"), OUTPUT_SCHEMA)
    _commit_concat(excluded_file, parts("excl_parts"), OUTPUT_SCHEMA)
    if errors_file is not None:
        _commit_concat(errors_file, parts("err_parts"), DEADLETTER_SCHEMA)
    merged = AggregationResult()
    for cur in cursors:
        if cur is None:
            continue
        merged.received += cur.received
        merged.success += cur.success
        merged.filtered += cur.filtered
        merged.errors += cur.errors
        merged.read_errors += cur.read_errors
    if run_report is not None:
        summed: dict = {}
        for h in host_reports:
            for k, v in h.get("metrics", {}).items():
                # Same merge rule as the coordinated path: counters sum
                # across ranks, gauges (gang-agreed values like the
                # membership epoch) merge by max.
                if is_merge_gauge(k):
                    summed[k] = max(summed.get(k, v), v)
                else:
                    summed[k] = summed.get(k, 0.0) + v
        report = build_run_report(
            values=summed,
            wall_time_s=max(
                [h.get("wall_time_s", 0.0) for h in host_reports]
                or [round(time.perf_counter() - wall_t0, 3)]
            ),
            counts={
                "received": merged.received,
                "success": merged.success,
                "filtered": merged.filtered,
                "errors": merged.errors,
                "read_errors": merged.read_errors,
            },
            provenance=provenance,
            hosts=host_reports,
        )
        write_run_report(run_report, report)
    if supervisor is not None:
        # Joiners leave on their own once every stripe is consumed
        # (fence-and-leave: report shard, lease withdrawal, clean exit);
        # reap them before the membership dir disappears under them.
        supervisor.drain(timeout_s=max(2.0, 4.0 * lease_ttl_s))
    store.withdraw()
    shutil.rmtree(root, ignore_errors=True)
    say(
        f"merged {num_processes} stripe(s): {merged.received} outcomes "
        f"({merged.success} kept, {merged.filtered} excluded, "
        f"{merged.errors} errors, {merged.read_errors} read errors)"
    )
    return merged


def _main(argv: Optional[Sequence[str]] = None) -> int:
    """Per-process module entry — a thin alias for
    ``textblast run --coordinator ...`` (the production path, `cli.py`)."""
    import argparse

    from ..config.pipeline import load_pipeline_config
    from ..utils.metrics import setup_prometheus_metrics

    ap = argparse.ArgumentParser()
    ap.add_argument("--coordinator", required=True)
    ap.add_argument("--num-processes", type=int, required=True)
    ap.add_argument("--process-id", type=int, required=True)
    ap.add_argument("--pipeline-config", required=True)
    ap.add_argument("-i", "--input-file", required=True)
    ap.add_argument("-o", "--output-file", required=True)
    ap.add_argument("-e", "--excluded-file", required=True)
    ap.add_argument("--errors-file", default=None)
    ap.add_argument("--text-column", default="text")
    ap.add_argument("--id-column", default="id")
    ap.add_argument("--read-batch-size", type=int, default=1024)
    ap.add_argument("--buckets", default="512,2048,8192")
    ap.add_argument("--device-batch", type=int, default=None)
    ap.add_argument("--auto-geometry", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument(
        "--exchange-deadline-s", type=float,
        default=DEFAULT_EXCHANGE_DEADLINE_S,
        help="budget for each lockstep KV exchange; on expiry a typed "
        "PeerFailure names the rank(s) that never posted",
    )
    ap.add_argument(
        "--lease-ttl-s", type=float, default=DEFAULT_LEASE_TTL_S,
        help="liveness-lease TTL (renewed at TTL/3); a rank whose lease "
        "is older is classified dead",
    )
    ap.add_argument(
        "--elastic", action="store_true",
        help="elastic membership: shared-filesystem leases + per-stripe "
        "checkpoint cursors; survivors adopt dead ranks' stripes, "
        "relaunched ranks rejoin in place, and new ranks "
        "(--process-id >= --num-processes) join live via an admission "
        "request",
    )
    ap.add_argument(
        "--autoscale", default=None, metavar="MIN:MAX",
        help="elastic-only supervisor: the lowest live home rank spawns "
        "joiner ranks (ids >= --num-processes) while backlog persists, "
        "up to MAX total workers; joiners drain (fence-and-leave) at "
        "idle",
    )
    ap.add_argument(
        "--exchange-transport", choices=("auto", "kv", "file"),
        default="auto",
        help="lockstep exchange carrier: kv = the XLA/coordination-service "
        "funnel, file = shared-filesystem slots riding the membership "
        "leases (required for --survive-peer-loss); auto picks file iff "
        "--survive-peer-loss",
    )
    ap.add_argument(
        "--survive-peer-loss", action="store_true",
        help="gang reformation on the coordinated path: on a peer death "
        "the survivors fence the dead rank's incarnation, re-elect the "
        "member set, adopt its stripe, and finish the run (file exchange "
        "transport only)",
    )
    ap.add_argument(
        "--pipeline-depth", type=int, default=None,
        help="in-flight lockstep round window for THIS host; the joint "
        "depth is the min over every host's value, allgathered once at "
        "run start (cli.py run exposes the same flag)",
    )
    ap.add_argument(
        "--no-overlap", action="store_true",
        help="disable the overlapped pipeline on this host (negotiates "
        "the whole gang down to serial depth 1)",
    )
    ap.add_argument(
        "--metrics-port", type=int, default=None,
        help="serve /metrics on this port + process-id (the offset keeps "
        "co-located processes from colliding on the bind)",
    )
    ap.add_argument(
        "--trace", default=None, metavar="OUT.JSON",
        help="record a Chrome trace (process 0 writes OUT.JSON, process i "
        "writes OUT.JSON.host<i>)",
    )
    ap.add_argument(
        "--run-report", default=None, metavar="REPORT.JSON",
        help="process 0 writes a merged machine-readable run report "
        "(pass on every process — the snapshot exchange is a collective)",
    )
    ap.add_argument(
        "--doc-sample-rate", type=int, default=0, metavar="N",
        help="sample 1-in-N documents for per-doc tail-latency lineage "
        "(deterministic on the doc id, so every host samples the same "
        "docs; 0 = off)",
    )
    args = ap.parse_args(argv)

    if args.exchange_deadline_s <= args.lease_ttl_s:
        ap.error(
            f"--exchange-deadline-s ({args.exchange_deadline_s:g}) must "
            f"exceed --lease-ttl-s ({args.lease_ttl_s:g}): with the "
            "exchange deadline at or under the lease TTL, every slow lease "
            "renewal is misclassified as a peer death"
        )
    if args.survive_peer_loss and args.exchange_transport == "kv":
        ap.error(
            "--survive-peer-loss requires the file-lease exchange "
            "transport; pass --exchange-transport file or auto"
        )
    if args.elastic and (
        args.survive_peer_loss or args.exchange_transport == "file"
    ):
        ap.error(
            "--elastic is incompatible with --survive-peer-loss / "
            "--exchange-transport file: elastic membership has no lockstep "
            "exchanges for the transport to carry"
        )

    if args.metrics_port is not None:
        setup_prometheus_metrics(args.metrics_port + args.process_id)
    if args.trace:
        trace_path = (
            args.trace if args.process_id == 0
            else f"{args.trace}.host{args.process_id}"
        )
        TRACER.configure(
            trace_path,
            process_name=f"textblast-host{args.process_id}",
            pid=args.process_id,
        )
    if args.doc_sample_rate > 0:
        from ..utils.telemetry import TELEMETRY

        TELEMETRY.configure(args.doc_sample_rate)

    config = load_pipeline_config(args.pipeline_config)
    if args.no_overlap:
        config.overlap.enabled = False
    if args.pipeline_depth is not None:
        config.overlap.pipeline_depth = max(1, args.pipeline_depth)
    try:
        result = run_multihost(
            config,
            args.input_file,
            args.output_file,
            args.excluded_file,
            coordinator=args.coordinator,
            num_processes=args.num_processes,
            process_id=args.process_id,
            text_column=args.text_column,
            id_column=args.id_column,
            read_batch_size=args.read_batch_size,
            buckets=tuple(int(b) for b in args.buckets.split(",")),
            device_batch=args.device_batch,
            auto_geometry=args.auto_geometry,
            errors_file=args.errors_file,
            force=args.force,
            run_report=args.run_report,
            exchange_deadline_s=args.exchange_deadline_s,
            lease_ttl_s=args.lease_ttl_s,
            elastic=args.elastic,
            exchange_transport=args.exchange_transport,
            survive_peer_loss=args.survive_peer_loss,
            autoscale=args.autoscale,
            provenance={
                "entry": "textblaster_tpu.parallel.multihost",
                "pipeline_config": args.pipeline_config,
                "steps": [s.type for s in config.pipeline],
                "input_file": args.input_file,
                "num_processes": args.num_processes,
                "buckets": args.buckets,
                "auto_geometry": args.auto_geometry,
                "doc_sample_rate": args.doc_sample_rate,
            },
        )
    finally:
        TRACER.close()
        if args.doc_sample_rate > 0:
            from ..utils.telemetry import TELEMETRY

            TELEMETRY.close()
    print(
        f"process {args.process_id}: {result.received} outcomes "
        f"({result.success} kept, {result.filtered} excluded)"
    )
    from ..utils.metrics import METRICS

    reformations = int(METRICS.get("multihost_gang_reformations_total"))
    if reformations:
        print(
            f"process {args.process_id}: survived {reformations} gang "
            "reformation(s); "
            f"{int(METRICS.get('multihost_adopted_stripes_total'))} "
            "stripe(s) adopted"
        )
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(_main())
