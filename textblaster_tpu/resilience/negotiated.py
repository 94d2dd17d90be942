"""Negotiated resilience for the lockstep multi-host SPMD path.

The single-host degradation ladder (ops/pipeline.py ``_execute_packed``)
makes *unilateral* decisions: retry this batch, split it, rerun it on the
host oracle.  Under ``jax.distributed`` that is exactly what the lockstep
contract forbids — every process must dispatch the same programs in the
same order, so one host quietly re-dispatching (or skipping) a round while
its peers move on desynchronizes the global program sequence and hangs the
job until the coordination-service heartbeat tears it down (~90 s).

This module makes the ladder's decisions *jointly*.  After every lockstep
round each host contributes a 1-element fault flag to a small allgather
(the same ``host_allgather`` machinery the round schedule is negotiated
with, see ``parallel/multihost.py _negotiate_max``) and every host applies
the identical verdict:

* **any host faulted → negotiated retry**: ALL hosts re-dispatch the same
  round — including hosts whose own attempt succeeded, because the compiled
  program is a global SPMD execution that every process must participate
  in.  The shared :class:`RetryPolicy` schedule runs with **jitter forced
  to zero** so every host computes the same backoff for the same attempt
  and the dispatch sequences stay aligned in time as well as in order.
* **retry budget exhausted → negotiated degradation**: every host routes
  its chunk of the round to the bit-exact host oracle.  The degraded round
  is skipped *jointly* — agreement, not dispatch, is what lockstep
  requires, and this is the safe form of the "pad round": a host whose
  device cannot launch the pad program would strand its peers' in-flight
  collectives, whereas a negotiated skip keeps the global program sequence
  identical on every host by construction.
* **persistent faults → negotiated breaker latch**: a per-bucket
  :class:`CircuitBreaker` counts negotiated round failures.  Its state is
  driven *only* by the shared verdict sequence (cooldown is pinned to 0 —
  a wall-clock cooldown would let host clocks disagree about the state),
  so when a bucket trips, every host latches it at the same round and
  routes the rest of that bucket's documents to the host oracle without
  dispatching.

Residual risk, documented rather than hidden: if a compiled program carries
cross-host collectives (XLA's choice) and one host's *launch* fails while a
peer's succeeds, the peer's fetch can block on a collective that never
completes — the verdict negotiation only runs after the fetch returns or
raises.  The data-parallel filter programs this build compiles move no
row between devices; their only collectives are the scalar ORs of three
batch-wide gates (see parallel/mesh.py).  On a per-host mesh those stay on
the host, so the fetch completes locally and the negotiation always
convenes; on a mesh that spans processes the heartbeat teardown remains the
backstop, exactly as for hard process death.
"""

from __future__ import annotations

import logging
from typing import Callable, Dict, Optional, Sequence

import numpy as np

from ..utils.events import EVENTS
from ..utils.metrics import METRICS
from ..utils.trace import TRACER
from .breaker import CircuitBreaker
from .retry import RetryPolicy, classify_error
from .watchdog import WATCHDOG

logger = logging.getLogger(__name__)

__all__ = ["NegotiatedGuard"]


class NegotiatedGuard:
    """Joint fault/verdict protocol for one multi-host run.

    One instance guards one ``run_local_shard`` call (all phases), so the
    per-bucket breaker state persists for the shard's life.  Every
    participating process must construct it with the same config and bucket
    set and drive it through the identical round sequence — the verdict
    allgathers are collectives.
    """

    def __init__(
        self,
        rc=None,
        buckets: Sequence[int] = (),
        sleep: Optional[Callable[[float], None]] = None,
    ) -> None:
        if rc is None:
            from ..config.pipeline import ResilienceConfig

            rc = ResilienceConfig()
        # Jitter MUST be zero: each host computes its own backoff locally,
        # and the negotiated retry only preserves lockstep if every host
        # sleeps the same schedule before re-dispatching.
        overrides = {"jitter": 0.0}
        if sleep is not None:
            overrides["sleep"] = sleep
        self.policy = RetryPolicy.from_config(rc, **overrides)
        # cooldown_s=0 latches the breaker open: its transitions then depend
        # only on the (allgathered, therefore identical) verdict sequence,
        # never on a host-local clock.
        self.breakers: Dict[int, CircuitBreaker] = {
            b: CircuitBreaker(
                rc.breaker_threshold, name=f"negotiated-bucket-{b}",
                cooldown_s=0.0,
            )
            for b in buckets
        }

    # --- verdict exchange ---------------------------------------------------

    def _negotiate(self, local_fault: bool) -> bool:
        """Allgather every host's fault flag; True if ANY host faulted.

        Piggybacks on the same :func:`~textblaster_tpu.parallel.multihost.
        host_allgather` transport the round schedule is negotiated with —
        one int per host per call (XLA allgather on accelerators, the
        coordination-service KV store on multi-process CPU)."""
        return self.negotiate_batch([local_fault])[0]

    def negotiate_batch(self, local_faults: Sequence[bool]) -> list:
        """ONE verdict post carrying the fault flag of EVERY round the
        caller resolved since the last exchange; returns the per-round
        joint verdicts in the same order.

        The window drain in ``run_local_shard`` resolves its in-flight
        rounds in a burst; posting their flags as one vector collapses
        ``len(local_faults)`` transport posts into a single one.  A
        1-element batch posts the identical ``[0|1]`` vector the classic
        per-round :meth:`_negotiate` posted, so depth-1 traffic is
        byte-identical on the wire.  Callers must walk the verdicts in
        order and treat the FIRST fault as authoritative: the flags of the
        rounds behind it were measured on launched-ahead state the joint
        drain is about to discard, so every host voids them identically
        and re-negotiates those rounds at their own (post-drain) resolve."""
        from ..parallel.multihost import host_allgather

        flags = host_allgather(
            np.array([1 if f else 0 for f in local_faults])
        )
        if len(local_faults) > 1:
            METRICS.inc(
                "resilience_negotiated_batched_verdicts_total",
                len(local_faults),
            )
        return [bool(v) for v in (flags.max(axis=0) > 0)]

    def negotiate_freight(
        self, local_faults: Sequence[bool], freight: Sequence[int]
    ):
        """:meth:`negotiate_batch` with extra lanes riding the same post.

        The speculative phase barrier (``run_local_shard``) piggybacks its
        cross-barrier state — join-admission lanes and the next phase's
        optimistic round counts — onto the tail rounds' verdict vector, so
        one allgather replaces what used to be up to three separate posts
        (the win is largest on the file-lease transport, where each post
        is a filesystem round-trip).  Returns ``(verdicts, rows)``: the
        per-round joint verdicts in order, plus every host's raw freight
        lanes as an ``[n_proc, len(freight)]`` int array for the caller to
        reduce (union for join lanes, colmax for round counts).

        Void protocol, the cross-barrier extension of the batched-verdict
        contract: if ANY verdict in ``verdicts`` is a fault, the freight is
        VOID on every host — the counts were measured against tail state
        the joint drain is about to discard, and acting on them would let
        hosts disagree about the next phase's schedule.  Callers void
        speculated launches and the freight together, re-run the faulted
        round under :meth:`run_round` (``prior_fault``), and re-post a
        fresh barrier exchange — every host takes the identical branch
        because the verdicts themselves are allgathered."""
        from ..parallel.multihost import host_allgather

        n = len(local_faults)
        vec = [1 if f else 0 for f in local_faults] + [
            int(x) for x in freight
        ]
        rows = host_allgather(np.array(vec, dtype=np.int64))
        if n > 1:
            METRICS.inc(
                "resilience_negotiated_batched_verdicts_total", n
            )
        verdicts = (
            [bool(v) for v in (rows[:, :n].max(axis=0) > 0)] if n else []
        )
        return verdicts, rows[:, n:]

    @staticmethod
    def _epoch() -> int:
        """Current membership epoch, for labeling verdict trace instants —
        an epoch-aware Perfetto timeline shows which gang composition a
        retry/degradation happened under (lazy import, same cycle-avoidance
        as :meth:`_negotiate`)."""
        from ..parallel.multihost import current_exchange_epoch

        return current_exchange_epoch()

    # --- breaker ------------------------------------------------------------

    def bucket_degraded(self, bucket: int) -> bool:
        """True once ``bucket`` latched open — every host answers the same,
        because the breaker only moves on negotiated verdicts."""
        b = self.breakers.get(bucket)
        return b is not None and b.tripped

    def record_round_success(self, bucket: int) -> None:
        """Book a round whose joint verdict arrived via
        :meth:`negotiate_batch` as a success — the same metrics/breaker
        transition the clean-verdict exit of :meth:`run_round` performs,
        so the breaker's verdict sequence is identical whether a round's
        flag traveled alone or piggybacked in a batch."""
        METRICS.inc("resilience_negotiated_rounds_total")
        self.breakers[bucket].record_success()

    # --- the guarded round --------------------------------------------------

    def run_round(
        self,
        bucket: int,
        dispatch: Callable[[], object],
        fetch: Callable[[object], Dict[str, np.ndarray]],
        inflight: Optional[object] = None,
        launch_fault: bool = False,
        on_fault: Optional[Callable[[], None]] = None,
        prior_fault: bool = False,
        prior_local_fault: bool = False,
    ):
        """Resolve one lockstep round under the negotiated protocol.

        ``dispatch`` launches the round's global program (async) and
        ``fetch`` blocks for this process's host-side stats.  ``inflight``
        carries an already-dispatched result tree (the in-flight window in
        ``run_local_shard``); ``launch_fault`` marks that the overlapped
        launch already raised a retryable error, so the first attempt goes
        straight to the verdict.

        ``on_fault`` runs exactly once, on the FIRST joint fault verdict of
        this round (before the retry/degradation branch) — the window-drain
        hook: launched-ahead younger rounds must be discarded so every
        host's global program order after the verdict is the same
        ``[retry(r), r+1, r+2, ...]`` sequence.  The verdict is allgathered,
        so every host invokes its hook at the identical point.

        ``prior_fault`` marks that this round's FIRST joint verdict was
        already exchanged (fault) via :meth:`negotiate_batch` — the loop
        enters the fault branch directly instead of re-posting it, with
        ``prior_local_fault`` preserving this host's own flag for the
        verdict trace.  Every later attempt negotiates per-round as usual.

        Returns the fetched stats, or ``None`` when all hosts jointly
        degraded the round to the host oracle.  Fatal (deterministic)
        errors propagate immediately — they would repeat identically on
        every retry and on every host.

        Gang reformation (``--survive-peer-loss`` on the file-lease
        transport): the verdict exchange itself can discover a dead peer,
        in which case the transport reforms the gang and raises
        :exc:`GangReformed` *through* this method — deliberately uncaught
        here, because a round verdict cannot be salvaged when the member
        set changed mid-exchange.  The phase driver in ``run_local_shard``
        catches it at the round boundary and replays every unresolved
        round (this one included) over the survivor set; a trace instant
        marks the interruption point.
        """
        from ..errors import GangReformed

        METRICS.inc("resilience_negotiated_rounds_total")
        attempt = 0
        pre_verdict = bool(prior_fault)
        while True:
            if pre_verdict:
                # The batched window exchange already posted this round's
                # first flag and delivered a joint fault — fall through to
                # the fault branch without a second post for the same
                # verdict.
                pre_verdict = False
                local_fault, stats = bool(prior_local_fault), None
                inflight, launch_fault = None, False
                any_fault = True
            else:
                local_fault = bool(launch_fault)
                stats = None
                if not local_fault:
                    try:
                        out = inflight if inflight is not None else dispatch()
                        stats = fetch(out)
                    except BaseException as e:  # noqa: BLE001 — classifier decides
                        if classify_error(e) != "retryable":
                            raise
                        WATCHDOG.escalated(e)
                        logger.warning(
                            "Lockstep round (bucket %s) faulted locally on "
                            "attempt %d: %s",
                            bucket, attempt + 1, e,
                        )
                        local_fault = True
                # Past the first attempt nothing is in flight: a negotiated
                # retry must re-dispatch on EVERY host, succeeded ones
                # included.
                inflight, launch_fault = None, False
                try:
                    any_fault = self._negotiate(local_fault)
                except GangReformed:
                    TRACER.instant(
                        "negotiated_reformed",
                        {"bucket": bucket, "attempt": attempt,
                         "epoch": self._epoch()},
                    )
                    if EVENTS.enabled:
                        EVENTS.emit("negotiated_reformed", bucket=bucket,
                                    attempt=attempt)
                    raise
            if not any_fault:
                self.breakers[bucket].record_success()
                return stats
            TRACER.instant(
                "negotiated_verdict",
                {"bucket": bucket, "local_fault": local_fault,
                 "attempt": attempt, "epoch": self._epoch()},
            )
            if EVENTS.enabled:
                EVENTS.emit("negotiated_verdict", bucket=bucket,
                            local_fault=bool(local_fault), attempt=attempt)
            if on_fault is not None:
                on_fault()
                on_fault = None
            if attempt >= self.policy.max_retries:
                METRICS.inc("resilience_negotiated_degraded_rounds_total")
                TRACER.instant(
                    "negotiated_degraded",
                    {"bucket": bucket, "epoch": self._epoch()},
                )
                if EVENTS.enabled:
                    EVENTS.emit("negotiated_degraded", bucket=bucket)
                self.breakers[bucket].record_failure(
                    "negotiated round retries exhausted"
                )
                logger.error(
                    "Lockstep round (bucket %s) exhausted %d negotiated "
                    "retries; all hosts degrade this round to the host "
                    "oracle.",
                    bucket, self.policy.max_retries,
                )
                return None
            delay = self.policy.delay_for(attempt)
            attempt += 1
            METRICS.inc("resilience_negotiated_retries_total")
            TRACER.instant(
                "negotiated_retry",
                {"bucket": bucket, "attempt": attempt, "backoff_s": delay,
                 "epoch": self._epoch()},
            )
            if EVENTS.enabled:
                EVENTS.emit("negotiated_retry", bucket=bucket,
                            attempt=attempt)
            logger.warning(
                "Negotiated retry %d/%d of lockstep round (bucket %s) on "
                "all hosts, shared backoff %.3fs.",
                attempt, self.policy.max_retries, bucket, delay,
            )
            if delay > 0.0:
                self.policy.sleep(delay)
