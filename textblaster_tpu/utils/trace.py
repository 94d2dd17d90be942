"""The program's span tracer: Chrome trace-event JSON, and program spans
in the profiler's own trace.

The overlapped pipeline (utils/overlap.py + ops/pipeline.py process_chunk)
runs read/pack/dispatch/device-wait/post/write across four thread lanes,
and the multihost path adds negotiated lockstep rounds on top — the flat
Prometheus counters in utils/metrics.py say *how much* time each stage
took, but not *where the bubbles are*.  ``TRACER.span`` marks per-batch,
per-group and per-chunk stages (never per document) and has two sinks:

* **Chrome trace-event JSON** (``--trace out.json``), which loads directly
  in Perfetto (https://ui.perfetto.dev) or chrome://tracing:

  - ``"X"`` complete events — one per span, with microsecond ``ts``/``dur``;
  - ``"i"`` instant events — resilience transitions (retry, breaker
    trip/probe/recovery, negotiated verdicts, joint degradation);
  - ``"C"`` counter events — queue depths, so Perfetto draws them as tracks;
  - ``"M"`` metadata events — process/thread names, so each overlap thread
    (textblast-prefetch / textblast-pack-N / textblast-writer / MainThread)
    gets its own labeled lane.

* **The profiler's trace.**  While a ``jax.profiler`` session is active
  (``--trace-device``, or any ``jax.profiler.start_trace``), every span
  also opens a ``jax.profiler.TraceAnnotation`` named ``tb.<span>`` with
  the span's args as its metadata.  The spans then land in the same
  ``.xplane.pb`` as the device operations, on the profiler's clock, each on
  the line of the thread that emitted it — so an idle stretch of the device
  can be read against the program stage that was running on the host.  The
  ``tb.`` prefix keeps them apart from JAX's and the runtime's own events.

Design constraints, in order:

1. **Near-zero cost when off.**  With neither sink on, ``TRACER.span()`` is
   one attribute check and one ``TraceAnnotation.is_enabled()`` call
   returning a shared no-op context manager — no allocation, no lock.  All
   span sites are per batch, group, chunk or round (never per document),
   so even enabled the event rate is tiny next to the work being traced.
2. **Bounded memory.**  JSON events accumulate in a ring buffer; with a file
   configured the buffer spills to disk whenever it fills, so a
   multi-hour run holds at most ``ring`` events in memory.  Without a
   file (in-memory mode, used by tests) the ring simply drops the oldest
   events once full.
3. **Thread safety.**  One lock guards the ring; spans capture their
   timestamps outside it, so the critical section is a list append.
4. **Crash tolerance.**  The file is spilled incrementally as a JSON
   array; Perfetto's JSON importer tolerates a truncated (unterminated)
   array, so a killed run still yields a loadable trace.  ``close()``
   writes the terminator for well-formed JSON.

``device_profile`` (``--trace-device``) runs a ``jax.profiler`` session
around a block: the XLA device profile and the program's ``tb.*`` spans in
one file — the spans show *which stage* the device waited on; the device
lines show *what ran*.
"""

from __future__ import annotations

import json
import logging
import os
import sys
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

logger = logging.getLogger(__name__)

__all__ = ["Tracer", "TRACER", "device_profile"]

#: Name prefix of the program's spans in the profiler's trace.
SPAN_PREFIX = "tb."

#: ``jax.profiler.TraceAnnotation`` once JAX is imported (see
#: ``_session_active``).
_Annotation = None


def _session_active() -> bool:
    """Whether a profiler session is recording.  No session can be active
    before JAX is imported, and this module does not import JAX itself;
    once it is, the name is rebound to ``TraceAnnotation.is_enabled``, so
    every later call is that one C++ call."""
    global _Annotation, _session_active
    if "jax" not in sys.modules:
        return False
    from jax.profiler import TraceAnnotation

    _Annotation = TraceAnnotation
    _session_active = TraceAnnotation.is_enabled
    return TraceAnnotation.is_enabled()


def _annotation(name: str, args: Optional[Dict[str, Any]]):
    """The ``TraceAnnotation`` of span ``name``, its args as metadata."""
    return _Annotation(SPAN_PREFIX + name, **(args or {}))


class _NullSpan:
    """Shared no-op context manager returned by every call while neither
    sink records — the entire off-cost of a span site."""

    __slots__ = ()

    #: Whether the span records anywhere; a site computes costly args
    #: only for a live span.
    live = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def add_args(self, args) -> None:
        """No-op counterpart of :meth:`_Span.add_args`."""


_NULL_SPAN = _NullSpan()


class _ProfilerSpan:
    """A span for the profiler's trace alone (JSON tracing off)."""

    __slots__ = ("_ann",)
    live = True

    def __init__(self, name: str, args: Optional[Dict[str, Any]]):
        self._ann = _annotation(name, args)

    def __enter__(self):
        self._ann.__enter__()
        return self

    def add_args(self, args: Dict[str, Any]) -> None:
        """Args known only at the end of the span become metadata of the
        same profiler event."""
        self._ann.set_metadata(**args)

    def __exit__(self, *exc):
        self._ann.__exit__(None, None, None)
        return False


class _Span:
    __slots__ = ("_tracer", "_name", "_args", "_t0", "_ann")
    live = True

    def __init__(self, tracer: "Tracer", name: str, args: Optional[Dict[str, Any]]):
        self._tracer = tracer
        self._name = name
        self._args = args

    def __enter__(self):
        self._ann = None
        if _session_active():
            self._ann = _annotation(self._name, self._args)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def add_args(self, args: Dict[str, Any]) -> None:
        """Merge args discovered mid-span (e.g. the profiler's achieved
        bytes/s, known only once the device wait resolves) into the event
        emitted at exit.  Copies — the entry dict may be caller-shared."""
        if self._args is None:
            self._args = dict(args)
        else:
            self._args = {**self._args, **args}
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self._tracer._complete(self._name, self._t0, t1, self._args)
        return False


class Tracer:
    """Thread-safe Chrome trace-event recorder (see module docstring)."""

    def __init__(self) -> None:
        self.enabled = False
        self._lock = threading.Lock()
        self._ring: List[Dict[str, Any]] = []
        self._ring_cap = 65536
        self._dropped = 0
        self._warned_drop = False
        self._path: Optional[str] = None
        self._fh = None
        self._wrote_any = False
        self._t0 = 0.0
        self._offset_us = 0  # cross-host clock alignment (align())
        self._pid = 0
        self._process_name = "textblast"
        self._tids: Dict[int, int] = {}  # thread ident -> compact tid

    # --- lifecycle ----------------------------------------------------------

    def configure(
        self,
        path: Optional[str] = None,
        *,
        ring: int = 65536,
        process_name: str = "textblast",
        pid: int = 0,
    ) -> None:
        """Enable tracing.  ``path=None`` keeps events in the bounded ring
        (test mode); otherwise the ring spills to ``path`` incrementally.
        ``pid`` labels the Perfetto process lane — multihost runs pass the
        process index so per-host traces can be concatenated."""
        with self._lock:
            if self._fh is not None:
                self._fh.close()
            self._ring = []
            self._ring_cap = max(16, int(ring))
            self._dropped = 0
            self._warned_drop = False
            self._tids = {}
            self._path = path
            self._fh = None
            self._wrote_any = False
            self._t0 = time.perf_counter()
            self._offset_us = 0
            self._pid = int(pid)
            self._process_name = process_name
            if path is not None:
                parent = os.path.dirname(os.path.abspath(path))
                os.makedirs(parent, exist_ok=True)
                self._fh = open(path, "w", encoding="utf-8")
                self._fh.write("[\n")
            self.enabled = True
        self._emit(
            {
                "name": "process_name",
                "ph": "M",
                "pid": self._pid,
                "tid": 0,
                "args": {"name": process_name},
            }
        )

    def close(self) -> None:
        """Flush the ring, terminate the JSON array, and disable tracing."""
        with self._lock:
            if not self.enabled:
                return
            self.enabled = False
            if self._fh is not None:
                self._spill_locked()
                if self._fh is not None:  # spill failure closes the file
                    try:
                        self._fh.write("\n]\n")
                        self._fh.close()
                    except OSError as e:
                        logger.warning(
                            "Trace close on %s failed: %s", self._path, e
                        )
                    self._fh = None
            if self._dropped:
                logger.warning(
                    "Trace ring overflowed in-memory mode: %d events dropped",
                    self._dropped,
                )

    def drain(self) -> List[Dict[str, Any]]:
        """Return and clear the in-memory events (test hook)."""
        with self._lock:
            out, self._ring = self._ring, []
            return out

    # --- cross-host clock alignment -----------------------------------------

    def wall_at_origin_us(self) -> int:
        """This trace's time origin (``ts`` 0) as wall-clock microseconds.

        ``ts`` values are ``perf_counter`` deltas from ``configure()``; to
        put several hosts' traces on one Perfetto timeline, each host maps
        its origin onto the shared wall clock and shifts by the difference
        (:meth:`align`)."""
        return int((time.time() - (time.perf_counter() - self._t0)) * 1e6)

    def align(self, offset_us: int, args: Optional[Dict[str, Any]] = None) -> None:
        """Shift every *subsequent* event's ``ts`` by ``offset_us`` and
        record a ``trace_clock_offset`` metadata event documenting it.

        Multihost runs call this once after the startup clock handshake
        (``parallel/multihost.py _align_trace_clocks``): host ``i``'s offset
        is its origin's wall-clock distance from the earliest host's origin,
        so concatenated per-host traces share one timeline instead of each
        starting at ``ts`` 0.  Events emitted before the handshake (tracer
        setup, config loading) keep their unshifted, near-zero timestamps.
        """
        if not self.enabled:
            return
        self._offset_us = int(offset_us)
        self._emit(
            {
                "name": "trace_clock_offset",
                "ph": "M",
                "pid": self._pid,
                "tid": 0,
                "args": {"offset_us": int(offset_us), **(args or {})},
            }
        )

    # --- recording ----------------------------------------------------------

    def span(self, name: str, args: Optional[Dict[str, Any]] = None):
        """Context manager recording one span on the current thread's lane:
        an ``"X"`` complete event while JSON tracing is on, and a
        ``tb.<name>`` annotation while a profiler session is active."""
        if self.enabled:
            return _Span(self, name, args)
        if _session_active():
            return _ProfilerSpan(name, args)
        return _NULL_SPAN

    def instant(self, name: str, args: Optional[Dict[str, Any]] = None) -> None:
        """Record a zero-duration ``"i"`` event (resilience transitions)."""
        if not self.enabled:
            return
        self._emit(
            {
                "name": name,
                "ph": "i",
                "s": "t",
                "ts": self._now_us(),
                "pid": self._pid,
                "tid": self._tid(),
                **({"args": args} if args else {}),
            }
        )

    def counter(self, name: str, value: float) -> None:
        """Record a ``"C"`` counter sample (Perfetto draws a track)."""
        if not self.enabled:
            return
        self._emit(
            {
                "name": name,
                "ph": "C",
                "ts": self._now_us(),
                "pid": self._pid,
                "tid": 0,
                "args": {"value": value},
            }
        )

    def now_us(self) -> int:
        """Current time in microseconds on this trace's clock.

        With tracing configured, the value is a ``perf_counter`` delta from
        ``configure()`` shifted by the multihost :meth:`align` offset — the
        same clock every span and instant is stamped with, so consumers
        (the event journal) interleave correctly with the trace.  With
        tracing off, ``_t0`` is 0 and the value degrades to raw
        ``perf_counter`` microseconds: still monotone within the process,
        just not cross-host aligned."""
        return self._now_us()

    # --- internals ----------------------------------------------------------

    def _now_us(self) -> int:
        return int((time.perf_counter() - self._t0) * 1e6) + self._offset_us

    def _tid(self) -> int:
        """Compact per-thread lane id; first sight emits the thread_name
        metadata event so Perfetto labels the lane."""
        t = threading.current_thread()
        tid = self._tids.get(t.ident)
        if tid is None:
            with self._lock:
                tid = self._tids.get(t.ident)
                if tid is None:
                    tid = len(self._tids) + 1
                    self._tids[t.ident] = tid
                    self._append_locked(
                        {
                            "name": "thread_name",
                            "ph": "M",
                            "pid": self._pid,
                            "tid": tid,
                            "args": {"name": t.name},
                        }
                    )
        return tid

    def _complete(
        self, name: str, t0: float, t1: float, args: Optional[Dict[str, Any]]
    ) -> None:
        if not self.enabled:  # closed while the span was open
            return
        self._emit(
            {
                "name": name,
                "ph": "X",
                "ts": int((t0 - self._t0) * 1e6) + self._offset_us,
                "dur": max(0, int((t1 - t0) * 1e6)),
                "pid": self._pid,
                "tid": self._tid(),
                **({"args": args} if args else {}),
            }
        )

    def _emit(self, event: Dict[str, Any]) -> None:
        with self._lock:
            self._append_locked(event)

    def _append_locked(self, event: Dict[str, Any]) -> None:
        self._ring.append(event)
        if len(self._ring) >= self._ring_cap:
            if self._fh is not None:
                self._spill_locked()
            else:
                # In-memory mode: drop the oldest half, keep counting.
                drop = len(self._ring) // 2
                self._count_dropped_locked(drop)
                del self._ring[:drop]

    def _count_dropped_locked(self, n: int) -> None:
        """Account ``n`` dropped events: local counter, the
        ``trace_events_dropped_total`` metric, and a one-line stderr
        warning on the first drop (drops used to be silent — an unwritable
        spill path lost the whole ring with no sign anywhere)."""
        self._dropped += n
        first = not self._warned_drop
        self._warned_drop = True
        # Lazy import: metrics.py and trace.py are both leaf modules; the
        # one edge lives inside this rarely-hit path to keep it that way.
        from .metrics import METRICS

        METRICS.inc("trace_events_dropped_total", n)
        if first:
            print(
                f"textblast: trace events dropped ({n} so far) — ring "
                "overflow or unwritable spill file; trace will be "
                "incomplete",
                file=sys.stderr,
            )

    def _spill_locked(self) -> None:
        if not self._ring:
            return
        chunks = []
        for ev in self._ring:
            if self._wrote_any:
                chunks.append(",\n")
            self._wrote_any = True
            chunks.append(json.dumps(ev, separators=(",", ":")))
        try:
            self._fh.write("".join(chunks))
            self._fh.flush()
        except OSError as e:
            # Disk full / revoked path: count every event we just lost,
            # warn once, and stop spilling (the ring keeps the newest
            # events in memory so close() still has something to report).
            self._count_dropped_locked(len(self._ring))
            logger.warning("Trace spill to %s failed: %s", self._path, e)
            try:
                self._fh.close()
            except OSError:
                pass
            self._fh = None
        self._ring = []


#: Process-wide tracer.  Import this, never construct your own — span
#: sites across the codebase all talk to the same instance.
TRACER = Tracer()


@contextmanager
def device_profile(log_dir: Optional[str]):
    """Opt-in bridge to ``jax.profiler.trace``: captures the XLA device
    profile (TensorBoard/Perfetto-loadable) into ``log_dir`` for the
    duration of the block, with the program's ``tb.*`` spans on the host
    threads' lines of the same file.  ``log_dir=None`` is a no-op, and a
    backend without profiler support degrades to a warning, not a
    failure."""
    if not log_dir:
        yield
        return
    ctx = None
    try:
        import jax

        ctx = jax.profiler.trace(log_dir)
        ctx.__enter__()
    except Exception as e:  # pragma: no cover - backend-dependent
        logger.warning("jax.profiler.trace unavailable (%s); continuing", e)
        ctx = None
    try:
        yield
    finally:
        if ctx is not None:
            try:
                ctx.__exit__(None, None, None)
            except Exception as e:  # pragma: no cover
                logger.warning("jax.profiler.trace teardown failed: %s", e)
