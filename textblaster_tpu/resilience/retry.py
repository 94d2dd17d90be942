"""Retry policy: exponential backoff + jitter with an error classifier.

The classifier is the load-bearing piece: only *transient* faults — device
runtime errors (preempted slice, dropped connection, resource
exhaustion), OS-level I/O hiccups — are worth re-attempting.  Deterministic
pipeline errors (a filter decision, a config problem, a checkpoint
fingerprint mismatch) repeat identically on every attempt and must surface
immediately; retrying them only delays the failure and hides its cause.

The clock is injectable (``sleep=``/``rng=``) so tier-1 unit tests cover the
full backoff schedule without ever sleeping for real.
"""

from __future__ import annotations

import logging
import random
import time
from typing import Callable, Optional, Tuple, TypeVar

from ..errors import (
    CheckpointError,
    ConfigError,
    ConfigValidationError,
    DocumentFiltered,
    PipelineError,
    RetryExhaustedError,
    StallError,
    StepError,
)
from ..utils.events import EVENTS
from ..utils.metrics import METRICS
from ..utils.trace import TRACER
from .watchdog import WATCHDOG

logger = logging.getLogger(__name__)

__all__ = [
    "RetryPolicy",
    "classify_error",
    "is_oom_error",
    "is_retryable_error",
]

T = TypeVar("T")

# Message markers of transient device/transport faults.  XLA runtime errors
# surface as `XlaRuntimeError` (jaxlib; exact class location varies by
# version) carrying a gRPC-style status in the message; transport failures
# add plain phrasing ("connection", "response body closed").
_TRANSIENT_MARKERS = (
    "RESOURCE_EXHAUSTED",
    "DEADLINE_EXCEEDED",
    "UNAVAILABLE",
    "ABORTED",
    "CANCELLED",
    "preempt",
    "connection",
    "socket",
    "timed out",
    "timeout",
    "temporarily",
    "response body closed",
    "out of memory",
)

_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "out of memory", "OOM", "oom")

# Errors no outer retry loop should re-attempt: deterministic pipeline
# errors repeat identically, and RetryExhaustedError means a budget was
# already spent on this fault (nested policies must not multiply attempts).
_DETERMINISTIC_TYPES = (
    DocumentFiltered,
    StepError,
    ConfigError,
    ConfigValidationError,
    CheckpointError,
    RetryExhaustedError,
)


def _message_transient(exc: BaseException) -> bool:
    msg = str(exc)
    return any(marker in msg for marker in _TRANSIENT_MARKERS)


def is_oom_error(exc: BaseException) -> bool:
    """Device out-of-memory — the ladder's split-in-half rung targets these.
    Unwraps :class:`RetryExhaustedError` so an OOM that survived the retry
    budget still routes to the split rung."""
    if isinstance(exc, RetryExhaustedError):
        exc = exc.last
    if isinstance(exc, MemoryError):
        return True
    msg = str(exc)
    return any(marker in msg for marker in _OOM_MARKERS)


def is_retryable_error(exc: BaseException) -> bool:
    return classify_error(exc) == "retryable"


def classify_error(exc: BaseException) -> str:
    """``"retryable"`` (transient device/IO fault) or ``"fatal"``
    (deterministic — do not re-attempt)."""
    if isinstance(exc, _DETERMINISTIC_TYPES):
        return "fatal"
    if isinstance(exc, StallError):
        # Watchdog stall: the stalled stage may complete on a re-attempt
        # (re-dispatch, fresh fetch), and the degradation ladder bounds the
        # damage if it never does — explicitly retryable so a hang enters
        # the same recovery machinery as a raised transient fault.
        return "retryable"
    if isinstance(exc, (KeyboardInterrupt, SystemExit, GeneratorExit)):
        return "fatal"
    if isinstance(exc, (OSError, TimeoutError, ConnectionError, MemoryError)):
        # IOError/socket/timeout family: the transient-by-construction bucket.
        return "retryable"
    if type(exc).__name__ == "XlaRuntimeError":
        # Device runtime fault: transient statuses retry; INVALID_ARGUMENT /
        # compile-shape errors repeat identically.
        return "retryable" if _message_transient(exc) else "fatal"
    if isinstance(exc, PipelineError):
        # Remaining pipeline errors (ParquetError, IoError, Unexpected…):
        # retry only when the message says transient transport/IO.
        return "retryable" if _message_transient(exc) else "fatal"
    return "retryable" if _message_transient(exc) else "fatal"


class RetryPolicy:
    """Exponential backoff + jitter around a callable.

    ``max_retries`` counts re-attempts *after* the first try (``0`` disables
    retrying while keeping classification/metrics).  Delays follow
    ``base * multiplier**k`` capped at ``max_delay``, each widened by up to
    ``jitter`` fraction of itself (seeded ``rng`` for determinism in tests).
    """

    def __init__(
        self,
        max_retries: int = 3,
        base_delay: float = 0.05,
        max_delay: float = 2.0,
        multiplier: float = 2.0,
        jitter: float = 0.5,
        sleep: Callable[[float], None] = time.sleep,
        rng: Optional[random.Random] = None,
        classify: Callable[[BaseException], str] = classify_error,
    ) -> None:
        if max_retries < 0:
            raise ValueError("max_retries must be >= 0")
        self.max_retries = max_retries
        self.base_delay = base_delay
        self.max_delay = max_delay
        self.multiplier = multiplier
        self.jitter = jitter
        self.sleep = sleep
        self.rng = rng if rng is not None else random.Random()
        self.classify = classify

    def delay_for(self, attempt: int) -> float:
        """Backoff for re-attempt ``attempt`` (0-based), jitter applied."""
        d = min(self.base_delay * (self.multiplier ** attempt), self.max_delay)
        if self.jitter > 0.0:
            d *= 1.0 + self.rng.uniform(0.0, self.jitter)
        return d

    def run(
        self,
        fn: Callable[[], T],
        seam: str = "generic",
        on_retry: Optional[Callable[[BaseException, int], None]] = None,
    ) -> T:
        """Call ``fn`` until it succeeds, a fatal error surfaces, or retries
        are exhausted.  Raises the *last* error on exhaustion (chained), so
        genuine failures keep their type and message.

        ``seam`` labels metrics (``resilience_retries_<seam>_total``);
        ``on_retry(exc, attempt)`` observes each re-attempt.  Exhausting the
        budget on a *retryable* error raises
        :class:`~textblaster_tpu.errors.RetryExhaustedError` (a
        ``PipelineError``, so CLI-level handling stays clean) chained to the
        last underlying error; fatal errors re-raise untouched.
        """
        attempt = 0
        while True:
            try:
                return fn()
            except BaseException as e:  # noqa: BLE001 — classifier decides
                if self.classify(e) != "retryable":
                    raise
                WATCHDOG.escalated(e)
                if attempt >= self.max_retries:
                    METRICS.inc("resilience_retry_exhausted_total")
                    if EVENTS.enabled:
                        EVENTS.emit("retry_exhausted", seam=seam,
                                    attempts=attempt + 1,
                                    error=type(e).__name__)
                    raise RetryExhaustedError(seam, attempt + 1, e) from e
                delay = self.delay_for(attempt)
                attempt += 1
                METRICS.inc("resilience_retries_total")
                METRICS.inc(f"resilience_retries_{seam}_total")
                TRACER.instant(
                    "retry", {"seam": seam, "attempt": attempt,
                              "error": type(e).__name__}
                )
                if EVENTS.enabled:
                    EVENTS.emit("retry", seam=seam, attempt=attempt,
                                error=type(e).__name__)
                logger.warning(
                    "Transient fault at seam '%s' (attempt %d/%d, backing off "
                    "%.3fs): %s",
                    seam, attempt, self.max_retries, delay, e,
                )
                if on_retry is not None:
                    on_retry(e, attempt)
                if delay > 0.0:
                    self.sleep(delay)

    @classmethod
    def from_config(cls, rc, **overrides) -> "RetryPolicy":
        """Build from a :class:`~textblaster_tpu.config.pipeline.ResilienceConfig`."""
        kw = dict(
            max_retries=rc.max_retries,
            base_delay=rc.backoff_base_s,
            max_delay=rc.backoff_max_s,
            multiplier=rc.backoff_multiplier,
            jitter=rc.jitter,
        )
        kw.update(overrides)
        return cls(**kw)
