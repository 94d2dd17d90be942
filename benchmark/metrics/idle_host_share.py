"""idle_host_share: the device's idle time while the driving thread's
innermost program span was host work — ``assemble`` (step evaluation,
per-row decisions, C4 rewrites), ``host_suffix`` (the host steps after the
last phase), ``host_tail`` (the host oracle), or the self time of ``post``
and ``phase`` (the window's bookkeeping) — as a share of the window
(``span_reduce``)."""

from benchmark import span_reduce

SPANS = ("assemble", "host_suffix", "host_tail", "post", "phase")


def read(record):
    return span_reduce.idle_share(record, SPANS)
