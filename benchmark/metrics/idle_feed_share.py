"""idle_feed_share: the device's idle time while the driving thread's
innermost program span was a wait on its feed — ``chunk_fill`` (taking the
next chunk from the reader), ``feed_wait`` (an empty read or pack queue),
``pack_wait`` (a pack future not done) or ``write_enqueue`` (handing a
batch to the writer) — as a share of the window (``span_reduce``)."""

from benchmark import span_reduce

SPANS = ("chunk_fill", "feed_wait", "pack_wait", "write_enqueue")


def read(record):
    return span_reduce.idle_share(record, SPANS)
