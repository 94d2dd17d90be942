"""idle_transfer_share: the device's idle time while the driving thread's
innermost program span was ``dispatch``, ``device_dispatch`` (launch and
upload) or ``device_wait`` (the fetch of a batch's results, and the GIL
behind it), as a share of the window (``span_reduce``)."""

from benchmark import span_reduce

SPANS = ("dispatch", "device_dispatch", "device_wait")


def read(record):
    return span_reduce.idle_share(record, SPANS)
