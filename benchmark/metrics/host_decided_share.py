"""host_decided_share: the share of admitted documents that the host oracle
decided instead of the device (``worker_host_tail_total`` +
``worker_host_fallback_total`` over the window)."""


def read(record):
    c = record["counters"]
    if not record["docs"]:
        return None
    n = c.get("worker_host_tail_total", 0.0) + c.get("worker_host_fallback_total", 0.0)
    return n / record["docs"]
