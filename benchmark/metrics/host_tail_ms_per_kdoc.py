"""host_tail_ms_per_kdoc: host seconds on the host oracle (leftover tails,
routed and over-length documents, overflow reruns, the ladder's host rung),
Δ``stage_host_tail_seconds``, in ms per 1,000 admitted documents; nothing
where the program has no such counter."""


def read(record):
    c = record["counters"]
    if not record["docs"] or "stage_host_tail_seconds" not in c:
        return None
    return c["stage_host_tail_seconds"] * 1e6 / record["docs"]
