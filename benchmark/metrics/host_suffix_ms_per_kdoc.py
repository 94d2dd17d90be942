"""host_suffix_ms_per_kdoc: host seconds running the steps that follow the
last device phase (TokenCounter), Δ``stage_host_suffix_seconds``, in ms per
1,000 admitted documents; nothing where the program has no such counter."""


def read(record):
    c = record["counters"]
    if not record["docs"] or "stage_host_suffix_seconds" not in c:
        return None
    return c["stage_host_suffix_seconds"] * 1e6 / record["docs"]
