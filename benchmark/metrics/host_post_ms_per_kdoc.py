"""host_post_ms_per_kdoc: host seconds of the post stage (assembly, host
steps such as TokenCounter, host tails) less the time it blocked on the
device, clamped at 0 as the program's ``stage_breakdown`` does, in ms per
1,000 admitted documents."""


def read(record):
    c = record["counters"]
    if not record["docs"]:
        return None
    post = max(0.0, c.get("stage_post_seconds", 0.0) - c.get("stage_device_wait_seconds", 0.0))
    return post * 1e6 / record["docs"]
