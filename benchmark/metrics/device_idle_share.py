"""device_idle_share: 1 - (union of the device's operation intervals) /
(window), from the profiler trace of the whole window, averaged over the
chips used."""


def read(record):
    t = record.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 1.0 - t["busy_s"] / t["window_s"]
