"""docs_per_s: documents admitted, over the window from the first admission
to the last write (host clock)."""


def read(record):
    if record["window_s"] <= 0:
        return None
    return record["docs"] / record["window_s"]
