"""io_ms_per_kdoc: the program's Parquet read and write seconds
(``stage_read_seconds`` + ``stage_write_seconds``) over the window, in ms
per 1,000 admitted documents.  The stages overlap on threads, so this is a
cost per document, not a share of the window."""


def read(record):
    c = record["counters"]
    if not record["docs"]:
        return None
    s = c.get("stage_read_seconds", 0.0) + c.get("stage_write_seconds", 0.0)
    return s * 1e6 / record["docs"]
