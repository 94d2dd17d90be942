"""pack_ms_per_kdoc: the program's packing seconds (``stage_pack_seconds``,
u16 wire encode and scatter) over the window, in ms per 1,000 admitted
documents."""


def read(record):
    if not record["docs"]:
        return None
    return record["counters"].get("stage_pack_seconds", 0.0) * 1e6 / record["docs"]
