"""mesh_upload_ms_per_kdoc: host seconds placing each batch on the chips of
the data mesh, sharded by rows, Δ``stage_mesh_upload_seconds``, in ms per
1,000 admitted documents; nothing where the program has no such counter."""


def read(record):
    c = record["counters"]
    if not record["docs"] or "stage_mesh_upload_seconds" not in c:
        return None
    return c["stage_mesh_upload_seconds"] * 1e6 / record["docs"]
