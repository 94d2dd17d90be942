"""aot_hit_share: the share of the warmup's programs loaded from the
executable store (``WarmupStats.cache_hits / programs``)."""


def read(record):
    w = record.get("warmup")
    if not w or not w["programs"]:
        return None
    return w["cache_hits"] / w["programs"]
