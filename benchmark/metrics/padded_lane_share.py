"""padded_lane_share: the share of the lanes dispatched to the device that
carried padding, 1 - real codepoints / padded lanes (the program's
occupancy counters over the window)."""


def read(record):
    c = record["counters"]
    lanes = c.get("occupancy_padded_lanes_total", 0.0)
    if lanes <= 0:
        return None
    return 1.0 - c.get("occupancy_real_codepoints_total", 0.0) / lanes
