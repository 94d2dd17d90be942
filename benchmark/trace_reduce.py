"""Reduction of a profiler trace to the numbers the benchmark reports.

Input: the ``.xplane.pb`` that ``jax.profiler`` writes.  A device is a plane
named ``/device:TPU:<n>``; its operations are the events on the line named
``XLA Ops``.  The measured window is the host event ``bench_window``, which
the harness opens at the first admission and closes after the last write.

Output (``reduce``): the window's length; per device the union of its
operation intervals inside the window (busy seconds), averaged over the
devices; the device seconds per operation name, each operation counted
without the operations nested in it; and the idle gaps between busy
intervals, each named by the host event that covers most of it.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_EVENT = "bench_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"

Interval = Tuple[int, int]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def extract(path: str) -> Dict:
    """The events the reduction reads, as plain lists:
    ``{"devices": {plane: [(name, start_ns, end_ns)]},
    "host": [(name, start_ns, end_ns)]}``."""
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(path)
    devices: Dict[str, List[Tuple[str, int, int]]] = {}
    host: List[Tuple[str, int, int]] = []
    for plane in prof.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        s = int(e.start_ns)
                        evs.append((e.name, s, s + int(e.duration_ns)))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    s = int(e.start_ns)
                    host.append((e.name, s, s + int(e.duration_ns)))
    return {"devices": devices, "host": host}


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def self_times(ops: Sequence[Tuple[str, int, int]]) -> Dict[str, int]:
    """Nanoseconds per operation name, each event counted less the events
    nested inside it (a loop's body runs inside the loop's own event)."""
    out: Dict[str, int] = {}
    stack: List[List] = []  # [name, start, end, nested ns]

    def pop() -> None:
        name, s, e, nested = stack.pop()
        out[name] = out.get(name, 0) + max(0, e - s - nested)
        if stack:
            stack[-1][3] += e - s

    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and s >= stack[-1][2]:
            pop()
        stack.append([name, s, e, 0])
    while stack:
        pop()
    return out


def _label(gap: Interval, host: Sequence[Tuple[str, int, int]]) -> str:
    """The host event that covers most of ``gap``; among those covering at
    least half of it, the shortest."""
    a, b = gap
    best, best_cover, best_len = None, 0, None
    half = (b - a) / 2
    for name, s, e in host:
        if name == WINDOW_EVENT or e <= a or s >= b:
            continue
        cover = min(e, b) - max(s, a)
        length = e - s
        if cover >= half:
            if best_cover < half or length < best_len:
                best, best_cover, best_len = name, cover, length
        elif best_cover < half and cover > best_cover:
            best, best_cover, best_len = name, cover, length
    return best or "no host event"


def reduce(events: Dict, top: int = 10) -> Optional[Dict]:
    """Busy and idle time, operation time by name, and the longest idle
    gaps inside the window; None where the trace holds no window or no
    device operation."""
    window = [(s, e) for name, s, e in events["host"] if name == WINDOW_EVENT]
    devices = {k: v for k, v in events["devices"].items() if v}
    if not window or not devices:
        return None
    lo, hi = window[0]
    busy_s: List[float] = []
    op_s: Dict[str, float] = {}
    gaps: List[Interval] = []
    for ops in devices.values():
        spans = _clip(union([(s, e) for _, s, e in ops]), lo, hi)
        busy_s.append(sum(e - s for s, e in spans) / 1e9)
        edges = [lo] + [x for se in spans for x in se] + [hi]
        gaps.extend((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops if e > lo and s < hi]
        for name, ns in self_times(inside).items():
            op_s[name] = op_s.get(name, 0.0) + ns / 1e9
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [[_label(g, events["host"]), (g[1] - g[0]) / 1e9] for g in gaps[:top]]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_s) / len(busy_s),
        "devices": len(devices),
        "op_s": op_s,
        "idle_gaps": named,
    }


_HLO = re.compile(r"^%[\w\-.]+ = (.*?) ([a-z][\w\-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def short_name(op: str) -> str:
    """An HLO operation's kind and result type without layouts, e.g.
    ``while (s32[], s32[64,2048], ...)``; a Pallas kernel is
    ``pallas`` and its result type.  Operations of one kind and shape
    share the name across programs."""
    m = _HLO.match(op)
    if not m:
        return op[:80]
    result, kind = m.groups()
    if 'custom_call_target="tpu_custom_call"' in op:
        kind = "pallas"
    while _LAYOUT.search(result):
        result = _LAYOUT.sub("", result)
    result = re.sub(r"/\*index=\d+\*/", "", result)
    return f"{kind} {result}"[:120]


def breakdown(reduced: Dict, top: int = 10) -> Dict:
    """The ``breakdown`` of the result line: the operations (by kind and
    result type) that took most device time, summed over devices, and the
    longest idle gaps."""
    by_name: Dict[str, float] = {}
    for op, sec in reduced["op_s"].items():
        k = short_name(op)
        by_name[k] = by_name.get(k, 0.0) + sec
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": reduced["idle_gaps"][:top]}
