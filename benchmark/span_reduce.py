"""Attribution of the device's idle time to the program's own spans.

Input: the ``.xplane.pb`` of a traced run.  The program marks its stages
with spans (``textblaster_tpu/utils/trace.py``) that a profiler session
records as host events named ``tb.<span>``, each on the ``/host:CPU`` line
of the thread that emitted it.  The driving line is the one that carries
``tb.dispatch``: the thread that runs ``process_documents_device`` under
``aggregate_results_from_stream``.  The device's idle time is taken as
``trace_reduce.reduce`` takes it: inside the ``bench_window`` host event,
the complement of the union of each device's ``XLA Ops`` intervals.

Output (``attribute``): for each span name, the idle nanoseconds during
which it was the innermost ``tb.*`` span open on the driving line, averaged
over the devices; idle time under no span is ``UNCOVERED``.  The parts add
up to the idle time, so their shares of the window add up to
``device_idle_share``.  ``program_seconds`` gives the device seconds of each
XLA module (the program for one bucket and phase is ``jit_tb_b<L>_p<P>``).

    python3 -m benchmark.span_reduce DIR

prints both, with the longest idle gaps and the spans under them, for the
trace under DIR (``run.py --keep DIR`` keeps one).
"""

from __future__ import annotations

import json
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark.trace_reduce import (
    DEVICE_PLANE,
    HOST_PLANE,
    OPS_LINE,
    WINDOW_EVENT,
    _clip,
    find_xplane,
    union,
)

PREFIX = "tb."
DRIVING_SPAN = PREFIX + "dispatch"
MODULES_LINE = "XLA Modules"
UNCOVERED = "(no span)"

Span = Tuple[str, int, int]


def extract(path: str, with_args: bool = False) -> Dict:
    """The events the attribution reads, as plain lists:
    ``{"devices": {plane: [(start_ns, end_ns)]}, "window": [(start, end)],
    "lines": [[(name, start_ns, end_ns)]]}``, one list per host thread line
    that holds a ``tb.*`` span; ``with_args`` adds ``"args"``, each span's
    args keyed by ``(name, start_ns)``."""
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(path)
    devices: Dict[str, List[Tuple[int, int]]] = {}
    window: List[Tuple[int, int]] = []
    lines: List[List[Span]] = []
    args: Dict[Tuple[str, int], Dict] = {}
    for plane in prof.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        s = int(e.start_ns)
                        evs.append((s, s + int(e.duration_ns)))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans: List[Span] = []
                for e in line.events:
                    name = e.name
                    if name.startswith(PREFIX):
                        s = int(e.start_ns)
                        spans.append((name, s, s + int(e.duration_ns)))
                        if with_args:
                            args[(name, s)] = dict(e.stats)
                    elif name == WINDOW_EVENT:
                        s = int(e.start_ns)
                        window.append((s, s + int(e.duration_ns)))
                if spans:
                    lines.append(spans)
    out = {"devices": devices, "window": window, "lines": lines}
    if with_args:
        out["args"] = args
    return out


def innermost(spans: Sequence[Span]) -> List[Span]:
    """Disjoint ``(name, start, end)`` segments, each the stretch during
    which ``name`` was the innermost open span of one thread's nested
    spans; stretches under no span are left out."""
    out: List[Span] = []
    stack: List[Tuple[str, int]] = []  # (name, end)
    cursor = 0

    def emit(name: str, a: int, b: int) -> None:
        if b > a:
            out.append((name, a, b))

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            top, end = stack.pop()
            emit(top, cursor, end)
            cursor = max(cursor, end)
        if stack:
            emit(stack[-1][0], cursor, s)
        cursor = s
        stack.append((name, e))
    while stack:
        top, end = stack.pop()
        emit(top, cursor, end)
        cursor = max(cursor, end)
    return out


def _overlap_by_name(segments: Sequence[Span], gaps: Sequence[Tuple[int, int]]) -> Dict[str, int]:
    """Nanoseconds of ``gaps`` under each segment's name; both sorted and
    disjoint."""
    out: Dict[str, int] = {}
    i = 0
    for a, b in gaps:
        while i < len(segments) and segments[i][2] <= a:
            i += 1
        j = i
        while j < len(segments) and segments[j][1] < b:
            name, s, e = segments[j]
            ov = min(e, b) - max(s, a)
            if ov > 0:
                out[name] = out.get(name, 0) + ov
            j += 1
    return out


def _driving_line(events: Dict) -> Optional[List[Span]]:
    driving = [ln for ln in events["lines"] if any(n == DRIVING_SPAN for n, _, _ in ln)]
    if not driving:
        return None
    # One driving thread; should a run hold more, the busiest is taken.
    return max(driving, key=lambda ln: sum(1 for n, _, _ in ln if n == DRIVING_SPAN))


def _idle_gaps(ops: Sequence[Tuple[int, int]], lo: int, hi: int) -> List[Tuple[int, int]]:
    busy = _clip(union(ops), lo, hi)
    edges = [lo] + [x for se in busy for x in se] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def attribute(events: Dict) -> Optional[Dict]:
    """The window, and the device's idle nanoseconds by innermost span of
    the driving line (averaged over devices); None where the trace holds no
    window, no device operation or no driving line."""
    devices = {k: v for k, v in events["devices"].items() if v}
    line = _driving_line(events)
    if not events["window"] or not devices or line is None:
        return None
    lo, hi = events["window"][0]
    clipped = [(n, max(s, lo), min(e, hi)) for n, s, e in line if e > lo and s < hi]
    segments = innermost(clipped)
    idle: Dict[str, float] = {}
    idle_total = 0.0
    for ops in devices.values():
        gaps = _idle_gaps(ops, lo, hi)
        total = sum(b - a for a, b in gaps)
        idle_total += total
        named = _overlap_by_name(segments, gaps)
        named[UNCOVERED] = total - sum(named.values())
        for name, ns in named.items():
            idle[name] = idle.get(name, 0.0) + ns
    n = len(devices)
    return {
        "window_ns": hi - lo,
        "devices": n,
        "idle_ns": idle_total / n,
        "by_span_ns": {k: v / n for k, v in idle.items()},
    }


_MEMO: Dict[str, Optional[Dict]] = {}


def for_record(record: Dict) -> Optional[Dict]:
    """``attribute`` of the run's trace, read once per trace file; None
    where the run kept no trace or the trace holds no program span (a
    program without them)."""
    trace_dir = record.get("trace_dir")
    if not trace_dir:
        return None
    try:
        path = find_xplane(trace_dir)
    except FileNotFoundError:
        return None
    if path not in _MEMO:
        _MEMO[path] = attribute(extract(path))
    return _MEMO[path]


def idle_share(record: Dict, spans: Sequence[str]) -> Optional[float]:
    """Device idle time under the innermost spans ``spans`` (names without
    the prefix), as a share of the window."""
    a = for_record(record)
    if a is None or a["window_ns"] <= 0:
        return None
    by = a["by_span_ns"]
    return sum(by.get(PREFIX + s, 0.0) for s in spans) / a["window_ns"]


def program_seconds(path: str) -> Dict[str, float]:
    """Device seconds per XLA module inside the window, summed over
    devices."""
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(path)
    window = None
    modules: List[Tuple[str, int, int]] = []
    for plane in prof.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    for e in line.events:
                        s = int(e.start_ns)
                        modules.append((e.name, s, s + int(e.duration_ns)))
        elif plane.name == HOST_PLANE and window is None:
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_EVENT:
                        s = int(e.start_ns)
                        window = (s, s + int(e.duration_ns))
                        break
    out: Dict[str, float] = {}
    for name, s, e in modules:
        if window is not None:
            s, e = max(s, window[0]), min(e, window[1])
        if e > s:
            key = name.split("(")[0]
            out[key] = out.get(key, 0.0) + (e - s) / 1e9
    return out


def longest_gaps(events: Dict, top: int = 10) -> List[Dict]:
    """The first device's longest idle gaps inside the window, each with
    the innermost spans of the driving line that cover it (seconds, most
    first) and the args of the ``phase`` span open at its middle."""
    devices = [v for v in events["devices"].values() if v]
    line = _driving_line(events)
    if not events["window"] or not devices or line is None:
        return []
    lo, hi = events["window"][0]
    gaps = sorted(_idle_gaps(devices[0], lo, hi), key=lambda g: g[0] - g[1])[:top]
    segments = innermost([(n, max(s, lo), min(e, hi)) for n, s, e in line
                          if e > lo and s < hi])
    args = events.get("args", {})
    out = []
    for a, b in gaps:
        covered = _overlap_by_name(segments, [(a, b)])
        mid = (a + b) // 2
        phase = [(n, s) for n, s, e in line if n == PREFIX + "phase" and s <= mid < e]
        out.append({
            "start_s": (a - lo) / 1e9,
            "gap_s": (b - a) / 1e9,
            "spans_s": {k: v / 1e9 for k, v in sorted(covered.items(), key=lambda kv: -kv[1])},
            "phase": args.get(phase[-1]) if phase else None,
        })
    return out


def main(argv: Sequence[str]) -> int:
    path = find_xplane(argv[0])
    events = extract(path, with_args=True)
    a = attribute(events)
    report = {"idle": None, "longest_gaps": longest_gaps(events),
              "programs_s": program_seconds(path)}
    if a is not None:
        w = a["window_ns"]
        report["idle"] = {
            "window_s": w / 1e9,
            "device_idle_share": a["idle_ns"] / w,
            "by_span_share": dict(sorted(((k, v / w) for k, v in a["by_span_ns"].items()),
                                         key=lambda kv: -kv[1])),
        }
    print(json.dumps(report, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
