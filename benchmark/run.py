"""Run one benchmark cell on the chips of this machine.

    python3 benchmark/run.py --workload danish_cc.mixed --seed 7 --seconds 10 --trace 0

The cell, its configuration (``benchmark/configs/<config>.{yaml,json}``),
its traffic mix (``benchmark/traffic/<mix>.json``) and its metrics
(``benchmark/metrics/<metric>.py``) are found by name from BENCHMARK.json.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiled run.  The last line of standard output is
one JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``; ``checks`` last).  Without
a TPU, or with fewer chips than the cell asks for, it exits non-zero and
prints no result.  ``--keep DIR`` copies the run's trace and record to DIR for
inspection.
"""

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep", default=None, help="copy the trace and record here")
    args = ap.parse_args(argv)

    from benchmark import harness

    try:
        cell = harness.load_cell(args.workload)
        harness.place_caches()
        line = harness.run(cell, args.seed, args.seconds, bool(args.trace), T0, keep=args.keep)
    except harness.BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
