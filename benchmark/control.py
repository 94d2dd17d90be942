"""Readings that set the limit of ``correct``: the program's and the
control's, over many seeds in one process.

    python3 benchmark/control.py --workload danish_cc.mixed --seeds 11,12,13 --seconds 10

Set-up is made once; then for each seed one window of the cell's own
traffic and size through the timed path, the program's written outcomes
against the plain reference (the lower reading), and the control against
the same reference (the upper reading).  The control is the reference put
in the program's place and computed in float32 where the configuration
states float64.  Prints one JSON line per seed and a summary line.  The
benchmark's own runs never run this.
"""

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Program and control readings over seeds.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from benchmark import compare, generator, harness

    cell = harness.load_cell(args.workload)
    harness.place_caches()
    block = int(generator.load_mix(cell.mix_path)["block_docs"])
    workers = harness.reference_workers()
    bench = harness.Bench(cell)
    print(f"set-up {time.monotonic() - T0:.1f}s", file=sys.stderr, flush=True)
    program, control = [], []
    for seed in (int(s) for s in args.seeds.split(",")):
        run_dir = os.path.join(harness.STATE, "runs", cell.name)
        shutil.rmtree(run_dir, ignore_errors=True)
        feed = harness.Feed(cell.mix_path, seed, os.path.join(run_dir, "input"))
        try:
            while not os.path.exists(feed.shard(harness.GENERATOR_AHEAD - 1)):
                time.sleep(0.05)
            record = bench.window(feed, args.seconds, block, run_dir, trace=False)
        finally:
            feed.close()
        bad, notes, _, (ids, texts, expected) = harness.check(cell, feed, record, block, workers)
        ctrl = compare.reference_outcomes(cell.pipeline_yaml, texts, workers, "float32")
        ctrl_bad, ctrl_notes = compare.mismatches(ids, expected, {i: [c] for i, c in zip(ids, ctrl)})
        shutil.rmtree(run_dir, ignore_errors=True)
        program.append(bad)
        control.append(ctrl_bad)
        print(json.dumps({"seed": seed, "docs": record["docs"],
                          "docs_per_s": record["docs"] / record["window_s"],
                          "program_mismatched": bad, "control_mismatched": ctrl_bad,
                          "program_notes": notes, "control_notes": ctrl_notes[:2]}), flush=True)
    print(json.dumps({"workload": cell.name, "seeds": len(program),
                      "lower_reading": max(program), "upper_reading": min(control)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
