"""Read the numbers ``correct`` compares, over many seeds in one process.

    python bench/calibrate.py --workload <cell> --seeds 11,12,13 \
        --variants program,control [--seconds 2]

For each variant and seed this builds the cell's driver, runs its set-up
(the first chunks of a training cell, which need no window) or a short
window at the cell's own load (a serving cell), and prints one JSON line
with the compared numbers.  ``program`` is the timed path as a run drives
it; ``control`` puts the reference one precision lower in its place;
``half`` and ``no_merge`` put the reference with that fault planted in
its place (training cells).  The limits in ``limits/<cell>.json`` are set
from these readings: above the largest that the program gives, below the
smallest that the control or a fault gives.  Needs the chips the cell
asks for, like ``run.py``.
"""

import argparse
import gc
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--variants", default="program",
                    help="comma-separated: program, control, half, no_merge")
    ap.add_argument("--seconds", type=float, default=2.0,
                    help="window of a serving cell's readings")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(BENCH.parent / "src"))
    import harness

    try:
        cell = harness.find_cell(args.workload)
        devices = harness.prepare(cell)
    except harness.HarnessError as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 1
    seeds = [int(s) for s in args.seeds.split(",")]
    for variant in args.variants.split(","):
        for seed in seeds:
            t0 = time.perf_counter()
            driver = harness.make_driver(
                cell, seed, devices,
                None if variant == "program" else variant)
            driver.setup()
            t1 = time.perf_counter()
            if cell.kind != "train_stream":
                driver.window(args.seconds, None)
            checks = driver.check()
            print(json.dumps({"workload": args.workload, "variant": variant,
                              "seed": seed, "checks": checks,
                              "setup_s": t1 - t0,
                              "check_s": time.perf_counter() - t1}),
                  flush=True)
            del driver
            gc.collect()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
