"""Sweep the offered rate of an open-loop serving cell, to find its knee.

    python bench/sweep_serve.py --workload <cell> --rates 1000,2000,4000 \
        [--seconds 10] [--seed 7]

For each rate this runs the cell's driver with its traffic at that rate
(everything else as the cell has it) for one window and prints one JSON
line: p50 and p99 latency from the due time, how many requests finished
late, and the backlog trend (mean latency of the window's last quarter of
requests over its first quarter; near 1 when the system keeps up, growing
with the window when it does not).  The knee is the highest rate without a
growing backlog; a cell offers a fixed share of it.  Needs the chips the
cell asks for, like ``run.py``.
"""

import argparse
import dataclasses
import gc
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated, per s")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(BENCH.parent / "src"))
    import numpy as np

    import harness

    try:
        cell = harness.find_cell(args.workload)
        devices = harness.prepare(cell)
    except harness.HarnessError as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 1
    for rate in (float(r) for r in args.rates.split(",")):
        at_rate = dataclasses.replace(
            cell, traffic=dict(cell.traffic, rate_rps=rate))
        driver = harness.make_driver(at_rate, args.seed, devices)
        driver.setup()
        gc.collect()
        gc.freeze()  # as a run does: see harness.run_cell
        e2e, counters = driver.window(args.seconds, None)
        gc.unfreeze()
        lat = driver.latency_ms
        q = max(1, len(lat) // 4)
        print(json.dumps({
            "rate_rps": rate, **e2e,
            "backlog_trend": float(np.mean(lat[-q:]) / np.mean(lat[:q])),
            "late_requests": int(np.sum(lat > 10 * np.median(lat))),
            **counters}), flush=True)
        del driver
        gc.collect()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
