"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up counts from the start of this process.  The last line of standard
output is the result as JSON; the numbers ``correct`` was decided on are
the last lines of standard error.  Exits non-zero, printing no result,
where JAX finds no TPU or fewer chips than the cell asks for, or where a
file the cell names is missing.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(BENCH.parent / "src"))
    try:
        import harness
        import repro  # noqa: F401
    except ImportError as e:
        print(f"bench: run from a checkout of the repository ({e})",
              file=sys.stderr)
        return 1
    try:
        result = harness.run_cell(
            args.workload, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace), t_start=T_START)
    except harness.HarnessError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
