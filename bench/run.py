"""Run one benchmark cell on the chip and print one result line.

  python3 bench/run.py --workload phold-t2.uniform --seed 7 --seconds 20 \\
      --trace 0

Cells, metrics and bounds are in ``BENCHMARK.json``; see ``bench/harness.py``
for how a cell is found and run.  ``--trace 1`` traces the window and reports
the per-layer metrics instead of the end-to-end ones.  The run exits nonzero
and prints no result where JAX finds no TPU or fewer chips than the cell asks
for, or where any step fails.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the checkout root in place of bench/ (whose trace.py would shadow the
# standard library's), and the program's sources after it.
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness import Bench, NoChip, report

    bench = Bench(args.workload)
    try:
        bench.setup()
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    report(bench.measure(args.seed, args.seconds, bool(args.trace),
                         t_start=T_START))
    return 0


if __name__ == "__main__":
    sys.exit(main())
