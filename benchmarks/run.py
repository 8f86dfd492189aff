"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (paper Figs 2–5 analogues + kernel
micro-benches).  ``--quick`` skips the subprocess scaling sweep.

The scaling sweep runs first, while this process has not touched JAX: each
of its children needs the device, and a parent that holds it would lock a
chip's children out.  A failed child fails the run.
"""
from __future__ import annotations

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="skip subprocess strong-scaling (fig3)")
    args, _ = ap.parse_known_args()

    from . import phold_scaling

    rows: list[dict] = []
    print("# PARSIR benchmarks (paper figure analogues)", file=sys.stderr)
    if not args.quick:
        print("# running fig3 strong scaling (subprocesses)...",
              file=sys.stderr, flush=True)
        phold_scaling.run(rows)

    from . import engine_comparison, kernel_bench, phold_modelsize, phold_speed
    for name, mod in [("fig2 speed vs L,M", phold_speed),
                      ("fig4 model size", phold_modelsize),
                      ("fig5 engine comparison", engine_comparison),
                      ("kernels", kernel_bench)]:
        print(f"# running {name}...", file=sys.stderr, flush=True)
        mod.run(rows)

    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.2f},{r['derived']}")


if __name__ == "__main__":
    main()
