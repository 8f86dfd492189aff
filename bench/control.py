"""Readings for the limits of ``correct``: the program's over many seeds, and
the control's.

  python3 bench/control.py --workload phold-t2.uniform --seeds 12 \\
      --control-seeds 3 --seconds 20 [--first-seed 1000]

One process sets the cell up once, then makes a run per seed through the
same path as ``bench/run.py`` (window, comparison with the reference) and
records the numbers compared: the lower readings.  The control is the
reference itself put in the program's place, computed at the precision next
below the one the configuration states (bfloat16 for its f32 timestamps and
state), on the first ``--control-seeds`` of those simulations: the upper
readings.  The benchmark's own runs never run this.  Prints one JSON line.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--first-seed", type=int, default=1000)
    args = ap.parse_args(argv)

    from bench import check
    from bench.harness import Bench, NoChip
    from bench.oracle import to_bfloat16

    bench = Bench(args.workload)
    try:
        bench.setup()
    except NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    program, control = [], []
    for i in range(args.seeds):
        seed = args.first_seed + i
        line = bench.measure(seed, args.seconds, trace=False)
        program.append({"seed": seed, "correct": line["correct"],
                        "compared": line["compared"],
                        **{k: v["value"] for k, v in line["checks"].items()}})
        print(f"program seed {seed}: {program[-1]}", file=sys.stderr,
              flush=True)
        if i < args.control_seeds:
            ref = bench.reference()
            gaps = check.compare(bench.sims, ref, bench.epoch_len,
                                 rnd=to_bfloat16, stand_in=ref)
            control.append({"seed": seed, **gaps})
            print(f"control seed {seed}: {control[-1]}", file=sys.stderr,
                  flush=True)
    print(json.dumps({"workload": args.workload, "program": program,
                      "control": control}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
