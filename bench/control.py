"""The readings that the limits of ``correct`` are set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed, in one process, this runs the cell as ``run.py`` does
(set-up, a window of ``--seconds``, the comparison) and prints two sets
of numbers: those the program reads against the reference (the lower
readings), and those the control reads: the reference itself one
precision step below what the configuration states, put in the
program's place on the same uploads and rounds (the upper
readings).  The benchmark's own runs never run the control.  The last
line of standard output is a JSON object with every seed's readings.
"""
from __future__ import annotations

import json
import sys

from run import Refused, measure, parse


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    readings = []
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            run = measure(parse(["--workload", args.workload, "--seed",
                                 str(seed), "--seconds", str(args.seconds)]),
                          control=True)
        except Refused as exc:
            print(f"bench/control.py: {exc}", file=sys.stderr)
            return 2
        row = {"seed": seed, "correct": run["line"]["correct"],
               "program": run["notes"]["numbers"], "control": run["control"],
               "metrics": {k: v["value"]
                           for k, v in run["line"]["metrics"].items()},
               "memory_peak_bytes":
                   run["line"]["device"]["memory_peak_bytes"]}
        print(json.dumps(row), file=sys.stderr, flush=True)
        readings.append(row)
    print(json.dumps({"workload": args.workload, "readings": readings}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
