#!/usr/bin/env python3
"""Readings that set the limits of a cell's check, read on the chip.

    python bench/readings.py --workload <name> --seeds 1,2,... \\
        [--control-seeds 101,102,103] [--seconds 10]

In one process (set-up is compiled once): one run of the cell for each
seed of ``--seeds``, as the benchmark runs it, and one run for each seed of
``--control-seeds`` with the control in the program's place.  Prints each
run's numbers compared, then one JSON line: for each number, ``lower`` (the
largest the program read), ``upper`` (the smallest the control read) and the
per-seed readings.  The benchmark's own runs do not run it.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, required=True)
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    from bench import harness
    runs = [(s, False) for s in args.seeds] + [(s, True) for s in args.control_seeds]
    out = {}
    for seed, control in runs:
        res = harness.run(args.workload, seed, args.seconds, False,
                          t_start=time.time(), control=control)
        who = "control" if control else "program"
        line = {"seed": seed, "who": who, "correct": res["correct"],
                "metrics": {k: v["value"] for k, v in res["metrics"].items()},
                "compared": {k: v["value"] for k, v in res["compared"].items()}}
        print(json.dumps(line), flush=True)
        for name, v in res["compared"].items():
            out.setdefault(name, {"program": [], "control": []})[who].append(
                [seed, v["value"]])
    for name, r in out.items():
        r["lower"] = max((v for _, v in r["program"]), default=None)
        r["upper"] = min((v for _, v in r["control"]), default=None)
    print(json.dumps({"workload": args.workload, "readings": out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
