#!/usr/bin/env python3
"""The readings that a cell's limits are set from, in one process.

    python3 chipbench/readings.py --workload er.vec --seconds 4 \
        --seeds 11 12 13 --control-seeds 21 22 23

Runs the program on each of ``--seeds`` and the control (the plain
reference at the precision below the configuration's, in the program's
place) on each of ``--control-seeds``, a short window each at the cell's
own traffic, and prints one JSON line per run with its compared numbers.
The last line gives, per number, the largest reading of the program (the
lower reading) and the smallest of the control (the upper reading). The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    spec = run.cell_spec(args.workload)
    control = run.load_module("drivers", spec.config["driver"]).control(
        spec.config)
    lower: dict = {}
    upper: dict = {}
    plan = ([("program", s, None) for s in args.seeds]
            + [("control", s, control) for s in args.control_seeds])
    for who, seed, system in plan:
        try:
            out = run.run(args.workload, seed, args.seconds, False,
                          system=system)
        except run.NoAccelerator as e:
            print(f"readings: {e}", file=sys.stderr)
            return 2
        numbers = {n: c["value"] for n, c in out["check"].items()}
        print(json.dumps({"who": who, "seed": seed, "correct": out["correct"],
                          "attempted": out["attempted"], **numbers}),
              flush=True)
        for n, v in numbers.items():
            if who == "program":
                lower[n] = max(lower.get(n, v), v)
            else:
                upper[n] = min(upper.get(n, v), v)
    print(json.dumps({"workload": args.workload, "lower": lower,
                      "upper": upper}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
