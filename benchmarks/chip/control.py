#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3
        [--seconds 51] [--out <file.jsonl>]

For each seed, in one process: a run of the cell at its own load for a
window (harness ``run_cell``), then the plain reference over the served
greedy tokens (the program's readings, harness/check.py) and the control
the configuration names at the same positions (``control_*``: the same
numbers for the tokens the control puts first).  A limit lies between
the largest program reading and the smallest control reading.  The
benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            res = run.run_cell(args.workload, seed, args.seconds, False,
                               control=True)
        except run.NoChip as e:
            print(f"control.py: {e}", file=sys.stderr)
            return 1
        row = {"seed": seed, **res["_check"],
               "compiles_in_window": res["_compiles_in_window"],
               "metrics": {k: v["value"] for k, v in res["metrics"].items()}}
        print(json.dumps(row), flush=True)
        rows.append(row)
    if args.out:
        with open(args.out, "w") as f:
            f.writelines(json.dumps(r) + "\n" for r in rows)
    for name in ("logit_gap", "mean_logit_gap", "off_argmax_share"):
        prog = [r[name] for r in rows if name in r]
        ctrl = [r[f"control_{name}"] for r in rows if f"control_{name}" in r]
        if prog and ctrl:
            print(f"{name}: program max {max(prog):.6g}, control min "
                  f"{min(ctrl):.6g} over {len(rows)} seeds")
    return 0


if __name__ == "__main__":
    sys.exit(main())
