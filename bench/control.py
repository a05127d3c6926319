#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's compared numbers and
the control's, over many seeds, in one process.

    python3 bench/control.py --workload qwen3-1.7b.batch-chat \\
        --seeds 101,102,103 --seconds 12

Each seed is one run of the cell as ``bench/run.py`` makes it (set-up,
a window of ``--seconds`` at the cell's own load, the comparison with the
float32 reference), followed by the control: the reference computed in
float8 put in the program's place, read on the same requests.  The
lower reading of a number is the largest the program gives, the upper
the smallest the control gives; ``bench/limits/<cell>.json`` keeps both
with the limit set between them.  The benchmark's own runs never run
this script.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--variant", default="fp8",
                    help="fp8 (the control) or half_batch (a planted fault "
                         "of a train cell)")
    ap.add_argument("--out", help="write the readings here as JSON")
    args = ap.parse_args(argv)
    compiles = run.CompileCounter()
    compiles.install()
    readings = []
    for seed in (int(s) for s in args.seeds.split(",")):
        code, res = run.run_cell(
            run.parse(["--workload", args.workload, "--seed", str(seed),
                       "--seconds", str(args.seconds)]),
            compiles=compiles, control=args.variant)
        if res is None:
            return code
        row = {"seed": seed,
               "program": {k: v["value"] for k, v in res["checks"].items()},
               "control": res["control_checks"],
               "metrics": {k: v["value"] for k, v in res["metrics"].items()}}
        print("[control] " + json.dumps(row), flush=True)
        readings.append(row)
    names = readings[0]["program"]
    summary = {n: {"lower": max(r["program"][n] for r in readings),
                   "upper": min(r["control"][n] for r in readings)}
               for n in names}
    print("[control] summary " + json.dumps(summary), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "readings": readings,
                       "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
