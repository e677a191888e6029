"""Readings that the limits of ``correct`` are set from, on the chip.

  python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5

For each seed: one run of the cell (a short window at the cell's own
load), then the numbers it compared, and the same numbers for the
control: the reference put in the program's place, one precision step
below the configuration's (``Precision.HIGH`` for fp32 at ``HIGHEST``).
All seeds run in one process, so set-up compiles once. Prints one JSON
object per seed. Benchmark runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run_cell(args.workload, seed, args.seconds, False,
                           control=True)
        print(json.dumps({
            "seed": seed, "correct": res["correct"],
            "attempted": res["attempted"], "failed": res["failed"],
            "program": {k: c["value"] for k, c in res["checks"].items()},
            "control": res["control"], "info": res["info"],
            "window_compiles": res["window_compiles"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
