"""Readings that the correctness limits are set from: for each seed, the
program's widest logit gap on the sample a run compares, and the float8
control's widest gap on the same prompts and served tokens, with the
control put through the same checks (``control_correct`` must come out
false).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds <s>

All seeds run in one process, each a whole run of the cell (its own
weights, engine, warm-up and window at the cell's own load).  One JSON
line per seed.  The benchmark's own runs never compute the control.
"""
from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = run.cells.load(args.workload)
    run.require_chips(cell.chips)
    run.enable_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        result, det = run.run_cell(cell, seed, args.seconds, False,
                                   t_start=run.now(), control=True,
                                   log=lambda s: print(s, file=sys.stderr,
                                                       flush=True))
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": result["correct"], "metrics": result["metrics"],
            "program_widest_gap": det["widest_gap"],
            "control_correct": det["control_correct"],
            "control_widest_gap":
                det["control_checks"]["widest_logit_gap"]["value"],
            "tokens_compared": det["tokens_compared"]}), flush=True)


if __name__ == "__main__":
    main()
