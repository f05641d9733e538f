"""Finds the highest request rate a Poisson cell's mix sustains (its knee),
and checks it by open-loop runs at multiples of it.

    python3 bench/sweep.py --workload <poisson cell> --seed <n> --seconds <s> \
        --loads 0.8,1.0,1.1

The knee is the rate at which the mix's requests complete when a backlog
keeps every lane busy: the same mix dealt as a queue of two
requests per lane, requests retired in the window over its
length.  Then the mix runs open loop at each load times that rate, and
each run prints its latency quantiles and how many of its requests the
drain limit left unfinished.  One JSON line per run; the benchmark's own
runs never sweep.  The rate the cell's traffic file holds is written
there by hand, from these lines.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys

import run

QUEUE_PER_LANE = 2      # the backlog cells' depth: every lane stays busy


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--loads", default="0.8,1.0,1.1",
                    help="comma-separated multiples of the knee")
    args = ap.parse_args(argv)
    cell = run.cells.load(args.workload)
    run.require_chips(cell.chips)
    run.enable_cache()
    log = lambda s: print(s, file=sys.stderr, flush=True)  # noqa: E731

    backlog = dataclasses.replace(cell, end_to_end=[], traffic={
        **cell.traffic, "arrival": "backlog",
        "queue_per_lane": QUEUE_PER_LANE})
    result, det = run.run_cell(backlog, args.seed, args.seconds, False,
                               t_start=run.now(), log=log)
    knee = result["attempted"] / det["window_s"]
    print(json.dumps({"workload": args.workload, "arrival": "backlog",
                      "seed": args.seed, "correct": result["correct"],
                      "retired": result["attempted"],
                      "window_s": det["window_s"], "knee_rps": knee}),
          flush=True)
    for i, load in enumerate(float(x) for x in args.loads.split(",")):
        mix = {**cell.traffic, "arrival": "poisson", "rate_rps": load * knee}
        result, det = run.run_cell(dataclasses.replace(cell, traffic=mix),
                                   args.seed + 1 + i, args.seconds, False,
                                   t_start=run.now(), log=log)
        print(json.dumps({
            "workload": args.workload, "load": load,
            "rate_rps": mix["rate_rps"], "seed": args.seed + 1 + i,
            "correct": result["correct"], "due": result["attempted"],
            "unfinished": result["checks"]["unfinished"]["value"],
            "metrics": {k: v["value"] for k, v in result["metrics"].items()
                        if k != "setup_s"}}), flush=True)


if __name__ == "__main__":
    main()
