"""Find the highest arrival rate an open-loop cell sustains, on the chip.

    python3 -m chipbench.sweep --workload <cell> --seconds <s> \
        --rates <r> [<r> ...]

Runs the cell once per rate (requests per second) in one process, each
time with the traffic file's mix at that rate, and prints a JSON line
per rate with its end-to-end numbers and how many requests due in the
window had no first token by its close (the backlog).  A rate is
sustained while the backlog stays near zero and TTFT does not climb
with the window.  Cells are then set at about four fifths of the knee.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys

from chipbench import files, run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench = files.benchmark()
    cell = files.cell(args.workload, bench)
    spec, base = files.config(cell["config"]), files.traffic(cell["traffic"])
    for rate in args.rates:
        traffic = copy.deepcopy(base)
        traffic["arrivals"]["rate_per_s"] = rate
        res = run.run_cell(args.workload, cell, spec, traffic, args.seed,
                           args.seconds, False, bench)
        w = res["window"]
        print(json.dumps({"rate_per_s": rate, **w["e2e"],
                          "attempted": w["counts"]["attempted"],
                          "backlog": w["counts"]["attempted"]
                          - w["counts"]["first_token_in_window"],
                          "correct": res["correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
