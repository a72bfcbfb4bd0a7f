"""Readings that set a cell's ``widest_gap`` limit, on the chip.

    python3 -m chipbench.control --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...]

For each seed, in one process: a run of the cell at its own load and a
short window, then the harness's own comparison with the fp8 control
(the reference computed one step below the configuration's bfloat16)
in the program's place, at the positions the program served, judged
against the cell's limits.  Prints one JSON line per seed, with the
control's ``correct``, and a summary: the program's largest reading
over the seeds (the limit's lower reading), the control's smallest (its
upper reading), and whether the control came out not correct on every
seed.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys

from chipbench import files, run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = files.benchmark()
    cell = files.cell(args.workload, bench)
    spec, traffic = files.config(cell["config"]), files.traffic(cell["traffic"])
    prog, ctrl, verdicts = [], [], []
    for seed in args.seeds:
        res = run.run_cell(args.workload, cell, spec, traffic, seed,
                           args.seconds, False, bench, control=True)
        c = res["checks"]
        prog.append(c["program_widest_gap"]["value"])
        ctrl.append(c["widest_gap"]["value"])
        verdicts.append(res["correct"])
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "control_widest_gap": ctrl[-1],
                          "limit": c["widest_gap"]["limit"],
                          "program_widest_gap": prog[-1],
                          "tokens_compared": c["tokens_compared"]["value"],
                          "window": res["window"]}), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "program_max": max(prog), "control_min": min(ctrl),
                      "control_never_correct": not any(verdicts)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
