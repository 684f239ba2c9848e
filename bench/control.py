"""The readings that a cell's correctness limit is set from, on the chip
at the cell's own size: for each seed, one run of the cell (the window
may be shorter than the benchmark's; the load is the cell's own) whose
finished greedy requests are compared with the float32 reference, as
every run does, and also with the control: the same reference computed
in fp8 (the architecture module's ``score``), whose first-ranked token
at each position of the same sequences is scored in the float32
reference.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 30

Prints one JSON line per seed: the program's widest gap and the
control's, each with the ``correct`` it gives through the harness's own
comparison (``check.checks`` against the cell's ``max_logit_gap``; the
control's has to be false). The limit lies between the largest program
reading over a dozen seeds or more and the smallest control reading.
"""
from __future__ import annotations

import argparse
import json
import sys

from run import BENCH, ROOT, chip, run_cell

from bench import check as CK


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    a = ap.parse_args(argv)
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == a.workload)
    dev = chip(cell)
    if dev is None:
        return 2
    for seed in [int(x) for x in a.seeds.split(",")]:
        result, _, _, extra = run_cell(
            bench, cell, seed, a.seconds, False, dev,
            out_dir=BENCH / "out" / f"control-{a.workload}-{seed}",
            control=True)
        g, c = extra["gaps"], extra["control_gaps"]
        print(json.dumps({"seed": seed, "requests": len(g),
                          "program": max(g) if g else None,
                          "program_correct": result["correct"],
                          "control": max(c) if c else None,
                          "control_correct":
                              CK.correct(extra["control_checks"]),
                          "program_each": g, "control_each": c}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
