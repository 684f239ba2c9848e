"""Find a cell's knee: run its open-loop traffic at several fixed rates,
each on several seeds, and report per run what the server completed,
how long requests waited and how full it ran. Run once when a cell is
defined (and again by a later benchmark change when an optimisation has
moved the knee); the benchmark itself never searches for a rate.

    python3 bench/sweep.py --workload <cell> --rates 0.35,0.5,0.65 \\
        --seeds 11,12 --seconds 51

One process holds the chip and builds the pod afresh for each run, with
the warm-up of a benchmark run, so nothing compiles inside a window
(each row says how many programs did); programs stay in memory from one
run to the next. The knee is the highest rate at
which the server keeps up: every request due in the window gets its
first token inside it, output keeps pace with what is offered, and the
queue wait does not climb; the cell's ``rate_rps`` is then about four
fifths of it.
"""
from __future__ import annotations

import argparse
import json
import sys

from run import BENCH, ROOT, chip, read_metric, run_cell

from bench.metrics._common import due_in_window, percentile


def row(rate: float, seed: int, result: dict, run) -> dict:
    due = due_in_window(run)
    late = [r for r in due if not r["times"] or r["times"][0] > run.w1]
    ttft = [(r["times"][0] - r["due"]) * 1e3 for r in due if r["times"]]
    done = [r for r in run.records if r["done"] and r["times"]
            and run.w0 <= r["times"][-1] <= run.w1]
    st = run.window_steps()
    rows = [len(s.decode_ctx) for s in st]
    # answers still streaming when the window closed, and the tokens they
    # still owed: output short of the offered rate with these alone, and
    # no row ever missing, is answers cut off by the close, not a queue
    open_ = [r for r in due if r["times"] and r["times"][0] <= run.w1
             and not (r["done"] and r["times"][-1] <= run.w1)]
    return {"rate_rps": rate, "seed": seed, "due": len(due),
            "no_token_in_window": len(late), "completed": len(done),
            "output_tok_s": read_metric("output_tok_s", run),
            "offered_tok_s": rate * sum(r["max_tokens"] for r in due)
            / max(len(due), 1),
            "ttft_p50_ms": percentile(ttft, 50),
            "ttft_mean_ms": read_metric("ttft_mean_ms", run),
            "ttft_max_ms": max(ttft, default=None),
            "queue_wait_mean_ms": read_metric("queue_wait_mean_ms", run),
            "itl_p99_ms": read_metric("itl_p99_ms", run),
            "token_gap_p95_ms": read_metric("token_gap_p95_ms", run),
            "step_ms_p50": read_metric("step_ms_p50", run),
            "decode_rows_mean": sum(rows) / max(len(rows), 1),
            "decode_rows_max": max(rows, default=0),
            "streaming_at_close": len(open_),
            "owed_at_close_tok": sum(
                r["max_tokens"] - sum(t <= run.w1 for t in r["times"])
                for r in open_),
            "kv_used_frac": read_metric("kv_used_frac", run),
            "compiles_in_window": len(run.compiles_in_window),
            "failed": result["failed"]}


def sweep(bench: dict, cell: dict, rates, seeds, seconds: float, device, *,
          overrides=None, engine_kw=None):
    """One row per (rate, seed), rates in the order given. No grace after
    the window: a first token after it counts as late, as it does here."""
    ov = overrides or {}
    mix = {**ov.get("mix", {}), "grace_s": 0}
    for rate in rates:
        for seed in seeds:
            knobs = {**ov.get("knobs", {}), "rate_rps": rate}
            result, run, _, _ = run_cell(
                bench, cell, seed, seconds, False, device,
                out_dir=BENCH / "out" / f"sweep-{cell['name']}-{rate}-{seed}",
                overrides={**ov, "mix": mix, "knobs": knobs},
                engine_kw=engine_kw,
                check=False, keep_programs=True)
            yield row(rate, seed, result, run)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seconds", type=float, default=51.0)
    a = ap.parse_args(argv)
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == a.workload)
    dev = chip(cell)
    if dev is None:
        return 2
    for r in sweep(bench, cell, [float(x) for x in a.rates.split(",")],
                   [int(x) for x in a.seeds.split(",")], a.seconds, dev):
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
