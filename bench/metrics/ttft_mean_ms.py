"""Time to first token, mean over every request due in the window: from
when it was due to when its first streamed token reached the client. A
refused or failed request, or one that got no token before the generator
gave up, counts as its whole wait (from due to the end of the run), so it
can only raise the mean.

A window at the chat cell's rate holds a few tens of arrivals: a 95th
percentile would be one of the last two, and the median flips between
neighbours (13.7% quartile spread over six runs on a TPU v5e at 0.22
requests/s, where the mean spread 2-4%)."""
from bench.metrics._common import due_in_window


def read(run):
    vals = []
    for r in due_in_window(run):
        ok = r["status"] == 200 and r["times"] and not r["error"]
        vals.append(((r["times"][0] if ok else run.t_end) - r["due"]) * 1e3)
    return sum(vals) / len(vals) if vals else None
