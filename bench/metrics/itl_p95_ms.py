"""Inter-token latency, 95th percentile of every gap between consecutive
streamed tokens of one request, over all requests, where both tokens
reached the client inside the window."""
from bench.metrics._common import percentile


def read(run):
    gaps = []
    for r in run.records:
        t = [x for x in r["times"] if run.w0 <= x <= run.w1]
        gaps += [(b - a) * 1e3 for a, b in zip(t, t[1:])]
    return percentile(gaps, 95)
