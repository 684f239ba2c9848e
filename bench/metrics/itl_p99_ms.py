"""Inter-token latency, 99th percentile of every gap between consecutive
streamed tokens of one request, over all requests, where both tokens
reached the client inside the window (``window_gaps``)."""
from bench.metrics._common import percentile, window_gaps


def read(run):
    return percentile(window_gaps(run), 99)
