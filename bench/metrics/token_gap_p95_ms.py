"""95th percentile of the window's token gaps (``window_gaps``). On the
reasoning cell (one TPU v5e) the steps that admit a prompt, with the
host's rarer slow steps, stretch 4.4-6.1% of the gaps, so this reads a
decode step or an admission step by how many of them the window holds:
a per-layer reading of that share, not an end-to-end bound."""
from bench.metrics._common import percentile, window_gaps


def read(run):
    return percentile(window_gaps(run), 95)
