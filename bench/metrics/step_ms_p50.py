"""Host wall time of one ``Engine.step`` (it ends in a blocking fetch of
the sampled tokens), median over the steps of the window (every instance's)."""
from bench.metrics._common import percentile


def read(run):
    return percentile([(s.t1 - s.t0) * 1e3 for s in run.window_steps()], 50)
