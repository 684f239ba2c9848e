"""Host time of one ``Engine.step`` outside the blocking fetch of its
tokens: the sum of the step's phases other than ``step/wait``, median
over the steps of the window, from the program's own phase record."""
from bench.metrics._common import percentile
from bench.metrics._steps import WAIT, window_steps


def read(run):
    return percentile([1e3 * sum(v for k, v in s.phases.items()
                                 if k != WAIT)
                       for s in window_steps(run)], 50)
