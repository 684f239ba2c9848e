"""The longest admission of any step in the window: ``step/plan`` +
``step/wave`` (whole prompts) + ``step/chunks`` (prefill chunks), from the
program's own phase record."""
from bench.metrics._steps import ADMIT, window_steps


def read(run):
    steps = window_steps(run)
    if not steps:
        return None
    return max(1e3 * sum(s.phases.get(k, 0.0) for k in ADMIT)
               for s in steps)
