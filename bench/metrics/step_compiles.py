"""Programs compiled, or loaded from the persistent compilation cache,
while the window's engine steps ran, from the program's own compile
counter (the growth of ``compiles_total`` at each step)."""
from bench.metrics._steps import window_steps


def read(run):
    steps = window_steps(run)
    if not steps:
        return None
    return sum(s.compiles for s in steps)
