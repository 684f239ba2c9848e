"""Share of the traced sub-window in which no operation ran on a chip:
one minus the union of the chip's op intervals over the window, mean over
the chips in the trace (``trace_reduce.Reduced.busy_s`` averages each
chip's busy time)."""


def read(run):
    if run.trace is None or not run.trace.window_s:
        return None
    return 1.0 - run.trace.busy_s / run.trace.window_s
