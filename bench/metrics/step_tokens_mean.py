"""Tokens the scheduler packed into one engine step (decode tokens plus
prefill chunk tokens), mean over the steps of the window (every
instance's)."""


def read(run):
    v = [s.packed for s in run.window_steps() if s.packed is not None]
    return sum(v) / len(v) if v else None
