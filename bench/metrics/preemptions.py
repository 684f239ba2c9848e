"""Requests the engine preempted for KV pool room during the window (the
engine's own counter, read after each step)."""


def read(run):
    steps = run.window_steps()
    before = [s for s in run.steps if s.t1 <= run.w0]
    if not steps:
        return None
    start = before[-1].preempted if before else 0
    return steps[-1].preempted - start
