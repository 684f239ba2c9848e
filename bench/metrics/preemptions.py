"""Requests the engines preempted for KV pool room during the window (each
engine's own counter, read after each of its steps), summed over the
pod's instances."""


def read(run):
    steps = run.window_steps()
    if not steps:
        return None
    n = 0
    for i in {s.instance for s in steps}:
        mine = [s for s in steps if s.instance == i]
        before = [s for s in run.steps if s.instance == i and s.t1 <= run.w0]
        n += mine[-1].preempted - (before[-1].preempted if before else 0)
    return n
