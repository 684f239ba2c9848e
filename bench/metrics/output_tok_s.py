"""Output tokens streamed to clients inside the window, over the window's
length."""


def read(run):
    n = sum(1 for r in run.records for t in r["times"]
            if run.w0 <= t <= run.w1)
    return n / (run.w1 - run.w0)
