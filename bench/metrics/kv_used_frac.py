"""KV pool blocks in use over pool blocks, read after each engine step,
mean over the steps of the window (every instance's)."""


def read(run):
    v = [s.blocks_in_use / s.n_blocks for s in run.window_steps()]
    return sum(v) / len(v) if v else None
