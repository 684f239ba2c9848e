"""Set-up: process start to the window's start. Loading, weights,
compilation (or loading programs from the compilation cache), the primed
streams and the lead-in."""


def read(run):
    return run.setup_s
