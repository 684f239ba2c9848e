"""Peak device memory in use over the run up to the window's end
(``memory_stats()["peak_bytes_in_use"]``) on the fullest of the cell's
chips, in GB."""


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 1e9
