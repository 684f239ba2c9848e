"""How late the load generator sent requests due in the window, the
latest of them: send time minus due time. A starved generator reads high
here, not as a fast server."""
from bench.metrics._common import due_in_window


def read(run):
    late = [(r["sent"] - r["due"]) * 1e3 for r in due_in_window(run)]
    return max(late) if late else None
