"""How long a migrated stream stood still, mean over the KV migrations
between instances inside the window, from the orchestrator's flight
recorder: each ``migration`` event's ``stall_s`` (an overlapped migration
stalls its stream for the phase-2 delta copy only; a stop-the-world one
for the whole transfer), in milliseconds. No migration in the window
reads nothing; ``plan_changes`` says how often the controller acted."""


def read(run):
    stalls = [e["stall_s"] for e in run.flight
              if e["kind"] == "migration" and run.w0 <= e["t"] <= run.w1]
    return 1e3 * sum(stalls) / len(stalls) if stalls else None
