"""Engine steps as the program's flight recorder holds them: one ``step``
event per ``Engine.step``, recorded when it returned (``t``), with its
host seconds (``seconds``) and its instance's monotonic totals at that
point: ``steps_total``, the host seconds of each phase so far
(``phase_seconds_total``: ``step/plan``, ``step/wave``, ``step/chunks``,
``step/decode_room``, ``step/inputs``, ``step/dispatch``, ``step/wait``,
``step/bookkeep``) and the programs compiled or loaded while steps ran
(``compiles_total``). One step's own split is the difference from the
instance's previous event. A program that records no such events gives
none, and the readers built on them read nothing. A step of an instance
that had nothing queued or running (the benchmark's own ``StepRecord``
of it, the last to end before the event, says ``idle``) is left out:
the orchestrator steps every instance of a pod on each tick."""
import bisect
from typing import Dict, NamedTuple

WAIT = "step/wait"                        # the blocking fetch of the tokens
ADMIT = ("step/plan", "step/wave", "step/chunks")


class Step(NamedTuple):
    phases: Dict[str, float]              # phase name -> host seconds
    compiles: int


def _idle(run):
    """instance -> (end times, idle flags) of its step records."""
    ends = {}
    for s in run.steps:
        t, idle = ends.setdefault(s.instance, ([], []))
        t.append(s.t1)
        idle.append(s.idle)
    return ends


def window_steps(run):
    """The steps wholly inside the window, each the difference of its
    event from the previous event of the same instance."""
    ends = _idle(run)
    prev, out = {}, []
    for e in run.flight:
        if e["kind"] != "step" or "phase_seconds_total" not in e:
            continue
        p, prev[e["instance"]] = prev.get(e["instance"]), e
        if p is None or e["steps_total"] != p["steps_total"] + 1:
            continue                     # first seen, or a respawned engine
        if run.w0 <= e["t"] - e["seconds"] and e["t"] <= run.w1:
            t, idle = ends.get(e["instance"], ([], []))
            i = bisect.bisect_right(t, e["t"]) - 1
            if i >= 0 and idle[i]:
                continue
            was = p["phase_seconds_total"]
            out.append(Step({k: v - was.get(k, 0.0) for k, v
                             in e["phase_seconds_total"].items()},
                            e["compiles_total"] - p["compiles_total"]))
    return out
