"""Control-plane actions inside the window, from the orchestrator's flight
recorder: controller verdicts that acted (scale-up, scale-down with an
action), KV migrations, pod grow and shrink."""

ACTS = ("migration", "pod_grow", "pod_shrink")


def read(run):
    n = 0
    for e in run.flight:
        if not run.w0 <= e["t"] <= run.w1:
            continue
        if e["kind"] in ACTS:
            n += 1
        elif e["kind"] == "controller" and e.get("action") and \
                e["action"] != "scale-down[noop]":
            n += 1
    return n
