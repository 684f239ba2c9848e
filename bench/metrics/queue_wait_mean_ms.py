"""Time requests waited in the engine's queue before their first prefill
chunk, mean, from the ingress tracer's ``queue`` spans (summed per
request when a preemption queued it again), over requests accepted
inside the window."""


def read(run):
    vals = []
    for rec in run.traces:
        root = rec["spans"][0]
        if not run.w0 <= root["t0"] <= run.w1:
            continue
        q = [s["t1"] - s["t0"] for s in rec["spans"]
             if s["name"] == "queue" and s.get("t1") is not None]
        if q:
            vals.append(sum(q) * 1e3)
    return sum(vals) / len(vals) if vals else None
