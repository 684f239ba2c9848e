"""Share of its roofline the paged decode attention kernel reaches, in
percent: for every decode step inside the traced sub-window, the least
time the chip needs for the kernel's work in every layer that runs it
(``run.arch.paged_layers``; ``flops.py``:
keys and values read once, q.k and p.v operations, against the peaks)
over the device time of the kernel's calls in that step.

The kernel is found in the trace as the Pallas (``tpu_custom_call``)
custom call of the paged decode step program, on every chip, and each
call is given to the ``engine_step`` host span (whose ``step`` argument
indexes the run's step records, every instance's) that contains it;
only steps wholly inside the traced sub-window count."""
from bench import flops as F

PROGRAM = "jit__paged_step_impl"
TARGET = 'custom_call_target="tpu_custom_call"'


def read(run):
    if run.trace is None:
        return None
    calls = sorted((o.t0, o.t1) for o in run.trace.ops
                   if o.module == PROGRAM and o.kind == "custom-call"
                   and TARGET in o.text)
    w0, w1 = run.trace.window
    need = took = 0.0
    for name, a, b, args in run.trace.spans:
        if name != "engine_step" or not (w0 <= a and b <= w1):
            continue
        s = run.steps[args["step"]]
        mine = [(c0, c1) for c0, c1 in calls if a <= c0 and c1 <= b]
        if not mine or not s.decode_ctx:
            continue
        fl, by = F.paged_decode_cost(run.dm, s.decode_ctx)
        need += (run.arch.paged_layers(run.dm)
                 * F.roofline_seconds(fl, by, run.peak))
        took += sum(c1 - c0 for c0, c1 in mine) / 1e9
    return 100.0 * need / took if took else None
