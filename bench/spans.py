"""Host spans and counters, taken from the benchmark's side of each call.

Nothing here edits the program: ``instrument`` wraps the calls into each
layer on the live objects (the ingress pump's work, the orchestrator
tick, the control tick, ``Engine.step`` and its admission pass) in a
``jax.profiler.TraceAnnotation`` of a fixed name, so a profiler trace
shows what the host was doing when the device sat idle. Around each
``Engine.step`` it also records one ``StepRecord`` from the engine's
public state: wall time, the tokens the scheduler packed, the prefill
chunks and decode contexts the step ran, pool blocks in use, the
preemption counter, which instance of the pod stepped and whether it had
anything queued or running when the step began (the orchestrator steps
every instance of a pod on each tick, idle ones too). The records of
every instance go into one list, in the order the steps ran (the
orchestrator steps in-process instances one after another).

``CompileCounter`` records XLA compilations and persistent-cache loads by
the time they finish, and ``GcPauses`` the interpreter's garbage
collections, so a run can say which fell inside its window.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import logging
import re
import threading
import time
from typing import List, Optional

import jax

# span names, as the trace reduction and the breakdown print them
PUMP_SUBMIT = "pump_submit"
PUMP_STREAMS = "pump_push_streams"
GOVERNOR = "pump_budget_governor"
ORCH_TICK = "orchestrator_tick"
CONTROL = "control_tick"
SNAPSHOT = "telemetry_snapshot"
ENGINE_STEP = "engine_step"
ENGINE_ADMIT = "engine_admit"
WINDOW = "bench_window"


@dataclasses.dataclass
class StepRecord:
    t0: float
    t1: float
    packed: Optional[int]        # tokens the scheduler packed (None: no plan)
    decode_ctx: List[int]        # keys each decoding row attended over
    prefill: List[tuple]         # (start, n, ends_prompt) per chunk run
    blocks_in_use: int
    n_blocks: int
    preempted: int               # engine preemption counter after the step
    instance: int = 0            # the pod's instance (engine) index
    idle: bool = False           # nothing queued or running at its start


def _annotate(obj, attr: str, name: str):
    """Wrap ``obj.attr`` (a bound method) in a TraceAnnotation, in place.
    A missing attribute raises: a renamed method would otherwise drop its
    span, and the idle gaps it labels, without a word."""
    fn = getattr(obj, attr, None)
    if not callable(fn):
        raise AttributeError(f"{type(obj).__name__}.{attr}: no such method "
                             f"to trace as {name!r}")

    @functools.wraps(fn)
    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **k)

    setattr(obj, attr, wrapped)


def instrument_engine(eng, steps: list, instance: int = 0):
    """Record every ``Engine.step`` of ``eng`` into ``steps``; the
    ``engine_step`` span's ``step`` argument is the record's index."""
    inner = eng.step

    @functools.wraps(inner)
    def step(*a, **k):
        pre_pf = {r.rid: r.prefill_pos for r in eng.prefilling.values()}
        pre_act = {r.rid for r in eng.active.values()}
        idle = not (eng.queue or eng.active or eng.prefilling)
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation(ENGINE_STEP, step=len(steps)):
            out = inner(*a, **k)
        t1 = time.monotonic()
        ctx, chunks = [], []
        for r in list(eng.active.values()) + list(out or []):
            # decoded this step: the cache now holds prompt + generated - 1
            ctx.append(len(r.prompt) + len(r.generated) - 1)
            if r.rid not in pre_act:
                start = pre_pf.get(r.rid, 0)
                end = len(r.prompt) + len(r.generated) - 2
                chunks.append((start, end - start, True))
        for r in eng.prefilling.values():
            start = pre_pf.get(r.rid, 0)
            if r.prefill_pos > start:
                chunks.append((start, r.prefill_pos - start, False))
        st = eng.pstate
        steps.append(StepRecord(t0, t1, eng.last_step_packed, ctx, chunks,
                                st.blocks_in_use(), st.n_blocks,
                                eng.preempt_count, instance, idle))
        return out

    eng.step = step
    _annotate(eng, "_admit", ENGINE_ADMIT)


def instrument(ingress, orch, steps: list):
    """Annotate the pump's work, the orchestrator and every engine; the
    step records of all engines go into ``steps``."""
    _annotate(ingress, "_drain_submissions", PUMP_SUBMIT)
    _annotate(ingress, "_push_streams", PUMP_STREAMS)
    if ingress.governor is not None:
        _annotate(ingress.governor, "tick", GOVERNOR)
    _annotate(orch, "step", ORCH_TICK)
    _annotate(orch, "control_tick", CONTROL)
    _annotate(orch, "snapshot", SNAPSHOT)
    for i, eng in enumerate(orch.engines):
        instrument_engine(eng, steps, i)


class GcPauses:
    """Every garbage collection of the interpreter: when it ended, its
    generation and the seconds it took (``gc.callbacks``)."""

    def __init__(self):
        self.events: List[tuple] = []     # (monotonic, generation, seconds)
        self._t0 = None
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.events.append((time.monotonic(), info["generation"],
                                time.perf_counter() - self._t0))

    def close(self):
        if self._cb in gc.callbacks:
            gc.callbacks.remove(self._cb)

    def within(self, t0: float, t1: float) -> List[tuple]:
        return [e for e in self.events if t0 <= e[0] <= t1]


class CompileCounter(logging.Handler):
    """Every program JAX compiles, or loads from the persistent compilation
    cache (each is a program not yet in this process's memory): when it
    finished, its name and the seconds it took, from JAX's own
    "Finished XLA compilation" log record (emitted at debug level, which
    this handler alone receives; nothing is printed)."""

    LOGGERS = ("jax._src.dispatch", "jax._src.interpreters.pxla")
    _MSG = re.compile(r"Finished XLA compilation of (.*) in ([0-9.e+-]+) sec")

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.events: List[tuple] = []     # (monotonic, name, seconds)
        self._guard = threading.Lock()
        self._saved = []
        for name in self.LOGGERS:
            lg = logging.getLogger(name)
            self._saved.append((lg, lg.level, lg.propagate))
            lg.setLevel(logging.DEBUG)
            lg.propagate = False       # debug records stop here
            lg.addHandler(self)

    def emit(self, record):
        m = self._MSG.match(record.getMessage())
        if m:
            with self._guard:
                self.events.append((time.monotonic(), m.group(1),
                                    float(m.group(2))))
        if record.levelno >= logging.WARNING:   # what JAX would show
            logging.getLogger("jax").handle(record)

    def close(self):
        for lg, level, prop in self._saved:
            lg.removeHandler(self)
            lg.setLevel(level)
            lg.propagate = prop
        super().close()

    def within(self, t0: float, t1: float) -> List[tuple]:
        with self._guard:
            return [e for e in self.events if t0 <= e[0] <= t1]
