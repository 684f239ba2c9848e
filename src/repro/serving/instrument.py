"""Instrumentation for the serving hot loop.

``count_host_syncs()`` patches ``jax.device_get`` — the one primitive the
engines use for every device→host read — and counts calls. The engines
deliberately never use ``int(arr)`` / ``np.asarray(arr)`` on device arrays
in their steady-state step, so the counter is an exact census of blocking
syncs per ``Engine.step`` (the quantity the paged-engine acceptance bound
"≤ 1 host sync per step" is asserted against in tests and reported by
benchmarks/paged_engine_bench.py).

``EngineTelemetry`` is the LIVE metrics source of the module-scaling loop:
the orchestrator records every engine step (wall seconds, tokens) and
every finished request (engine-clock latency) here, and turns the rolling
windows into ``core.monitor.MetricsSnapshot``s — the paper's NVML+timer
feed, replaced by real engine counters instead of synthetic traces.

``phase`` times one named phase of the host loop (``Engine.step``, the
ingress pump, ``Orchestrator.step``): a ``jax.profiler.TraceAnnotation``
of a fixed name puts it on the profiler's clock beside the device ops,
and its host seconds add into the owner's record. ``compile_counter()``
is the process-wide count of programs compiled or loaded from the
persistent compilation cache, by program, from ``jax.monitoring``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from collections import deque
from typing import Deque, Dict, Iterable, Optional

import jax
import numpy as np


# Phase span names. The step phases tile ``Engine.step``: admission
# (plan, whole-prompt wave, prefill chunks), pool room for the next
# token, host inputs, the jitted call until it returns, the blocking
# fetch of the sampled tokens, and the bookkeeping after it.
STEP_PLAN = "step/plan"
STEP_WAVE = "step/wave"
STEP_CHUNKS = "step/chunks"
STEP_DECODE_ROOM = "step/decode_room"
STEP_INPUTS = "step/inputs"
STEP_DISPATCH = "step/dispatch"
STEP_WAIT = "step/wait"
STEP_BOOKKEEP = "step/bookkeep"
STEP_PHASES = (STEP_PLAN, STEP_WAVE, STEP_CHUNKS, STEP_DECODE_ROOM,
               STEP_INPUTS, STEP_DISPATCH, STEP_WAIT, STEP_BOOKKEEP)
# the host loop between steps: the ingress pump's own work, and the
# orchestrator's around the engine steps
PUMP_SUBMIT = "pump/submit"
PUMP_PUSH = "pump/push"
PUMP_MIRROR = "pump/mirror"
ORCH_STREAMS = "orch/streams"
ORCH_SPANS = "orch/spans"
ORCH_CONTROL = "orch/control"


@contextlib.contextmanager
def phase(record: Dict[str, float], name: str, **attrs):
    """Time one phase: a ``TraceAnnotation`` of ``name`` (with ``attrs``
    as its arguments) while the block runs, and the block's host seconds
    added into ``record[name]``. Without an active profiler session the
    annotation is a few microseconds."""
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name, **attrs):
            yield
    finally:
        record[name] = record.get(name, 0.0) + time.perf_counter() - t0


class CompileCounter:
    """Programs compiled, or loaded from the persistent compilation cache,
    in this process: one ``/jax/core/compile/backend_compile_duration``
    event each (JAX times the cache lookup and the compile together, so a
    cache load is one event too), counted by ``fun_name`` with the
    seconds they took. Listeners may run on any thread that compiles."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self._lock = threading.Lock()
        self.total = 0
        self.seconds = 0.0
        self.by_program: Dict[str, int] = {}

    def _on_event(self, event: str, duration: float, **kw):
        if event != self.EVENT:
            return
        name = str(kw.get("fun_name", "?"))
        with self._lock:
            self.total += 1
            self.seconds += duration
            self.by_program[name] = self.by_program.get(name, 0) + 1

    def state(self) -> dict:
        """A consistent copy: total, seconds, and the count per program."""
        with self._lock:
            return {"total": self.total, "seconds": self.seconds,
                    "by_program": dict(self.by_program)}


_compiles: Optional[CompileCounter] = None
_compiles_lock = threading.Lock()


def compile_counter() -> CompileCounter:
    """The process-wide ``CompileCounter``, listening from the first call
    on (installed once per process)."""
    global _compiles
    with _compiles_lock:
        if _compiles is None:
            _compiles = CompileCounter()
            jax.monitoring.register_event_duration_secs_listener(
                _compiles._on_event)
        return _compiles


@dataclasses.dataclass
class SyncCounter:
    n: int = 0


@dataclasses.dataclass
class FaultCounters:
    """Plane-wide failure-domain counters, owned by the orchestrator
    (one per plane, not per engine — a quarantine is a fleet event).
    Feeds the ``rpc_timeouts`` / ``quarantines`` / ``respawns`` gauges
    of ``core.monitor.MetricsSnapshot`` and the recovery-latency
    percentiles of benchmarks/chaos_bench.py."""
    rpc_timeouts: int = 0     # step/control calls that missed a deadline
    quarantines: int = 0      # hung peers severed (socket open, no reply)
    respawns: int = 0         # supervised restarts that re-admitted
    evictions: int = 0        # flap-detector permanent removals
    # wall seconds from control fan-out to failure classification, one
    # entry per recovery — the "detected within 2x deadline" evidence.
    # Bounded: a week-long chaos soak records one float per recovery
    # forever, and the quantiles only need the recent window anyway.
    detect_latencies: Deque[float] = dataclasses.field(
        default_factory=lambda: deque(maxlen=512))

    def detect_quantile(self, q: float) -> float:
        if not self.detect_latencies:
            return 0.0
        return float(np.quantile(np.asarray(self.detect_latencies), q))

    def as_dict(self) -> dict:
        return {"rpc_timeouts": self.rpc_timeouts,
                "quarantines": self.quarantines,
                "respawns": self.respawns,
                "evictions": self.evictions,
                "detect_p50_s": self.detect_quantile(0.50),
                "detect_p95_s": self.detect_quantile(0.95)}


@dataclasses.dataclass
class IngressCounters:
    """Front-door counters, owned by ``serving.ingress.Ingress`` (one
    per server). Surfaced by ``GET /stats`` next to the orchestrator's
    MetricsSnapshot, and the evidence benchmarks/ingress_bench.py and
    tests/test_ingress.py assert on (routed_prefix vs routed_vacancy is
    the affinity-hit ledger; rejected_429 the backpressure one)."""
    requests: int = 0         # completions requests accepted
    streamed: int = 0         # of those, served with stream=true
    rejected_429: int = 0     # admissions shed by backpressure
    bad_requests: int = 0     # malformed -> HTTP 400
    tokens_out: int = 0       # tokens flushed to clients
    routed_prefix: int = 0    # admissions routed by chain affinity
    routed_vacancy: int = 0   # admissions routed by vacancy fallback
    aborted_streams: int = 0  # streams cut by shutdown / client hangup

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class EngineTelemetry:
    """Rolling-window per-engine counters feeding core/monitor."""

    def __init__(self, window: int = 64):
        self.step_seconds: Deque[float] = deque(maxlen=window)
        self.step_tokens: Deque[int] = deque(maxlen=window)
        self.finished_latencies: Deque[float] = deque(maxlen=window)
        # continuous-batching signals (token-budget scheduler): tokens
        # PACKED per step (decode + granted prefill chunk tokens) against
        # the engine's fixed budget, time-to-first-token and queue delay
        # per finished request — all on the ENGINE clock, like latencies
        self.packed_tokens: Deque[int] = deque(maxlen=window)
        self.budget = 0
        self.ttfts: Deque[float] = deque(maxlen=window)
        self.queue_delays: Deque[float] = deque(maxlen=window)
        # per-SLO-class rolling windows (class -> deque), populated
        # lazily so a plane that never sends slo_class pays nothing.
        # TTFT as above; ITL is the per-finished-request mean
        # inter-token gap ON THE ENGINE CLOCK — ~1.0 for a stream that
        # decoded every step, >1 when preemption/budget pressure
        # stalled it, which is exactly the per-class fairness signal
        self.class_ttfts: dict = {}
        self.class_itls: dict = {}
        self.total_tokens = 0
        self.total_finished = 0
        self.preemptions_seen = 0
        # prefix-sharing gauges (latest engine counters, not windows):
        # cumulative cache lookups/hits plus the INSTANTANEOUS number of
        # physical blocks sharing is saving — the quantity that inflates
        # the controller's pool-vacancy signal
        self.prefix_queries = 0
        self.prefix_hits = 0
        self.blocks_saved = 0
        # monotonic totals of every step (not windows): steps, host
        # seconds per step phase (STEP_PHASES), programs compiled or
        # loaded while a step ran
        self.steps_total = 0
        self.phase_seconds_total: Dict[str, float] = {}
        self.compiles_total = 0

    def record_step(self, wall_s: float, n_tokens: int,
                    packed: Optional[int] = None,
                    budget: Optional[int] = None,
                    phases: Optional[Dict[str, float]] = None,
                    compiles: int = 0):
        self.step_seconds.append(wall_s)
        self.step_tokens.append(n_tokens)
        self.total_tokens += n_tokens
        if packed is not None:
            self.packed_tokens.append(packed)
        if budget:
            self.budget = budget
        self.steps_total += 1
        for k, v in (phases or {}).items():
            self.phase_seconds_total[k] = \
                self.phase_seconds_total.get(k, 0.0) + v
        self.compiles_total += compiles

    def record_finished(self, requests: Iterable):
        w = self.step_seconds.maxlen
        for r in requests:
            self.finished_latencies.append(r.finish_time - r.submit_time)
            self.total_finished += 1
            cls = getattr(r, "slo_class", "standard")
            if r.first_token_time is not None:
                ttft = r.first_token_time - r.submit_time
                self.ttfts.append(ttft)
                self.class_ttfts.setdefault(
                    cls, deque(maxlen=w)).append(ttft)
                n = len(getattr(r, "generated", ()))
                if n > 1:
                    itl = (r.finish_time - r.first_token_time) / (n - 1)
                    self.class_itls.setdefault(
                        cls, deque(maxlen=w)).append(itl)
            start = getattr(r, "prefill_start_time", None)
            if start is not None:
                self.queue_delays.append(start - r.submit_time)

    def record_preemptions(self, n: int):
        self.preemptions_seen += n

    def record_prefix(self, queries: int, hits: int, blocks_saved_now: int):
        """Overwrite the sharing gauges with the engine's live counters
        (queries/hits are cumulative on the engine side; blocks saved is
        an instantaneous point read)."""
        self.prefix_queries = queries
        self.prefix_hits = hits
        self.blocks_saved = blocks_saved_now

    def prefix_hit_rate(self) -> float:
        """Fraction of looked-up full prompt blocks served by aliasing an
        already-resident block instead of re-prefilling it."""
        return (self.prefix_hits / self.prefix_queries
                if self.prefix_queries else 0.0)

    def budget_utilization(self) -> float:
        """Mean fraction of the per-step token budget actually packed
        (decode tokens + granted prefill chunks) over the window — the
        continuous-batching load gauge: ~1.0 means the step loop is
        saturated, low values mean the budget could shrink (latency) or
        traffic is light. 0.0 when the engine runs the phase scheduler
        (no budget to pack)."""
        if not self.budget or not self.packed_tokens:
            return 0.0
        return (sum(self.packed_tokens)
                / (len(self.packed_tokens) * self.budget))

    def ttft_quantile(self, q: float) -> float:
        """Engine-clock time-to-first-token quantile over the window —
        the signal chunked prefill exists to bound: admission no longer
        waits for a whole free slot + full-prompt prefill."""
        if not self.ttfts:
            return 0.0
        return float(np.quantile(np.asarray(self.ttfts), q))

    def queue_delay_quantile(self, q: float) -> float:
        """Engine-clock submit -> first-chunk-admitted delay quantile."""
        if not self.queue_delays:
            return 0.0
        return float(np.quantile(np.asarray(self.queue_delays), q))

    def class_ttft_quantile(self, cls: str, q: float) -> float:
        """Per-SLO-class TTFT quantile (0.0 when the class has no
        finished requests in the window yet)."""
        d = self.class_ttfts.get(cls)
        if not d:
            return 0.0
        return float(np.quantile(np.asarray(d), q))

    def class_itl_quantile(self, cls: str, q: float) -> float:
        """Per-SLO-class mean-inter-token-latency quantile (engine
        clock; 1.0 = never stalled)."""
        d = self.class_itls.get(cls)
        if not d:
            return 0.0
        return float(np.quantile(np.asarray(d), q))

    def tokens_per_s(self) -> float:
        wall = sum(self.step_seconds)
        return sum(self.step_tokens) / wall if wall > 0 else 0.0

    def mean_step_s(self) -> float:
        return (sum(self.step_seconds) / len(self.step_seconds)
                if self.step_seconds else 0.0)

    def latency_quantile(self, q: float) -> float:
        if not self.finished_latencies:
            return 0.0
        return float(np.quantile(np.asarray(self.finished_latencies), q))

    def slo_violation_rate(self, slo_latency: float) -> float:
        """Fraction of recently finished requests whose ENGINE-CLOCK
        latency (finish - submit) blew the SLO — the §5 scale-down
        trigger, measured on real requests rather than a trace."""
        if not self.finished_latencies:
            return 0.0
        lats = np.asarray(self.finished_latencies)
        return float((lats > slo_latency).mean())

    # ------------------------------------------------ wire serialization
    # A remote engine server (serving/remote_engine.py) measures its own
    # steps — wall seconds WITHOUT the RPC round trip — and ships this
    # state back piggybacked on every step reply; the orchestrator-side
    # mirror is refreshed with load_state, so core/monitor sees the same
    # schema whether the engine is a local object or another process.

    def to_state(self) -> dict:
        return {"window": self.step_seconds.maxlen,
                "step_seconds": list(self.step_seconds),
                "step_tokens": list(self.step_tokens),
                "finished_latencies": list(self.finished_latencies),
                "total_tokens": self.total_tokens,
                "total_finished": self.total_finished,
                "preemptions_seen": self.preemptions_seen,
                "prefix_queries": self.prefix_queries,
                "prefix_hits": self.prefix_hits,
                "blocks_saved": self.blocks_saved,
                "packed_tokens": list(self.packed_tokens),
                "budget": self.budget,
                "ttfts": list(self.ttfts),
                "queue_delays": list(self.queue_delays),
                "class_ttfts": {c: list(d)
                                for c, d in self.class_ttfts.items()},
                "class_itls": {c: list(d)
                               for c, d in self.class_itls.items()},
                "steps_total": self.steps_total,
                "phase_seconds_total": dict(self.phase_seconds_total),
                "compiles_total": self.compiles_total}

    def load_state(self, state: dict):
        """Overwrite this telemetry with a serialized snapshot (in place:
        the orchestrator holds a reference to this object)."""
        w = state.get("window") or self.step_seconds.maxlen
        self.step_seconds = deque(state["step_seconds"], maxlen=w)
        self.step_tokens = deque(state["step_tokens"], maxlen=w)
        self.finished_latencies = deque(state["finished_latencies"],
                                        maxlen=w)
        self.total_tokens = state["total_tokens"]
        self.total_finished = state["total_finished"]
        self.preemptions_seen = state["preemptions_seen"]
        self.prefix_queries = state["prefix_queries"]
        self.prefix_hits = state["prefix_hits"]
        self.blocks_saved = state["blocks_saved"]
        # .get defaults: replies from an engine server predating the
        # continuous-batching gauges still load
        self.packed_tokens = deque(state.get("packed_tokens", []),
                                   maxlen=w)
        self.budget = state.get("budget", 0)
        self.ttfts = deque(state.get("ttfts", []), maxlen=w)
        self.queue_delays = deque(state.get("queue_delays", []),
                                  maxlen=w)
        self.class_ttfts = {c: deque(v, maxlen=w) for c, v
                            in state.get("class_ttfts", {}).items()}
        self.class_itls = {c: deque(v, maxlen=w) for c, v
                           in state.get("class_itls", {}).items()}
        self.steps_total = state.get("steps_total", 0)
        self.phase_seconds_total = dict(state.get("phase_seconds_total",
                                                  {}))
        self.compiles_total = state.get("compiles_total", 0)


def timed_step(engine, telemetry: EngineTelemetry):
    """Run one engine step and record it into ``telemetry`` — THE step
    accounting definition, shared by the local handle
    (serving/instance.LocalInstance) and the remote engine server
    (serving/remote_engine.EngineServer) so the two planes' metrics can
    never silently diverge. Returns the finished requests."""
    t0 = time.perf_counter()
    done = engine.step() or []
    telemetry.record_step(time.perf_counter() - t0,
                          len(engine.active) + len(done),
                          packed=getattr(engine, "last_step_packed", None),
                          budget=getattr(engine, "token_budget", 0),
                          phases=getattr(engine, "last_step_phases", None),
                          compiles=getattr(engine, "last_step_compiles", 0))
    telemetry.record_finished(done)
    return done


# count_host_syncs patches the GLOBAL jax.device_get: with nested or
# concurrent contexts (an ingress pump thread stepping engines while a
# test counts its own block), naive save/restore corrupts the chain —
# the inner exit can reinstall an outer context's counted wrapper as
# "the original". Instead: one process-wide patch installed when the
# FIRST context enters and removed when the LAST leaves, every active
# counter incremented per sync.
_sync_lock = threading.Lock()
_sync_active: list = []
_sync_orig = None


@contextlib.contextmanager
def count_host_syncs():
    """Context manager yielding a SyncCounter; every ``jax.device_get``
    anywhere in the process increments it while the block is active.
    Re-entrant and thread-safe: nested/concurrent contexts each get an
    exact count, and the original ``jax.device_get`` is restored only
    when the outermost context exits."""
    global _sync_orig
    counter = SyncCounter()
    with _sync_lock:
        if not _sync_active:
            _sync_orig = jax.device_get

            def counted(x):
                with _sync_lock:
                    active = list(_sync_active)
                for c in active:
                    c.n += 1
                return _sync_orig(x)

            jax.device_get = counted
        _sync_active.append(counter)
    try:
        yield counter
    finally:
        with _sync_lock:
            _sync_active.remove(counter)
            if not _sync_active:
                jax.device_get = _sync_orig
                _sync_orig = None
