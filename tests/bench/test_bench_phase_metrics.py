"""The readers of the program's own step record (``bench/metrics/_steps.py``)
on synthetic flight-recorder events, and the program's phase spans on the
profiler's clock inside the benchmark's ``engine_step`` span."""
import json

import jax
import numpy as np
import pytest

from bench import model as M
from bench import run as R
from bench import spans as S
from repro.configs import get_config
from repro.models import transformer as T
from repro.serving import instrument as INS
from repro.serving.engine import Engine
from repro.serving.request import RequestSpec

CONF = M.load_config("yi-1.5-9b")
ARCH = M.arch(CONF)
DM = ARCH.dims(CONF)
PEAK = json.load(open(R.BENCH / "peaks.json"))["TPU v5 lite"]


def make_run(flight, records=()):
    return R.Run(cell={}, conf={}, dm=DM, arch=ARCH, mix={}, knobs={},
                 seed=1, seconds=10.0, setup_s=1.0, w0=100.0, w1=110.0,
                 t_end=111.0,
                 records=[], steps=list(records), traces=[],
                 flight=list(flight),
                 peak=PEAK, memory_peak_bytes=None, compiles_in_window=[],
                 gc_in_window=[])


def steps(*specs, instance=0):
    """Step events as the orchestrator records them, from each step's own
    (t1, seconds, compiles, {phase: ms}): every event carries the
    instance's totals up to and including its step."""
    totals, n, out = {}, 0, []
    for t1, seconds, compiles, ms in specs:
        for k, v in ms.items():
            totals[f"step/{k}"] = totals.get(f"step/{k}", 0.0) + v / 1e3
        n += compiles
        out.append({"kind": "step", "t": t1, "seconds": seconds,
                    "instance": instance, "steps_total": len(out) + 1,
                    "phase_seconds_total": dict(totals),
                    "compiles_total": n})
    return out


FLIGHT = steps(
    (99.5, 0.4, 9, dict(plan=5.0, wait=1.0)),             # the first seen
    (99.9, 0.4, 5, dict(plan=900.0, wait=1.0)),           # ends before
    (100.3, 0.4, 3, dict(plan=2.0, wait=350.0)),          # starts before
    (101.0, 0.4, 1, dict(plan=1.0, wave=3.0, dispatch=2.0, wait=390.0,
                         bookkeep=1.0)),                  # host 7 ms
    (101.5, 0.4, 0, dict(plan=1.0, chunks=30.0, inputs=1.0, wait=360.0)),
    (102.0, 0.4, 2, dict(plan=1.0, dispatch=2.0, wait=395.0)),
    (110.2, 0.4, 7, dict(plan=800.0, wait=1.0)),          # ends after
)
FLIGHT.insert(6, {"kind": "controller", "t": 102.1, "action": None})


def test_readers_take_the_window_steps_only():
    run = make_run(FLIGHT)
    # host ms of the window's three steps: 7, 32, 3 -> median 7
    assert R.read_metric("step_host_ms_p50", run) == pytest.approx(7.0)
    assert R.read_metric("admit_ms_max", run) == pytest.approx(31.0)
    assert R.read_metric("step_compiles", run) == 3


@pytest.mark.parametrize("name", ["step_host_ms_p50", "admit_ms_max",
                                  "step_compiles"])
def test_readers_read_nothing_from_a_program_without_step_events(name):
    # a program that records no step events (or none in the window)
    run = make_run([e for e in FLIGHT if e["kind"] != "step"])
    assert R.read_metric(name, run) is None
    assert R.read_metric(name, make_run([FLIGHT[1], FLIGHT[-1]])) is None


def test_readers_difference_each_instance_and_skip_a_restart():
    # two instances interleaved; instance 1 restarts (its totals begin
    # anew), so its first step after the restart has no previous event
    a = steps((100.5, 0.4, 0, dict(plan=1.0, wait=300.0)),
              (101.0, 0.4, 1, dict(plan=4.0, wave=6.0, wait=300.0)))
    b = steps((100.6, 0.4, 0, dict(plan=1.0, wait=300.0)),
              (101.1, 0.4, 0, dict(plan=2.0, wait=300.0)), instance=1)
    b_again = steps((101.6, 0.4, 4, dict(plan=50.0, wait=300.0)),
                    instance=1)
    run = make_run([a[0], b[0], a[1], b[1]] + b_again)
    assert R.read_metric("admit_ms_max", run) == pytest.approx(10.0)
    assert R.read_metric("step_host_ms_p50", run) == pytest.approx(2.0)
    assert R.read_metric("step_compiles", run) == 1


def test_readers_leave_out_an_idle_instances_steps():
    # instance 1 is stepped on every tick of the pod; at 101.1 it had
    # nothing queued or running (its own step record says so)
    a = steps((100.5, 0.4, 0, dict(plan=1.0, wait=300.0)),
              (101.0, 0.4, 1, dict(plan=4.0, wave=6.0, wait=300.0)))
    b = steps((100.6, 0.4, 0, dict(plan=1.0, wait=300.0)),
              (101.1, 0.05, 2, dict(plan=0.01)), instance=1)
    recs = [S.StepRecord(e["t"] - e["seconds"], e["t"] - 1e-4, None, [], [],
                         0, 100, 0, e["instance"], idle)
            for e, idle in ((a[0], False), (b[0], False), (a[1], False),
                            (b[1], True))]
    flight = [a[0], b[0], a[1], b[1]]
    assert R.read_metric("step_compiles", make_run(flight)) == 3
    run = make_run(flight, recs)
    assert R.read_metric("step_compiles", run) == 1
    assert R.read_metric("step_host_ms_p50", run) == pytest.approx(10.0)
    assert R.read_metric("admit_ms_max", run) == pytest.approx(10.0)


def test_program_spans_lie_inside_the_engine_step_span(tmp_path):
    cfg = get_config("tinyllama-1.1b").reduced()
    params = T.init_params(cfg, jax.random.PRNGKey(0), "float32")
    eng = Engine(cfg, params, max_batch=2, max_len=64, cache_kind="paged",
                 block_size=8)
    steps = []
    S.instrument_engine(eng, steps)
    eng.submit(RequestSpec(rid=0, prompt=np.arange(2, 12, dtype=np.int32),
                           max_tokens=8))
    eng.step()                                   # compiles, untraced
    jax.profiler.start_trace(str(tmp_path))
    eng.step()
    eng.step()
    jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    path = next(tmp_path.rglob("*.xplane.pb"))
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
               dict(e.stats))
              for p in ProfileData.from_file(str(path)).planes
              if p.name.startswith("/host") for ln in p.lines
              for e in ln.events]
    outer = [(a, b) for n, a, b, _ in events if n == S.ENGINE_STEP]
    assert len(outer) == 2
    inner = [(n, a, b, st) for n, a, b, st in events
             if n in INS.STEP_PHASES]
    for a, b in outer:
        names = {n for n, c, d, _ in inner if a <= c and d <= b}
        assert names == set(INS.STEP_PHASES), names
    dispatch = [st for n, _, _, st in inner if n == INS.STEP_DISPATCH]
    assert [d["rows"] for d in dispatch] == [1, 1]
    assert {"step", "bucket", "chunks"} <= set(dispatch[0])
