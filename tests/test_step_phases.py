"""The engine's phase record and compile counter (serving/instrument.py):
every ``Engine.step`` splits its host time into the named phases that tile
it, counts the programs compiled or loaded while it ran, and folds both
into the telemetry's monotonic totals, which travel over the wire."""
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as T
from repro.serving import instrument as INS
from repro.serving.engine import Engine
from repro.serving.orchestrator import Orchestrator
from repro.serving.request import RequestSpec


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("tinyllama-1.1b").reduced()
    return cfg, T.init_params(cfg, jax.random.PRNGKey(0), "float32")


def _engine(tiny, **kw):
    cfg, params = tiny
    # shapes of their own (5 rows, 40 positions), so the first step here
    # compiles whatever else this process ran before
    return Engine(cfg, params, max_batch=5, max_len=40, cache_kind="paged",
                  block_size=8, **kw)


def _timed(eng):
    t0 = time.perf_counter()
    eng.step()
    return time.perf_counter() - t0


def test_phases_tile_the_step_and_compiles_count_new_shapes(tiny):
    eng = _engine(tiny)
    prompt = np.arange(3, 20).astype(np.int32)        # 17 tokens: bucket 4
    eng.submit(RequestSpec(rid=0, prompt=prompt, max_tokens=8))
    walls, phases, compiles = [], [], []
    for _ in range(4):
        walls.append(_timed(eng))
        phases.append(dict(eng.last_step_phases))
        compiles.append(eng.last_step_compiles)
    for wall, ph in zip(walls, phases):
        # a decoding step of the budget scheduler runs every phase once
        assert set(ph) == set(INS.STEP_PHASES), ph
        assert all(v >= 0.0 for v in ph.values())
        assert sum(ph.values()) <= wall
    admit = [ph[INS.STEP_WAVE] + ph[INS.STEP_CHUNKS] for ph in phases]
    assert admit[0] > max(admit[1:])    # the prompt's prefill ran in step 1
    assert compiles[0] >= 1             # new shapes: chunk and decode step
    assert compiles[1:] == [0, 0, 0]    # the same bucket again: nothing new


def test_idle_step_records_admission_only(tiny):
    eng = _engine(tiny)
    assert eng.step() is None
    assert set(eng.last_step_phases) == {INS.STEP_PLAN, INS.STEP_WAVE,
                                         INS.STEP_CHUNKS}
    assert eng.last_step_compiles == 0


def test_phase_scheduler_and_dense_engines_take_the_same_names(tiny):
    cfg, params = tiny
    for eng in (_engine(tiny, scheduler="phase"),
                Engine(cfg, params, max_batch=2, max_len=32)):
        eng.submit(RequestSpec(rid=0, prompt=np.arange(2, 9, dtype=np.int32),
                               max_tokens=3))
        eng.step()
        assert set(eng.last_step_phases) >= {
            INS.STEP_WAVE, INS.STEP_INPUTS, INS.STEP_DISPATCH,
            INS.STEP_WAIT, INS.STEP_BOOKKEEP}
        assert set(eng.last_step_phases) <= set(INS.STEP_PHASES)


def test_compile_counter_counts_by_program():
    c = INS.compile_counter()
    assert INS.compile_counter() is c           # one per process
    before = c.state()

    def triple_plus_one(x):
        return x * 3 + 1

    jax.jit(triple_plus_one)(np.arange(11.0))
    after = c.state()
    assert after["total"] == before["total"] + 1
    assert after["seconds"] > before["seconds"]
    assert after["by_program"]["jit(triple_plus_one)"] == \
        before["by_program"].get("jit(triple_plus_one)", 0) + 1


def test_telemetry_folds_steps_into_totals_and_round_trips(tiny):
    eng = _engine(tiny)
    tel = INS.EngineTelemetry()
    eng.submit(RequestSpec(rid=0, prompt=np.arange(2, 12, dtype=np.int32),
                           max_tokens=3))
    phases, compiles = {}, 0
    for _ in range(3):
        INS.timed_step(eng, tel)
        for k, v in eng.last_step_phases.items():
            phases[k] = phases.get(k, 0.0) + v
        compiles += eng.last_step_compiles
    assert tel.steps_total == 3
    assert set(tel.phase_seconds_total) == set(INS.STEP_PHASES)
    assert tel.phase_seconds_total == pytest.approx(phases)
    assert tel.compiles_total == compiles
    assert sum(tel.step_seconds) >= sum(phases.values())
    dst = INS.EngineTelemetry()
    dst.load_state(tel.to_state())
    assert dst.to_state() == tel.to_state()
    assert dst.steps_total == 3
    assert dst.phase_seconds_total == tel.phase_seconds_total


def test_orchestrator_records_each_step_and_its_own_phases(tiny):
    cfg, params = tiny
    orch = Orchestrator(cfg, params, n_instances=1, max_batch=2,
                        max_len=64, block_size=8, telemetry_every=1)
    try:
        orch.submit(RequestSpec(rid=0, prompt=np.arange(2, 9,
                                                        dtype=np.int32),
                                max_tokens=3))
        orch.run_until_done()
        steps = orch.flightrec.events("step")
        tel = orch.telemetry[0]
        assert len(steps) == tel.steps_total >= 2
        assert [e["steps_total"] for e in steps] == \
            list(range(1, len(steps) + 1))
        assert all(e["instance"] == 0 and set(e["phase_seconds_total"])
                   <= set(INS.STEP_PHASES) for e in steps)
        # each event holds the totals as they stood after its own step
        assert steps[-1]["phase_seconds_total"] == tel.phase_seconds_total
        assert steps[-1]["compiles_total"] == tel.compiles_total
        assert [e["seconds"] for e in steps] == list(tel.step_seconds)
        for a, b in zip(steps, steps[1:]):
            assert all(v >= a["phase_seconds_total"].get(k, 0.0)
                       for k, v in b["phase_seconds_total"].items())
        assert set(orch.phase_seconds) == {INS.ORCH_CONTROL,
                                           INS.ORCH_STREAMS,
                                           INS.ORCH_SPANS}
    finally:
        orch.close()
