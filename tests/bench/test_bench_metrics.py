"""The metric readers on synthetic client records, step records, traces
and flight-recorder events."""
import gc
import json

import pytest

from bench import check as CK
from bench import flops as F
from bench import model as M
from bench import run as R
from bench import spans as S
from bench import trace_reduce as TR

CONF = M.load_config("mistral-7b-v0.3")
ARCH = M.arch(CONF)
DM = ARCH.dims(CONF)
PEAK = json.load(open(R.BENCH / "peaks.json"))["TPU v5 lite"]


def rec(rid, due, sent, times, status=200, phase="window", error=None):
    return {"id": rid, "phase": phase, "due": due, "sent": sent,
            "status": status, "times": times, "tokens": list(range(len(times))),
            "done": True, "error": error, "temperature": 0.0,
            "max_tokens": len(times)}


def step(t0, t1, packed=8, ctx=(), prefill=(), used=10, pre=0, instance=0,
         idle=False):
    return S.StepRecord(t0, t1, packed, list(ctx), list(prefill), used, 100,
                        pre, instance, idle)


def make_run(records=(), steps=(), flight=(), traces=(), trace=None,
             trace_host=None, mem=None, cell=None):
    return R.Run(cell=cell or {}, conf={}, dm=DM, arch=ARCH, mix={},
                 knobs={}, seed=1, seconds=10.0, setup_s=42.5, w0=100.0,
                 w1=110.0, t_end=130.0,
                 records=list(records), steps=list(steps),
                 traces=list(traces), flight=list(flight), peak=PEAK,
                 memory_peak_bytes=mem, compiles_in_window=[],
                 gc_in_window=[], trace=trace,
                 trace_host=trace_host)


def read(name, run):
    return R.read_metric(name, run)


def test_ttft_counts_failures_as_their_whole_wait():
    recs = [rec(i, 100.0 + 0.5 * i, 100.0 + 0.5 * i,
                [100.0 + 0.5 * i + 0.1 * (i + 1)]) for i in range(19)]
    recs.append(rec(19, 109.0, 109.0, [], status=429))
    recs.append(rec(20, 90.0, 90.0, [90.5], phase="lead"))   # before window
    run = make_run(recs)
    # 19 successes waited 100..1900 ms; the refused one 109 s -> 130 s
    assert read("ttft_mean_ms", run) == pytest.approx((19000 + 21000) / 20)
    recs.append(rec(21, 109.5, 109.5, [], error="boom"))
    # the failed one waited until 130 s as well
    assert read("ttft_mean_ms", make_run(recs)) == pytest.approx(
        (19000 + 21000 + 20500) / 21)


def test_itl_and_output_rate_count_the_window_only():
    recs = [rec(0, 99.0, 99.0, [99.9, 100.1, 100.2, 100.4, 111.0]),
            rec(1, 101.0, 101.0, [101.0, 101.5])]
    run = make_run(recs)
    # gaps inside the window: 100, 200 (req 0) and 500 (req 1) ms
    assert read("token_gap_p95_ms", run) == pytest.approx(500.0)
    assert read("itl_p99_ms", run) == pytest.approx(500.0)
    assert read("output_tok_s", run) == pytest.approx(5 / 10.0)
    assert read("setup_s", run) == 42.5


@pytest.mark.parametrize("name,want", [("token_gap_p95_ms", 95.0),
                                       ("itl_p99_ms", 99.0)])
def test_gap_percentiles_by_nearest_rank(name, want):
    # one request whose 100 gaps inside the window are 1, 2, ..., 100 ms
    times = [100.0]
    for g in range(1, 101):
        times.append(times[-1] + g / 1e3)
    run = make_run([rec(0, 100.0, 100.0, times)])
    assert read(name, run) == pytest.approx(want)


def test_generator_lateness():
    recs = [rec(i, 101.0, 101.0 + 0.001 * i, [102.0]) for i in range(100)]
    assert read("gen_late_max_ms", make_run(recs)) == pytest.approx(99.0)


STEPS = [step(99.0, 99.5, packed=100, used=90, pre=1),
         step(100.0, 100.2, packed=10, used=20, pre=1),
         step(100.3, 100.6, packed=30, used=40, pre=3),
         step(109.9, 110.5, packed=1000, used=99, pre=9)]
# a second instance, stepped between the first one's steps, with its own
# preemption counter; its last step found it with nothing to do
OTHER = [step(99.6, 99.9, packed=70, used=80, pre=4, instance=1),
         step(100.2, 100.3, packed=50, used=60, pre=5, instance=1),
         step(100.6, 101.0, packed=90, used=70, pre=5, instance=1),
         step(101.1, 101.1001, packed=None, used=0, pre=5, instance=1,
              idle=True)]


def test_step_counters():
    run = make_run(steps=STEPS)
    assert read("step_tokens_mean", run) == 20
    assert read("kv_used_frac", run) == pytest.approx(0.3)
    assert read("preemptions", run) == 2
    assert read("step_ms_p50", run) == pytest.approx(200.0)


@pytest.mark.parametrize("instances,want", [
    # one instance: what the readers read before pods existed
    (1, {"step_tokens_mean": 20, "kv_used_frac": 0.3, "preemptions": 2,
         "step_ms_p50": 200.0}),
    # two: window steps pooled (packed 10, 30, 50, 90; used .2 .4 .6 .7;
    # 200, 300, 100, 400 ms), preemptions summed per instance (2 + 1)
    (2, {"step_tokens_mean": 45, "kv_used_frac": 0.475, "preemptions": 3,
         "step_ms_p50": 200.0}),
])
def test_step_readers_pool_every_instance(instances, want):
    steps = sorted(STEPS + (OTHER if instances == 2 else []),
                   key=lambda s: s.t0)
    run = make_run(steps=steps)
    assert {k: read(k, run) for k in want} == pytest.approx(want)
    assert len(run.window_steps()) == 2 * instances


def test_plan_changes_and_memory():
    flight = [{"t": 101.0, "kind": "controller", "action": None},
              {"t": 102.0, "kind": "controller", "action": "scale-down[noop]"},
              {"t": 103.0, "kind": "controller", "action": "scale-up[x]"},
              {"t": 104.0, "kind": "migration"},
              {"t": 99.0, "kind": "migration"},
              {"t": 105.0, "kind": "route", "verdict": "admit"}]
    assert read("plan_changes", make_run(flight=flight)) == 2
    assert read("hbm_peak_gb", make_run(mem=12_500_000_000)) == 12.5
    assert read("hbm_peak_gb", make_run()) is None


@pytest.mark.parametrize("events,want", [
    # two migrations inside the window: 4 ms and 10 ms of stall
    ([(101.0, 0.004), (105.0, 0.010)], 7.0),
    # one before and one after the window do not count
    ([(99.0, 0.5), (103.0, 0.002), (111.0, 0.5)], 2.0),
    ([(99.0, 0.5)], None),
    ([], None),
])
def test_migration_stall_ms(events, want):
    flight = [{"t": t, "kind": "migration", "stall_s": s, "mode": "overlap"}
              for t, s in events]
    flight.append({"t": 104.0, "kind": "controller", "action": "scale-up[x]"})
    got = read("migration_stall_ms", make_run(flight=flight))
    assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize("moved,want", [((), {19}), ((4,), {19, 4})])
def test_check_sample_keeps_the_longest_and_a_migrated_request(moved, want):
    recs = []
    for i in range(20):
        r = rec(i, 100.0, 100.0, [101.0] * (i + 1))
        r["server_id"] = i + 1000
        recs.append(r)
    got = CK.sample(recs, 100.0, 5, 7, {i + 1000 for i in moved})
    assert len(got) == 5 and want <= {r["id"] for r in got}
    if not moved:    # what the sample drew before pods: same seed, same picks
        assert got == CK.sample(recs, 100.0, 5, 7)


def test_queue_wait_from_tracer_spans():
    traces = [{"spans": [{"name": "request", "t0": 100.0 + i, "t1": 105.0},
                         {"name": "queue", "t0": 100.0 + i,
                          "t1": 100.0 + i + 0.01 * (i + 1)}]}
              for i in range(5)]
    traces.append({"spans": [{"name": "request", "t0": 90.0, "t1": 95.0},
                             {"name": "queue", "t0": 90.0, "t1": 94.0}]})
    # 10..50 ms inside the window; the one accepted before it is left out
    assert read("queue_wait_mean_ms", make_run(traces=traces)) == \
        pytest.approx(30.0)


def _trace():
    """A 1-second traced window: two decode steps, each with one kernel
    call of 100 ms and another op of 50 ms, and a host gap between."""
    ops, spans = [], []
    for k, a in enumerate((0.0, 0.5e9)):
        txt = '%cc = bf16[1] custom-call(), custom_call_target="tpu_custom_call"'
        ops.append(TR.Op("jit__paged_step_impl", "cc",
                         "custom-call", txt, a + 0.1e9, a + 0.2e9))
        ops.append(TR.Op("jit__paged_step_impl", "f",
                         "fusion", "%f = ...", a + 0.2e9, a + 0.25e9))
        spans.append(("engine_step", a + 0.05e9, a + 0.3e9, {"step": k}))
    busy = {"/device:TPU:0": 0.3e9}
    gaps = [(0.25e9, 0.6e9, "pump_push_streams")]
    return TR.Reduced((0.0, 1.0e9), busy, ops, gaps, spans)


def test_device_metrics_from_a_trace():
    ctx = [1000, 3000]
    steps = [step(100.0, 100.3, ctx=ctx), step(100.5, 100.8, ctx=ctx,
                                               prefill=[(0, 64, True)])]
    run = make_run(steps=steps, trace=_trace(), trace_host=(100.0, 101.0))
    assert read("device_idle_frac", run) == pytest.approx(0.7)
    fl, by = F.paged_decode_cost(DM, ctx)
    need = 2 * DM["L"] * F.roofline_seconds(fl, by, PEAK)
    assert read("paged_decode_roofline", run) == pytest.approx(
        100 * need / 0.2)
    model = 2 * sum(ARCH.decode_token_flops(DM, c) for c in ctx) + \
        ARCH.prefill_chunk_flops(DM, 0, 64, True)
    assert read("step_mfu", run) == pytest.approx(
        100 * model / PEAK["bf16_flops"])
    assert _trace().top_gaps(1) == [["pump_push_streams", 0.35]]


def test_step_mfu_counts_the_peak_of_every_chip():
    steps = [step(100.0, 100.3, ctx=[1000, 3000])]
    one = make_run(steps=steps, trace=_trace(), trace_host=(100.0, 101.0))
    four = make_run(steps=steps, trace=_trace(), trace_host=(100.0, 101.0),
                    cell={"chips": 4})
    assert read("step_mfu", four) == pytest.approx(read("step_mfu", one) / 4)


def test_device_metrics_need_a_trace():
    run = make_run(steps=[step(100.0, 100.3, ctx=[5])])
    for name in ("device_idle_frac", "paged_decode_roofline", "step_mfu"):
        assert read(name, run) is None


def test_every_metric_has_a_reader_and_a_valid_entry():
    bench = json.load(open(R.ROOT / "BENCHMARK.json"))
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (R.BENCH / "metrics" / f"{m['name']}.py").exists(), m["name"]
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        for cell in m["workloads"]:
            assert m["moves"] in [x["name"] for x in
                                  R.metric_names(bench, cell, "end_to_end")]


def test_a_span_hook_on_a_missing_method_raises():
    class Obj:
        def step(self):
            return 1

    o = Obj()
    S._annotate(o, "step", S.ORCH_TICK)
    assert o.step() == 1
    with pytest.raises(AttributeError, match="_push_streams"):
        S._annotate(o, "_push_streams", S.PUMP_STREAMS)


def test_garbage_collections_are_recorded():
    pauses = S.GcPauses()
    try:
        gc.collect()
    finally:
        pauses.close()
    assert any(g == 2 and s >= 0 for _, g, s in pauses.events)
    assert pauses._cb not in gc.callbacks
