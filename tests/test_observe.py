"""The observability plane (serving/observe.py) and its wiring: span
trees for every completion, the in-repo Prometheus registry + strict
text-format validator (the CI conformance gate), the control-plane
flight recorder, and the instrumentation satellites (telemetry
serialization drift, re-entrant host-sync counting, bounded fault
windows).

Tier-1 covers the pure machinery plus local-instance end-to-end traces
through a real Ingress; the ``slow``-marked tests at the bottom spawn
real engine-server processes to prove cross-process trace propagation
with clock-skew correction and scrape a live multi-process pod's
``/metrics`` through the validator.
"""
import json
import os
import socket
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as T
from repro.serving import instrument as INS
from repro.serving import observe as OBS
from repro.serving.request import RequestSpec
from repro.serving.ingress import Ingress
from repro.serving.orchestrator import Orchestrator

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("tinyllama-1.1b").reduced()
    return cfg, T.init_params(cfg, KEY, "float32")


@pytest.fixture(scope="module")
def served(tiny):
    cfg, params = tiny
    orch = Orchestrator(cfg, params, n_instances=2, max_batch=2,
                        max_len=64, block_size=8, max_queue=4)
    ing = Ingress(orch, model_id="tiny-test").start()
    yield orch, ing
    ing.close()
    orch.close()


# ----------------------------------------------------- raw-socket client
def _request(ing, method, path, body=None):
    s = socket.create_connection(("127.0.0.1", ing.port), timeout=60)
    payload = b"" if body is None else json.dumps(body).encode()
    raw = f"{method} {path} HTTP/1.1\r\nHost: t\r\n".encode()
    if payload:
        raw += b"Content-Type: application/json\r\n"
        raw += b"Content-Length: %d\r\n" % len(payload)
    raw += b"\r\n" + payload
    s.sendall(raw)
    data = b""
    while chunk := s.recv(65536):
        data += chunk
    s.close()
    head, _, body = data.partition(b"\r\n\r\n")
    lines = head.decode("latin1").split("\r\n")
    status = int(lines[0].split(" ")[1])
    headers = {}
    for ln in lines[1:]:
        k, _, v = ln.partition(":")
        headers[k.strip().lower()] = v.strip()
    return status, headers, body


def _finished_record(tracer, trace_id):
    for rec in tracer.finished:
        if rec["trace_id"] == trace_id:
            return rec
    raise AssertionError(f"trace {trace_id} never finished; "
                         f"have {[r['trace_id'] for r in tracer.finished]}")


# ======================================================= span primitives
def test_span_tree_ok_accepts_a_sound_tree():
    t0 = OBS.server_now()
    root = OBS.make_span("t1", "request", t0, t0 + 1.0, span_id="t1")
    root["parent"] = None
    pre = OBS.make_span("t1", "prefill", t0 + 0.1, t0 + 0.4)
    chunk = OBS.make_span("t1", "prefill_chunk", t0 + 0.1, t0 + 0.2,
                          parent=pre["id"])
    assert OBS.span_tree_ok([root, pre, chunk]) is None


def test_span_tree_ok_reports_violations():
    t0 = 100.0
    root = OBS.make_span("t", "request", t0, t0 + 1.0, span_id="t")
    root["parent"] = None

    assert "empty" in OBS.span_tree_ok([])
    two = dict(root, id="t2")
    assert "2 roots" in OBS.span_tree_ok([root, two])
    orphan = OBS.make_span("t", "x", t0 + 0.1, t0 + 0.2, parent="nope")
    assert "orphan" in OBS.span_tree_ok([root, orphan])
    open_ = OBS.make_span("t", "decode", t0 + 0.1)
    assert "never closed" in OBS.span_tree_ok([root, open_])
    backwards = OBS.make_span("t", "x", t0 + 0.5, t0 + 0.1)
    assert "before it starts" in OBS.span_tree_ok([root, backwards])
    outside = OBS.make_span("t", "x", t0 + 0.5, t0 + 2.0)
    assert "outside root" in OBS.span_tree_ok([root, outside])


def test_estimate_clock_offset_recovers_injected_skew():
    skew = 5.0

    def call():
        time.sleep(0.001)            # symmetric fake RTT
        ts = time.monotonic() + skew
        time.sleep(0.001)
        return ts

    off = OBS.estimate_clock_offset(call, samples=5)
    assert abs(off - skew) < 0.05
    spans = [OBS.make_span("t", "x", 10.0, 11.0),
             OBS.make_span("t", "open", 10.0)]
    OBS.correct_spans(spans, 5.0)
    assert spans[0]["t0"] == 5.0 and spans[0]["t1"] == 6.0
    assert spans[1]["t1"] is None    # open spans shift t0 only


def test_tracer_lifecycle_and_jsonl_export(tmp_path):
    out = tmp_path / "traces.jsonl"
    tr = OBS.Tracer(out_path=str(out))
    t0 = OBS.server_now()
    tid = tr.begin(7, t0=t0, prompt_tokens=4)
    assert tid.startswith("req-7-")
    assert tr.ctx(7) == {"trace_id": tid, "rid": 7}
    assert tr.trace_id(7) == tid
    assert tr.live_rids() == [7]

    tr.span(7, "route", t0, attrs={"instance": 1})
    eng = OBS.make_span(tid, "decode", OBS.server_now(), OBS.server_now(),
                        origin="local")
    tr.ingest([eng])
    # spans for a trace nobody began are counted, never raised
    tr.span(99, "route", t0)
    tr.ingest([OBS.make_span("req-unknown", "x", t0, t0)])
    assert tr.dropped_spans == 2

    rec = tr.finish(7, tokens=3)
    assert rec["trace_id"] == tid
    assert OBS.span_tree_ok(rec["spans"]) is None
    assert rec["spans"][0]["attrs"] == {"prompt_tokens": 4, "tokens": 3}
    assert tr.live_rids() == [] and tr.ctx(7) is None
    assert tr.finish(7) is None      # double finish: no-op
    # a second trace, then read the JSONL sink back
    tr.begin(8)
    tr.finish(8)
    tr.close()
    lines = [json.loads(ln) for ln in out.read_text().splitlines()]
    assert tr.exported == 2 and len(lines) == 2
    assert lines[0]["trace_id"] == tid
    assert {s["name"] for s in lines[0]["spans"]} == \
        {"request", "route", "decode"}


def test_engine_span_recorder_records_only_registered_rids():
    rec = OBS.EngineSpanRecorder(origin="unit")

    class R:
        rid = 1

    rec.on_submit(R)                 # unregistered: dict miss, no span
    assert rec.drain() == []
    rec.register(1, "tid")
    rec.on_submit(R)
    rec.on_chunk(1, 8, rec.now())
    rec.on_chunk(1, 4, rec.now())
    rec.on_activate(R, fresh_first=True)
    rec.on_finish(R)
    spans = rec.drain()
    names = [s["name"] for s in spans]
    # chunks run asynchronously: counted on the prefill span, not timed
    assert "prefill_chunk" not in names
    pre = next(s for s in spans if s["name"] == "prefill")
    assert pre["attrs"] == {"chunks": 2, "chunk_tokens": 12}
    assert {"queue", "prefill", "first_token", "decode"} <= set(names)
    assert rec.drain() == []         # drained means drained


def test_flight_recorder_ring_dump_and_auto_dump(tmp_path):
    fr = OBS.FlightRecorder(capacity=4)
    for i in range(6):
        fr.record("route", idx=i % 2)
    evts = fr.events()
    assert len(evts) == 4            # ring dropped the 2 oldest
    assert [e["seq"] for e in evts] == [3, 4, 5, 6]
    assert len(fr.events("route")) == 4 and fr.events("nope") == []
    d = fr.dump()
    assert d["capacity"] == 4 and d["recorded"] == 6
    assert fr.auto_dump("no path configured") is None

    path = tmp_path / "flightrec.json"
    fr2 = OBS.FlightRecorder(capacity=8, dump_path=str(path))
    fr2.record("quarantine", instance=1)
    assert fr2.auto_dump("crash_recovery:test") == str(path)
    assert fr2.dumps == 1
    payload = json.loads(path.read_text())
    assert payload["reason"] == "crash_recovery:test"
    assert payload["events"][0]["kind"] == "quarantine"


def test_flight_recorder_keeps_a_ring_per_kind():
    """A frequent kind (engine steps) never pushes a rare one out."""
    fr = OBS.FlightRecorder(capacity=3)
    fr.record("migration", rid=7)
    for i in range(10):
        fr.record("step", step=i)
    assert [e["step"] for e in fr.events("step")] == [7, 8, 9]
    assert [e["kind"] for e in fr.events()] == ["migration"] + ["step"] * 3
    assert [e["seq"] for e in fr.events()] == [1, 9, 10, 11]
    assert fr.recorded == 11 == fr.dump()["recorded"]


# ================================================ Prometheus exposition
def test_registry_renders_conformant_exposition():
    reg = OBS.MetricsRegistry()
    reg.counter("repro_requests_total", "Accepted completions.", 12)
    reg.counter("repro_routed_total", "By reason.", 9,
                labels={"reason": "prefix"})
    reg.counter("repro_routed_total", "By reason.", 3,
                labels={"reason": "vacancy"})
    reg.gauge("repro_queue_depth", "Queue depth.", 2, labels={"instance": 0})
    reg.gauge("repro_weird", "Escaping.", 1,
              labels={"path": 'a"b\\c\nd'})
    reg.histogram("repro_itl_seconds", "Inter-token latency.",
                  [0.004, 0.009, 0.05], buckets=(0.005, 0.01),
                  labels={"instance": 0})
    text = reg.render()
    fams = OBS.parse_prometheus(text)
    assert fams["repro_requests_total"]["type"] == "counter"
    assert fams["repro_requests_total"]["samples"][0][2] == 12.0
    routed = {s[1]["reason"]: s[2]
              for s in fams["repro_routed_total"]["samples"]}
    assert routed == {"prefix": 9.0, "vacancy": 3.0}
    # label escaping survives the round trip
    weird = fams["repro_weird"]["samples"][0][1]["path"]
    assert weird == 'a"b\\c\nd'
    hist = {s[0]: s for s in fams["repro_itl_seconds"]["samples"]}
    buckets = {s[1]["le"]: s[2]
               for s in fams["repro_itl_seconds"]["samples"]
               if s[0] == "repro_itl_seconds_bucket"}
    assert buckets == {"0.005": 1.0, "0.01": 2.0, "+Inf": 3.0}
    assert hist["repro_itl_seconds_count"][2] == 3.0


def test_registry_rejects_bad_input():
    reg = OBS.MetricsRegistry()
    with pytest.raises(ValueError):
        reg.counter("bad-name", "h", 1)
    with pytest.raises(ValueError):
        reg.counter("ok_name", "h", 1, labels={"bad-label": "x"})
    reg.counter("repro_x", "h", 1)
    with pytest.raises(ValueError):
        reg.gauge("repro_x", "h", 1)      # type redeclaration


@pytest.mark.parametrize("text,needle", [
    ("repro_x 1\n", "no # TYPE"),
    ("# TYPE repro_x counter\nrepro_x 1\n# TYPE repro_x counter\n",
     "duplicate TYPE"),
    ("# TYPE repro_x counter\nrepro_x 1\n# TYPE repro_x gauge\n",
     "duplicate TYPE"),
    ("# HELP repro_x h\nrepro_x 1\n# TYPE repro_x counter\n",
     "after its samples"),
    ("# HELP repro_x h\n", "HELP without TYPE"),
    ("# TYPE repro_x counter\nrepro_x{le=1} 3\n", "bad label"),
    ("# TYPE repro_x counter\nrepro_x abc\n", "bad value"),
    ("# TYPE repro_x counter\nrepro_x 1 soon\n", "bad timestamp"),
    ("# TYPE repro_x wat\n", "bad type"),
    # histogram structure: no +Inf / non-cumulative / _count mismatch
    ('# TYPE repro_h histogram\nrepro_h_bucket{le="1"} 2\n'
     "repro_h_sum 2\nrepro_h_count 2\n", "no +Inf"),
    ('# TYPE repro_h histogram\nrepro_h_bucket{le="1"} 5\n'
     'repro_h_bucket{le="+Inf"} 3\nrepro_h_sum 2\nrepro_h_count 3\n',
     "not cumulative"),
    ('# TYPE repro_h histogram\nrepro_h_bucket{le="1"} 1\n'
     'repro_h_bucket{le="+Inf"} 3\nrepro_h_sum 2\nrepro_h_count 7\n',
     "+Inf bucket"),
])
def test_parser_rejects_malformed_exposition(text, needle):
    with pytest.raises(ValueError) as ei:
        OBS.parse_prometheus(text)
    assert needle in str(ei.value), (needle, str(ei.value))


# =========================================== instrumentation satellites
def test_telemetry_state_covers_every_gauge():
    """Serialization drift gate: a gauge added to EngineTelemetry MUST
    be added to to_state/load_state in the same change, or the remote
    plane silently reports stale zeros for it. ``vars()`` is the live
    attribute set; the wire schema must be exactly that plus the window
    size."""
    tel = INS.EngineTelemetry()
    assert set(tel.to_state()) == set(vars(tel)) | {"window"}


def test_telemetry_round_trip_is_lossless():
    src = INS.EngineTelemetry(window=8)
    for i in range(12):              # overflow the window: maxlen rides
        src.record_step(0.01 * (i + 1), i, packed=i, budget=32)

    class _R:
        def __init__(self, i):
            self.submit_time = 0.0
            self.first_token_time = 0.5 + i
            self.finish_time = 2.0 + i
            self.prefill_start_time = 0.25

    src.record_finished([_R(0), _R(1)])
    src.record_preemptions(3)
    src.record_prefix(10, 7, 4)

    dst = INS.EngineTelemetry()
    dst.load_state(src.to_state())
    assert dst.to_state() == src.to_state()
    assert dst.step_seconds.maxlen == 8
    assert dst.tokens_per_s() == src.tokens_per_s()
    assert dst.budget_utilization() == src.budget_utilization()
    assert dst.prefix_hit_rate() == src.prefix_hit_rate()


def test_fault_detect_latencies_window_is_bounded():
    fc = INS.FaultCounters()
    for i in range(600):
        fc.detect_latencies.append(float(i))
    assert len(fc.detect_latencies) == 512
    assert fc.detect_latencies[0] == 88.0       # oldest evicted
    assert fc.detect_quantile(1.0) == 599.0


def test_count_host_syncs_nested_and_threaded():
    orig = jax.device_get
    x = np.zeros(1)
    with INS.count_host_syncs() as outer:
        jax.device_get(x)
        with INS.count_host_syncs() as inner:
            jax.device_get(x)
        jax.device_get(x)
    assert (outer.n, inner.n) == (3, 1)
    assert jax.device_get is orig   # outermost exit restored the original

    # two concurrent contexts: each counts every sync in its window and
    # the LAST one out restores the original (no wrapper left behind)
    counts = []
    gate = threading.Barrier(2)

    def worker():
        with INS.count_host_syncs() as c:
            gate.wait()
            jax.device_get(x)
            gate.wait()
            counts.append(c.n)

    ts = [threading.Thread(target=worker) for _ in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert counts == [2, 2]
    assert jax.device_get is orig


# ============================================ end-to-end traces (local)
def test_local_completion_produces_connected_trace(tiny):
    cfg, params = tiny
    orch = Orchestrator(cfg, params, n_instances=2, max_batch=2,
                        max_len=64, block_size=8,
                        tracer=OBS.Tracer(), telemetry_every=10_000)
    reqs = [RequestSpec(rid=i,
                        prompt=np.arange(2 + i, 12 + i, dtype=np.int32),
                        max_tokens=6) for i in range(3)]
    for r in reqs:
        orch.tracer.begin(r.rid, prompt_tokens=len(r.prompt))
        orch.submit(r)
    orch.run_until_done()
    assert orch.tracer.live_rids() == []         # every trace closed
    assert len(orch.tracer.finished) == 3
    for rec in orch.tracer.finished:
        err = OBS.span_tree_ok(rec["spans"])
        assert err is None, err
        names = {s["name"] for s in rec["spans"]}
        assert {"request", "queue", "prefill", "first_token",
                "decode"} <= names, names
        assert rec["spans"][0]["attrs"]["tokens"] == 6
    assert orch.tracer.dropped_spans == 0
    orch.close()


def test_mid_decode_migration_appends_hop_span(tiny):
    cfg, params = tiny
    orch = Orchestrator(cfg, params, n_instances=2, max_batch=2,
                        max_len=64, block_size=8, n_blocks=24,
                        tracer=OBS.Tracer(), telemetry_every=10_000)
    req = RequestSpec(rid=0, prompt=np.arange(2, 12, dtype=np.int32),
                      max_tokens=10)
    orch.tracer.begin(req.rid)
    orch.submit_to(0, req)
    for _ in range(4):
        orch.step()
    live = next(r for r in orch.engines[0].active.values() if r.rid == 0)
    assert len(live.generated) >= 2              # mid-decode
    recs = orch.migrate_requests(0, 1)
    assert len(recs) == 1 and recs[0].resumed
    orch.run_until_done()

    rec = orch.tracer.finished[-1]
    err = OBS.span_tree_ok(rec["spans"])
    assert err is None, err                      # tree stays connected
    hops = [s for s in rec["spans"] if s["name"] == "migration_hop"]
    assert len(hops) == 1
    assert hops[0]["attrs"]["src"] == 0 and hops[0]["attrs"]["dst"] == 1
    # the source closed its decode span at the pause; the destination
    # opened its own continuation — both halves are in the tree
    decodes = [s for s in rec["spans"] if s["name"] == "decode"]
    assert len(decodes) == 2
    assert any(s["attrs"].get("paused") for s in decodes)
    # and only ONE first_token: the continuation did not re-emit it
    assert len([s for s in rec["spans"]
                if s["name"] == "first_token"]) == 1
    # the flight recorder kept the migration's phase evidence
    evts = orch.flightrec.events("migration")
    assert len(evts) == 1 and evts[0]["rid"] == 0
    assert evts[0]["bytes_moved"] > 0
    orch.close()


def test_flight_recorder_captures_controller_inputs(tiny):
    cfg, params = tiny
    orch = Orchestrator(cfg, params, n_instances=2, max_batch=2,
                        max_len=64, block_size=8, telemetry_every=10_000)
    req = RequestSpec(rid=0, prompt=np.arange(2, 10, dtype=np.int32),
                      max_tokens=4)
    orch.submit(req)
    orch.run_until_done()
    orch.control_tick()
    evts = orch.flightrec.events("controller")
    assert evts, "control_tick recorded no decision"
    inputs = evts[-1]["inputs"]
    assert {"slo_violation_rate", "queue_len", "tokens_per_s",
            "pod_size"} <= set(inputs)
    orch.close()


# ====================================== ingress: tracing + /metrics
def test_unary_completion_carries_request_id_and_trace(served):
    orch, ing = served
    status, headers, body = _request(
        ing, "POST", "/v1/completions",
        body={"prompt": [5, 6, 7, 8], "max_tokens": 4})
    assert status == 200
    tid = headers["x-request-id"]
    assert tid.startswith("req-")
    rec = _finished_record(ing.tracer, tid)
    err = OBS.span_tree_ok(rec["spans"])
    assert err is None, err
    names = {s["name"] for s in rec["spans"]}
    assert {"request", "accept", "route", "queue", "prefill",
            "first_token", "decode"} <= names, names
    route = next(s for s in rec["spans"] if s["name"] == "route")
    assert route["attrs"]["reason"] in ("prefix", "vacancy")
    assert rec["spans"][0]["attrs"]["tokens"] == 4


def test_stream_completion_carries_request_id_and_trace(served):
    orch, ing = served
    status, headers, body = _request(
        ing, "POST", "/v1/completions",
        body={"prompt": "trace me", "max_tokens": 4, "stream": True})
    assert status == 200
    assert headers["content-type"].startswith("text/event-stream")
    tid = headers["x-request-id"]
    assert b"[DONE]" in body
    rec = _finished_record(ing.tracer, tid)
    err = OBS.span_tree_ok(rec["spans"])
    assert err is None, err


def test_metrics_endpoint_is_conformant_and_moves(served):
    orch, ing = served
    status, headers, text = _request(ing, "GET", "/metrics")
    assert status == 200
    assert headers["content-type"].startswith("text/plain; version=0.0.4")
    fams = OBS.parse_prometheus(text.decode())
    required = {"repro_requests_total", "repro_http_429_total",
                "repro_bad_requests_total", "repro_tokens_out_total",
                "repro_routed_total", "repro_tokens_per_s",
                "repro_budget_utilization", "repro_prefix_hit_rate",
                "repro_pod_size", "repro_faults_total",
                "repro_instance_up", "repro_queue_depth",
                "repro_block_vacancy", "repro_ttft_steps",
                "repro_itl_seconds", "repro_traces_exported_total",
                "repro_trace_spans_dropped_total",
                "repro_flightrec_events"}
    assert required <= set(fams), required - set(fams)
    # per-instance labels for both pod members
    up = {s[1]["instance"] for s in fams["repro_instance_up"]["samples"]}
    assert up == {"0", "1"}

    def counter(fams, name):
        return fams[name]["samples"][0][2]

    before = counter(fams, "repro_requests_total")
    _request(ing, "POST", "/v1/completions",
             body={"prompt": [9, 9, 9], "max_tokens": 2})
    _, _, text = _request(ing, "GET", "/metrics")
    fams = OBS.parse_prometheus(text.decode())
    assert counter(fams, "repro_requests_total") == before + 1
    assert counter(fams, "repro_tokens_out_total") > 0
    assert counter(fams, "repro_trace_spans_dropped_total") == 0


def test_metrics_endpoint_exports_step_phases_and_compiles(served):
    """The operator's view of where step time goes: steps, host seconds
    per step phase and per pump phase, and compiles by program — every
    family a counter that grows between two scrapes — and each
    instance's compiles, which never shrink."""
    orch, ing = served

    def scrape():
        _, _, text = _request(ing, "GET", "/metrics")
        fams = OBS.parse_prometheus(text.decode())
        return {name: {tuple(sorted(lab.items())): v
                       for _, lab, v in fams[name]["samples"]}
                for name in ("repro_engine_steps_total",
                             "repro_step_phase_seconds_total",
                             "repro_pump_phase_seconds_total",
                             "repro_compiles_total",
                             "repro_compile_seconds_total",
                             "repro_engine_compiles_total")}

    _request(ing, "POST", "/v1/completions",
             body={"prompt": [4, 4, 2], "max_tokens": 3})
    a = scrape()

    def doubled_plus_two(x):
        return x * 2 + 2

    jax.jit(doubled_plus_two)(np.arange(13.0))      # one more program
    _request(ing, "POST", "/v1/completions",
             body={"prompt": [4, 2, 4], "max_tokens": 3})
    b = scrape()
    for name in a:
        assert a[name] and set(a[name]) <= set(b[name]), name
        assert all(b[name][k] >= v for k, v in a[name].items()), name
    # the same shapes again: the engines need not compile anything new
    engine_compiles = a.pop("repro_engine_compiles_total")
    assert set(engine_compiles) == set(a["repro_engine_steps_total"])
    for name in a:
        assert sum(b[name].values()) > sum(a[name].values()), name
    assert {dict(k)["phase"] for k in b["repro_step_phase_seconds_total"]} \
        == set(INS.STEP_PHASES)
    assert {dict(k)["phase"] for k in b["repro_pump_phase_seconds_total"]} \
        >= {INS.PUMP_SUBMIT, INS.PUMP_PUSH, INS.PUMP_MIRROR,
            INS.ORCH_STREAMS, INS.ORCH_SPANS}
    assert (("program", "jit(doubled_plus_two)"),) in \
        b["repro_compiles_total"]


def test_flightrec_endpoint_serves_routing_verdicts(served):
    orch, ing = served
    _request(ing, "POST", "/v1/completions",
             body={"prompt": [3, 1, 4, 1, 5], "max_tokens": 2})
    status, headers, body = _request(ing, "GET", "/debug/flightrec")
    assert status == 200
    dump = json.loads(body)
    assert dump["capacity"] == 512 and dump["recorded"] >= 1
    routes = [e for e in dump["events"] if e["kind"] == "route"]
    assert routes and routes[-1]["verdict"] == "admit"
    assert routes[-1]["reason"] in ("prefix", "vacancy")
    assert {"seq", "t", "wall"} <= set(routes[-1])


def test_single_sync_invariant_holds_under_ingress_pump(served):
    """The host-sync counter is safe while the pump thread steps real
    engines concurrently (the process-wide patch, not save/restore),
    and the plane stays within the paged-engine bound: at most one
    blocking device->host sync per engine step, fleet-wide."""
    orch, ing = served
    orig = jax.device_get
    ticks0 = orch.rpc_stats["ticks"]
    with INS.count_host_syncs() as c:
        status, _, _ = _request(
            ing, "POST", "/v1/completions",
            body={"prompt": [2, 7, 1, 8], "max_tokens": 4})
        assert status == 200
        ticks1 = orch.rpc_stats["ticks"]
    assert jax.device_get is orig
    assert c.n >= 1, "the pump's engine steps were not counted"
    n_inst = len(orch.instances)
    # +1 tick of slack: the pump may be mid-step at either read
    assert c.n <= (ticks1 - ticks0 + 1) * n_inst, \
        (c.n, ticks1 - ticks0, n_inst)


# ==================================== cross-process (tier-2: spawned)
@pytest.mark.slow
def test_remote_trace_skew_corrected_over_tcp(tiny):
    """A spawned TCP engine server with an injected 7.5s clock skew:
    the proxy's RTT offset estimate recovers the skew, ingestion shifts
    the server-stamped spans back onto the ingress clock, and the
    finished trace is one connected tree with every engine span inside
    the root window — which cannot hold if correction is off by the
    injected amount."""
    cfg, params = tiny
    os.environ[OBS._SKEW_ENV] = "7.5"
    os.environ["REPRO_RPC_TRANSPORT"] = "tcp"
    try:
        orch = Orchestrator(cfg, params, n_instances=1, max_batch=2,
                            max_len=64, block_size=8, remote=True,
                            tracer=OBS.Tracer(), telemetry_every=10_000)
    finally:
        # the parent must NOT run skewed: only the spawned server
        # (which inherited the env) reports a shifted server_now()
        del os.environ[OBS._SKEW_ENV]
        del os.environ["REPRO_RPC_TRANSPORT"]
    try:
        assert abs(orch.instances[0].clock_offset - 7.5) < 1.0
        req = RequestSpec(rid=0, prompt=np.arange(2, 12, dtype=np.int32),
                          max_tokens=6)
        orch.tracer.begin(req.rid)
        orch.submit(req)
        orch.run_until_done()
        rec = orch.tracer.finished[-1]
        err = OBS.span_tree_ok(rec["spans"])
        assert err is None, err
        remote = [s for s in rec["spans"]
                  if s["origin"].startswith("server:")]
        assert remote, "no engine-server spans arrived"
        assert {"queue", "prefill", "decode"} <= \
            {s["name"] for s in remote}
        assert orch.tracer.dropped_spans == 0
    finally:
        orch.close()


@pytest.mark.slow
def test_live_pod_metrics_scrape_is_conformant(tiny):
    """The CI nightly conformance gate: scrape a REAL 2-worker
    multi-process pod's /metrics through the strict validator."""
    cfg, params = tiny
    orch = Orchestrator(cfg, params, n_instances=2, max_batch=2,
                        max_len=64, block_size=8, remote=True)
    ing = Ingress(orch, model_id="tiny-pod").start()
    try:
        for k in range(3):
            status, headers, _ = _request(
                ing, "POST", "/v1/completions",
                body={"prompt": [5 + k, 6, 7], "max_tokens": 3})
            assert status == 200
            assert "x-request-id" in headers
        status, headers, text = _request(ing, "GET", "/metrics")
        assert status == 200
        fams = OBS.parse_prometheus(text.decode())
        up = {s[1]["instance"]: s[2]
              for s in fams["repro_instance_up"]["samples"]}
        assert up == {"0": 1.0, "1": 1.0}
        assert fams["repro_tokens_out_total"]["samples"][0][2] >= 9
        assert fams["repro_pod_size"]["samples"][0][2] == 2
        # every completion over the RPC plane closed a connected trace
        assert len(ing.tracer.finished) == 3
        for rec in ing.tracer.finished:
            err = OBS.span_tree_ok(rec["spans"])
            assert err is None, err
    finally:
        ing.close()
        orch.close()
