"""The HTTP front door (serving/ingress.py), exercised the hard way: raw
sockets and hand-parsed HTTP/1.1 — no client library — so the framing
itself (status lines, Content-Length, chunked transfer encoding) is
under test, not just the payloads. Covers: response framing, streamed
chunk ordering, deterministic 429 backpressure (the ``hold_pump`` test
hook), malformed-request 400s, and graceful shutdown mid-stream."""
import json
import socket
import time

import jax
import pytest

from repro.configs import get_config
from repro.models import transformer as T
from repro.serving.ingress import Ingress, byte_tokens
from repro.serving.orchestrator import Orchestrator

KEY = jax.random.PRNGKey(0)
MAX_QUEUE = 2


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("tinyllama-1.1b").reduced()
    return cfg, T.init_params(cfg, KEY, "float32")


@pytest.fixture(scope="module")
def served(tiny):
    cfg, params = tiny
    orch = Orchestrator(cfg, params, n_instances=2, max_batch=2,
                        max_len=64, block_size=8, max_queue=MAX_QUEUE)
    ing = Ingress(orch, model_id="tiny-test").start()
    yield orch, ing
    ing.close()
    orch.close()


# ----------------------------------------------------- raw-socket client
def _connect(ing):
    return socket.create_connection(("127.0.0.1", ing.port), timeout=60)


def _send(sock, method, path, body=None, raw=None):
    if raw is None:
        payload = b"" if body is None else json.dumps(body).encode()
        raw = f"{method} {path} HTTP/1.1\r\nHost: t\r\n".encode()
        if payload:
            raw += b"Content-Type: application/json\r\n"
            raw += b"Content-Length: %d\r\n" % len(payload)
        raw += b"\r\n" + payload
    sock.sendall(raw)


def _recv_all(sock):
    data = b""
    while chunk := sock.recv(65536):
        data += chunk
    return data


def _parse(data):
    """Strict HTTP/1.1 response parse: (status, headers, raw body)."""
    head, _, body = data.partition(b"\r\n\r\n")
    lines = head.decode("latin1").split("\r\n")
    proto, status, *_ = lines[0].split(" ")
    assert proto == "HTTP/1.1"
    headers = {}
    for ln in lines[1:]:
        k, _, v = ln.partition(":")
        headers[k.strip().lower()] = v.strip()
    return int(status), headers, body


def _parse_chunked(body):
    """Decode chunked transfer encoding STRICTLY; returns (payloads,
    saw_terminator). Any framing slip (bad size line, missing CRLF)
    fails the test here rather than being papered over."""
    chunks, rest, done = [], body, False
    while rest:
        size_line, _, rest = rest.partition(b"\r\n")
        size = int(size_line, 16)
        if size == 0:
            assert rest[:2] in (b"\r\n", b"")   # final CRLF
            done = True
            break
        assert len(rest) >= size + 2, "truncated chunk"
        chunks.append(rest[:size])
        assert rest[size:size + 2] == b"\r\n"
        rest = rest[size + 2:]
    return chunks, done


def _request(ing, method, path, body=None, raw=None):
    s = _connect(ing)
    _send(s, method, path, body=body, raw=raw)
    data = _recv_all(s)
    s.close()
    return _parse(data)


# ------------------------------------------------------------- framing
def test_health_and_models_framing(served):
    _, ing = served
    status, headers, body = _request(ing, "GET", "/healthz")
    assert status == 200
    assert int(headers["content-length"]) == len(body)
    assert headers["content-type"] == "application/json"
    assert headers["connection"] == "close"
    obj = json.loads(body)
    assert obj["status"] == "ok" and obj["pod_size"] == 2

    status, headers, body = _request(ing, "GET", "/v1/models")
    assert status == 200
    assert int(headers["content-length"]) == len(body)
    assert json.loads(body)["data"][0]["id"] == "tiny-test"


def test_stats_surfaces_snapshot_and_counters(served):
    _, ing = served
    status, _, body = _request(ing, "GET", "/stats")
    assert status == 200
    obj = json.loads(body)
    assert set(obj) >= {"snapshot", "ingress", "pod", "finished",
                        "dropped"}
    assert obj["snapshot"]["pod_size"] == 2
    assert set(obj["ingress"]) >= {"requests", "rejected_429",
                                   "tokens_out", "routed_prefix",
                                   "routed_vacancy"}


def test_byte_tokenizer_is_deterministic(tiny):
    cfg, _ = tiny
    a, b = byte_tokens("same text", cfg.vocab_size), \
        byte_tokens("same text", cfg.vocab_size)
    assert (a == b).all() and len(a) == len("same text")
    assert (a >= 2).all() and (a < cfg.vocab_size).all()


# ---------------------------------------------------------- completions
def test_unary_completion(served):
    _, ing = served
    status, headers, body = _request(
        ing, "POST", "/v1/completions",
        body={"prompt": [5, 6, 7, 8], "max_tokens": 4})
    assert status == 200
    assert int(headers["content-length"]) == len(body)
    obj = json.loads(body)
    assert len(obj["tokens"]) == 4
    assert obj["usage"]["completion_tokens"] == 4
    assert obj["routing"]["reason"] in ("prefix", "vacancy")
    assert obj["routing"]["instance"] in (0, 1)


def test_streaming_chunk_framing_and_order(served):
    _, ing = served
    status, headers, body = _request(
        ing, "POST", "/v1/completions",
        body={"prompt": "stream me please", "max_tokens": 6,
              "stream": True})
    assert status == 200
    assert headers["transfer-encoding"] == "chunked"
    assert headers["content-type"] == "text/event-stream"
    assert "content-length" not in headers
    chunks, terminated = _parse_chunked(body)
    assert terminated, "missing 0\\r\\n\\r\\n chunked terminator"
    events = []
    for c in chunks:
        assert c.startswith(b"data: ") and c.endswith(b"\n\n")
        events.append(c[len(b"data: "):].strip())
    # first event: the routing verdict; last: [DONE]; between: tokens
    # with strictly consecutive indices (order is the contract)
    head = json.loads(events[0])
    assert head["routing"] in ("prefix", "vacancy")
    assert events[-1] == b"[DONE]"
    toks = [json.loads(e) for e in events[1:-1]]
    assert [t["index"] for t in toks] == list(range(6))
    assert all(isinstance(t["token"], int) for t in toks)


def test_tokens_arrive_incrementally(served):
    """Streaming means streaming: at least one token chunk must be on
    the wire BEFORE the request finishes — observed as data arriving in
    more than one socket read with a gap between them."""
    _, ing = served
    s = _connect(ing)
    _send(s, "POST", "/v1/completions",
          body={"prompt": "incremental", "max_tokens": 8, "stream": True})
    reads = []
    while True:
        chunk = s.recv(65536)
        if not chunk:
            break
        reads.append((time.monotonic(), chunk))
    s.close()
    assert len(reads) > 1, "entire stream arrived in one flush"
    body = b"".join(c for _, c in reads).partition(b"\r\n\r\n")[2]
    _, terminated = _parse_chunked(body)
    assert terminated


# ------------------------------------------------------- backpressure
def test_deterministic_429_and_recovery(served):
    """With the pump held, accepted-but-unpumped requests fill every
    seat (2 instances x max_queue) and the next admission is shed with
    429 + Retry-After; releasing the pump drains the backlog and every
    held request completes."""
    _, ing = served
    seats = 2 * MAX_QUEUE
    base = ing.counters.requests
    ing.hold_pump.set()
    socks = []
    try:
        for k in range(seats):
            s = _connect(ing)
            _send(s, "POST", "/v1/completions",
                  body={"prompt": [10 + k], "max_tokens": 2})
            socks.append(s)
        deadline = time.monotonic() + 30
        while ing.counters.requests < base + seats:
            assert time.monotonic() < deadline, "accepts not registered"
            time.sleep(0.01)
        status, headers, body = _request(
            ing, "POST", "/v1/completions",
            body={"prompt": [99], "max_tokens": 2})
        assert status == 429
        assert headers["retry-after"] == "1"
        assert json.loads(body)["error"]
        assert ing.counters.rejected_429 >= 1
    finally:
        ing.hold_pump.clear()
    for s in socks:
        status, _, body = _parse(_recv_all(s))
        s.close()
        assert status == 200
        assert len(json.loads(body)["tokens"]) == 2


# ------------------------------------------------------------- rejects
@pytest.mark.parametrize("body", [
    {"max_tokens": 4},                          # no prompt
    {"prompt": ""},                             # empty text
    {"prompt": []},                             # empty ids
    {"prompt": [1, -2, 3]},                     # negative id
    {"prompt": [1, "x"]},                       # non-int id
    {"prompt": [1, 2], "max_tokens": 0},        # out-of-range knobs
    {"prompt": [1, 2], "max_tokens": 99999},
    {"prompt": [1, 2], "temperature": "hot"},
])
def test_malformed_completions_get_400(served, body):
    _, ing = served
    status, _, resp = _request(ing, "POST", "/v1/completions", body=body)
    assert status == 400
    assert json.loads(resp)["error"]


def test_broken_http_framing_gets_400(served):
    _, ing = served
    for raw in [b"GARBAGE\r\n\r\n",
                b"GET /healthz\r\n\r\n",              # no HTTP version
                b"POST /v1/completions HTTP/1.1\r\n"
                b"Content-Length: 99999999\r\n\r\n",  # absurd length
                b"POST /v1/completions HTTP/1.1\r\n"
                b"Content-Length: 4\r\n\r\nnot-"]:    # non-JSON body
        status, _, _ = _request(ing, "POST", "/x", raw=raw)
        assert status == 400


def test_unknown_path_404_and_wrong_method_405(served):
    _, ing = served
    assert _request(ing, "GET", "/nope")[0] == 404
    assert _request(ing, "GET", "/v1/completions")[0] == 405
    # GET-only routes don't match under POST -> falls through to 404
    assert _request(ing, "POST", "/healthz", body={"x": 1})[0] == 404


# ----------------------------------------------------- graceful shutdown
def test_graceful_shutdown_mid_stream(tiny):
    """close() during an in-flight stream must leave the client a WELL-
    FORMED tail: an error event, then the zero-length chunk terminator —
    never a connection reset mid-chunk. New intake gets 503."""
    cfg, params = tiny
    orch = Orchestrator(cfg, params, n_instances=1, max_batch=2,
                        max_len=64, block_size=8)
    ing = Ingress(orch).start()
    # hold the pump: the request is accepted and its stream opened, but
    # it can never finish, so it is provably in flight when close() runs
    ing.hold_pump.set()
    s = _connect(ing)
    _send(s, "POST", "/v1/completions",
          body={"prompt": "long running stream", "max_tokens": 256,
                "stream": True})
    # wait for the stream to be genuinely in flight (headers + routing
    # event on the wire), then shut down under it
    first = s.recv(65536)
    assert b"200 OK" in first
    ing.close()
    tail = first + _recv_all(s)
    s.close()
    body = tail.partition(b"\r\n\r\n")[2]
    chunks, terminated = _parse_chunked(body)
    assert terminated, "shutdown must emit the chunked terminator"
    assert any(b"shutting down" in c for c in chunks)
    assert ing.counters.aborted_streams >= 1
    orch.close()


def test_closing_ingress_rejects_new_intake_with_503(tiny):
    cfg, params = tiny
    orch = Orchestrator(cfg, params, n_instances=1, max_batch=2,
                        max_len=64, block_size=8)
    ing = Ingress(orch).start()
    ing._closing = True        # the first thing close() sets
    status, _, body = _request(ing, "GET", "/healthz")
    assert status == 503 and b"shutting down" in body
    ing._closing = False
    ing.close()
    orch.close()


# ------------------------------------------- SLO fields + 400 taxonomy
def test_slo_completion_accepted_end_to_end(served):
    """A body carrying slo_class + deadline_ms is parsed into the
    RequestSpec and served normally — the fields are additive, not a
    different endpoint."""
    _, ing = served
    status, _, resp = _request(
        ing, "POST", "/v1/completions",
        body={"prompt": "hello slo", "max_tokens": 4,
              "slo_class": "interactive", "deadline_ms": 2000})
    assert status == 200
    out = json.loads(resp)
    assert len(out["tokens"]) == 4
    assert out["usage"]["completion_tokens"] == 4


def test_unknown_slo_class_gets_typed_400(served):
    _, ing = served
    status, _, resp = _request(
        ing, "POST", "/v1/completions",
        body={"prompt": "x", "slo_class": "platinum"})
    assert status == 400
    out = json.loads(resp)
    assert out["error"] == "unknown_slo_class"
    assert "platinum" in out["detail"]
    assert "interactive" in out["detail"]       # the menu is in the body


def test_bad_deadline_gets_typed_400(served):
    _, ing = served
    for bad in (0, -5, -0.1):
        status, _, resp = _request(
            ing, "POST", "/v1/completions",
            body={"prompt": "x", "deadline_ms": bad})
        assert status == 400
        out = json.loads(resp)
        assert out["error"] == "bad_deadline"
        assert "deadline_ms" in out["detail"]


def test_unknown_body_fields_get_typed_400(served):
    _, ing = served
    status, _, resp = _request(
        ing, "POST", "/v1/completions",
        body={"prompt": "x", "slo": "interactive", "maxTokens": 4})
    assert status == 400
    out = json.loads(resp)
    assert out["error"] == "unknown_fields"
    assert sorted(out["fields"]) == ["maxTokens", "slo"]


def test_taxonomy_bodies_are_distinct(served):
    """The three typed rejections carry three distinct machine-readable
    codes — a client can branch without parsing prose."""
    _, ing = served
    codes = set()
    for body in ({"prompt": "x", "slo_class": "nope"},
                 {"prompt": "x", "deadline_ms": -1},
                 {"prompt": "x", "bogus_key": 1}):
        status, _, resp = _request(ing, "POST", "/v1/completions",
                                   body=body)
        assert status == 400
        codes.add(json.loads(resp)["error"])
    assert codes == {"unknown_slo_class", "bad_deadline",
                     "unknown_fields"}


# ------------------------------------------------- the budget governor
class _FakeInstance:
    def __init__(self):
        self.calls = []

    def set_token_budget(self, budget):
        self.calls.append(budget)
        return budget


class _FakeRec:
    def __init__(self):
        self.events = []

    def record(self, kind, **fields):
        self.events.append((kind, fields))


class _FakeOrch:
    def __init__(self, tel):
        self.telemetry = [tel]
        self.instances = [_FakeInstance()]
        self.flightrec = _FakeRec()

    def _alive(self):
        return [0]


def _saturated_telemetry(budget=128, packed=None, delay=10.0):
    from repro.serving.instrument import EngineTelemetry
    tel = EngineTelemetry()
    tel.budget = budget
    tel.packed_tokens.extend([packed if packed is not None else budget] * 8)
    tel.queue_delays.extend([delay] * 8)
    return tel


def test_budget_governor_grows_under_saturation_and_delay():
    from repro.serving.ingress import BudgetGovernor
    orch = _FakeOrch(_saturated_telemetry())
    gov = BudgetGovernor(orch, period_s=0.5)
    assert gov.tick(now=10.0)
    assert orch.instances[0].calls == [192]          # 128 * 1.5
    assert gov.budgets[0] == 192 and gov.adjustments == 1
    kind, ev = orch.flightrec.events[0]
    assert kind == "budget_governor"
    assert ev["budget"] == 192 and ev["prev"] == 128
    # rate limit: a second tick inside period_s is a no-op
    assert not gov.tick(now=10.2)
    assert gov.adjustments == 1


def test_budget_governor_shrinks_when_budget_rides_empty():
    from repro.serving.ingress import BudgetGovernor
    orch = _FakeOrch(_saturated_telemetry(budget=128, packed=16,
                                          delay=0.0))
    gov = BudgetGovernor(orch, period_s=0.0)
    assert gov.tick(now=1.0)
    assert orch.instances[0].calls == [96]           # 128 * 0.75
    # repeated shrink bottoms out at min_budget, then goes quiet
    for t in range(2, 20):
        gov.tick(now=float(t))
    assert gov.budgets[0] == gov.min_budget
    last = orch.instances[0].calls[-1]
    assert last == gov.min_budget
    n = gov.adjustments
    gov.tick(now=100.0)
    assert gov.adjustments == n                      # clamped: no churn


def test_budget_governor_skips_phase_engines_and_holds_steady_band():
    from repro.serving.instrument import EngineTelemetry
    from repro.serving.ingress import BudgetGovernor
    # phase engine: no budget, no packed window -> untouched
    orch = _FakeOrch(EngineTelemetry())
    gov = BudgetGovernor(orch, period_s=0.0)
    assert gov.tick(now=1.0)
    assert orch.instances[0].calls == []
    # mid-band utilization: saturated but NO queueing -> no grow either
    orch2 = _FakeOrch(_saturated_telemetry(delay=0.0))
    gov2 = BudgetGovernor(orch2, period_s=0.0)
    assert gov2.tick(now=1.0)
    assert orch2.instances[0].calls == []
