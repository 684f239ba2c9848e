"""Multi-process serving instances: a real paged ``Engine`` in a child
process behind an engine-server loop, driven through ``EngineProxy``.

Topology (one proxy <-> one server, one AF_UNIX stream each)::

    orchestrator process                      engine-server process
    ────────────────────                      ─────────────────────
    EngineProxy ──frames──▶ transport.serve ──▶ Engine(cache_kind=paged)
       │  submit/step/apply_plan/pause/...          │ real JAX execution
       ◀── step reply: finished Requests,  ◀────────┘
           serialized EngineTelemetry, gauge dict

The child is SPAWNED (never forked — JAX runtimes do not survive a
fork), connects back to the parent's rendezvous socket, receives one
``init`` frame ({cfg, params as a host-array tree, engine kwargs}),
builds the engine, and enters the dispatch loop. Everything after init
is msgpack frames: admissions, telemetry, controller plans (replication
degree lists), and the column-keyed block payloads of
``paged_kv.export_blocks`` — the same wire format the in-process path
uses, now actually crossing a process boundary. No shared memory, no
fork-inherited state: what the frames carry is ALL the two sides share,
which is exactly the multi-host contract.

Three rendezvous modes share that contract (the frames are identical;
only who dials whom differs):

* **spawned, child dials back** (the PR-4 default): the parent listens
  on a fresh rendezvous socket — AF_UNIX normally, loopback TCP when
  ``REPRO_RPC_TRANSPORT=tcp`` — and the spawned child connects back.
* **spawned, child listens** (``endpoint="tcp://host:port"``): the
  child binds the endpoint and the parent connects with retry/backoff
  (a just-spawned server that hasn't bound yet looks like connection
  refused). This is how launch/pod.py runs local inventory nodes.
* **attached** (``endpoint=..., spawn=False``): the engine server is
  already running on ANOTHER HOST (``python -m repro.launch.pod
  --serve tcp://0.0.0.0:PORT``); the proxy only connects. There is no
  child process to join — liveness is purely the transport's.

Liveness: the proxy keeps a ``pristine`` clone of every request the
server currently holds (``inflight_requests``). When the server dies —
crash, OOM kill, host loss, or the test-only ``crash`` op — the next
RPC (or the orchestrator's batched poll) raises ``TransportClosed`` and
the orchestrator re-queues those clones on a surviving instance;
counter-based sampling keys replay them token-identically, so a worker
loss costs recompute, never output.

"""
from __future__ import annotations

import multiprocessing as mp
import os
from typing import Dict, List, Optional

from repro.serving import instrument as INS
from repro.serving import observe as OBS
from repro.serving import transport as TR
from repro.serving.instance import InstanceHandle, pristine
from repro.serving.request import RequestSpec
from repro.serving.instrument import EngineTelemetry
from repro.serving.engine import Request


# ============================================================ server side
class EngineServer:
    """Dispatch table around one Engine (runs in the child process)."""

    def __init__(self, engine):
        self.engine = engine
        self.telemetry = EngineTelemetry()
        self.recorder = None   # lazy observe.EngineSpanRecorder

    # ---- serving ops
    def submit(self, spec: RequestSpec):
        self.engine.submit(spec)
        return len(self.engine.queue)

    def set_token_budget(self, budget: int) -> int:
        return self.engine.set_token_budget(int(budget))

    def step(self):
        done = INS.timed_step(self.engine, self.telemetry)
        out = {"finished": done, "telemetry": self.telemetry.to_state(),
               "info": self.info(),
               # full per-stream token lists each step (tiny at decode
               # rates; idempotent under migration/replay) — the ingress
               # streaming feed rides the reply, no extra RPC
               "streams": {int(r): list(t) for r, t
                           in self.engine.stream_progress().items()}}
        if self.recorder is not None:
            # spans ship home piggybacked like telemetry; stamped with
            # THIS process's clock — the proxy skew-corrects on ingest
            out["spans"] = self.recorder.drain()
        return out

    # ---- tracing
    def _on_trace(self, ctx: dict):
        """Trace context piggybacked on an RPC frame (transport.serve
        delivers it before the op runs): install the span recorder on
        first use, register rid -> trace id so the engine's lifecycle
        hooks start recording for this request."""
        if self.recorder is None:
            self.recorder = OBS.EngineSpanRecorder(
                origin=f"server:{os.getpid()}")
            self.engine.span_hook = self.recorder
        self.recorder.register(int(ctx["rid"]), ctx["trace_id"])

    def trace_register(self, ctx: dict):
        """Explicit registration op — migration/replay continuations
        arrive via resume/commit payloads, not a traced submit frame."""
        self._on_trace(ctx)
        return True

    def clock_sync(self) -> float:
        """This process's span clock, for the proxy's RTT-midpoint
        offset estimation (observe.estimate_clock_offset)."""
        return OBS.server_now()

    def apply_plan(self, p: List[int]):
        self.engine.apply_plan(list(p))
        return True

    def requeue_front(self, req: Request):
        self.engine.queue.appendleft(req)
        return len(self.engine.queue)

    def push_queue(self, req: Request):
        self.engine.queue.append(req)
        return len(self.engine.queue)

    def drain_queue(self):
        out = []
        while self.engine.queue:
            out.append(self.engine.queue.popleft())
        return out

    # ---- telemetry
    def info(self) -> dict:
        e = self.engine
        return {"clock": e.clock,
                "queue_len": len(e.queue),
                "active": {int(s): int(r) for s, r in e.slot_rids().items()},
                "free_blocks": e.pstate.free_block_count(),
                "blocks_in_use": e.pstate.blocks_in_use(),
                "n_blocks": e.pstate.n_blocks,
                "max_batch": e.max_batch,
                "pool_bytes": e.pstate.pool_bytes(),
                "preempt_count": e.preempt_count,
                "prefix_stats": e.prefix_stats(),
                "block_size": e.block_size,
                "paged_attn_impl": e.paged_attn_impl,
                # sorted list (sets aren't msgpack-able); the proxy
                # rebuilds the set on read
                "prefix_keys": sorted(e.prefix_keys())}

    # ---- migration (each blocks until device state is real — the reply
    # frame doubles as the transfer-complete barrier — and piggybacks
    # the gauge dict so the proxy's cache stays fresh without a second
    # round trip inside the migration stall window)
    def _sync(self):
        import jax
        jax.block_until_ready((self.engine.pstate.k, self.engine.pstate.v))

    def _reply(self, result):
        """Migration reply envelope: the gauge dict, plus any spans the
        op itself closed (a pause closes the victim's decode span —
        shipping it HERE instead of on the next step reply means the
        trace can finish before this server ever steps again)."""
        out = {"result": result, "info": self.info()}
        if self.recorder is not None:
            spans = self.recorder.drain()
            if spans:
                out["spans"] = spans
        return out

    def pause_request(self, slot: int, since_epoch=None):
        payload = self.engine.pause_request(slot, since_epoch=since_epoch)
        return self._reply(payload)

    def resume_request(self, payload: dict):
        ok = self.engine.resume_request(payload)
        self._sync()
        return self._reply(ok)

    def snapshot_request(self, slot: int):
        return self.engine.snapshot_request(slot)

    def prepare_resume(self, snap: dict):
        slot = self.engine.prepare_resume(snap)
        self._sync()
        return self._reply(slot)

    def commit_resume(self, slot: int, payload: dict):
        ok = self.engine.commit_resume(slot, payload)
        self._sync()
        return self._reply(ok)

    def abort_resume(self, slot: int):
        self.engine.abort_resume(slot)
        return self._reply(True)

    # ---- liveness
    def ping(self):
        return "pong"

    def heartbeat(self):
        """The hung-vs-dead probe payload: cheap, never touches the
        device — a worker stalled in a long device op still answers
        once the in-order queue reaches it, a blackholed one never
        does. Returns enough identity for the orchestrator to log."""
        return {"clock": self.engine.clock,
                "queue_len": len(self.engine.queue),
                "pid": os.getpid()}

    def crash(self):
        """Test-only fault injection: die without a word — the parent's
        next recv sees EOF, exactly like a kill -9 / OOM kill."""
        os._exit(17)

    def dispatch(self) -> dict:
        d = {op: getattr(self, op) for op in (
            "submit", "set_token_budget", "step", "apply_plan",
            "requeue_front", "push_queue",
            "drain_queue", "info", "pause_request", "resume_request",
            "snapshot_request", "prepare_resume", "commit_resume",
            "abort_resume", "ping", "heartbeat", "crash",
            "trace_register", "clock_sync")}
        # not a wire op: transport.serve's hook for trace contexts
        # piggybacked on ordinary frames
        d["_on_trace"] = self._on_trace
        return d


def _serve_connection(conn: "TR.Connection"):
    """Shared tail of both server entries: build the engine from the
    orchestrator's init frame, ack ready, serve until shutdown/hangup."""
    init = conn.recv()
    from repro.serving.engine import Engine  # import after spawn, in-child
    engine = Engine(init["cfg"], init["params"], **init["engine_kw"])
    server = EngineServer(engine)
    conn.send({"id": 0, "ok": True, "result": "ready"})
    TR.serve(conn, server.dispatch())
    conn.close()


def engine_server_main(address: str):
    """Child-process entry, dial-back mode: connect to the parent's
    rendezvous listener (AF_UNIX path or ``tcp://host:port``), then
    serve."""
    _serve_connection(TR.connect(address))


def engine_server_listen(address: str):
    """Engine-server entry, listening mode: bind ``address`` (normally
    ``tcp://host:port`` — the multi-host deployment unit), accept ONE
    orchestrator, serve it, exit. Run standalone on a pod node via
    ``python -m repro.launch.pod --serve tcp://0.0.0.0:PORT``."""
    srv = TR.listen(address)
    try:
        conn = TR.accept(srv, timeout=None)
    finally:
        srv.close()
    _serve_connection(conn)


# ============================================================= proxy side
class _PendingStage:
    """Pipelined prepare_resume: unwraps the piggybacked gauge dict on
    completion and maps a dead peer to TransportClosed."""

    def __init__(self, proxy: "EngineProxy", pending: TR.Pending):
        self._proxy = proxy
        self._pending = pending

    def wait(self):
        try:
            return self._proxy._unwrap(self._pending.wait())
        except TR.TransportClosed:
            self._proxy._dead = True
            raise


def refuse_spawn_on_accelerator(what: str):
    """Spawned engine servers each need the accelerator, but a chip
    belongs to one process at a time and this one (which builds the
    orchestrator and the weights) already holds it: a spawned child
    would fail on the device lock or hang. Refuse, before anything is
    spawned, on any backend but the CPU."""
    import jax
    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"{what} spawns engine-server processes, but this process "
            f"holds the {backend} device they would need; serve the "
            f"instances in-process with --instances N instead")


def rendezvous_transport() -> str:
    """Transport family for spawned proxies with no explicit endpoint:
    ``REPRO_RPC_TRANSPORT=tcp`` lifts the whole plane onto loopback TCP
    (frames identical; the tier-2 suite runs unchanged under it),
    anything else keeps the AF_UNIX default."""
    return os.environ.get("REPRO_RPC_TRANSPORT", "unix").lower()


class EngineProxy(InstanceHandle):
    """The orchestrator-side handle of a remote engine: mirrors the
    in-process ``Engine`` control surface over RPC frames. Gauges
    (queue depth, pool vacancy, clock, prefix stats) read a cache
    refreshed by every step reply — one RPC round trip per orchestrator
    step in steady state, and the step reply itself is drained through
    the orchestrator's batched poll (``step_async`` + ``finish_step``),
    so N instances cost one multiplexed wait, not N sequential ones."""

    def __init__(self, cfg, params, *, start_timeout: float = 120.0,
                 endpoint: Optional[str] = None, spawn: bool = True,
                 adopt_process=None, peer_label: Optional[str] = None,
                 **engine_kw):
        self.telemetry = EngineTelemetry()
        self._inflight: Dict[int, Request] = {}   # rid -> pristine clone
        self._streams: Dict[int, List[int]] = {}  # last step's stream feed
        self._span_feed: List[dict] = []   # skew-corrected server spans
        self.clock_offset = 0.0            # server clock - ours (est.)
        self._dead = False
        self.process = None
        self.endpoint = endpoint
        self.peer_label = peer_label
        # everything respawn() needs to bring up a fresh replacement
        self._spec = {"cfg": cfg, "params": params,
                      "start_timeout": start_timeout,
                      "engine_kw": dict(engine_kw)}
        self._listen_mode = endpoint is not None
        # supervised respawn can recreate any server WE own a process
        # for (spawned either way, or adopted from the pod launcher);
        # an attached server on another host is that host's to restart
        self._respawnable = (endpoint is None or spawn
                             or adopt_process is not None)
        self._generation = 0
        try:
            self._start(cfg, params, start_timeout, endpoint, spawn,
                        adopt_process, engine_kw)
        except BaseException:
            # never leak a spawned engine server: a failed rendezvous /
            # init handshake reaps the child before propagating
            if self.process is not None and self.process.is_alive():
                self.process.kill()
                self.process.join(timeout=10)
            raise

    def _start(self, cfg, params, start_timeout, endpoint, spawn,
               adopt_process, engine_kw):
        import jax
        import numpy as np

        if endpoint is None or spawn:
            refuse_spawn_on_accelerator("a spawned engine server")
        ctx = mp.get_context("spawn")     # never fork a live JAX runtime
        if endpoint is None:
            # dial-back rendezvous: parent listens, spawned child connects
            if rendezvous_transport() == "tcp":
                srv = TR.listen("tcp://127.0.0.1:0")
                address = TR.bound_endpoint(srv)
            else:
                address = TR.listener_address()
                srv = TR.listen(address)
            self.endpoint = address
            self.process = ctx.Process(target=engine_server_main,
                                       args=(address,), daemon=True)
            self.process.start()
            try:
                self.conn = TR.accept(srv, timeout=start_timeout)
            finally:
                srv.close()
                if TR.parse_endpoint(address)[0] == "unix":
                    try:
                        os.unlink(address)
                    except OSError:
                        pass
        else:
            # listening server at a known endpoint: spawn it locally
            # (pod inventory node on this host), adopt one the pod
            # launcher already spawned (so liveness/kill still see the
            # child), or attach to a server running on another host;
            # either way the proxy dials in, retrying while the server
            # boots toward its bind
            if spawn:
                self.process = ctx.Process(target=engine_server_listen,
                                           args=(endpoint,), daemon=True)
                self.process.start()
            elif adopt_process is not None:
                self.process = adopt_process

            def child_died() -> Optional[str]:
                # a spawned server that died before binding (EADDRINUSE
                # on a colliding inventory port, import failure) would
                # otherwise look like "still booting" for the whole
                # connect deadline
                if self.process is not None and not self.process.is_alive():
                    return (f"engine server exited with code "
                            f"{self.process.exitcode} before accepting")
                return None

            self.conn = TR.connect(endpoint, timeout=start_timeout,
                                   abort=child_died)
        self.conn.peer_label = self.peer_label
        self.rpc = TR.Rpc(self.conn)
        host_params = jax.tree_util.tree_map(np.asarray, params)
        self.conn.send({"cfg": cfg, "params": host_params,
                        "engine_kw": dict(engine_kw,
                                          cache_kind="paged")})
        ready = self.conn.recv()          # init ack doubles as ready gate
        assert ready.get("result") == "ready", ready
        self._info = self._call("info")
        # estimate the server's span-clock offset from a few cheap round
        # trips while the connection is otherwise idle — a respawned
        # server gets a fresh proxy, hence a fresh estimate
        self.clock_offset = OBS.estimate_clock_offset(
            lambda: self._call("clock_sync"))

    # ------------------------------------------------------------- rpc
    def _call(self, op, *args, **kw):
        if self._dead:
            raise TR.TransportClosed(f"instance already dead ({op})")
        try:
            return self.rpc.call(op, *args, **kw)
        except TR.TransportClosed:
            self._dead = True
            raise

    # ------------------------------------------------------ serving ops
    # Queue/migration mutations refresh the cached gauge dict (the queue
    # ops piggyback the server's returned depth; migration ops re-pull
    # info — they are rare, the extra round trip is noise), so routing
    # and run-until-done loops never act on a stale zero.
    def submit(self, spec: RequestSpec, trace: Optional[dict] = None):
        # the mirror holds the minted-but-never-run Request: pristine by
        # construction, replayable token-identically after a crash
        self._inflight[spec.rid] = spec.to_request()
        self._info["queue_len"] = self._call("submit", spec, _trace=trace)

    def set_token_budget(self, budget: int) -> int:
        return int(self._call("set_token_budget", int(budget)))

    def step(self) -> List[Request]:
        return self.finish_step(self._call("step"))

    def step_async(self) -> TR.Pending:
        """Fan-out half of the batched control-plane poll: send the step
        request without waiting. The orchestrator drains the reply via
        ``transport.drain_pendings`` and hands it to ``finish_step``."""
        if self._dead:
            raise TR.TransportClosed("instance already dead (step)")
        try:
            return self.rpc.call_async("step")
        except TR.TransportClosed:
            self._dead = True
            raise

    def finish_step(self, reply: dict) -> List[Request]:
        """Apply one step reply: refresh the telemetry mirror and gauge
        cache, retire finished requests from the inflight mirror."""
        self.telemetry.load_state(reply["telemetry"])
        self._info = reply["info"]
        self._streams = {int(r): list(t) for r, t
                         in reply.get("streams", {}).items()}
        spans = reply.get("spans")
        if spans:
            self._span_feed.extend(
                OBS.correct_spans(spans, self.clock_offset))
        done = reply["finished"]
        for r in done:
            self._inflight.pop(r.rid, None)
        return done

    # ---------------------------------------------------------- tracing
    def register_trace(self, ctx: dict):
        self._call("trace_register", ctx)

    def drain_spans(self) -> List[dict]:
        if not self._span_feed:
            return []
        out, self._span_feed = self._span_feed, []
        return out

    def apply_plan(self, p):
        p = list(p.p) if hasattr(p, "p") else list(p)
        self._call("apply_plan", p)

    def requeue_front(self, req: Request):
        self._inflight[req.rid] = pristine(req)
        self._info["queue_len"] = self._call("requeue_front", req)

    def push_queue(self, req: Request):
        self._inflight[req.rid] = pristine(req)
        self._info["queue_len"] = self._call("push_queue", req)

    def drain_queue(self) -> List[Request]:
        out = self._call("drain_queue")
        for r in out:
            self._inflight.pop(r.rid, None)
        self._info["queue_len"] = 0
        return out

    # -------------------------------------------------------- telemetry
    def refresh_info(self):
        self._info = self._call("info")

    def queue_len(self) -> int:
        return self._info["queue_len"]

    def active_rids(self) -> Dict[int, int]:
        return {int(s): rid for s, rid in self._info["active"].items()}

    def free_blocks(self) -> int:
        return self._info["free_blocks"]

    def blocks_in_use(self) -> int:
        return self._info["blocks_in_use"]

    @property
    def n_blocks(self) -> int:
        return self._info["n_blocks"]

    @property
    def max_batch(self) -> int:
        return self._info["max_batch"]

    def pool_bytes(self) -> int:
        return self._info["pool_bytes"]

    def clock(self) -> float:
        return self._info["clock"]

    def preempt_count(self) -> int:
        return self._info["preempt_count"]

    def prefix_stats(self) -> dict:
        return self._info["prefix_stats"]

    @property
    def block_size(self) -> int:
        return self._info.get("block_size", 0)

    def prefix_keys(self) -> set:
        return set(self._info.get("prefix_keys", ()))

    def stream_view(self) -> Dict[int, List[int]]:
        return self._streams

    # -------------------------------------------------------- migration
    def _unwrap(self, reply: dict):
        """Migration replies piggyback the server's gauge dict (and any
        spans the op closed — skew-corrected into the feed like the
        step-reply ones)."""
        self._info = reply["info"]
        spans = reply.get("spans")
        if spans:
            self._span_feed.extend(
                OBS.correct_spans(spans, self.clock_offset))
        return reply["result"]

    def pause_request(self, slot: int,
                      since_epoch: Optional[int] = None) -> dict:
        payload = self._unwrap(self._call("pause_request", slot,
                                          since_epoch=since_epoch))
        self._inflight.pop(payload["request"].rid, None)
        return payload

    def resume_request(self, payload: dict) -> bool:
        ok = self._unwrap(self._call("resume_request", payload))
        if ok:
            self._inflight[payload["request"].rid] = \
                pristine(payload["request"])
        return ok

    def snapshot_request(self, slot: int) -> dict:
        return self._call("snapshot_request", slot)

    def prepare_resume_async(self, snap: dict) -> "_PendingStage":
        if self._dead:
            raise TR.TransportClosed("instance already dead "
                                     "(prepare_resume)")
        return _PendingStage(self, self.rpc.call_async("prepare_resume",
                                                       snap))

    def commit_resume(self, slot: int, payload: dict) -> bool:
        ok = self._unwrap(self._call("commit_resume", slot, payload))
        if ok:
            self._inflight[payload["request"].rid] = \
                pristine(payload["request"])
        return ok

    def abort_resume(self, slot: int):
        self._unwrap(self._call("abort_resume", slot))

    # --------------------------------------------------------- liveness
    def set_rpc_deadline(self, seconds: Optional[float]):
        """Stamp a per-call deadline on every future RPC (None
        disables). A missed deadline raises ``RpcTimeout`` / resolves
        to a ``hung`` poll entry instead of stalling the caller."""
        self.rpc.call_timeout = seconds

    def probe(self, timeout: float = 1.0) -> str:
        """Classify this peer after a missed deadline:

        * ``"dead"``  — process exited or transport closed;
        * ``"alive"`` — heartbeat answered within ``timeout``: the peer
          is merely slow, or the lost call's request frame was dropped
          (in-order serving means a heartbeat answered after a call was
          sent proves that call either already replied or never
          arrived);
        * ``"hung"``  — socket open, heartbeat unanswered: blackholed /
          half-open / livelocked — quarantine territory.
        """
        if self._dead:
            return "dead"
        if self.process is not None and not self.process.is_alive():
            self._dead = True
            return "dead"
        try:
            self.rpc.call_timed("heartbeat", timeout)
            return "alive"
        except TR.RpcTimeout:
            return "hung"
        except TR.TransportClosed:
            self._dead = True
            return "dead"

    def quarantine(self):
        """Take a hung peer out of the plane for good: close the
        transport (a merely-slow server's dispatch loop exits on the
        EOF) and hard-kill an owned process — a quarantined worker must
        never act again, so the idempotent replay of its inflight
        mirror cannot race a zombie's late writes. Safe on an
        already-dead peer (idempotent)."""
        self._dead = True
        self.conn.close()
        if self.process is not None and self.process.is_alive():
            self.process.kill()
            self.process.join(timeout=10)

    @property
    def respawnable(self) -> bool:
        return self._respawnable

    def respawn(self, start_timeout: Optional[float] = None
                ) -> "EngineProxy":
        """Bring up a FRESH engine server from this proxy's init spec —
        the supervised-restart half of the failure domain. Listening
        servers respawn at the same endpoint; dial-back children get a
        new rendezvous. The replacement starts empty (queue and KV are
        gone with the process — the orchestrator already replayed the
        inflight mirror elsewhere) and carries an incarnation-suffixed
        peer label (``w1`` -> ``w1~r1``) so a static FaultPlan never
        re-targets the replacement of a peer it already faulted."""
        if not self._respawnable:
            raise RuntimeError(
                f"instance at {self.endpoint!r} is attach-only: its "
                "server is not ours to restart")
        spec = self._spec
        base = (self.peer_label.split("~", 1)[0]
                if self.peer_label else None)
        label = f"{base}~r{self._generation + 1}" if base else None
        fresh = EngineProxy(
            spec["cfg"], spec["params"],
            start_timeout=(spec["start_timeout"] if start_timeout is None
                           else start_timeout),
            endpoint=self.endpoint if self._listen_mode else None,
            spawn=True, peer_label=label, **spec["engine_kw"])
        fresh._generation = self._generation + 1
        fresh.set_rpc_deadline(self.rpc.call_timeout)
        return fresh

    def alive(self) -> bool:
        if self._dead:
            return False
        # attached servers (no child to watch) are alive until the
        # transport says otherwise
        return self.process is None or self.process.is_alive()

    def mark_dead(self):
        """Record a transport death observed OUTSIDE ``_call`` — e.g. a
        ``closed`` entry from the orchestrator's batched poll."""
        self._dead = True

    def inflight_requests(self) -> List[Request]:
        return list(self._inflight.values())

    def kill(self):
        """Hard-kill the server (crash-recovery tests): SIGKILL for a
        spawned child, abrupt socket close for an attached one — either
        way the next RPC observes TransportClosed."""
        if self.process is not None:
            self.process.kill()
            self.process.join(timeout=10)
        else:
            self.conn.close()
            self._dead = True

    def inject_crash(self):
        """Ask the server to os._exit mid-protocol (fault injection)."""
        try:
            self.rpc.call_async("crash")    # no reply will ever come
        except TR.TransportClosed:
            pass
        if self.process is not None:
            self.process.join(timeout=10)

    def close(self):
        if not self._dead and (self.process is None
                               or self.process.is_alive()):
            try:
                self.rpc.call("shutdown")
            except TR.TransportError:
                pass
        self._dead = True
        if self.process is not None:
            self.process.join(timeout=10)
            if self.process.is_alive():   # pragma: no cover - stuck child
                self.process.terminate()
                self.process.join(timeout=5)
        self.rpc.close()
