"""Bring-up smoke test: serve TinyLlama-1.1B at its published widths on a
TPU chip, end to end through the HTTP front door.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: replication + cross-chip
                                      # drain, and what they are compared with

One process drives the chip (a chip belongs to one process at a time).
The phases, each of which raises on failure:

1. device      - JAX version, platform, device kind and count. Anything but
                 a TPU stops the run with a nonzero exit: no CPU fallback.
2. build       - the model and the pod through ``launch/serve.py``'s own
                 construction (``build_model`` + ``build_pod``): published
                 widths, weights from a fixed seed in the config's dtype
                 (bfloat16), a bfloat16 KV pool, two in-process paged
                 instances with the token-budget scheduler, prefix sharing
                 and the Pallas paged-decode kernel.
3. serve       - the HTTP ingress on an ephemeral port; eight completions
                 over real HTTP (unary and streamed, two sharing a prompt
                 prefix of three full blocks, one prompt longer than the
                 token budget so it runs as several prefill chunks).
                 Every request returns its tokens, /stats shows 0
                 dropped, the prefix cache hit, /metrics parses.
4. parity      - the engine's first-token logits for one prompt, and one
                 decode step's logits over three rows with the kernel and
                 with the gather, against a plain float32 dense forward on
                 the same weights: within three times the distance of a
                 control (the same dense forward in the storage dtype).
5. module ops  - ``Engine.apply_plan`` with degrees > 1 on one instance,
                 then ``Orchestrator.drain_instance`` while its streams
                 decode: 0 dropped, and the migrated streams token-identical
                 to the same requests served without the drain.

``--chips 4`` runs, instead of 3-5: (a) one instance at replication degree
4 over the four-chip mesh against degree 1 (token-identical, collectives in
the compiled step), and (b) two instances each on its own chip, drained
from one to the other (token-identical, 0 dropped).

Times are printed as information only. The last line of standard output
is ``{"ok": true, "device": {...}}``, printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import http.client
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import replication as R  # noqa: E402
from repro.launch import serve as SV  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.serving import engine as E  # noqa: E402
from repro.serving.ingress import Ingress  # noqa: E402
from repro.serving.observe import parse_prometheus  # noqa: E402
from repro.serving.request import RequestSpec, SamplingParams  # noqa: E402

ARCH = "tinyllama-1.1b"
MAX_TOKENS = 32


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def check(cond, what: str):
    if not cond:
        raise SmokeFailure(what)


def log(phase: str, msg: str):
    print(f"[{phase}] {msg}", flush=True)


def smoke_config(**over) -> SV.ServeConfig:
    """The serve.py configuration the smoke test drives: published widths,
    two in-process instances, the token-budget scheduler, the HTTP door."""
    kw = dict(arch=ARCH, full_width=True, instances=2, max_batch=8,
              max_len=512, token_budget=128, scheduler="budget", http=True,
              http_port=0)
    kw.update(over)
    return SV.ServeConfig(**kw).validate()


# ------------------------------------------------------------------ device
def phase_device() -> dict:
    d = jax.devices()[0]
    dev = {"platform": d.platform, "kind": d.device_kind,
           "count": len(jax.devices())}
    log("device", f"jax {jax.__version__} platform={dev['platform']} "
        f"kind={dev['kind']} count={dev['count']}")
    return dev


class CacheEvents:
    """Counts JAX's persistent-compilation-cache hits and misses."""

    def __init__(self):
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def _nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree))


def peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats()
    return str(stats["peak_bytes_in_use"]) if stats else "not reported"


# ------------------------------------------------------------------- build
def phase_build(args: SV.ServeConfig, cfg, params, dtype, *,
                interpret: bool = False):
    """The pod, built by serve.py's own ``build_pod``."""
    orch = SV.build_pod(args, cfg, params, dtype, paged_attn_impl="kernel",
                        interpret=interpret)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    pool = sum(h.pool_bytes() for h in orch.instances)
    eng = orch.engines[0]
    log("build", f"{cfg.name}: {cfg.num_layers} layers, d_model "
        f"{cfg.d_model}, {cfg.num_heads} q heads / {cfg.num_kv_heads} kv "
        f"heads, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}")
    log("build", f"{n_params} parameters ({n_params:.3e}), weights "
        f"{_nbytes(params)} bytes in {jnp.dtype(dtype).name}, KV pool "
        f"{pool} bytes in {eng.pstate.k.dtype} over {len(orch.instances)} "
        f"instances")
    log("build", f"decode attention: {eng.paged_attn_impl} "
        f"(interpret={interpret}); sliding window "
        f"{cfg.sliding_window} not applied: served without a window, as "
        f"the model is published")
    log("build", f"peak_bytes_in_use={peak_bytes()}")
    return orch


# ------------------------------------------------------------------- serve
def _http(port: int, method: str, path: str, body=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)
    try:
        payload = None if body is None else json.dumps(body)
        headers = {} if body is None else {"Content-Type":
                                           "application/json"}
        conn.request(method, path, body=payload, headers=headers)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def complete(port: int, body: dict) -> dict:
    """One completion over HTTP: ``{"id", "tokens"}`` from the unary JSON
    body or from the SSE events of a streamed one."""
    status, raw = _http(port, "POST", "/v1/completions", body)
    check(status == 200, f"completion answered {status}: {raw[:200]!r}")
    if not body.get("stream"):
        obj = json.loads(raw)
        return {"id": obj["id"], "tokens": obj["tokens"]}
    events = [ln[len(b"data: "):] for ln in raw.split(b"\n")
              if ln.startswith(b"data: ")]
    check(events and events[-1] == b"[DONE]",
          f"stream did not end with [DONE]: {raw[-200:]!r}")
    head = json.loads(events[0])
    toks = [json.loads(e) for e in events[1:-1]]
    check([t["index"] for t in toks] == list(range(len(toks))),
          "streamed token indices out of order")
    return {"id": head["id"], "tokens": [t["token"] for t in toks]}


def serve_bodies(cfg, block_size: int, token_budget: int,
                 max_tokens: int, seed: int = 7):
    """(first, rest): the first completion alone (it compiles), then seven
    sent together. ``first`` and ``rest[0]`` share a prompt prefix of
    three full blocks; ``rest[1]`` is longer than the token budget."""
    rng = np.random.default_rng(seed)

    def toks(n):
        return rng.integers(2, cfg.vocab_size, size=n).tolist()

    shared = toks(3 * block_size)
    first = {"prompt": shared + toks(block_size), "max_tokens": max_tokens}
    rest = [{"prompt": shared + toks(block_size // 2 + 3),
             "max_tokens": max_tokens, "stream": True},
            {"prompt": toks(2 * token_budget + 44),
             "max_tokens": max_tokens}]
    for n in (20, 33, 50, 70, 97):
        rest.append({"prompt": toks(n), "max_tokens": max_tokens,
                     "stream": n == 50})
    return first, rest


def phase_serve(orch, cfg, args: SV.ServeConfig, *,
                max_tokens: int = MAX_TOKENS) -> dict:
    bs = orch.instances[0].block_size
    first, rest = serve_bodies(cfg, bs, args.token_budget, max_tokens)
    ing = Ingress(orch, host="127.0.0.1", port=0, model_id=cfg.name,
                  govern_budget=args.govern_budget).start()
    try:
        t0 = time.perf_counter()
        outs = [complete(ing.port, first)]
        t_first = time.perf_counter() - t0
        with concurrent.futures.ThreadPoolExecutor(len(rest)) as ex:
            outs += list(ex.map(lambda b: complete(ing.port, b), rest))
        wall = time.perf_counter() - t0
        status, raw = _http(ing.port, "GET", "/stats")
        check(status == 200, f"/stats answered {status}")
        stats = json.loads(raw)
        status, raw = _http(ing.port, "GET", "/metrics")
        check(status == 200, f"/metrics answered {status}")
        families = parse_prometheus(raw.decode("utf-8"))
        traces = {rec["rid"]: rec for rec in ing.tracer.finished}
    finally:
        ing.close()
        # the ingress's budget governor resizes budgets while it serves;
        # the later phases run under the configured one
        for h in orch.instances:
            h.set_token_budget(args.token_budget)
    check(ing.pump_error is None, f"ingress pump died: {ing.pump_error!r}")
    bodies = [first] + rest
    for body, out in zip(bodies, outs):
        check(len(out["tokens"]) == body["max_tokens"],
              f"request {out['id']} returned {len(out['tokens'])} of "
              f"{body['max_tokens']} tokens")
    check(stats["dropped"] == 0, f"/stats dropped={stats['dropped']}")
    hit_rate = orch.stats()["prefix_hit_rate"]
    check(hit_rate > 0, "no prefix-cache hit for the shared prompt prefix")
    long_id = outs[2]["id"]
    chunks = sum(s["attrs"].get("chunks", 0)
                 for s in traces[long_id]["spans"] if s["name"] == "prefill")
    check(chunks >= 2, f"the {len(bodies[2]['prompt'])}-token prompt ran "
          f"as {chunks} prefill chunk(s) under a budget of "
          f"{args.token_budget}")
    log("serve", f"{len(outs)} completions over HTTP "
        f"({sum(bool(b.get('stream')) for b in bodies)} streamed), "
        f"{sum(len(o['tokens']) for o in outs)} tokens, dropped="
        f"{stats['dropped']}, prefix_hit_rate={hit_rate:.3f}, long prompt "
        f"({len(bodies[2]['prompt'])} tokens) in {chunks} prefill chunks, "
        f"/metrics: {len(families)} families parsed")
    log("serve", f"first request (compiles) {t_first:.3f} s; all "
        f"{len(outs)} requests {wall:.3f} s wall")
    return {"first_s": t_first, "wall_s": wall}


# ------------------------------------------------------------------ parity
# The engine stores weights and KV in bfloat16, so it cannot match a
# float32 reference exactly. The control is the plain dense forward in the
# same storage dtype on the same weights and prompt: its distance from the
# float32 reference is the rounding error that dtype costs at this depth
# on this chip. The engine's logits may be at most PARITY_FACTOR times as
# far; dropping a layer or a head moves them by far more.
PARITY_FACTOR = 3.0


def rel_l2(got, ref) -> float:
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def engine_first_token_logits(eng, prompt, rid: int) -> np.ndarray:
    """Serve one prompt on ``eng`` and return the logits its first token
    is sampled from, taken at the engine's own sampling call."""
    seen = []
    sample = eng._sample_batch

    def spy(logits, reqs):
        rids = [r.rid for r in reqs]
        if rid in rids:
            seen.append(np.asarray(logits[rids.index(rid)], np.float32))
        return sample(logits, reqs)

    eng._sample_batch = spy
    try:
        eng.submit(RequestSpec(rid=rid, prompt=np.asarray(prompt, np.int32),
                               max_tokens=1))
        eng.run_until_done()
    finally:
        del eng._sample_batch
    check(len(seen) == 1, f"expected one first-token sample, saw {len(seen)}")
    return seen[0]


def _dense_logits(params, cfg, seqs) -> np.ndarray:
    """Plain ``models/transformer.forward`` prefill logits after the last
    token of each sequence in ``seqs`` (right-padded; causal attention
    keeps the padding out of every earlier position)."""
    n = max(len(q) for q in seqs)
    toks = np.zeros((len(seqs), n), np.int32)
    for r, q in enumerate(seqs):
        toks[r, :len(q)] = q
    last = np.asarray([len(q) - 1 for q in seqs], np.int32)
    out = jax.jit(lambda p, t, li: T.forward(p, cfg, t, mode="prefill",
                                             last_idx=li)[0])(params, toks,
                                                              last)
    return np.asarray(out, np.float32)[:, :cfg.vocab_size]


def _reference_logits(params, cfg, seqs) -> np.ndarray:
    """``_dense_logits`` in float32 throughout: weights cast up, and every
    matmul at full float32 precision (a TPU's default rounds float32 matmul
    inputs to bfloat16)."""
    p32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        return _dense_logits(p32, cfg, seqs)


def _decode_rows(eng, cfg, lens, *, seed: int, min_tokens: int = 3):
    """Greedy requests of prompt lengths ``lens`` submitted to ``eng`` and
    stepped until each has decoded ``min_tokens`` tokens. Returns, in slot
    order, what the next decode step attends over: prompt + generated."""
    rng = np.random.default_rng(seed)
    for n, length in enumerate(lens):
        eng.submit(RequestSpec(
            rid=910_000 + n, prompt=rng.integers(
                2, cfg.vocab_size, size=length).astype(np.int32),
            max_tokens=4 * min_tokens))
    for _ in range(100):
        reqs = list(eng.active.values())
        if len(reqs) == len(lens) and all(len(r.generated) >= min_tokens
                                          for r in reqs):
            break
        eng.step()
    check(len(eng.active) == len(lens), f"{len(eng.active)} of {len(lens)} "
          f"parity requests decoding")
    return [np.concatenate([eng.active[s].prompt, eng.active[s].generated])
            for s in sorted(eng.active)]


def _reference_and_control(params, cfg, seqs):
    """The float32 reference logits for ``seqs``, and the control's
    distance from them: the parity tolerance is PARITY_FACTOR times it."""
    ref = _reference_logits(params, cfg, seqs)
    return ref, rel_l2(_dense_logits(params, cfg, seqs), ref)


def phase_parity(orch, cfg, params, dtype, *, prompt_len: int,
                 seed: int = 11) -> dict:
    """The engine's first-token logits (the prefill path) and one decode
    step's logits (the kernel, and the gather it replaces) against a
    float32 dense forward on the same weights, each within PARITY_FACTOR
    times the error of the control on the same sequences."""
    rng = np.random.default_rng(seed)
    prompt = rng.integers(2, cfg.vocab_size, size=prompt_len)
    eng = orch.engines[0]
    got = engine_first_token_logits(eng, prompt, rid=900_001)[:cfg.vocab_size]
    check(np.isfinite(got).all(), "engine logits are not finite")
    ref, control = _reference_and_control(params, cfg, [prompt])
    ref = ref[0]
    tol = PARITY_FACTOR * control
    rel = rel_l2(got, ref)
    log("parity", f"{prompt_len}-token prompt, first-token logits vs "
        f"float32 dense prefill: rel L2 error {rel:.3e}, max |diff| "
        f"{float(np.abs(got - ref).max()):.3e}, argmax engine="
        f"{int(got.argmax())} reference={int(ref.argmax())}; control "
        f"(dense prefill in {jnp.dtype(dtype).name}) {control:.3e}, "
        f"tolerance {tol:.3e} = {PARITY_FACTOR:g} x control")
    check(rel <= tol, f"first-token logits off by {rel:.3e} > {tol:.3e}")

    seqs = _decode_rows(eng, cfg, (prompt_len // 4, prompt_len // 2 + 5,
                                   prompt_len + 17), seed=seed + 1)
    ref, dcontrol = _reference_and_control(params, cfg, seqs)
    dtol = PARITY_FACTOR * dcontrol
    impls = {impl: step_logits(eng, cfg, None, impl=impl)
             for impl in ("kernel", "gather")}
    eng.run_until_done()
    errs = {impl: rel_l2(v, ref) for impl, v in impls.items()}
    between = rel_l2(impls["kernel"], impls["gather"])
    log("parity", f"one decode step over {len(seqs)} rows (contexts "
        f"{[len(q) - 1 for q in seqs]} tokens) vs float32 dense forward: "
        f"rel L2 error kernel {errs['kernel']:.3e}, gather "
        f"{errs['gather']:.3e}; kernel vs gather {between:.3e}; control "
        f"{dcontrol:.3e}, tolerance {dtol:.3e} = {PARITY_FACTOR:g} x "
        f"control, each")
    for impl, err in errs.items():
        check(err <= dtol, f"{impl} decode-step logits off by {err:.3e} > "
              f"{dtol:.3e}")
    check(between <= dtol, f"kernel and gather decode steps differ by "
          f"{between:.3e} > {dtol:.3e}")
    return {"rel_err": rel, "tol": tol, "control": control,
            "decode_err": errs, "decode_tol": dtol,
            "kernel_vs_gather": between}


# -------------------------------------------------------------- module ops
def _specs(cfg, base: int, n: int, max_tokens: int, seed: int):
    """``n`` sampled requests whose prompts and seeds depend only on
    ``seed``: the same requests under any rid ``base``."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        prompt = rng.integers(2, cfg.vocab_size, size=24 + 13 * i)
        out.append(RequestSpec(
            rid=base + i, prompt=prompt.astype(np.int32),
            max_tokens=max_tokens,
            sampling=SamplingParams(temperature=0.8, top_k=40,
                                    seed=1000 + i)))
    return out


def _other(orch, idx: int) -> int:
    """An instance other than ``idx``. A reference run goes there: on
    ``idx`` it would leave the prompts in that pool's prefix cache, and
    the compared run would then prefill them down the cache-hit path, a
    different program from the reference's."""
    return next(i for i in range(len(orch.instances)) if i != idx)


def _serve_on(orch, idx: int, specs) -> dict:
    for s in specs:
        orch.submit_to(idx, s)
    rids = {s.rid for s in specs}
    fin = orch.run_until_done()
    base = min(rids)
    return {r.rid - base: list(r.generated) for r in fin if r.rid in rids}


def _decode_until_active(orch, idx: int, rids, min_tokens: int = 2):
    """Step until every request in ``rids`` decodes on instance ``idx``."""
    for _ in range(200):
        view = orch.stream_view()
        if (set(orch.instances[idx].active_rids().values()) >= rids
                and all(len(view.get(r, ())) >= min_tokens for r in rids)):
            return
        orch.step()
    view = orch.stream_view()
    raise SmokeFailure(
        f"requests {sorted(rids)} never all decoded on instance {idx}: "
        f"active {sorted(orch.instances[idx].active_rids().values())}, "
        f"tokens {[len(view.get(r, ())) for r in sorted(rids)]}, finished "
        f"{sorted(r.rid for r in orch.finished if r.rid in rids)}")


def phase_module_ops(orch, cfg, *, src: int = 1, degree: int = 2,
                     n: int = 4, max_tokens: int = MAX_TOKENS) -> dict:
    """apply_plan (degrees ``degree`` > 1; 1 skips it) on instance
    ``src``, then drain it while its streams decode; compare against the
    same requests served undrained."""
    # the controller is held off: the plan and the drain under test are
    # the ones this phase applies
    every, orch.telemetry_every = orch.telemetry_every, 10 ** 9
    try:
        ref = _serve_on(orch, _other(orch, src),
                        _specs(cfg, 700_000, n, max_tokens, seed=5))
        if degree > 1:
            orch.instances[src].apply_plan([degree] * cfg.num_layers)
        specs = _specs(cfg, 800_000, n, max_tokens, seed=5)
        for s in specs:
            orch.submit_to(src, s)
        rids = {s.rid for s in specs}
        _decode_until_active(orch, src, rids)
        step_degrees = orch.engines[src]._step_degrees
        recs = orch.drain_instance(src)
        orch.run_until_done()
        done = {r.rid - 800_000: list(r.generated)
                for r in orch.finished if r.rid in rids}
        if degree > 1:
            orch.instances[src].apply_plan([1] * cfg.num_layers)
    finally:
        orch.telemetry_every = every
    check(len(done) == n, f"{len(done)} of {n} drained requests finished")
    moved = [r for r in recs if r.resumed]
    check(moved, "the drain migrated no stream")
    check(orch.stats()["dropped"] == 0, "streams dropped")
    check(done == ref, "drained streams differ from the undrained run")
    plan = ("no plan" if step_degrees is None else
            f"apply_plan degree {degree}: step runs unrolled at degree "
            f"{step_degrees[0]} x {len(step_degrees)} layers")
    hops = ", ".join(f"{r.mode} {r.n_blocks} blocks stall "
                     f"{r.stall_s * 1e3:.1f} ms" for r in moved)
    log("module ops", f"instance {src} ({plan}) drained while decoding: "
        f"{len(moved)} streams migrated ({hops}), dropped=0, "
        f"token-identical to the undrained run "
        f"({sum(len(v) for v in done.values())} tokens)")
    return {"migrated": len(moved)}


# --------------------------------------------------------------- four chips
def _devices_of(tree) -> set:
    return {d for x in jax.tree_util.tree_leaves(tree) for d in x.devices()}


def check_placement(orch):
    """Every instance's weights and pool sit on its own device only."""
    homes = []
    for i, eng in enumerate(orch.engines):
        devs = _devices_of((eng.params, eng.pstate.k, eng.pstate.v))
        check(devs == {eng.device}, f"instance {i} spans {devs}, not "
              f"{eng.device}")
        homes.append(eng.device)
    check(len(set(homes)) == len(homes), f"instances share devices: {homes}")
    log("placement", ", ".join(f"instance {i} on {d}"
                               for i, d in enumerate(homes)))


def step_logits(eng, cfg, hook, *, impl=None) -> np.ndarray:
    """One decode step's logits for ``eng``'s decoding rows (in slot
    order), computed from its pool as it stands (nothing is written back),
    with decode attention ``impl`` (default: the engine's) and the layers
    under ``hook`` (None: the plain scanned step, on one device: a
    Pallas kernel outside a hook's batch shards cannot be partitioned over
    replicated weights, so they are copied home first)."""
    # the step writes each row's new K/V at column lengths // block_size:
    # reserve that block first, as the engine does before its own step
    # (a row at a block boundary would otherwise attend without itself)
    eng._ensure_decode_room()
    st = eng.pstate
    active = np.zeros((eng.max_batch,), bool)
    tokens = np.zeros((eng.max_batch,), np.int32)
    for slot, req in eng.active.items():
        active[slot], tokens[slot] = True, req.generated[-1]
    state = (eng.params, st.k, st.v)
    if hook is None:
        state = jax.device_put(state, eng.device or jax.devices()[0])

    def fn(params, k, v, tables, lengths, act, toks):
        handle = {"k": k, "v": v, "block_tables": tables,
                  "lengths": lengths, "active": act}
        return T.forward_paged(params, cfg, toks[:, None], handle,
                               attn_impl=impl or eng.paged_attn_impl,
                               interpret=eng._interpret, layer_hook=hook)[0]

    out = jax.jit(fn, compiler_options=E.STEP_COMPILER_OPTIONS)(
        *state, st.block_tables, st.lengths.astype(np.int32), active, tokens)
    return np.asarray(out, np.float32)[active][:, :cfg.vocab_size]


def compiled_step_text(eng) -> str:
    """Compiled HLO text of the greedy paged decode step ``eng``'s next
    step runs, under its current plan and placement: where a replication
    plan's scatter/gather collectives are counted."""
    B = eng.max_batch
    active = np.zeros((B,), bool)
    active[list(eng.active)] = True
    args, kw = eng._paged_step_args(np.zeros((B,), np.int32), active,
                                    *eng._sampling_arrays(), False, 0)
    return E._jitted_steps()[1].lower(*args, **kw).compile().as_text()


def _first_divergence(a: dict, b: dict) -> dict:
    """rid -> index of the first differing token (absent: identical)."""
    return {i: next((j for j, (x, y) in enumerate(zip(a[i], b[i]))
                     if x != y), min(len(a[i]), len(b[i])))
            for i in a if a[i] != b.get(i)}


def phase_replication(orch, cfg, *, idx: int = 0, degree: int = 4,
                      n: int = 4, max_tokens: int = MAX_TOKENS) -> dict:
    """One instance at replication degree ``degree`` over the local
    replication mesh, against the same requests at degree 1: the step's
    collectives, one step's logits from the same state, and the token
    streams."""
    every, orch.telemetry_every = orch.telemetry_every, 10 ** 9
    eng = orch.engines[idx]
    try:
        ref = _serve_on(orch, _other(orch, idx),
                        _specs(cfg, 500_000, n, max_tokens, seed=6))
        orch.instances[idx].apply_plan([degree] * cfg.num_layers)
        step_degrees = eng._step_degrees
        mesh = R.default_replication_mesh()
        spread = _devices_of(eng.params)
        specs = _specs(cfg, 600_000, n, max_tokens, seed=6)
        for sp in specs:
            orch.submit_to(idx, sp)
        _decode_until_active(orch, idx, {sp.rid for sp in specs})
        text = compiled_step_text(eng)
        one = step_logits(eng, cfg, None)
        rep = step_logits(eng, cfg, R.LayerHook(step_degrees, mesh))
        orch.run_until_done()
        got = {r.rid - 600_000: list(r.generated) for r in orch.finished
               if 600_000 <= r.rid < 600_000 + n}
        orch.instances[idx].apply_plan([1] * cfg.num_layers)
    finally:
        orch.telemetry_every = every
    coll = R.count_collectives(text)
    rel = rel_l2(rep, one)
    # both steps round every op exactly in the storage dtype: they may
    # differ by at most one rounding of it (u), and on the chip they agree
    tol = float(jnp.finfo(eng.pstate.k.dtype).eps) / 2
    diverge = _first_divergence(ref, got)
    log("replication", f"instance {idx} at degree {step_degrees[0]} over "
        f"{mesh.devices.size} devices: weights on {len(spread)} devices, "
        f"compiled step collectives {coll}; one step's logits vs the "
        f"unreplicated step: rel L2 {rel:.3e} (tolerance u = {tol:.3e}), max "
        f"|diff| {float(np.abs(rep - one).max()):.3e}; {n - len(diverge)} "
        f"of {n} sampled streams token-identical to degree 1 "
        f"(first divergence by stream: {diverge})")
    check(step_degrees == (min(degree, mesh.devices.size),)
          * cfg.num_layers, f"step degrees {step_degrees}")
    check(len(spread) == mesh.devices.size, f"replicated weights on "
          f"{len(spread)} of {mesh.devices.size} devices")
    check(coll.get("all-gather", 0) > 0,
          f"no all-gather in the replicated step: {coll}")
    check(rel <= tol, f"replicated step logits off by {rel:.3e} > {tol:.3e}")
    check(not diverge, f"degree {degree} streams differ from degree 1")
    return {"collectives": coll, "rel_err": rel}


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the replication and cross-chip drain "
                         "phases, on a four-chip host")
    opts = ap.parse_args(argv)

    dev = phase_device()
    if dev["platform"] != "tpu":
        print(f"[device] no TPU: JAX runs on {dev['platform']}; this smoke "
              f"test runs only on the chip", file=sys.stderr)
        return 2
    if dev["count"] < opts.chips:
        print(f"[device] --chips {opts.chips} needs {opts.chips} chips, "
              f"found {dev['count']}", file=sys.stderr)
        return 2
    cache_dir = SV.enable_compile_cache()
    cache = CacheEvents()
    t0 = time.perf_counter()

    args = smoke_config()
    cfg, params, dtype = SV.build_model(args)
    orch = phase_build(args, cfg, params, dtype)
    try:
        if opts.chips == 4:
            check_placement(orch)
            phase_replication(orch, cfg)
            check_placement(orch)
            phase_module_ops(orch, cfg, src=1, degree=1)
        else:
            phase_serve(orch, cfg, args)
            phase_parity(orch, cfg, params, dtype,
                         prompt_len=2 * args.token_budget + 44)
            phase_module_ops(orch, cfg)
    finally:
        orch.close()
    log("done", f"{time.perf_counter() - t0:.1f} s after the device "
        f"check; peak_bytes_in_use={peak_bytes()}; compile cache "
        f"{cache_dir}: {cache.hits} hits, {cache.misses} misses "
        f"({'hit' if cache.hits else 'cold'})")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
