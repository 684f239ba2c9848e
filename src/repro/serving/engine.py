"""Continuous-batching inference engine (real JAX execution).

Iteration-level scheduling in the Orca/vLLM style, with PAGED KV as the
primary decode path (``cache_kind="paged"``) and TOKEN-BUDGET continuous
batching as the default step loop (DESIGN.md §10):

* **Scheduling** is one budget-packed loop, not phases: every step,
  ``serving.scheduler.TokenBudgetScheduler`` charges each active decode
  slot one token, continues in-flight prefills oldest-first, and admits
  fresh prompts with whatever budget is left — at most one partial,
  block-aligned chunk per step. ``Request.prefill_pos`` is a first-class
  cursor (always equal to ``pstate.lengths[slot]`` mid-prefill), so
  preemption, migration and sliding-window reclamation compose with
  chunking. ``scheduler="phase"`` pins the legacy prefill-wave/decode-
  step alternation (identity baseline; forced for dense caches).
* **Prefill** of a paged engine has ONE path, the fused
  ``_chunk_prefill_fn`` (pool context gather → splice into a throwaway
  dense cache → decode-mode extend over a POWER-OF-TWO suffix bucket,
  with a per-row last-token gather picking each prompt's real logits →
  suffix K/V scatter into the donated block pool). A whole prompt under
  the phase scheduler, a budget-sliced chunk under the default one (a
  chunk continuation IS a suffix prefill against the written span), a
  prefix-cache hit (its suffix against the aliased blocks) and a miss (a
  suffix with no context) all run it, one bucketed call per group
  (``_prefill_shared_batch``). PREFIX SHARING (on by default,
  ``prefix_sharing=``): an admission whose prompt opens with an
  already-cached full-block prefix ALIASES those blocks (refcounted,
  copy-on-write — paged_kv's prefix cache) and prefills only its
  private suffix, so a shared system prompt is stored and prefilled
  once per pool, not once per request. Block
  allocation/eviction is driven by the host-side free list — admission
  applies backpressure (requests wait in the queue) when the pool is out
  of blocks, and decode-time pressure preempts the youngest request back
  onto the queue (its re-admission replays deterministically thanks to
  counter-based sampling keys; shared blocks merely decref). Sliding-
  window archs run paged too: blocks that fall fully out of the window
  return to the pool (``paged_kv.free_out_of_window``) — prefix matching
  is gated off under a window, whose reclamation invalidates full-prefix
  residency.
* **Decode** is ONE fused jitted call per engine step: single-token
  forward against the block pool (``models.transformer.forward_paged``)
  plus batched on-device sampling (``serving.sampling``). The only
  device→host transfer per step is fetching the sampled token ids —
  host-side cached lengths/tables make everything else host-resident, so
  a step performs exactly one host sync (asserted in tests via
  ``serving.instrument.count_host_syncs``). The block-table width fed to
  the step is bucketed to powers of two, so decode compute and HBM
  traffic scale with the *actual* longest context, not ``max_len``.

The engine is also the unit CoCoServe's live module scaling operates on:
``apply_plan`` puts the plan's per-layer replication degrees on the fused
step (static jit arg -> unrolled ``forward_paged`` with batch-sharding
hooks), and ``pause_request``/``resume_request`` export/import one
request's KV blocks + position + sampling counters so an orchestrator
(serving/orchestrator.py) can migrate it mid-stream, token-identically.
The two-phase OVERLAPPED variant splits that into ``snapshot_request``
(bulk export, stream keeps decoding) -> destination ``prepare_resume``
(staged import into an admission-excluded slot) -> ``pause_request(...,
since_epoch)`` (dirty-set delta only) -> ``commit_resume`` — the stream
leaves decode rotation just for the delta copy (DESIGN.md §7).

The legacy dense path (``cache_kind="dense"``, a ``[B, max_len]`` cache)
remains for MLA/SSM/hybrid/audio families and as the parity oracle; it
shares the same fused decode+sample step shape.
Inactive slots decode garbage that is masked out — the standard
static-batch trick that keeps the jitted step shape-stable.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Callable, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs.base import ModelConfig
from repro.models import transformer as T
from repro.models.layers import paged_kernel_serves
from repro.serving import instrument as INS
from repro.serving import kvcache as KV
from repro.serving import paged_kv as PK
from repro.serving import sampling as SMP
from repro.serving import scheduler as SCH
from repro.serving.request import MIGRATION_WIRE_VERSION, RequestSpec


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray              # [S] int32
    max_new_tokens: int = 32
    eos_id: Optional[int] = None
    temperature: float = 0.0        # 0 => greedy
    top_k: int = 0                  # 0 => full distribution
    seed: int = 0
    # SLO contract (request.RequestSpec is the construction API; these
    # ride the Request so they survive preemption, crash replay and
    # cross-instance migration exactly like the sampling state does)
    slo_class: str = "standard"
    deadline_ms: Optional[float] = None
    # filled by the engine
    generated: list = dataclasses.field(default_factory=list)
    slot: Optional[int] = None
    submit_time: float = 0.0
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    preemptions: int = 0
    # chunked-prefill cursor: how many prefill tokens are already written
    # into this request's KV (continuous batching slices long prompts
    # across steps; the cursor is FIRST-CLASS state so a mid-prefill
    # request can be preempted or even MIGRATED without replaying the
    # chunks that already landed — it travels the wire with the Request)
    prefill_pos: int = 0
    prefill_start_time: Optional[float] = None   # first chunk admitted

    @property
    def done(self) -> bool:
        return self.finish_time is not None


def _pow2_at_least(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


@dataclasses.dataclass(eq=False)  # identity equality: Request holds arrays
class _ChunkSpec:
    """One prefill chunk ready for execution: ``n`` tokens starting at
    ``req.prefill_pos`` in ``slot``. ``fresh`` marks a first chunk whose
    admission must be rolled back (slot freed, request requeued) if the
    chunk's block allocation fails — a continuation just retries."""
    req: "Request"
    slot: int
    n: int
    fresh: bool = False


# --------------------------------------------------------------- jitted steps
# Module-level with a STATIC (hashable, frozen) ModelConfig so the XLA
# compile cache is shared across Engine instances — restarting an engine,
# or running dense and paged engines side by side (benchmarks, parity
# tests), never recompiles an already-seen step shape.

# The paged decode step rounds each bf16 op exactly as written. By default
# XLA may keep fused bf16 intermediates in f32, so results depend on how a
# program happens to fuse: on a TPU v5e the unrolled per-layer step of a
# replication plan then differed from the scanned step by ~1e-2 relative
# in the logits, enough to change sampled tokens mid-stream. With exact
# rounding the scanned, unrolled and batch-sharded steps give identical
# logits, so apply_plan keeps streams token-identical. The step is the one
# program a plan recompiles; the others keep XLA's default.
STEP_COMPILER_OPTIONS = {"xla_allow_excess_precision": False}


@functools.partial(jax.jit, static_argnames=("cfg", "window"))
def _prefill_fn(params, tokens, cache, enc, *, cfg, window):
    # the dense engine's whole-prompt prefill
    return T.forward(params, cfg, tokens, mode="prefill", cache=cache,
                     window=window, encoder_input=enc)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "window", "cache_len", "dtype"),
                   donate_argnums=(1, 2))
def _chunk_prefill_fn(params, pool_k, pool_v, tbl, suffix, spos, pos,
                      last_idx, bidx, oidx, *, cfg, window, cache_len,
                      dtype):
    """FUSED chunk/suffix prefill: pool context gather -> throwaway dense
    cache splice -> decode-mode extension over the suffix bucket ->
    suffix K/V scatter back into the (donated) pool — one executable per
    power-of-two (group, context, suffix) bucket instead of ~15 eager
    dispatches and four whole-buffer copies. This is what makes a chunk
    step cost like a decode step on the host side, which is the whole
    point of slicing prefills under the token budget (DESIGN.md §10).
    Every paged prefill runs here: a whole prompt that missed the prefix
    cache is a suffix with no context (one gathered block, all masked)."""
    L, _, KV, bs, hd = pool_k.shape
    G, n_blk = tbl.shape
    # the gathered blocks may outrun a short miss's cache; the context
    # never does (it ends within the cache_len - Sb context bucket)
    cb = min(n_blk * bs, cache_len)
    ctx_k = pool_k[:, tbl].transpose(0, 1, 2, 4, 3, 5).reshape(
        L, G, n_blk * bs, KV, hd)[:, :, :cb]
    ctx_v = pool_v[:, tbl].transpose(0, 1, 2, 4, 3, 5).reshape(
        L, G, n_blk * bs, KV, hd)[:, :, :cb]
    cache = T.init_cache(cfg, G, cache_len, dtype)
    kd = cache["layers"]["k"].dtype
    cache["layers"]["k"] = cache["layers"]["k"].at[:, :, :cb].set(
        ctx_k.astype(kd))
    cache["layers"]["v"] = cache["layers"]["v"].at[:, :, :cb].set(
        ctx_v.astype(kd))
    cache["positions"] = pos
    logits, cache, _ = T.forward(params, cfg, suffix, positions=spos,
                                 mode="decode", cache=cache, window=window,
                                 last_idx=last_idx)
    idx = spos[None, :, :, None, None]
    k_sfx = jnp.take_along_axis(cache["layers"]["k"], idx, axis=2)
    v_sfx = jnp.take_along_axis(cache["layers"]["v"], idx, axis=2)
    Sb = suffix.shape[1]
    kf = k_sfx.reshape(L, G * Sb, KV, hd).transpose(1, 0, 2, 3)
    vf = v_sfx.reshape(L, G * Sb, KV, hd).transpose(1, 0, 2, 3)
    pool_k = pool_k.at[:, bidx, :, oidx].set(kf.astype(pool_k.dtype),
                                             mode="drop")
    pool_v = pool_v.at[:, bidx, :, oidx].set(vf.astype(pool_v.dtype),
                                             mode="drop")
    return logits, pool_k, pool_v


def _dense_step_impl(params, cache, tokens, positions, temps, topks, seeds,
                     counters, *, cfg, window, stochastic, max_top_k):
    logits, nc, _ = T.forward(params, cfg, tokens, positions=positions,
                              mode="decode", cache=cache, window=window)
    toks = SMP.sample_tokens(logits, temps, topks, seeds, counters,
                             cfg.vocab_size, stochastic=stochastic,
                             max_top_k=max_top_k)
    return toks, nc


def _paged_step_impl(params, k, v, tables, lengths, active, tokens, temps,
                     topks, seeds, counters, *, cfg, window, impl, interp,
                     stochastic, max_top_k, degrees=None):
    handle = {"k": k, "v": v, "block_tables": tables,
              "lengths": lengths, "active": active}
    hook = None
    if degrees is not None:
        # live module replication: the (hashable, static) per-layer degree
        # tuple unrolls the stack with one batch-sharding constraint per
        # layer — a plan change recompiles exactly this step, nothing else
        from repro.core import replication as R
        hook = R.LayerHook(degrees, R.default_replication_mesh())
    logits, nc, _ = T.forward_paged(params, cfg, tokens[:, None], handle,
                                    window=window, attn_impl=impl,
                                    interpret=interp, layer_hook=hook)
    toks = SMP.sample_tokens(logits, temps, topks, seeds, counters,
                             cfg.vocab_size, stochastic=stochastic,
                             max_top_k=max_top_k)
    return toks, nc["k"], nc["v"]


@functools.lru_cache(maxsize=1)
def _jitted_steps():
    """Buffer donation (in-place KV update) needs the backend, and probing
    it at import time would freeze JAX's platform before callers like
    launch/dryrun.py set their XLA flags — so the donating jits are built
    lazily at first step."""
    can_donate = jax.default_backend() != "cpu"
    dense = jax.jit(_dense_step_impl,
                    static_argnames=("cfg", "window", "stochastic",
                                     "max_top_k"),
                    donate_argnums=(1,) if can_donate else ())
    paged = jax.jit(_paged_step_impl,
                    static_argnames=("cfg", "window", "impl", "interp",
                                     "stochastic", "max_top_k", "degrees"),
                    donate_argnums=(1, 2) if can_donate else (),
                    compiler_options=STEP_COMPILER_OPTIONS)
    return dense, paged


@functools.partial(jax.jit, static_argnames=("vocab_size", "stochastic",
                                             "max_top_k"))
def _sample_fn(logits, temps, topks, seeds, counters, *, vocab_size,
               stochastic, max_top_k):
    return SMP.sample_tokens(logits, temps, topks, seeds, counters,
                             vocab_size, stochastic=stochastic,
                             max_top_k=max_top_k)


class Engine:
    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 4,
                 max_len: int = 128, dtype="float32", swa: bool = False,
                 encoder_input_fn: Optional[Callable] = None,
                 cache_kind: str = "dense", block_size: int = 16,
                 n_blocks: Optional[int] = None,
                 paged_attn_impl: str = "auto", interpret: bool = False,
                 prefix_sharing: Optional[bool] = None,
                 scheduler: Optional[str] = None, token_budget: int = 128,
                 device: Optional[jax.Device] = None):
        assert cache_kind in ("dense", "paged"), cache_kind
        self.cfg = cfg
        # ``device`` pins this engine's weights and KV pool to one device
        # (one instance per chip on a multi-chip host); None leaves them
        # where JAX puts them by default
        self.device = device
        self.params = (params if device is None
                       else jax.device_put(params, device))
        self.max_batch = max_batch
        # dense SWA ring-buffers down to the window; the PAGED path keeps
        # the logical length (block-table columns are absolute positions)
        # and instead FREES leading blocks as they leave the window
        self.max_len = (max_len if cache_kind == "paged"
                        else KV.cache_capacity(cfg, max_len, swa=swa))
        self.logical_max = max_len
        self.window = cfg.sliding_window if swa else None
        self.dtype = dtype
        self.encoder_input_fn = encoder_input_fn
        self.cache_kind = cache_kind
        self.active: Dict[int, Request] = {}   # slot -> request
        # slots whose prompt is only PARTIALLY written (chunked prefill
        # under the token-budget scheduler): they hold blocks and an
        # admission-order position but do not decode yet
        self.prefilling: Dict[int, Request] = {}
        self._prefill_matched: Dict[int, list] = {}  # slot -> prefix hit
        # slots holding a phase-1 migration import awaiting its delta
        # (commit_resume / abort_resume); excluded from admission
        self._staged: Dict[int, int] = {}      # slot -> rid
        self.queue: Deque[Request] = collections.deque()
        self.clock = 0.0
        self._step_count = 0
        self.preempt_count = 0   # pool-pressure evictions (live OOM signal)
        # host mirror of per-slot cache lengths for the DENSE path (the
        # paged path's canonical host lengths live in pstate.lengths) —
        # this is what lets a decode step avoid reading device state.
        self._host_lengths = np.zeros((max_batch,), np.int64)
        self._admit_order: List[int] = []      # slots, oldest first
        self._admit_finished: List[Request] = []  # done at admission

        # prompt-prefix sharing (paged_kv prefix cache + copy-on-write):
        # ON by default for the paged path; matching/registration are
        # additionally gated off per-admission under a sliding window
        # (whose block reclamation invalidates full-prefix residency)
        self.prefix_sharing = ((cache_kind == "paged")
                               if prefix_sharing is None
                               else bool(prefix_sharing))

        if cache_kind == "paged":
            if not cfg.supports_paged_kv:
                raise ValueError(
                    f"cache_kind='paged' needs a GQA attention decoder "
                    f"(family={cfg.family}, attn={cfg.attention_kind})")
            if n_blocks is None:
                # SWA pools only need the live window (+1 block of write
                # headroom per slot); the table still spans max_len columns
                live = KV.cache_capacity(cfg, max_len, swa=swa)
                n_blocks = -(-max_batch * live // block_size)
                if self.window:
                    n_blocks += max_batch
            self.pstate = PK.init_paged(cfg, max_batch, n_blocks,
                                        block_size=block_size, dtype=dtype,
                                        max_len=self.max_len,
                                        prefix_cache=self.prefix_sharing)
            if device is not None:
                self.pstate.k = jax.device_put(self.pstate.k, device)
                self.pstate.v = jax.device_put(self.pstate.v, device)
            self.cache = None
        else:
            self.cache = T.init_cache(cfg, max_batch, self.max_len, dtype)
            self.pstate = None

        # scheduler: resolved through the policy registry
        # (serving/scheduler.py). TOKEN-BUDGET continuous batching is the
        # default paged path (one step loop packs decode tokens + bounded
        # prefill chunks — long prompts never stall decodes); "slo" adds
        # class-aware splits of the same budget; "phase" keeps the
        # original prefill-wave/decode-step alternation as the parity
        # oracle and the bench baseline. Dense engines are always phase
        # (chunking targets the block pool's progressive allocation).
        if scheduler is None:
            scheduler = "budget" if cache_kind == "paged" else "phase"
        if cache_kind != "paged":
            scheduler = "phase"
        self.sched = SCH.make_scheduler(scheduler, token_budget=token_budget,
                                        chunk_align=block_size)
        self.scheduler_kind = self.sched.name
        self.token_budget = token_budget if self.sched.budgeted else 0
        self.last_step_packed: Optional[int] = None  # telemetry, per step
        # per step, reset at each: host seconds of each phase
        # (instrument.STEP_PHASES) and programs compiled or loaded
        self.last_step_phases: Dict[str, float] = {}
        self.last_step_compiles = 0
        self._step_chunks = 0      # prefill chunks run by this step
        self._compiles = INS.compile_counter()

        # decode attention over the pool, chosen ONCE here: "auto" takes
        # the Pallas kernel on a TPU wherever it computes this model's
        # attention (no softcap, no window) and the jnp gather otherwise;
        # an explicit "kernel" the kernel cannot serve is refused
        can_kernel = paged_kernel_serves(cfg, self.window)
        if paged_attn_impl == "auto":
            paged_attn_impl = ("kernel" if can_kernel
                               and jax.default_backend() == "tpu"
                               else "gather")
        if paged_attn_impl not in ("kernel", "gather"):
            raise ValueError(f"paged_attn_impl must be auto|kernel|gather, "
                             f"got {paged_attn_impl!r}")
        if paged_attn_impl == "kernel" and not can_kernel:
            raise ValueError(
                f"paged_attn_impl='kernel' cannot serve {cfg.name}: the "
                f"kernel has no logit softcap or sliding window")
        self.paged_attn_impl = paged_attn_impl
        self._interpret = interpret
        # optional observe.EngineSpanRecorder: lifecycle span hooks
        # (queue / prefill chunks / first token / decode / finish).
        # None (the default) keeps every hook site a falsy check —
        # tracing off costs nothing in the step loop.
        self.span_hook = None
        # live module-scaling state (Engine.apply_plan)
        self.replication_degrees: Optional[tuple] = None  # plan intent
        self._step_degrees: Optional[tuple] = None        # quantized/static
        self._replicated = False    # weights + pool spread over the mesh
        self._prefill_shapes = set()  # (G, Sb) paged prefills run so far

    # ------------------------------------------------------------- sampling
    def _sample_batch(self, logits, reqs) -> np.ndarray:
        """Sample one token per request from [len(reqs), Vpad] logits —
        one fused device call + one device_get for the whole batch."""
        temps = np.asarray([r.temperature for r in reqs], np.float32)
        topks = np.asarray([r.top_k for r in reqs], np.int32)
        seeds = np.asarray([r.seed for r in reqs], np.uint32)
        ctrs = np.asarray([len(r.generated) for r in reqs], np.uint32)
        return jax.device_get(_sample_fn(
            logits, temps, topks, seeds, ctrs,
            vocab_size=self.cfg.vocab_size,
            stochastic=bool((temps > 0).any()),
            max_top_k=int(topks.max())))

    def _sampling_arrays(self):
        B = self.max_batch
        temps = np.zeros((B,), np.float32)
        topks = np.zeros((B,), np.int32)
        seeds = np.zeros((B,), np.uint32)
        ctrs = np.zeros((B,), np.uint32)
        for slot, req in self.active.items():
            temps[slot] = req.temperature
            topks[slot] = req.top_k
            seeds[slot] = req.seed
            ctrs[slot] = len(req.generated)
        return temps, topks, seeds, ctrs

    # ------------------------------------------------------------- lifecycle
    def submit(self, spec: RequestSpec) -> Request:
        """Admit one request. Accepts ONLY a ``RequestSpec`` (the
        construction-time contract — serving/request.py); the engine
        mints and returns the mutable ``Request`` it will drive.
        Already-minted Requests re-enter through queue surgery
        (``queue.appendleft`` on preemption, ``resume_request`` on
        migration, push/requeue handle hooks on replay), never through
        ``submit``."""
        if not isinstance(spec, RequestSpec):
            raise TypeError(
                f"Engine.submit takes a RequestSpec, got "
                f"{type(spec).__name__} (build one via "
                "repro.serving.request, or RequestSpec.from_request "
                "for replays)")
        spec.validate()
        req = spec.to_request()
        req.submit_time = self.clock
        if self.span_hook:
            self.span_hook.on_submit(req)
        self.queue.append(req)
        return req

    def _queue_remove(self, req: Request):
        """Pop ``req`` from the waiting queue by IDENTITY (Request's
        field-wise __eq__ would compare prompt arrays)."""
        for i, r in enumerate(self.queue):
            if r is req:
                del self.queue[i]
                return
        raise ValueError(f"rid={req.rid} is not in the waiting queue")

    def set_token_budget(self, budget: int) -> int:
        """Retarget the per-step token budget LIVE (the ingress budget
        governor's knob). No-op on phase/dense engines — there is no
        budget to govern. Returns the budget now in force."""
        if self.sched.budgeted:
            budget = max(int(budget), 1)
            self.sched.token_budget = budget
            self.token_budget = budget
        return self.token_budget

    def _free_slots(self):
        return [s for s in range(self.max_batch)
                if s not in self.active and s not in self.prefilling
                and s not in self._staged]

    def slot_rids(self) -> Dict[int, int]:
        """slot -> rid of every request holding a slot — decoding OR
        mid-prefill. This is the enumeration migration and drain
        operate on (a mid-prefill request is pausable/migratable)."""
        out = {s: r.rid for s, r in self.active.items()}
        out.update({s: r.rid for s, r in self.prefilling.items()})
        return out

    def prefill_total(self, req: Request) -> int:
        """Tokens the cache must hold before the request can decode —
        the scheduler's unit of prefill work (see _prefill_tokens)."""
        n = len(req.prompt)
        if req.generated:
            n += len(req.generated) - 1
        return n

    @staticmethod
    def _prefill_tokens(req: Request) -> np.ndarray:
        """Tokens the cache must hold before the next decode step: the
        prompt, plus — for a preempted/resumed request — every generated
        token except the last (which the next step feeds in)."""
        if req.generated:
            return np.concatenate([np.asarray(req.prompt, np.int32),
                                   np.asarray(req.generated[:-1], np.int32)])
        return np.asarray(req.prompt, np.int32)

    def _activate(self, req: Request, slot: int, length: int,
                  first_tok: Optional[int]):
        fresh_first = first_tok is not None and not req.generated
        req.prefill_pos = length
        if req.prefill_start_time is None:
            req.prefill_start_time = self.clock
        if first_tok is not None:
            req.generated.append(int(first_tok))
        if req.first_token_time is None:
            req.first_token_time = self.clock
        if self.span_hook:
            self.span_hook.on_activate(req, fresh_first)
        # the admission-sampled token can already satisfy a finish
        # condition (eos on the first token, max_new_tokens == 1): retire
        # without ever occupying a decode slot
        hit_eos = (req.eos_id is not None and req.generated
                   and req.generated[-1] == req.eos_id)
        if hit_eos or len(req.generated) >= req.max_new_tokens:
            req.finish_time = self.clock
            req.slot = None
            if self.cache_kind == "paged":
                PK.free_slot(self.pstate, slot)
            if slot in self._admit_order:   # was mid-prefill (chunked)
                self._admit_order.remove(slot)
            if self.span_hook:
                self.span_hook.on_finish(req)
            self._admit_finished.append(req)
            return
        req.slot = slot
        self.active[slot] = req
        if slot not in self._admit_order:   # chunked slots already queued
            self._admit_order.append(slot)
        if self.cache_kind == "dense":
            self._host_lengths[slot] = length

    # ---------------------------------------------------------- dense admit
    def _admit_dense(self):
        for slot in self._free_slots():
            if not self.queue:
                break
            req = self.queue.popleft()
            toks = self._prefill_tokens(req)
            enc = (self.encoder_input_fn(req)
                   if self.cfg.family == "audio" and self.encoder_input_fn
                   else None)
            if enc is None and self.cfg.family == "audio":
                enc = jnp.zeros((1, self.cfg.encoder_seq_len,
                                 self.cfg.d_model), jnp.float32)
            # a throwaway cache of max_len, so that insert_request's
            # shapes line up
            rcache = T.init_cache(self.cfg, 1, self.max_len, self.dtype)
            logits, rcache, _ = _prefill_fn(
                self.params, jnp.asarray(toks[None, :]), rcache, enc,
                cfg=self.cfg, window=self.window)
            first = None
            if not req.generated:
                first = self._sample_batch(logits, [req])[0]
            self.cache = KV.insert_request(self.cache, slot, rcache,
                                           len(toks))
            self._activate(req, slot, len(toks), first)

    # ---------------------------------------------------------- paged admit
    def _check_fits(self, req: Request, S: int):
        """Reject a request that can never be admitted: its LIVE block
        columns — prompt plus headroom for the first decode write, minus
        the leading columns a sliding window has already retired
        (``allocate`` skips them, so a long prompt never needs transient
        full-length residency in a window pool) — outnumber the pool, or
        its decode write head lies past the block-table row (prompt >=
        max_len). Marks it finished with no output and raises
        OutOfBlocks, on which the caller puts its other requests back."""
        bs = self.pstate.block_size
        width = self.pstate.block_tables.shape[1]
        need = self._live_blocks(S)
        if need > self.pstate.n_blocks or S // bs >= width:
            req.finish_time = self.clock  # rejected: no output
            if self.span_hook:
                self.span_hook.on_finish(req)
            raise PK.OutOfBlocks(
                f"request rid={req.rid} needs {need} live blocks up to "
                f"column {S // bs}; pool has {self.pstate.n_blocks}, "
                f"table rows hold {width}")

    def _live_blocks(self, S: int) -> int:
        """Blocks an S-token prompt holds once written, with room for its
        first decode write (``allocate``'s own dead-column rule under a
        window — never fewer, so this bound never under-reserves)."""
        bs = self.pstate.block_size
        cols = S // bs + 1
        if self.window:
            cols -= min(PK.dead_columns(S, bs, self.window), cols - 1)
        return cols

    def _prefix_hit(self, toks) -> tuple:
        """``(matched block ids, context tokens)`` an admission of
        ``toks`` may alias from the prefix cache; ``([], 0)`` on a miss.
        The context is capped at S-1 so at least one suffix token remains
        to produce first-token logits (a fully-aliased aligned prompt
        recomputes its last token — the write into the shared tail block
        is what copy-on-write forks). Matching is off under a window,
        whose reclamation invalidates full-prefix residency."""
        if not self.prefix_sharing or self.window:
            return [], 0
        matched = PK.match_prefix(self.pstate, toks, record=False)
        ctx = min(len(matched) * self.pstate.block_size, len(toks) - 1)
        if not (matched and ctx >= 1):
            return [], 0
        return matched, ctx

    def _admit_paged(self, wave: Optional[List[Request]] = None):
        """Admit a prefill WAVE of whole prompts through the chunk-prefill
        program: a prefix hit prefills its suffix against the aliased
        context, and a miss is a suffix with no context. One bucketed
        forward per (context, suffix) group; the misses' groups run
        first, so a wave-mate that hits a miss's just-registered prefix
        reads written pages. Phase scheduling pops its own wave from the
        queue; the token-budget scheduler passes the full grants it
        popped as ``wave``. Returns the requests actually admitted
        (callers detect backpressure requeues by comparing against the
        wave)."""
        if wave is None:
            # phase mode still drains mid-prefill slots first (a
            # migrated-in chunked request must finish somewhere): grant
            # each its full remainder
            if self.prefilling:
                self._run_chunks([
                    _ChunkSpec(req, slot,
                               self.prefill_total(req) - req.prefill_pos,
                               fresh=False)
                    for slot, req in sorted(self.prefilling.items())])
        free = self._free_slots()
        if wave is None:
            if not free or not self.queue:
                return []
            taken: List[Request] = []
            while self.queue and len(taken) < len(free):
                taken.append(self.queue.popleft())
        else:
            taken = list(wave)
            if not taken:
                return []
            assert len(taken) <= len(free), (len(taken), len(free))
        ptoks = {id(r): self._prefill_tokens(r) for r in taken}

        # pre-pass BEFORE any allocation: a request that can never fit is
        # rejected now rather than head-of-line blocking everything
        # behind it; the rest of the wave goes back to the queue intact,
        # nothing is lost and no block leaks.
        for req in taken:
            try:
                self._check_fits(req, len(ptoks[id(req)]))
            except PK.OutOfBlocks:
                for r in reversed([t for t in taken if t is not req]):
                    self.queue.appendleft(r)
                raise

        admitted: List[Request] = []
        slot_of: Dict[int, int] = {}
        ctx_of: Dict[int, int] = {}       # id(req) -> aliased context tokens
        matched_of: Dict[int, list] = {}  # id(req) -> matched block ids
        for idx, req in enumerate(taken):
            slot = free[len(admitted)]
            toks = ptoks[id(req)]
            # prefix-cache lookup BEFORE the backpressure check: a hit
            # only needs pool capacity for its suffix (aliased blocks are
            # revived/shared in place, never popped), so a shared-prefix
            # request admits under pressure that would stall a cold one —
            # the regime sharing exists for.
            matched, ctx = self._prefix_hit(toks)
            # live blocks cover every prompt column + write headroom
            # (enough for the worst-case CoW fork too); aliased columns
            # need no pop, but reviving a cached-free block does consume
            # a unit of free_block_count
            revive = sum(1 for b in matched
                         if int(self.pstate.refcount[b]) == 0)
            if (self._live_blocks(len(toks)) - len(matched)
                    > self.pstate.free_block_count() - revive):
                # out of blocks: backpressure — requeue IN ORDER and stop
                for r in reversed(taken[idx:]):
                    self.queue.appendleft(r)
                break
            if matched:
                try:
                    PK.adopt_prefix(self.pstate, slot, matched, ctx)
                    PK.allocate(self.pstate, slot, len(toks) - ctx)
                except PK.OutOfBlocks:
                    PK.free_slot(self.pstate, slot)   # decref the adoption
                    for r in reversed(taken[idx:]):
                        self.queue.appendleft(r)
                    break
            else:
                PK.allocate(self.pstate, slot, len(toks),
                            window=self.window)
                if self.prefix_sharing and not self.window:
                    # publish this prompt's full blocks NOW so wave-mates
                    # behind it match them: the misses' groups run before
                    # any hit group below, so the content is written by
                    # the time a hit's context gather reads it. Hit
                    # requests register AFTER their suffix prefill
                    # instead — it can still fail (CoW fork under pool
                    # pressure), and keys must never describe unwritten
                    # blocks.
                    PK.register_prefix(self.pstate, slot, toks)
            ctx_of[id(req)] = ctx
            matched_of[id(req)] = matched
            slot_of[id(req)] = slot
            admitted.append(req)
        # BUCKETED prefill: group by (pow2 context bucket, pow2 suffix
        # bucket), so each group runs ONE batched extend + ONE suffix
        # scatter + ONE sampling call and admission compiles
        # O(log^2 max_len) executables, not one per request or prompt
        # length. A miss is a chunk with no context: context bucket 1,
        # the program a first chunk runs. Misses group apart from hits
        # and run first; then activate in SUBMISSION order (group
        # iteration would reorder _admit_order and break youngest-first
        # preemption)
        groups: Dict[tuple, List[Request]] = {}
        for req in admitted:
            ctx = ctx_of[id(req)]
            key = (ctx > 0, _pow2_at_least(max(ctx, 1)),
                   _pow2_at_least(len(ptoks[id(req)]) - ctx))
            groups.setdefault(key, []).append(req)
        first_of: Dict[int, Optional[int]] = {}
        failed: List[Request] = []
        # misses first (a stable sort keeps each class's order)
        for (hit, cb, Sb), greqs in sorted(groups.items(),
                                           key=lambda kv: kv[0][0]):
            ok: List[Request] = []
            for req in greqs:
                # copy-on-write forks BEFORE the group forward: a hit's
                # suffix write may land inside an aliased tail block. A
                # fork that finds no free block (wave-mates consumed the
                # headroom) drops just that request — nothing was
                # written or registered for it — and it retries next step
                try:
                    if hit:
                        PK.ensure_writable(
                            self.pstate, slot_of[id(req)], ctx_of[id(req)],
                            len(ptoks[id(req)]) - ctx_of[id(req)])
                    ok.append(req)
                except PK.OutOfBlocks:
                    PK.free_slot(self.pstate, slot_of[id(req)])
                    failed.append(req)
            if not ok:
                continue
            # pad the GROUP dim to a power of two as well (dummy rows
            # replicate the last member; their pool writes drop, their
            # logits are discarded) so executables are keyed on
            # (pow2 G, context bucket, pow2 suffix) — a warmed wave shape
            # serves every later wave regardless of its exact size
            Gb = _pow2_at_least(len(ok))
            pad = [ok[-1]] * (Gb - len(ok))
            logits = self._prefill_shared_batch(
                [slot_of[id(r)] for r in ok + pad],
                [ptoks[id(r)] for r in ok + pad],
                [ctx_of[id(r)] for r in ok + pad], cb, Sb,
                n_real=len(ok))
            if hit:     # a hit implies sharing on and no window
                for r in ok:
                    PK.register_prefix(self.pstate, slot_of[id(r)],
                                       ptoks[id(r)])
            firsts = self._sample_batch(logits, ok + pad)[:len(ok)]
            for i, r in enumerate(ok):
                first_of[id(r)] = None if r.generated else firsts[i]
        if failed:
            for r in reversed(failed):      # preserve submission order
                self.queue.appendleft(r)
            failed_ids = {id(r) for r in failed}
            admitted = [r for r in admitted if id(r) not in failed_ids]
        for req in admitted:
            if self.prefix_sharing and not self.window:
                # gauge bookkeeping once per SUCCESSFUL admission — the
                # failure exits above (backpressure, fork OutOfBlocks)
                # never reach here, so retries don't skew the hit rate
                PK.record_lookup(self.pstate, ptoks[id(req)],
                                 matched_of[id(req)])
            self._activate(req, slot_of[id(req)], len(ptoks[id(req)]),
                           first_of[id(req)])
        if self.window:
            for req in admitted:
                if req.slot is not None:  # may have retired at admission
                    PK.free_out_of_window(self.pstate, req.slot, self.window)
        return admitted

    def _prefill_shared_batch(self, slots: List[int], toks_list,
                              ctxs: List[int], cb: int, Sb: int,
                              n_real: Optional[int] = None):
        """Bucketed suffix prefill for a GROUP of slots — the one paged
        prefill: prefix-cache hits, chunks, and misses (no context: one
        gathered block, every position masked). Splice every row's
        written context K/V (ONE batched pool gather) into a shared
        throwaway dense cache as attention context, run one decode-mode
        continuation over the padded suffix rows, and scatter ONLY the
        suffix K/V back (one batched pool write — the shared spans are
        never re-written).
        Prefill compute scales with the suffixes, and executable count
        with the number of (context, suffix) power-of-two buckets —
        O(log² max_len) — not with the number of requests. Per-row true
        context lengths ride in the positions array (poisoned past
        ctx_i: BIG_POS rows are masked out of attention), so one
        executable serves every member of the bucket. Callers run
        ``ensure_writable`` (CoW fork) per member beforehand."""
        G = len(slots)
        n_real = G if n_real is None else n_real
        # dummy pad rows (duplicated slots past n_real) scatter nothing:
        # their new-token count is forced to 0 below, which the scatter
        # plan drops row-wise
        n_news = [(len(t) - c) if i < n_real else 0
                  for i, (t, c) in enumerate(zip(toks_list, ctxs))]
        # cb and Sb are already pow2-bucketed, so cb+Sb takes O(log^2)
        # values — no need to round the throwaway cache up again (a late
        # 256-ctx/64-chunk call attends over 320 keys, not 512)
        cache_len = cb + Sb
        self._prefill_shapes.add((G, Sb))
        st = self.pstate
        bs = st.block_size
        n_blk = -(-cb // bs)
        tbl = st.block_tables[np.asarray(slots, np.int64), :n_blk]
        tbl = np.where(tbl >= 0, tbl, 0)   # holes gather garbage; masked
        pos = np.full((G, cache_len), int(T.BIG_POS), np.int32)
        suffix = np.zeros((G, Sb), np.int32)
        spos = np.zeros((G, Sb), np.int32)
        for i, (toks, ctx, n_new) in enumerate(zip(toks_list, ctxs,
                                                   n_news)):
            pos[i, :ctx] = np.arange(ctx)
            suffix[i, :n_new] = toks[ctx:ctx + n_new]
            spos[i] = np.arange(ctx, ctx + Sb)
        # host half of the pool append (advances lengths, stamps epoch);
        # the device half rides inside the fused executable
        bidx, oidx = PK.scatter_plan(st, slots, Sb, lengths=n_news)
        logits, st.k, st.v = _chunk_prefill_fn(
            self.params, st.k, st.v, jnp.asarray(tbl, jnp.int32),
            jnp.asarray(suffix), jnp.asarray(spos), jnp.asarray(pos),
            jnp.asarray(np.asarray(n_news, np.int32) - 1),
            jnp.asarray(bidx, jnp.int32), jnp.asarray(oidx, jnp.int32),
            cfg=self.cfg, window=self.window, cache_len=cache_len,
            dtype=self.dtype)
        return logits

    def _admit(self):
        if self.cache_kind == "paged" and self.sched.budgeted:
            self._admit_budget()      # times its plan, wave and chunks
            return
        with INS.phase(self.last_step_phases, INS.STEP_WAVE):
            if self.cache_kind == "paged":
                self._admit_paged()
            else:
                self._admit_dense()

    # ------------------------------------- token-budget admission (CB)
    def _admit_budget(self):
        """One continuous-batching admission pass: ask the scheduler how
        this step's token budget packs, then execute the grants — whole
        prompts ride the existing bucketed wave machinery (prefix
        matching, pow2 groups, backpressure requeue all intact), chunk
        grants run through ``_run_chunks``. Decode tokens were charged
        first inside ``plan``, so admission work is bounded and a long
        prompt is sliced across steps instead of stalling the batch."""
        with INS.phase(self.last_step_phases, INS.STEP_PLAN):
            plan = self.sched.plan(self)
            self.last_step_packed = plan.packed
            cont = [g for g in plan.grants if g.slot is not None]
            chunks = [_ChunkSpec(g.req, g.slot, g.n_tokens) for g in cont]
            wave: List[Request] = []
            partial = None
            for g in plan.grants:
                if g.slot is not None:
                    continue
                if g.final:
                    # granted fresh requests need not be a queue PREFIX —
                    # class-aware policies admit out of FIFO order — so
                    # pop each one wherever it sits (raises if the policy
                    # granted something not actually queued)
                    self._queue_remove(g.req)
                    wave.append(g.req)
                else:
                    partial = g         # stays queued for now
        requeued = False
        with INS.phase(self.last_step_phases, INS.STEP_WAVE):
            if wave:
                admitted = self._admit_paged(wave)
                requeued = len(admitted) < len(wave)
        with INS.phase(self.last_step_phases, INS.STEP_CHUNKS):
            if partial is not None and not requeued:
                # pool pressure on the wave means the partial (younger)
                # grant would only add pressure — leave it queued, FIFO
                # intact
                spec = self._begin_chunked(partial.req, partial.n_tokens)
                if spec is not None:
                    chunks.append(spec)
            ran = self._run_chunks(chunks) if chunks else 0
            self._step_chunks = ran
            # forward-progress guard: nothing decoding, nothing admitted,
            # every chunk blocked on the pool -> the prefilling slots are
            # starving each other; preempt the youngest so the oldest can
            # finish (never-fits rejection guarantees a lone prefill fits)
            if (not self.active and not ran and not wave
                    and len(self.prefilling) > 1):
                victims = [s for s in self._admit_order
                           if s in self.prefilling]
                if len(victims) > 1:
                    self._preempt(victims[-1])

    def _begin_chunked(self, req: Request, n: int) -> Optional[_ChunkSpec]:
        """Admit a WAITING request with a partial grant: claim a slot,
        run the wave path's never-fits rejection (``_check_fits``) and
        prefix-cache lookup (``_prefix_hit``), and hand back the first
        chunk for execution. The request may sit anywhere in the queue
        (class-aware policies grant out of FIFO order); it keeps its
        position on allocation failure (backpressure). Prefix hits
        advance the cursor for free (aliased context costs no compute).
        Returns None when nothing was admitted."""
        assert any(r is req for r in self.queue), req.rid
        toks = self._prefill_tokens(req)
        S = len(toks)
        try:
            self._check_fits(req, S)
        except PK.OutOfBlocks:
            self._queue_remove(req)
            raise
        free = self._free_slots()
        if not free:
            return None
        slot = free[0]
        matched, ctx = self._prefix_hit(toks)
        if matched:
            PK.adopt_prefix(self.pstate, slot, matched, ctx)
        self._queue_remove(req)
        req.slot = slot
        req.prefill_pos = ctx
        if req.prefill_start_time is None:
            req.prefill_start_time = self.clock
        self.prefilling[slot] = req
        self._admit_order.append(slot)
        self._prefill_matched[slot] = matched
        return _ChunkSpec(req, slot, min(n, S - ctx), fresh=True)

    def _run_chunks(self, specs: List[_ChunkSpec]) -> int:
        """Execute prefill chunks: allocate each chunk's block columns
        (progressive — only the columns these tokens land in), then run
        the chunks BUCKETED exactly like the prefix-hit suffix path: a
        chunk continuation over [cursor, cursor+n) IS a suffix prefill
        against the already-written span, so both share
        ``_prefill_shared_batch`` (context splice + decode-mode extend +
        suffix scatter), grouped by (pow2 context, pow2 chunk) with the
        group dim padded to pow2 — executable count stays
        O(log max_len)^2, independent of chunk count. Final chunks
        sample the first token and move the request into decode
        rotation. Returns the number of chunks that actually ran."""
        st = self.pstate
        bs = st.block_size
        ready: List[_ChunkSpec] = []
        for sp in specs:
            if self.prefilling.get(sp.slot) is not sp.req:
                continue   # preempted by the decode-room pass: replays
            start = sp.req.prefill_pos
            assert int(st.lengths[sp.slot]) == start, \
                (sp.slot, int(st.lengths[sp.slot]), start)
            try:
                PK.allocate(st, sp.slot, sp.n, window=self.window)
                if self.prefix_sharing:
                    # the chunk may write into an adopted (shared) tail
                    # block: fork it first, copy-on-write
                    PK.ensure_writable(st, sp.slot, start, sp.n)
            except PK.OutOfBlocks:
                if sp.fresh:
                    # first chunk found no blocks: undo the admission so
                    # the request waits in the QUEUE, not in a slot
                    del self.prefilling[sp.slot]
                    self._admit_order.remove(sp.slot)
                    self._prefill_matched.pop(sp.slot, None)
                    PK.free_slot(st, sp.slot)
                    sp.req.slot = None
                    sp.req.prefill_pos = 0
                    self.queue.appendleft(sp.req)
                continue                    # continuation retries next step
            ready.append(sp)
        if not ready:
            return 0
        # ONE group per step: every chunk shares a single (pow2 context,
        # pow2 suffix) bucket — per-row true starts ride in the positions
        # array, so mixing context lengths costs padded gather width, not
        # extra executables or extra forwards
        width_tokens = st.block_tables.shape[1] * bs
        starts = [int(st.lengths[sp.slot]) for sp in ready]
        cb = min(_pow2_at_least(max(max(starts), 1)), width_tokens)
        Sb = _pow2_at_least(max(sp.n for sp in ready))
        gsp = ready
        Gb = _pow2_at_least(len(gsp))
        padded = gsp + [gsp[-1]] * (Gb - len(gsp))
        toks_list = [self._prefill_tokens(sp.req)[:sp.req.prefill_pos
                                                  + sp.n]
                     for sp in padded]
        ctxs = [sp.req.prefill_pos for sp in padded]
        if self.span_hook:
            t = self.span_hook.now()
            for sp in gsp:
                self.span_hook.on_chunk(sp.req.rid, sp.n, t)
        logits = self._prefill_shared_batch(
            [sp.slot for sp in padded], toks_list, ctxs, cb, Sb,
            n_real=len(gsp))
        finals = [sp for sp in gsp
                  if sp.req.prefill_pos + sp.n
                  >= self.prefill_total(sp.req)]
        toks = None
        if any(not sp.req.generated for sp in finals):
            # one sampling sync per step, and ONLY when some member
            # finished its prompt — intermediate chunks discard their
            # logits without touching the host
            toks = self._sample_batch(
                logits, [sp.req for sp in padded])[:len(gsp)]
        for i, sp in enumerate(gsp):
            sp.req.prefill_pos += sp.n   # mirrors pstate.lengths
            if sp in finals:
                first = (None if sp.req.generated else int(toks[i]))
                self._finish_prefill(sp.req, sp.slot, first)
        if self.window:
            for sp in gsp:
                if sp.slot in self.prefilling \
                        or sp.slot in self.active:
                    PK.free_out_of_window(st, sp.slot, self.window)
        return len(ready)

    def _finish_prefill(self, req: Request, slot: int,
                        first: Optional[int]):
        """Last chunk landed: publish the finished prompt to the prefix
        cache (never earlier — keys must not describe unwritten blocks),
        count the lookup once per successful admission, and move the
        request into decode rotation."""
        del self.prefilling[slot]
        matched = self._prefill_matched.pop(slot, [])
        if self.prefix_sharing and not self.window:
            toks = self._prefill_tokens(req)
            PK.register_prefix(self.pstate, slot, toks)
            PK.record_lookup(self.pstate, toks, matched)
        self._activate(req, slot, req.prefill_pos, first)

    # ------------------------------------------------------------ preemption
    def _preempt(self, slot: int):
        """Return the request in ``slot`` to the queue head and free its
        blocks. Counter-based sampling keys make the resumed continuation
        identical to the uninterrupted one. A MID-PREFILL slot is an
        ordinary victim: its cursor resets and the chunks replay — the
        written span lived only in the freed blocks."""
        if slot in self.active:
            req = self.active.pop(slot)
        else:
            req = self.prefilling.pop(slot)
            self._prefill_matched.pop(slot, None)
        self._admit_order.remove(slot)
        PK.free_slot(self.pstate, slot)
        req.slot = None
        req.prefill_pos = 0
        req.preemptions += 1
        self.preempt_count += 1
        if self.span_hook:
            self.span_hook.on_preempt(req.rid)
        self.queue.appendleft(req)

    def _ensure_decode_room(self):
        """Every active slot needs pool room for one more token; under
        pressure, preempt the youngest request (vLLM-style). A lone
        request that has genuinely outgrown the pool (no victim left to
        preempt, requeueing would just re-admit it) is evicted with its
        partial output before raising, so the engine stays serviceable
        for everything behind it."""
        for slot in sorted(self.active.keys()):
            while slot in self.active:
                try:
                    PK.allocate(self.pstate, slot, 1)
                    if self.prefix_sharing:
                        # copy-on-write: the fused step writes this
                        # slot's next token into column lengths//bs in
                        # place — fork it now if it is still shared with
                        # another stream
                        PK.ensure_writable(self.pstate, slot,
                                           int(self.pstate.lengths[slot]), 1)
                    break
                except PK.OutOfBlocks:
                    # victim ORDER is policy (the SLO scheduler shields
                    # interactive streams by pushing batch slots to the
                    # tail); the engine just takes the tail
                    victims = self.sched.victims(self)
                    if len(victims) <= 1:
                        req = self.active[slot]
                        req.finish_time = self.clock  # truncated output
                        if self.span_hook:
                            self.span_hook.on_finish(req)
                        self._retire(slot)
                        raise PK.OutOfBlocks(
                            f"request rid={req.rid} outgrew the pool at "
                            f"{len(req.generated)} generated tokens; "
                            f"evicted with truncated output")
                    self._preempt(victims[-1])

    # ------------------------------------------------------------------ step
    def step(self, dt: float = 1.0):
        """One engine iteration: admit from queue, one fused decode+sample
        call for all active slots, retire finished requests. Exactly one
        device→host sync (the sampled-token fetch) in steady state. Each
        phase (``instrument.STEP_PHASES``) is a profiler span, and its
        host seconds land in ``last_step_phases``."""
        self.clock += dt
        self.last_step_packed = None   # set by the token-budget planner
        self.last_step_phases = {}
        self._step_chunks = 0
        c0 = self._compiles.total
        try:
            return self._step()
        finally:
            self.last_step_compiles = self._compiles.total - c0

    def _step(self):
        self._admit()
        finished = self._admit_finished
        self._admit_finished = []
        if self.cache_kind == "paged" and self.active:
            # may preempt: must run BEFORE the step snapshots active slots
            with INS.phase(self.last_step_phases, INS.STEP_DECODE_ROOM):
                self._ensure_decode_room()
        if not self.active:
            return finished or None
        paged = self.cache_kind == "paged"
        with INS.phase(self.last_step_phases, INS.STEP_INPUTS):
            B = self.max_batch
            tokens = np.zeros((B,), np.int32)
            active_mask = np.zeros((B,), bool)
            for slot, req in self.active.items():
                tokens[slot] = req.generated[-1]
                active_mask[slot] = True
            temps, topks, seeds, ctrs = self._sampling_arrays()
            # static flags: all-greedy batches skip the sampler's top-k +
            # Gumbel work inside the fused step entirely, and the
            # batch-max top_k bounds the threshold search to lax.top_k
            # instead of a full-vocab sort (a handful of compiled
            # variants at most)
            stoch = bool((temps > 0).any())
            max_top_k = int(topks.max())
            if paged:
                st = self.pstate
                pre_lengths = st.lengths.copy()
                args, kw = self._paged_step_args(tokens, active_mask, temps,
                                                 topks, seeds, ctrs, stoch,
                                                 max_top_k)
                shape = {"bucket": args[3].shape[1],
                         "chunks": self._step_chunks}
            else:
                pre_lengths = self._host_lengths.copy()
                positions = pre_lengths[:, None].astype(np.int32)
                shape = {}
        with INS.phase(self.last_step_phases, INS.STEP_DISPATCH,
                       step=self._step_count, rows=len(self.active),
                       **shape):
            if paged:
                toks_dev, st.k, st.v = _jitted_steps()[1](*args, **kw)
            else:
                toks_dev, self.cache = _jitted_steps()[0](
                    self.params, self.cache, tokens[:, None],
                    positions, temps, topks, seeds, ctrs,
                    cfg=self.cfg, window=self.window, stochastic=stoch,
                    max_top_k=max_top_k)
        with INS.phase(self.last_step_phases, INS.STEP_WAIT):
            toks = jax.device_get(toks_dev)     # the ONE host sync
        with INS.phase(self.last_step_phases, INS.STEP_BOOKKEEP):
            if paged:
                bs = st.block_size
                st.lengths[active_mask] += 1
                # dirty-set bookkeeping for overlapped migration: the
                # fused step scattered each active slot's token into the
                # block at its pre-step write head (host arithmetic only
                # — no sync)
                PK.mark_written(st, [
                    int(st.block_tables[s, int(pre_lengths[s]) // bs])
                    for s in self.active])
                if self.window:
                    for slot in self.active:
                        PK.free_out_of_window(st, slot, self.window)
            else:
                self._host_lengths[active_mask] += 1
            self._step_count += 1

            for slot, req in list(self.active.items()):
                tok = int(toks[slot])
                req.generated.append(tok)
                hit_eos = req.eos_id is not None and tok == req.eos_id
                full = len(req.generated) >= req.max_new_tokens
                over = int(pre_lengths[slot]) + 2 >= self.logical_max
                if hit_eos or full or over:
                    req.finish_time = self.clock
                    if self.span_hook:
                        self.span_hook.on_finish(req)
                    finished.append(req)
                    self._retire(slot)
        return finished

    def _paged_step_args(self, tokens, active_mask, temps, topks, seeds,
                         ctrs, stoch: bool, max_top_k: int):
        """Arguments and static options of one fused paged decode step
        (``_jitted_steps()[1](*args, **kw)``)."""
        st = self.pstate
        bs = st.block_size
        # power-of-2 bucket of the widest needed table prefix: decode
        # cost tracks the true max context, with O(log) recompiles.
        # Derived from LENGTHS (col of the incoming write), not from
        # block counts — window-freed rows have leading holes.
        need = (int(st.lengths[active_mask].max()) // bs + 1) if \
            active_mask.any() else 1
        nb = min(_pow2_at_least(max(need, 1)), st.block_tables.shape[1])
        tables = np.ascontiguousarray(st.block_tables[:, :nb])
        args = (self.params, st.k, st.v, tables, st.lengths.astype(np.int32),
                active_mask, tokens, temps, topks, seeds, ctrs)
        kw = dict(cfg=self.cfg, window=self.window,
                  impl=self.paged_attn_impl, interp=self._interpret,
                  stochastic=stoch, max_top_k=max_top_k,
                  degrees=self._step_degrees)
        return args, kw

    def _retire(self, slot: int):
        del self.active[slot]
        self._admit_order.remove(slot)
        if self.cache_kind == "paged":
            PK.free_slot(self.pstate, slot)
        else:
            self._host_lengths[slot] = 0
            self.cache = KV.evict_request(self.cache, slot)

    def prefix_stats(self) -> dict:
        """Live prefix-sharing gauges (hit rate, CoW forks, blocks saved)
        — the telemetry the orchestrator folds into MetricsSnapshot."""
        if self.cache_kind != "paged":
            return {"queries": 0, "hits": 0, "hit_rate": 0.0,
                    "cow_forks": 0, "blocks_saved_total": 0,
                    "blocks_saved_now": 0, "cached_blocks": 0}
        return PK.prefix_stats(self.pstate)

    @property
    def block_size(self) -> int:
        """Pool block granularity (0 for a dense engine) — what the pod
        router hashes incoming prompts by (serving/router.py)."""
        return self.pstate.block_size if self.cache_kind == "paged" else 0

    def prefix_keys(self) -> set:
        """Hex-encoded content-chain keys RESIDENT in this engine's
        prefix cache — the router's pod-wide affinity signal. Hex (not
        raw bytes) so the set survives msgpack/JSON round trips
        unchanged."""
        if self.cache_kind != "paged" or not self.prefix_sharing:
            return set()
        return {k.hex() for k in self.pstate.prefix_cache}

    def stream_progress(self) -> Dict[int, List[int]]:
        """rid -> tokens generated so far, for every SLOT-HOLDING
        request (decoding or mid-prefill) — the ingress streaming feed.
        Full lists each step, not deltas: idempotent under migration
        overlap and crash replay (a restarted stream re-emits a prefix
        of itself; consumers keep a high-water mark)."""
        out = {r.rid: list(r.generated) for r in self.active.values()}
        out.update({r.rid: list(r.generated)
                    for r in self.prefilling.values()})
        return out

    def run_until_done(self, max_steps: int = 10_000):
        out = []
        steps = 0
        while (self.queue or self.active or self.prefilling) \
                and steps < max_steps:
            fin = self.step() or []
            out.extend(fin)
            steps += 1
        return out

    # ------------------------------------------- live module scaling API
    def apply_plan(self, plan):
        """Apply a PlacementPlan's per-layer replication degrees (P) to
        the LIVE decode step — CoCoServe scale-up without draining: the
        next ``step()`` runs ``forward_paged`` unrolled, each layer under
        its plan-assigned batch-sharding constraint (degrees quantized to
        the local replication mesh; an all-ones plan restores the O(1)
        lax.scan step). Token streams are unaffected — resharding changes
        where the batch computes, not what it computes."""
        if self.cache_kind != "paged":
            raise ValueError("apply_plan targets the paged decode step; "
                             "dense engines predate module scaling")
        from repro.core import replication as R
        p = tuple(plan.p) if hasattr(plan, "p") else tuple(plan)
        if len(p) != self.cfg.num_layers:
            raise ValueError(f"plan covers {len(p)} layers, "
                             f"model has {self.cfg.num_layers}")
        self.replication_degrees = p
        mesh = R.default_replication_mesh()
        if all(d == 1 for d in p):
            self._step_degrees = None
            if self._replicated:
                # replicas released: weights and pool go back home
                self._place(self.device if self.device is not None
                            else jax.devices()[0])
                self._replicated = False
        else:
            self._step_degrees = tuple(
                R.quantize_degrees(list(p), mesh.devices.size))
            if mesh.devices.size > 1 and not self._replicated:
                # every replica holds a copy of the weights (and the pool
                # the step reads and writes), so the replicated layers
                # never ship them per step
                self._place(NamedSharding(mesh, PartitionSpec()))
                self._replicated = True

    def _place(self, where):
        """Move the weights and the KV pool to ``where`` (a device or a
        sharding)."""
        self.params = jax.device_put(self.params, where)
        self.pstate.k = jax.device_put(self.pstate.k, where)
        self.pstate.v = jax.device_put(self.pstate.v, where)

    # --------------------------------------- request migration (paged)
    def pause_request(self, slot: int,
                      since_epoch: Optional[int] = None) -> dict:
        """Detach the ACTIVE request in ``slot`` and export its full
        serving state: KV blocks (paged_kv.export_blocks wire format),
        position (token count), and the counter-based sampling state —
        which is just (seed, len(generated)), carried by the Request
        itself. Shared (refcount > 1) blocks are MATERIALIZED into the
        payload with their prefix keys, so the export is self-contained;
        the slot then releases its claim (decref — co-holders of shared
        blocks are untouched, sole-owned blocks return to the pool).
        ``resume_request`` on any engine with identical cfg/params
        continues the stream token-identically.

        ``since_epoch`` (a prior ``snapshot_request``'s ``epoch``) makes
        this the phase-2 pause of an OVERLAPPED migration: the payload
        carries only the blocks written since the snapshot — the short
        delta the destination's ``commit_resume`` applies over its
        staged phase-1 base."""
        if self.cache_kind != "paged":
            raise ValueError("pause/resume migrates paged KV blocks; "
                             "dense slabs go through core.migration")
        if slot in self.active:
            req = self.active.pop(slot)
            phase = "decode"
        else:
            # a MID-PREFILL request pauses too: the cursor (lengths ==
            # prefill_pos) and the chunk-written blocks travel in the
            # payload, so the destination resumes WITHOUT replaying the
            # prefill work that already landed
            req = self.prefilling.pop(slot)
            self._prefill_matched.pop(slot, None)
            phase = "prefill"
        self._admit_order.remove(slot)
        if self.span_hook:
            self.span_hook.on_pause(req.rid)
        payload = PK.export_blocks(self.pstate, slot,
                                   since_epoch=since_epoch)
        PK.free_slot(self.pstate, slot)
        req.slot = None
        # "position"/"counter" are INFORMATIONAL wire-format mirrors (for
        # cross-host transports/logging); the authoritative copies travel
        # inside the payload: import_blocks restores position from
        # kv["length"], the sampler re-derives the counter from
        # len(request.generated). "v" stamps the payload shape — resume
        # ops reject a mismatch loudly instead of KeyError-ing mid-bind.
        return {"v": MIGRATION_WIRE_VERSION, "request": req, "kv": payload,
                "position": payload["length"],
                "counter": len(req.generated),
                "phase": phase}

    @staticmethod
    def _check_payload_version(payload: dict, op: str):
        """Reject an old- or alien-shape migration payload with a clear
        error (surfaced as ``RemoteError`` over RPC) rather than letting
        a missing field KeyError deep inside the bind path."""
        v = payload.get("v") if isinstance(payload, dict) else None
        if v != MIGRATION_WIRE_VERSION:
            raise ValueError(
                f"{op}: migration payload version {v!r} unsupported "
                f"(this engine speaks v{MIGRATION_WIRE_VERSION}; "
                "re-export from a matching peer)")

    def resume_request(self, payload: dict) -> bool:
        """Rebind a paused request's blocks into this engine's pool and
        put it back in decode rotation. Imported blocks arrive OWNED
        (refcount 1); prefix keys carried in the payload re-seed this
        pool's cache so later admissions can alias the migrated prompt.
        Returns False — WITHOUT dropping the request or touching the pool
        — when no slot or not enough blocks are free (the caller
        re-queues it; counter-based sampling replays the continuation
        deterministically)."""
        if self.cache_kind != "paged":
            raise ValueError("resume_request needs a paged engine")
        self._check_payload_version(payload, "resume_request")
        req = payload["request"]
        free = self._free_slots()
        if not free:
            return False
        slot = free[0]
        try:
            PK.import_blocks(self.pstate, slot, payload["kv"])
        except PK.OutOfBlocks:
            return False
        self._bind_resumed(req, slot, payload)
        return True

    def _bind_resumed(self, req: Request, slot: int, payload: dict):
        """Place a migrated-in request: decode rotation normally, or —
        when it was paused MID-PREFILL — the prefilling set, cursor
        restored from the imported length, where the scheduler's next
        plan grants its remaining chunks (the phase scheduler drains it
        with one full-remainder chunk)."""
        req.slot = slot
        if payload.get("phase", "decode") == "prefill":
            req.prefill_pos = int(payload["kv"]["length"])
            self.prefilling[slot] = req
        else:
            self.active[slot] = req
        self._admit_order.append(slot)  # migrated-in = youngest
        if self.span_hook:
            self.span_hook.on_resume(req, payload.get("phase", "decode"))

    # ------------------------------- overlapped (two-phase) migration
    def snapshot_request(self, slot: int) -> dict:
        """Phase 1 of an overlapped migration: export the ACTIVE request
        in ``slot`` WITHOUT detaching it — the stream keeps decoding
        while the bulk payload travels and the destination stages it
        (``prepare_resume``). The returned ``epoch`` is the dirty-set
        cursor: pass it to ``pause_request(slot, since_epoch=epoch)``
        for the phase-2 delta (blocks written since this snapshot)."""
        if self.cache_kind != "paged":
            raise ValueError("snapshot_request needs a paged engine")
        req = (self.active.get(slot) or self.prefilling[slot])
        payload = PK.export_blocks(self.pstate, slot)
        return {"v": MIGRATION_WIRE_VERSION, "rid": req.rid, "kv": payload,
                "epoch": payload["epoch"], "position": payload["length"]}

    def prepare_resume(self, snap: dict) -> Optional[int]:
        """Stage a phase-1 snapshot into this pool: import the blocks
        into a free slot that admission cannot touch (``_staged``), but
        do NOT activate anything — the request itself is still decoding
        at the source. Returns the staging slot, or None (without
        mutating the pool) when no slot or not enough blocks are free."""
        if self.cache_kind != "paged":
            raise ValueError("prepare_resume needs a paged engine")
        self._check_payload_version(snap, "prepare_resume")
        free = self._free_slots()
        if not free:
            return None
        slot = free[0]
        try:
            PK.import_blocks(self.pstate, slot, snap["kv"])
        except PK.OutOfBlocks:
            return None
        self._staged[slot] = snap["rid"]
        return slot

    def commit_resume(self, slot: int, payload: dict) -> bool:
        """Phase 2: apply the pause-time delta over the staged base and
        put the request into decode rotation. ``payload`` is the source's
        ``pause_request(slot, since_epoch=snapshot epoch)`` result. On
        OutOfBlocks (the delta needed new columns a now-full pool can't
        provide) the staging is rolled back and False returned — the
        caller re-queues the request, which replays deterministically."""
        assert slot in self._staged, f"slot {slot} holds no staged import"
        self._check_payload_version(payload, "commit_resume")
        req = payload["request"]
        try:
            PK.import_blocks_delta(self.pstate, slot, payload["kv"])
        except PK.OutOfBlocks:
            self.abort_resume(slot)
            return False
        del self._staged[slot]
        # the phase is decided at PAUSE time: a request snapshotted
        # mid-prefill may have finished its prompt during the overlap
        # steps — the delta carries the later writes either way
        self._bind_resumed(req, slot, payload)
        return True

    def abort_resume(self, slot: int):
        """Drop a staged phase-1 import (source died, request finished
        at the source, or the caller chose replay): free the staged
        blocks and return the slot to admission."""
        if slot in self._staged:
            del self._staged[slot]
            PK.free_slot(self.pstate, slot)
