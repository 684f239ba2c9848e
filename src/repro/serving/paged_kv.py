"""Paged KV cache — the block-table pool behind the Engine's PRIMARY
decode path (serving/engine.py with ``cache_kind="paged"``).

Layout: a global pool of fixed-size blocks per layer, stored
KV-HEAD-MAJOR — ``k/v: [L, n_blocks, KV, bs, hd]`` — so each block's
page ``[KV, bs, hd]`` (every kv head) is contiguous. That is exactly what
the Pallas decode kernel (kernels/paged_decode.py) copies with one DMA per
page, so the kernel reads the pool natively instead of transposing the
whole pool per call (which would defeat its length-proportional HBM
traffic on real hardware). A per-request block table ``[B, max_blocks]`` of pool indices
(-1 = unallocated) maps absolute token position ``p`` to table column
``p // block_size``.

Allocation is on-demand per ``block_size`` tokens, so memory — and
decode-step HBM traffic — scales with *actual* tokens (the paged-KV
property that prevents the HFT static-reservation OOMs, and the substrate
CoCoServe's module replication moves around: KV blocks, not dense slabs).
Freeing a request returns whole blocks to the pool; fragmentation is
bounded by ``block_size - 1`` tokens per request. Sliding-window archs
additionally return *leading* blocks once every token in them has fallen
out of the attention window (``free_out_of_window``) — the block table
keeps holes (-1) at those columns, and allocation is column-indexed so
holes never get rewritten.

Ownership model (prefix sharing / copy-on-write, vLLM §4.3):

Every block carries a REFERENCE COUNT. A block is in exactly one of four
states, and every transition goes through ``_incref``/``_decref``:

* **free**      — refcount 0, on ``state.free``; content is garbage.
* **owned**     — refcount 1, bound in exactly one block table; the owner
  may write into it (``allocate`` hands blocks out in this state).
* **shared**    — refcount > 1, bound in several block tables (prompt-
  prefix aliasing); READ-ONLY: any stream about to write into a shared
  block must fork it first (``ensure_writable`` — the copy-on-write).
* **cached-free** — refcount 0 but still holding a registered full
  prompt block: parked on the LRU ``cached_free`` list, revivable by a
  later ``match_prefix`` hit, evicted (cache entry dropped) only under
  allocation pressure.

The prefix cache keys FULL prompt blocks by a content chain hash
(``H(parent_key, block_tokens)``), so a hit on block c guarantees tokens
``[0, (c+1)*bs)`` are identical — and, K/V being a deterministic function
of the token prefix and absolute positions, the cached block's contents
are exactly what a fresh prefill would recompute. Admissions that hit
alias the cached blocks instead of re-prefilling them; the engine runs
prefill only over the suffix.

Division of labour with the engine:

* ``allocate`` / ``free_slot`` / ``free_out_of_window`` and the prefix-
  cache ops (``match_prefix`` / ``adopt_prefix`` / ``register_prefix`` /
  ``ensure_writable``) run on the HOST free list + refcounts (forking is
  the only one that touches the device: one pool-block copy);
* ``scatter_plan`` is the host half of every prefill write into the
  pool: the engine's jitted chunk-prefill program does the device half
  (misses, prefix hits and chunks alike), and ``write_tokens`` is the
  eager single-request write that migration tooling and tests use;
* ``export_blocks`` / ``import_blocks`` are the block-granular migration
  wire format (DESIGN.md): CoCoServe's scale-down moves a live request's
  KV blocks between instances' pools without touching dense slabs;
  shared blocks are MATERIALIZED into the payload (content copied) and
  their prefix keys travel along, so the destination can re-seed its own
  cache — sharing survives migration without cross-pool refcounts.
  An import whose carried prefix key is ALREADY RESIDENT in the
  destination cache aliases (increfs) the resident block instead of
  materializing a duplicate — cross-instance dedupe (content-chain keys
  certify identical content, so aliasing is exact);
* the DIRTY SET behind overlapped migration: every pool write stamps the
  written blocks with a monotonically increasing ``write_epoch``
  (``mark_written``), so ``export_blocks(..., since_epoch=e)`` can ship
  only the blocks touched after a phase-1 snapshot — the short delta a
  two-phase migration pause-copies while the bulk streamed overlapped
  with decode (``import_blocks_delta`` applies it over the staged base);
* the per-step decode read and write is ``models.transformer.forward_paged``
  — an XLA scatter of each row's new token plus a gather over the block
  table inside the jitted step, or the Pallas kernel in
  kernels/paged_decode.py, which reads ``pool[layer, block]`` pages of the
  whole pool and writes each new token into its page in place (the step
  never copies the pool);
* ``paged_attention_ref`` below is the vectorized pure-jnp oracle both
  are tested against.
"""
from __future__ import annotations

import dataclasses
import hashlib
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig


@dataclasses.dataclass
class PagedState:
    """Device arrays + host-side free list / refcounts for one engine.

    Invariants (asserted by tests/test_prefix_sharing.py):

    * ``refcount[b] == 0``  iff  ``b`` is on ``free`` or ``cached_free``;
    * ``refcount[b]`` equals the number of block-table cells holding ``b``;
    * ``block_key[b] == key`` iff ``prefix_cache[key] == b`` (a bijection
      over registered blocks);
    * blocks on ``free`` are never registered; blocks on ``cached_free``
      always are (their cache entry is dropped when they are evicted).
    """
    k: jnp.ndarray            # [L, n_blocks, KV, bs, hd] (KV-head-major)
    v: jnp.ndarray
    block_tables: np.ndarray  # [B, max_blocks] int32 host array (-1 empty)
    lengths: np.ndarray       # [B] int32 host array
    free: List[int]
    block_size: int
    # --- prefix sharing / copy-on-write ---
    refcount: Optional[np.ndarray] = None     # [n_blocks] int32
    enable_prefix_cache: bool = False
    prefix_cache: Dict[bytes, int] = dataclasses.field(default_factory=dict)
    block_key: Dict[int, bytes] = dataclasses.field(default_factory=dict)
    cached_free: "OrderedDict[int, None]" = \
        dataclasses.field(default_factory=OrderedDict)
    # --- dirty set for overlapped (two-phase) migration ---
    write_epoch: int = 0                       # bumps once per pool write
    block_epoch: Optional[np.ndarray] = None   # [n_blocks] int64 last write
    # --- counters (feed serving/instrument + core/monitor gauges) ---
    prefix_queries: int = 0       # full prompt blocks looked up
    prefix_hits: int = 0          # ... of which aliased an existing block
    cow_forks: int = 0            # copy-on-write block copies performed
    blocks_saved_total: int = 0   # cumulative allocations avoided by hits
    dedup_imports: int = 0        # imported blocks aliased to residents

    def __post_init__(self):
        if self.refcount is None:     # direct constructions (tests, tools)
            self.refcount = np.zeros((self.k.shape[1],), np.int32)
        if self.block_epoch is None:
            self.block_epoch = np.zeros((self.k.shape[1],), np.int64)

    @property
    def n_blocks(self) -> int:
        return self.k.shape[1]

    def free_block_count(self) -> int:
        """Blocks allocatable right now: the plain free list plus the
        cached-free (refcount-0 but prefix-registered) blocks that
        allocation pressure may evict."""
        return len(self.free) + len(self.cached_free)

    def blocks_in_use(self) -> int:
        return self.n_blocks - self.free_block_count()

    def shared_blocks_saved(self) -> int:
        """Physical blocks the pool is saving RIGHT NOW through sharing:
        each block referenced r > 1 times stands in for r - 1 copies."""
        return int(np.maximum(self.refcount - 1, 0).sum())

    def pool_bytes(self) -> int:
        return int(self.k.size * self.k.dtype.itemsize
                   + self.v.size * self.v.dtype.itemsize)

    def utilization(self) -> float:
        """Fraction of allocated slots actually holding tokens (1 - frag).
        Capped at 1: windowed requests count absolute ``lengths`` but only
        hold their live (in-window) blocks, and shared blocks serve
        several requests' tokens at once."""
        used_blocks = self.blocks_in_use()
        if used_blocks == 0:
            return 1.0
        toks = int(self.lengths.sum())
        return min(1.0, toks / (used_blocks * self.block_size))


def init_paged(cfg: ModelConfig, max_batch: int, n_blocks: int,
               block_size: int = 16, dtype="bfloat16",
               max_len: int = 4096,
               prefix_cache: bool = False) -> PagedState:
    """Build a pool. ``prefix_cache=True`` enables prompt-prefix sharing:
    full prompt blocks are content-hashed so later admissions alias them
    (the Engine turns this on for its paged path by default)."""
    dtype = jnp.dtype(dtype)
    hd = cfg.resolved_head_dim
    L, KV = cfg.num_layers, cfg.num_kv_heads
    max_blocks = -(-max_len // block_size)
    shape = (L, n_blocks, KV, block_size, hd)
    return PagedState(
        k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
        block_tables=np.full((max_batch, max_blocks), -1, np.int32),
        lengths=np.zeros((max_batch,), np.int32),
        free=list(range(n_blocks)), block_size=block_size,
        refcount=np.zeros((n_blocks,), np.int32),
        enable_prefix_cache=prefix_cache)


class OutOfBlocks(RuntimeError):
    pass


# ----------------------------------------------------- refcount primitives
def _pop_block(state: PagedState) -> int:
    """Take a refcount-0 block for a new owner: plain free list first,
    then the OLDEST cached-free block (its prefix-cache entry is evicted
    — LRU under allocation pressure). Raises OutOfBlocks, mutating
    nothing, when neither has one."""
    if state.free:
        return state.free.pop()
    if state.cached_free:
        b = next(iter(state.cached_free))
        del state.cached_free[b]
        key = state.block_key.pop(b)
        state.prefix_cache.pop(key, None)
        return b
    raise OutOfBlocks("pool exhausted: no free or cached-free blocks")


def _incref(state: PagedState, b: int):
    if int(state.refcount[b]) == 0:
        # reviving a cached-free block: content stays valid, it just
        # leaves the evictable list
        state.cached_free.pop(b, None)
    state.refcount[b] += 1


def _decref(state: PagedState, b: int):
    state.refcount[b] -= 1
    assert state.refcount[b] >= 0, f"refcount underflow on block {b}"
    if state.refcount[b] == 0:
        if b in state.block_key:        # registered: stay revivable
            state.cached_free[b] = None  # most-recently-freed = LRU tail
        else:
            state.free.append(b)


def mark_written(state: PagedState, block_ids) -> int:
    """Stamp ``block_ids`` as written at a fresh ``write_epoch`` — the
    dirty-set bookkeeping behind two-phase migration: a later
    ``export_blocks(..., since_epoch=e)`` ships exactly the blocks
    stamped after epoch ``e``. Called by every pool-content writer: the
    prefill scatter (``scatter_plan``), the fused decode step's host
    bookkeeping (serving/engine.py), CoW forks, and imports. Returns the
    new epoch."""
    state.write_epoch += 1
    ids = [int(b) for b in block_ids if 0 <= int(b) < state.n_blocks]
    if ids:
        state.block_epoch[ids] = state.write_epoch
    return state.write_epoch


# -------------------------------------------------------------- allocation
def dead_columns(length: int, block_size: int, window: int) -> int:
    """Leading block-table columns wholly out of the attention window of
    the query at position ``length``: it attends keys with position >
    ``length - window`` (see layers._attn_mask), so column c is dead once
    ``(c+1)*bs - 1 <= length - window``. The one rule behind window
    allocation, reclamation and the engine's admission bound."""
    return max((length - window + 1) // block_size, 0)


def allocate(state: PagedState, slot: int, n_tokens: int,
             window: Optional[int] = None):
    """Ensure ``slot`` has blocks for lengths[slot] + n_tokens tokens.

    Fresh blocks come out OWNED (refcount 1) by ``slot``. Column-indexed:
    position ``p`` lives in table column ``p // bs``, so a row with
    leading holes (sliding-window freeing) or an aliased shared prefix
    only allocates the columns the new tokens actually land in. With
    ``window``, columns already fully OUT of the attention window after
    the write are never allocated at all — a long prompt admitted into a
    window-sized pool only claims its live suffix (plus the current write
    head), never transient full-prompt residency. Raises OutOfBlocks —
    WITHOUT mutating any state — when the pool has too few free blocks or
    the needed column exceeds the table row (context > ``max_len``).
    Under pressure the pool evicts cached-free blocks (oldest first) to
    satisfy the request."""
    if n_tokens <= 0:
        return
    bs = state.block_size
    start = int(state.lengths[slot])
    first_col = start // bs
    last_col = (start + n_tokens - 1) // bs
    if window is not None:
        # columns dead for the next query (pos start+n_tokens)
        dead = dead_columns(start + n_tokens, bs, window)
        first_col = max(first_col, min(dead, last_col))
    if last_col >= state.block_tables.shape[1]:
        raise OutOfBlocks(
            f"slot {slot} block table full: needs column {last_col}, "
            f"table holds {state.block_tables.shape[1]}")
    missing = [c for c in range(first_col, last_col + 1)
               if state.block_tables[slot, c] < 0]
    if len(missing) > state.free_block_count():
        raise OutOfBlocks(
            f"need {len(missing)} blocks, {state.free_block_count()} free")
    for c in missing:
        b = _pop_block(state)
        state.refcount[b] = 1
        state.block_tables[slot, c] = b


def free_slot(state: PagedState, slot: int):
    """Release ``slot``'s claim on every block it holds (DECREF, not
    unconditional free): an owned block returns to the pool, a shared
    block survives for its other holders, and a registered block parks on
    the cached-free list so later admissions can still alias it."""
    for b in state.block_tables[slot]:
        if b >= 0:
            _decref(state, int(b))
    state.block_tables[slot] = -1
    state.lengths[slot] = 0


def free_out_of_window(state: PagedState, slot: int, window: int) -> int:
    """Sliding-window reclamation: release the leading blocks of ``slot``
    whose every token has fallen out of the attention window.

    The next query sits at position ``lengths[slot]``; the columns
    ``dead_columns`` counts for it become holes (-1) that the masked
    attention never reads and column-indexed ``allocate`` never refills.
    Out-of-window release is a DECREF like any other: a block another
    stream still references merely loses this slot's claim. Returns
    #blocks this slot released.

    Called per slot per decode step, so it must not rescan history: dead
    columns below the newly-dead ones are already holes (freed earlier or
    window-skipped at allocation), hence the backward scan stops at the
    first hole — O(newly dead + 1) per call, O(1) amortized.
    """
    n_dead = min(dead_columns(int(state.lengths[slot]), state.block_size,
                              window),
                 state.block_tables.shape[1])
    freed = 0
    for c in range(n_dead - 1, -1, -1):
        b = int(state.block_tables[slot, c])
        if b < 0:
            break
        _decref(state, b)
        state.block_tables[slot, c] = -1
        freed += 1
    return freed


# ----------------------------------------------------------- prefix cache
def _chain_keys(tokens, block_size: int) -> List[bytes]:
    """Content chain hash of every FULL block of ``tokens``: key_c =
    H(key_{c-1} || tokens[c*bs:(c+1)*bs]). Keying block c on the whole
    prefix (not just its own tokens) is what makes a hit mean "identical
    tokens from position 0" — the property that lets cached K/V stand in
    for a fresh prefill."""
    toks = np.ascontiguousarray(np.asarray(tokens, np.int32))
    keys, h = [], b""
    for c in range(len(toks) // block_size):
        h = hashlib.sha1(
            h + toks[c * block_size:(c + 1) * block_size].tobytes()).digest()
        keys.append(h)
    return keys


def match_prefix(state: PagedState, tokens, *,
                 record: bool = True) -> List[int]:
    """Longest cached prefix of ``tokens``: returns the pool block ids
    (in column order) of the leading FULL blocks whose content chain is
    registered. Read-only apart from the hit/query counters; the caller
    decides whether to ``adopt_prefix`` the result. Empty when the cache
    is disabled or nothing matches.

    ``record=False`` skips the counters — the engine uses it so that
    backpressure retries (the same queued prompt re-matched every step)
    don't inflate the hit-rate gauge; it records once per ADMITTED
    request via ``record_lookup``."""
    if not state.enable_prefix_cache:
        return []
    keys = _chain_keys(tokens, state.block_size)
    if not keys:
        return []
    out: List[int] = []
    for key in keys:
        b = state.prefix_cache.get(key)
        if b is None:
            break
        out.append(b)
    if record:
        state.prefix_queries += len(keys)
        state.prefix_hits += len(out)
    return out


def record_lookup(state: PagedState, tokens, matched: Sequence[int]):
    """Count one prefix-cache lookup in the hit-rate gauges: the full
    blocks of ``tokens`` as queries, ``matched`` as hits (which are also
    allocations avoided -> blocks_saved_total). Engines call this once
    per SUCCESSFULLY admitted request — never per attempt, so
    backpressure retries and fork-failure requeues don't skew the
    gauges."""
    state.prefix_queries += len(tokens) // state.block_size
    state.prefix_hits += len(matched)
    state.blocks_saved_total += len(matched)


def adopt_prefix(state: PagedState, slot: int, block_ids: Sequence[int],
                 n_tokens: int):
    """Alias a matched prefix into ``slot``: INCREF each block and bind it
    at its column; ``slot`` then owns ``n_tokens`` of context without a
    single pool write or prefill FLOP. ``n_tokens`` may stop short of the
    aliased span (the engine caps it at prompt_len - 1 so there is always
    at least one suffix token to recompute for first-token logits — the
    write-back into the shared tail block is what copy-on-write forks).
    Requires an empty slot row at those columns."""
    assert n_tokens <= len(block_ids) * state.block_size
    for c, b in enumerate(block_ids):
        assert state.block_tables[slot, c] < 0, \
            f"adopt into occupied column {c} of slot {slot}"
        _incref(state, int(b))
        state.block_tables[slot, c] = int(b)
    state.lengths[slot] = n_tokens


def register_prefix(state: PagedState, slot: int, tokens) -> int:
    """Publish ``slot``'s FULL, fully-written blocks into the prefix
    cache so later admissions can alias them. First binding of a key
    wins; partially-filled tail blocks and window holes are skipped.
    Registration does not change ownership — the block stays with its
    refcount, it merely becomes discoverable (and, once its refcount
    drops to 0, parks on cached_free instead of the free list).
    Returns the number of newly registered blocks."""
    if not state.enable_prefix_cache:
        return 0
    n = 0
    for c, key in enumerate(_chain_keys(tokens, state.block_size)):
        b = int(state.block_tables[slot, c])
        if b < 0 or key in state.prefix_cache or b in state.block_key:
            continue
        state.prefix_cache[key] = b
        state.block_key[b] = key
        n += 1
    return n


def ensure_writable(state: PagedState, slot: int, start: int,
                    n_tokens: int) -> int:
    """Copy-on-write: fork every SHARED block that the write of
    ``n_tokens`` tokens at position ``start`` would touch. A fork takes a
    fresh block (OutOfBlocks if none — no partial table corruption: the
    failing column is untouched), device-copies the shared block's pool
    content, rebinds ``slot``'s column to the private copy and DECREFs
    the original (which stays alive for its other holders, cache entry
    included). Owned (refcount-1) blocks pass through untouched — writes
    there are already private. Returns the number of forks performed."""
    if n_tokens <= 0:
        return 0
    bs = state.block_size
    pairs = []              # (shared src block, private dst block)
    try:
        for c in range(start // bs, (start + n_tokens - 1) // bs + 1):
            if c >= state.block_tables.shape[1]:
                break
            b = int(state.block_tables[slot, c])
            if b < 0 or int(state.refcount[b]) <= 1:
                continue
            nb = _pop_block(state)
            state.refcount[nb] = 1
            state.refcount[b] -= 1  # still >= 1: other holders keep it
            state.block_tables[slot, c] = nb
            state.cow_forks += 1
            pairs.append((b, nb))
    finally:
        # ONE batched gather+scatter for all forks (a functional pool
        # update copies the whole array, so per-fork .set calls would
        # cost N pool copies); the finally keeps already-rebound columns
        # backed by real content even when a later column's pop raises
        if pairs:
            src = jnp.asarray([p[0] for p in pairs], jnp.int32)
            dst = jnp.asarray([p[1] for p in pairs], jnp.int32)
            state.k = state.k.at[:, dst].set(state.k[:, src])
            state.v = state.v.at[:, dst].set(state.v[:, src])
            # a fork rebinds the column to a new physical block: the old
            # stamp lives on the old id, so the copy must be stamped for
            # the migration dirty set to ship the column's new content
            mark_written(state, [p[1] for p in pairs])
    return len(pairs)


def prefix_stats(state: PagedState) -> Dict:
    """The pool's sharing gauges (engine/orchestrator telemetry)."""
    q = state.prefix_queries
    return {"queries": q, "hits": state.prefix_hits,
            "hit_rate": state.prefix_hits / q if q else 0.0,
            "cow_forks": state.cow_forks,
            "blocks_saved_total": state.blocks_saved_total,
            "blocks_saved_now": state.shared_blocks_saved(),
            "dedup_imports": state.dedup_imports,
            "cached_blocks": len(state.prefix_cache)}


# ------------------------------------------------------------- pool writes
def scatter_plan(state: PagedState, slots, S: int,
                 lengths: Optional[Sequence[int]] = None):
    """Host half of a batched S-token append for G slots: per-token
    (block, offset) scatter indices, flattened [G*S]. Pad positions
    (``>= lengths[i]``) and unallocated columns (window-skipped prefill
    prefixes; -1 would WRAP, not drop) point at the out-of-range block
    ``n_blocks`` so a ``mode="drop"`` scatter discards them. ADVANCES
    ``state.lengths`` and stamps the write epoch — callers must execute
    the device scatter they planned (``write_tokens``, or the engine's
    fused chunk-prefill executable)."""
    bs = state.block_size
    if lengths is None:
        lengths = [S] * len(slots)
    n_pool = state.n_blocks
    max_col = state.block_tables.shape[1] - 1
    blocks, offs = [], []
    for slot, n in zip(slots, lengths):
        start = int(state.lengths[slot])
        pos = np.arange(start, start + S)
        cols = np.minimum(pos // bs, max_col)
        blk = state.block_tables[slot, cols]
        blk = np.where((np.arange(S) < n) & (blk >= 0), blk, n_pool)
        blocks.append(blk)
        offs.append(pos % bs)
        state.lengths[slot] = start + n
    bidx = np.concatenate(blocks)
    mark_written(state, np.unique(bidx))
    return bidx, np.concatenate(offs)


def write_tokens(state: PagedState, slot: int, k_new, v_new):
    """Append k/v for S new tokens of one request (k_new/v_new:
    [L, S, KV, hd]) in one functional pool scatter, outside any jit: the
    single-slot write that migration tooling and tests use (the engine's
    prefill writes ride inside its jitted chunk-prefill program).
    Requires allocate() first, and — refcount contract — every written
    column must be OWNED (refcount 1) by ``slot``: fork shared columns
    with ``ensure_writable`` first. Returns ``state`` with the updated
    device arrays."""
    S = k_new.shape[1]
    bidx, oidx = scatter_plan(state, [slot], S)
    bidx = jnp.asarray(bidx, jnp.int32)                     # [S]
    oidx = jnp.asarray(oidx, jnp.int32)
    # pool is [L, n_blocks, KV, bs, hd]: advanced indices at axes 1 and 3
    # move to the front, so updates are laid out [S, L, KV, hd]
    kf = jnp.asarray(k_new).transpose(1, 0, 2, 3)
    vf = jnp.asarray(v_new).transpose(1, 0, 2, 3)
    state.k = state.k.at[:, bidx, :, oidx].set(kf.astype(state.k.dtype),
                                               mode="drop")
    state.v = state.v.at[:, bidx, :, oidx].set(vf.astype(state.v.dtype),
                                               mode="drop")
    return state


# --------------------------------------------------- migration wire format
def export_blocks(state: PagedState, slot: int,
                  since_epoch: Optional[int] = None) -> Dict:
    """Serialize one request's KV to the block-granular migration wire
    format (DESIGN.md §block-migration): the live block-table COLUMNS
    (absolute position // block_size — holes from sliding-window freeing
    are preserved), the pool blocks at those columns as host arrays, the
    token count, and — for prefix-registered blocks — their content-chain
    ``keys`` (hex, per column) so the destination can re-seed its own
    prefix cache. SHARED blocks are materialized (content copied into the
    payload): refcounts never cross pools, so the payload is always
    self-contained and import-side correctness cannot depend on the
    source pool's sharing structure. Does NOT free or decref the source
    blocks — callers pair this with ``free_slot`` once the payload is
    safely away.

    ``since_epoch`` selects the DELTA wire format: only columns whose
    current block was written after that epoch (the dirty set since a
    phase-1 snapshot — decode-step appends, CoW forks, new columns) are
    shipped. The payload's ``epoch`` field is the pool's write epoch at
    export time: pass a snapshot's ``epoch`` back as ``since_epoch`` to
    get exactly the writes that landed in between.
    """
    cols = np.nonzero(state.block_tables[slot] >= 0)[0].astype(np.int32)
    if since_epoch is not None and len(cols):
        ids_np = state.block_tables[slot, cols]
        dirty = state.block_epoch[ids_np] > since_epoch
        cols = cols[dirty]
    if len(cols) == 1:
        # the overlapped-migration delta is usually ONE tail block: a
        # static slice + host copy beats the XLA gather by ~10x on CPU
        # pools, and this runs inside the migration's only stall window
        b = int(state.block_tables[slot, cols[0]])
        k = np.asarray(state.k[:, b])[:, None]  # [L, 1, KV, bs, hd]
        v = np.asarray(state.v[:, b])[:, None]
    elif len(cols):
        ids = jnp.asarray(state.block_tables[slot, cols], jnp.int32)
        k = np.asarray(state.k[:, ids])        # [L, n, KV, bs, hd]
        v = np.asarray(state.v[:, ids])
    else:
        L, _, KV, bs, hd = state.k.shape
        k = np.zeros((L, 0, KV, bs, hd), state.k.dtype)
        v = np.zeros((L, 0, KV, bs, hd), state.v.dtype)
    keys = {}
    for c in cols:
        b = int(state.block_tables[slot, c])
        if b in state.block_key:
            keys[int(c)] = state.block_key[b].hex()
    return {"cols": cols, "k": k, "v": v,
            "length": int(state.lengths[slot]),
            "block_size": state.block_size,
            "keys": keys,
            "epoch": state.write_epoch,
            "nbytes": int(k.nbytes + v.nbytes)}


def _register_carried_keys(state: PagedState, slot: int, payload: Dict):
    """Re-seed this pool's prefix cache from a payload's carried keys —
    first binding wins, so resident entries are never displaced."""
    if not state.enable_prefix_cache:
        return
    for c, hexkey in payload.get("keys", {}).items():
        key = bytes.fromhex(hexkey)
        b = int(state.block_tables[slot, int(c)])
        if b < 0 or key in state.prefix_cache or b in state.block_key:
            continue                    # existing binding wins
        state.prefix_cache[key] = b
        state.block_key[b] = key


def import_blocks(state: PagedState, slot: int, payload: Dict) -> PagedState:
    """Materialize an exported request into ``slot`` of (another) pool:
    allocate fresh OWNED (refcount-1) blocks, rebind them at the SAME
    table columns (absolute positions are preserved, so RoPE/window
    masking and the counter-based sampling replay are untouched), and
    scatter the block data in. Carried prefix ``keys`` are re-registered
    into this pool's cache (first binding wins) so admissions AFTER the
    migration can alias the migrated prompt — sharing structure survives
    the hop even though refcounts are pool-local.

    CROSS-INSTANCE DEDUPE: a column whose carried key is already
    resident in this pool's prefix cache ALIASES the resident block
    (incref — possibly reviving it off ``cached_free``) instead of
    materializing a duplicate copy. The content-chain key certifies the
    token prefix, and K/V is a deterministic function of it, so the
    resident content IS the payload content for that column. The aliased
    column arrives SHARED like any prefix hit; writes into it fork first
    (``ensure_writable``), exactly as for a same-pool alias.

    Raises OutOfBlocks without mutating state when the pool or the
    table row can't hold the payload."""
    if payload["block_size"] != state.block_size:
        raise ValueError(
            f"block_size mismatch: payload {payload['block_size']} "
            f"vs pool {state.block_size}")
    if (state.block_tables[slot] >= 0).any():
        raise ValueError(f"import into non-empty slot {slot}")
    cols = np.asarray(payload["cols"], np.int64)
    n = len(cols)
    if n and int(cols.max()) >= state.block_tables.shape[1]:
        raise OutOfBlocks(
            f"import needs column {int(cols.max())}, table holds "
            f"{state.block_tables.shape[1]}")
    alias: Dict[int, int] = {}          # payload index -> resident block
    if state.enable_prefix_cache:
        for i, c in enumerate(cols):
            hexkey = payload.get("keys", {}).get(int(c))
            if hexkey is None:
                continue
            b = state.prefix_cache.get(bytes.fromhex(hexkey))
            if b is not None:
                alias[i] = b
    fresh = [i for i in range(n) if i not in alias]
    # reviving a cached-free resident consumes a unit of vacancy too —
    # account for it so the no-mutation-on-raise contract holds exactly
    revive = len({b for b in alias.values() if int(state.refcount[b]) == 0})
    if len(fresh) > state.free_block_count() - revive:
        raise OutOfBlocks(f"import needs {len(fresh)} blocks, "
                          f"{state.free_block_count() - revive} free")
    for i, b in alias.items():          # incref FIRST: aliased residents
        _incref(state, b)               # must not be evicted by the pops
        state.block_tables[slot, cols[i]] = b
    ids = [_pop_block(state) for _ in fresh]
    for b in ids:
        state.refcount[b] = 1
    if fresh:
        state.block_tables[slot, cols[fresh]] = np.asarray(ids, np.int32)
    state.lengths[slot] = payload["length"]
    state.dedup_imports += len(alias)
    state.blocks_saved_total += len(alias)
    if fresh:
        idx = jnp.asarray(ids, jnp.int32)
        sel = np.asarray(fresh, np.int64)
        state.k = state.k.at[:, idx].set(
            jnp.asarray(payload["k"][:, sel]).astype(state.k.dtype))
        state.v = state.v.at[:, idx].set(
            jnp.asarray(payload["v"][:, sel]).astype(state.v.dtype))
        mark_written(state, ids)
    _register_carried_keys(state, slot, payload)
    return state


def import_blocks_delta(state: PagedState, slot: int,
                        payload: Dict) -> PagedState:
    """Apply a DELTA export (``export_blocks(..., since_epoch=...)``)
    over a previously imported phase-1 base in ``slot`` — the commit
    half of two-phase migration. Columns already staged are overwritten
    in place when the staged block is exclusively owned; a staged block
    that became shared (an admission aliased it) or registered is
    REBOUND to a fresh block instead — overwriting it would corrupt its
    co-holders / its cache key. New columns (decode appends past the
    snapshot) allocate fresh. ``lengths[slot]`` advances to the source's
    pause-time length. Raises OutOfBlocks without mutating state when
    the pool can't hold the new/rebound columns."""
    if payload["block_size"] != state.block_size:
        raise ValueError(
            f"block_size mismatch: payload {payload['block_size']} "
            f"vs pool {state.block_size}")
    cols = np.asarray(payload["cols"], np.int64)
    n = len(cols)
    if n and int(cols.max()) >= state.block_tables.shape[1]:
        raise OutOfBlocks(
            f"delta needs column {int(cols.max())}, table holds "
            f"{state.block_tables.shape[1]}")
    def in_place(b):
        return b >= 0 and int(state.refcount[b]) == 1 \
            and b not in state.block_key
    staged = [int(state.block_tables[slot, c]) for c in cols]
    need = sum(0 if in_place(b) else 1 for b in staged)
    if need > state.free_block_count():
        raise OutOfBlocks(f"delta needs {need} blocks, "
                          f"{state.free_block_count()} free")
    ids = []
    for c, b in zip(cols, staged):
        if in_place(b):
            ids.append(b)
            continue
        nb = _pop_block(state)
        state.refcount[nb] = 1
        if b >= 0:
            _decref(state, b)           # co-holders / cache keep the old one
        state.block_tables[slot, c] = nb
        ids.append(nb)
    state.lengths[slot] = payload["length"]
    if n == 1:
        # the common overlapped-migration delta is ONE tail block: a
        # dynamic_update_slice at the block offset lowers to a cheaper
        # kernel than a gather-scatter (~2x on CPU pools), and this op
        # sits inside the migration's only stall window
        kd = jnp.asarray(payload["k"]).astype(state.k.dtype)
        vd = jnp.asarray(payload["v"]).astype(state.v.dtype)
        at = (0, ids[0], 0, 0, 0)
        state.k = jax.lax.dynamic_update_slice(state.k, kd, at)
        state.v = jax.lax.dynamic_update_slice(state.v, vd, at)
        mark_written(state, ids)
    elif n:
        idx = jnp.asarray(ids, jnp.int32)
        state.k = state.k.at[:, idx].set(
            jnp.asarray(payload["k"]).astype(state.k.dtype))
        state.v = state.v.at[:, idx].set(
            jnp.asarray(payload["v"]).astype(state.v.dtype))
        mark_written(state, ids)
    _register_carried_keys(state, slot, payload)
    return state


# ------------------------------------------------------------ dense views
def gather_requests(state: PagedState, slots: Sequence[int], max_len: int):
    """Materialize G requests' KV as dense [L, G, max_len, KV, hd] in ONE
    batched pool gather — the context splice for BUCKETED shared-prefix
    suffix prefill (a whole hit group's contexts in one device op
    instead of one gather per request). Rows past a slot's allocated
    columns are garbage — callers mask by position."""
    bs = state.block_size
    n_blk = -(-max_len // bs)
    G = len(slots)
    tbl = state.block_tables[np.asarray(slots, np.int64), :n_blk]
    tbl = jnp.asarray(np.where(tbl >= 0, tbl, 0), jnp.int32)   # [G, n_blk]
    L, _, KV, _, hd = state.k.shape
    k = state.k[:, tbl]                       # [L, G, n_blk, KV, bs, hd]
    v = state.v[:, tbl]
    k = k.transpose(0, 1, 2, 4, 3, 5).reshape(
        L, G, n_blk * bs, KV, hd)[:, :, :max_len]
    v = v.transpose(0, 1, 2, 4, 3, 5).reshape(
        L, G, n_blk * bs, KV, hd)[:, :, :max_len]
    return k, v


def gather_request(state: PagedState, slot: int, max_len: int):
    """Single-request ``gather_requests`` (oracle / fallback path):
    dense [L, max_len, KV, hd]."""
    k, v = gather_requests(state, [slot], max_len)
    return k[:, 0], v[:, 0]


def paged_attention_ref(q, state: PagedState, slots, *, layer: int):
    """Pure-jnp paged decode attention for a batch of slots, vectorized
    over the batch (one batched gather + masked softmax — no per-slot
    Python loop, so oracle checks don't dominate test time).

    q: [B, H, hd]; returns [B, H, hd]. Oracle for kernels/paged_decode.py
    and for models.transformer.forward_paged's gather path.
    """
    import math
    B, H, hd = q.shape
    KV = state.k.shape[2]
    bs = state.block_size
    rep = H // KV
    slots = list(slots)
    lens = state.lengths[slots]                      # [B] host
    n_blk = max(1, -(-int(lens.max()) // bs))
    tbl = state.block_tables[slots, :n_blk]
    tbl = jnp.asarray(np.where(tbl >= 0, tbl, 0), jnp.int32)
    k = state.k[layer][tbl]                          # [B, n_blk, KV, bs, hd]
    v = state.v[layer][tbl]
    k = k.transpose(0, 1, 3, 2, 4).reshape(B, n_blk * bs, KV, hd)
    v = v.transpose(0, 1, 3, 2, 4).reshape(B, n_blk * bs, KV, hd)
    kh = jnp.repeat(k, rep, axis=2).astype(jnp.float32)  # [B, S, H, hd]
    vh = jnp.repeat(v, rep, axis=2).astype(jnp.float32)
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32),
                   kh) / math.sqrt(hd)
    mask = jnp.arange(n_blk * bs)[None, :] < jnp.asarray(lens)[:, None]
    s = jnp.where(mask[:, None, :], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bshd->bhd", w, vh).astype(q.dtype)
