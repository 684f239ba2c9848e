"""Prefix sharing with copy-on-write block refcounts (ISSUE-3 tentpole).

Pool-level: refcount lifecycle (free -> owned -> shared -> CoW-forked),
cached-free revival and LRU eviction, wire-format key carriage.
Engine-level: shared-system-prompt admissions alias cached blocks, skip
the shared span's prefill, stay token-identical to sharing-off (greedy
AND sampled), and use measurably fewer pool blocks. Orchestrator-level:
scale-down migration of streams holding shared blocks stays zero-drop
and token-identical.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as T
from repro.serving import paged_kv as PK
from repro.serving import engine as E
from repro.serving.engine import Engine
from repro.serving.orchestrator import Orchestrator
from repro.serving.request import RequestSpec, SamplingParams

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("tinyllama-1.1b").reduced()
    params = T.init_params(cfg, KEY, "float32")
    return cfg, params


def _check_invariants(st: PK.PagedState):
    """The PagedState refcount invariants from the dataclass docstring."""
    held = np.zeros(st.n_blocks, np.int64)
    for row in st.block_tables:
        for b in row:
            if b >= 0:
                held[b] += 1
    np.testing.assert_array_equal(held, st.refcount)
    for b in st.free:
        assert st.refcount[b] == 0 and b not in st.block_key
    for b in st.cached_free:
        assert st.refcount[b] == 0 and b in st.block_key
    for key, b in st.prefix_cache.items():
        assert st.block_key[b] == key
    assert len(st.prefix_cache) == len(st.block_key)


# ------------------------------------------------------------- pool level
def test_refcount_lifecycle(tiny):
    """free -> owned -> shared -> cached-free -> revived -> evicted."""
    cfg, _ = tiny
    st = PK.init_paged(cfg, 3, 8, block_size=4, dtype="float32",
                       max_len=32, prefix_cache=True)
    toks = np.arange(2, 11, dtype=np.int32)         # 9 tokens: 2 full blocks
    PK.allocate(st, 0, len(toks))
    assert st.blocks_in_use() == 3                  # cols 0,1,2 owned
    assert PK.register_prefix(st, 0, toks) == 2     # partial col 2 skipped
    _check_invariants(st)

    # a second slot with the same prompt aliases both full blocks
    matched = PK.match_prefix(st, toks)
    assert len(matched) == 2
    PK.adopt_prefix(st, 1, matched, 8)
    assert st.shared_blocks_saved() == 2
    assert st.blocks_in_use() == 3                  # no new physical block
    _check_invariants(st)

    # owner leaves: shared blocks survive, its private tail returns
    PK.free_slot(st, 0)
    assert st.shared_blocks_saved() == 0            # refcounts back to 1
    assert st.blocks_in_use() == 2
    _check_invariants(st)

    # last holder leaves: registered blocks PARK on cached_free...
    PK.free_slot(st, 1)
    assert st.blocks_in_use() == 0
    assert len(st.cached_free) == 2
    # ...and a fresh match still revives them
    revived = PK.match_prefix(st, toks)
    assert revived == matched
    PK.adopt_prefix(st, 2, revived, 8)
    assert not st.cached_free
    _check_invariants(st)
    PK.free_slot(st, 2)

    # allocation pressure evicts cached-free blocks (oldest first) and
    # drops their cache entries — the pool never refuses while they exist
    PK.allocate(st, 0, 8 * 4)                       # claim the whole pool
    assert st.blocks_in_use() == 8
    assert not st.prefix_cache and not st.cached_free
    _check_invariants(st)
    with pytest.raises(PK.OutOfBlocks):
        PK.allocate(st, 1, 4)


def test_cow_fork_isolates_writer(tiny):
    """ensure_writable forks a shared block: the writer gets a private
    copy (same content), the co-holder's view is untouched."""
    cfg, _ = tiny
    st = PK.init_paged(cfg, 2, 8, block_size=4, dtype="float32",
                       max_len=32, prefix_cache=True)
    L, KV, hd = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    rng = np.random.default_rng(0)
    toks = rng.integers(2, 100, size=8).astype(np.int32)
    kv = jnp.asarray(rng.normal(size=(L, 8, KV, hd)), jnp.float32)
    PK.allocate(st, 0, 8)
    st = PK.write_tokens(st, 0, kv, kv * 2)
    PK.register_prefix(st, 0, toks)
    PK.adopt_prefix(st, 1, PK.match_prefix(st, toks), 7)

    assert PK.ensure_writable(st, 1, 7, 1) == 1     # forks shared col 1
    assert st.cow_forks == 1
    assert st.refcount[st.block_tables[0, 1]] == 1
    assert st.block_tables[0, 1] != st.block_tables[1, 1]
    assert st.block_tables[0, 0] == st.block_tables[1, 0]  # col 0 untouched
    _check_invariants(st)
    # fork copied content; owner's blocks still hold the original
    k0, _ = PK.gather_request(st, 0, 8)
    k1, _ = PK.gather_request(st, 1, 8)
    np.testing.assert_array_equal(np.asarray(k0), np.asarray(k1))
    # owned (refcount-1) columns are never forked
    assert PK.ensure_writable(st, 1, 7, 1) == 0


def test_out_of_window_release_is_decref(tiny):
    """A shared block going out of one stream's window survives for the
    other holder instead of returning to the free list."""
    cfg, _ = tiny
    st = PK.init_paged(cfg, 2, 8, block_size=4, dtype="float32",
                       max_len=64, prefix_cache=True)
    toks = np.arange(2, 10, dtype=np.int32)
    PK.allocate(st, 0, 8)
    PK.register_prefix(st, 0, toks)
    PK.adopt_prefix(st, 1, PK.match_prefix(st, toks), 8)
    shared0 = int(st.block_tables[0, 0])
    st.lengths[0] = 9                       # pretend slot 0 decoded past
    assert PK.free_out_of_window(st, 0, window=4) == 1
    assert st.refcount[shared0] == 1        # slot 1 still holds it
    assert st.block_tables[1, 0] == shared0
    _check_invariants(st)


def test_export_import_carries_prefix_keys(tiny):
    """The migration wire format materializes shared blocks and re-seeds
    the destination's prefix cache from the carried keys."""
    cfg, _ = tiny
    src = PK.init_paged(cfg, 2, 8, block_size=4, dtype="float32",
                        max_len=32, prefix_cache=True)
    dst = PK.init_paged(cfg, 2, 8, block_size=4, dtype="float32",
                        max_len=32, prefix_cache=True)
    L, KV, hd = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    rng = np.random.default_rng(1)
    toks = rng.integers(2, 100, size=9).astype(np.int32)
    kv = jnp.asarray(rng.normal(size=(L, 9, KV, hd)), jnp.float32)
    PK.allocate(src, 0, 9)
    src = PK.write_tokens(src, 0, kv, kv * 2)
    PK.register_prefix(src, 0, toks)
    PK.adopt_prefix(src, 1, PK.match_prefix(src, toks), 8)  # now SHARED

    payload = PK.export_blocks(src, 0)
    assert len(payload["keys"]) == 2                # the 2 full blocks
    before_k, _ = PK.gather_request(src, 0, 9)
    PK.import_blocks(dst, 0, payload)
    after_k, _ = PK.gather_request(dst, 0, 9)
    np.testing.assert_array_equal(np.asarray(before_k), np.asarray(after_k))
    _check_invariants(dst)
    # the destination now serves the migrated prompt from its own cache
    assert len(PK.match_prefix(dst, toks)) == 2
    # source co-holder unaffected by releasing the migrated slot
    PK.free_slot(src, 0)
    assert src.refcount[src.block_tables[1, 0]] == 1
    _check_invariants(src)


# ----------------------------------------------------------- engine level
def _shared_prompt_requests(cfg, n, sys_len=24, temp=0.0, top_k=0,
                            max_new=6):
    rng = np.random.default_rng(7)
    sys_prompt = rng.integers(2, cfg.vocab_size, size=sys_len).astype(np.int32)
    reqs = []
    for i in range(n):
        user = rng.integers(2, cfg.vocab_size, size=3 + i).astype(np.int32)
        reqs.append(RequestSpec(
            rid=i, prompt=np.concatenate([sys_prompt, user]),
            max_tokens=max_new,
            sampling=SamplingParams(temperature=temp, top_k=top_k,
                                    seed=5 + i)))
    return reqs


def _run_engine(cfg, params, reqs, share, **kw):
    eng = Engine(cfg, params, max_batch=4, max_len=64, cache_kind="paged",
                 block_size=8, prefix_sharing=share, **kw)
    for r in reqs:
        eng.submit(r)
    peak, done = 0, []
    while eng.queue or eng.active:
        done += eng.step() or []
        peak = max(peak, eng.pstate.blocks_in_use())
    return {r.rid: r.generated for r in done}, peak, eng


@pytest.mark.parametrize("temperature,top_k", [(0.0, 0), (0.8, 16)])
def test_sharing_token_identical_and_saves_blocks(tiny, temperature, top_k):
    """The acceptance bar: sharing ON equals sharing OFF token-for-token
    (greedy and sampled) while a shared-system-prompt workload holds
    measurably fewer pool blocks."""
    cfg, params = tiny

    def reqs():
        return _shared_prompt_requests(cfg, 6, temp=temperature, top_k=top_k)

    off, peak_off, _ = _run_engine(cfg, params, reqs(), share=False)
    on, peak_on, eng = _run_engine(cfg, params, reqs(), share=True)
    assert on == off
    assert peak_on < peak_off, (peak_on, peak_off)
    stats = eng.prefix_stats()
    assert stats["hits"] > 0 and stats["hit_rate"] > 0.5
    assert stats["blocks_saved_total"] > 0
    assert eng.pstate.blocks_in_use() == 0          # fully drained
    _check_invariants(eng.pstate)


def test_aligned_duplicate_prompt_triggers_cow(tiny):
    """Identical block-aligned prompts alias EVERY prompt block; the
    recomputed last token's write forks the shared tail (copy-on-write)
    and the streams still match the unshared run exactly."""
    cfg, params = tiny
    prompt = np.random.default_rng(3).integers(
        2, cfg.vocab_size, size=16).astype(np.int32)

    def dup():
        return [RequestSpec(rid=i, prompt=prompt.copy(), max_tokens=5)
                for i in range(2)]

    on, _, eng = _run_engine(cfg, params, dup(), share=True)
    off, _, _ = _run_engine(cfg, params, dup(), share=False)
    assert on == off
    assert eng.pstate.cow_forks >= 1
    assert eng.pstate.blocks_in_use() == 0
    _check_invariants(eng.pstate)


def test_decode_rows_write_distinct_private_pages(tiny, monkeypatch):
    """The paged decode step writes each row's new token into its page in
    place, and relies on no two rows writing one page and no row writing
    a page another row reads. Before every step the engine has forked the
    shared blocks a write would touch, so each active row's write page is
    its own (refcount 1), under prefix sharing with aligned duplicate
    prompts that alias every prompt block."""
    cfg, params = tiny
    dense, paged = E._jitted_steps()
    eng = Engine(cfg, params, max_batch=4, max_len=64, cache_kind="paged",
                 block_size=8, prefix_sharing=True)
    pages_seen = []

    def checked(params, k, v, tables, lengths, active, *rest, **kw):
        bs = eng.pstate.block_size
        rows = np.flatnonzero(active)
        pages = [int(tables[r, lengths[r] // bs]) for r in rows]
        assert len(set(pages)) == len(pages), pages
        assert all(eng.pstate.refcount[p] == 1 for p in pages), pages
        pages_seen.extend(pages)
        return paged(params, k, v, tables, lengths, active, *rest, **kw)

    monkeypatch.setattr(E, "_jitted_steps", lambda: (dense, checked))
    prompt = np.random.default_rng(3).integers(
        2, cfg.vocab_size, size=16).astype(np.int32)
    for i in range(2):
        eng.submit(RequestSpec(rid=10 + i, prompt=prompt.copy(),
                               max_tokens=5))
    for r in _shared_prompt_requests(cfg, 2):
        eng.submit(r)
    eng.run_until_done()
    assert pages_seen and eng.pstate.cow_forks >= 1


def test_sharing_with_preemption_replays_identically(tiny):
    """Pool pressure preempts a stream holding shared blocks: decref on
    eviction + cache-hit on re-admission keep outputs identical to an
    unconstrained pool."""
    cfg, params = tiny
    reqs = _shared_prompt_requests(cfg, 4, sys_len=16, max_new=16)
    big, _, _ = _run_engine(cfg, params, list(reqs),
                            share=True)
    # a pool too small for all four: forces preemption mid-decode
    small, _, eng = _run_engine(cfg, params, list(reqs),
                                share=True, n_blocks=11)
    assert small == big
    assert eng.preempt_count > 0, "scenario exercised no preemption"
    assert eng.pstate.blocks_in_use() == 0
    _check_invariants(eng.pstate)


def test_sharing_skips_prefill_compute_for_shared_span(tiny):
    """A cache-hit admission compiles/pays only the SUFFIX prefill: the
    padded prefill shapes it adds are suffix-sized, far below the full
    prompt bucket."""
    cfg, params = tiny
    reqs = _shared_prompt_requests(cfg, 2, sys_len=32)   # 35/36-token prompts
    _, _, eng = _run_engine(cfg, params, reqs, share=True)
    shapes = eng._prefill_shapes
    full = [S for _, S in shapes if S >= 64]     # rid 0's full-prompt bucket
    suffix = [S for _, S in shapes if S <= 16]   # rid 1's suffix-only bucket
    assert full and suffix, shapes


@pytest.mark.parametrize("temperature,top_k", [(0.0, 0), (0.8, 16)])
def test_wave_miss_then_wave_mate_hit(tiny, temperature, top_k):
    """One admission wave holds a miss and a wave-mate whose prompt opens
    with the miss's two full blocks. The miss registers its blocks before
    its prefill, so the wave-mate aliases them in the same wave: the
    misses' group has to write them before the hit's group gathers them
    as context. Tokens equal the two requests admitted one step apart
    with sharing off."""
    cfg, params = tiny
    rng = np.random.default_rng(11)
    first = rng.integers(2, cfg.vocab_size, size=19).astype(np.int32)
    second = np.concatenate(
        [first[:16], rng.integers(2, cfg.vocab_size, size=5).astype(np.int32)])

    def spec(rid, prompt):
        return RequestSpec(rid=rid, prompt=prompt, max_tokens=6,
                           sampling=SamplingParams(temperature=temperature,
                                                   top_k=top_k, seed=3 + rid))

    def run(share, apart):
        eng = Engine(cfg, params, max_batch=2, max_len=64,
                     cache_kind="paged", block_size=8, prefix_sharing=share)
        eng.submit(spec(0, first))
        if apart:
            eng.step()
        eng.submit(spec(1, second))
        done = eng.step() or []
        assert sorted(r.rid for r in eng.active.values()) == [0, 1]
        done += eng.run_until_done()
        return {r.rid: r.generated for r in done}, eng

    together, eng = run(share=True, apart=False)
    assert eng.prefix_stats()["hits"] == 2      # both full blocks aliased
    assert eng.pstate.blocks_in_use() == 0
    _check_invariants(eng.pstate)
    apart, _ = run(share=False, apart=True)
    assert together == apart


def test_hit_admits_under_pressure_that_stalls_cold_request(tiny):
    """Backpressure accounts for aliasing: a request whose prefix is
    RESIDENT (held by an active stream) admits when the pool only has
    room for its suffix — the same request without sharing stays queued
    until the holder finishes."""
    cfg, params = tiny
    rng = np.random.default_rng(5)
    sys_prompt = rng.integers(2, cfg.vocab_size, size=16).astype(np.int32)
    users = [rng.integers(2, cfg.vocab_size, size=5).astype(np.int32)
             for _ in range(2)]

    def run(share):
        # 5-block pool: the holder takes 3, a COLD 21-token admission
        # wants blocks_needed=3 > 2 free; the suffix alone needs 1
        eng = Engine(cfg, params, max_batch=2, max_len=64,
                     cache_kind="paged", block_size=8, n_blocks=5,
                     prefix_sharing=share)
        eng.submit(RequestSpec(rid=0,
                           prompt=np.concatenate([sys_prompt, users[0]]),
                           max_tokens=3))
        eng.step()                         # rid 0 admitted, holds 3 blocks
        assert 0 in {r.rid for r in eng.active.values()}
        eng.submit(RequestSpec(rid=1,
                           prompt=np.concatenate([sys_prompt, users[1]]),
                           max_tokens=3))
        eng.step()
        admitted = 1 in {r.rid for r in eng.active.values()}
        done = eng.run_until_done()
        return admitted, {r.rid: r.generated for r in done}

    stalled_admit, off = run(share=False)
    shared_admit, on = run(share=True)
    assert not stalled_admit, "cold request should stall on a full pool"
    assert shared_admit, "aliased request should admit alongside holder"
    assert on == off                        # and still token-identical


# ----------------------------------------------------- orchestrator level
def test_migration_of_shared_blocks_token_identical(tiny):
    """Scale-down migration of a stream whose blocks are SHARED with a
    stream staying behind: zero drops, token-identical on both sides, and
    the destination learns the prefix for later admissions."""
    cfg, params = tiny
    rng = np.random.default_rng(11)
    sys_prompt = rng.integers(2, cfg.vocab_size, size=16).astype(np.int32)
    reqs = [RequestSpec(rid=i,
                        prompt=np.concatenate(
                            [sys_prompt,
                             rng.integers(2, cfg.vocab_size,
                                          size=4 + i).astype(np.int32)]),
                        max_tokens=10,
                        sampling=SamplingParams(temperature=0.8, top_k=16,
                                                seed=3 + i))
            for i in range(2)]

    # unmigrated oracle: each request solo on a fresh engine
    ref = {}
    for r in reqs:
        e = Engine(cfg, params, max_batch=1, max_len=64,
                   cache_kind="paged", block_size=8)
        e.submit(r)
        ref[r.rid] = e.run_until_done()[0].generated

    orch = Orchestrator(cfg, params, n_instances=2, max_batch=2,
                        max_len=64, block_size=8, n_blocks=24,
                        telemetry_every=10_000)
    for r in reqs:
        orch._home[r.rid] = 0
        orch.engines[0].submit(r)               # both on A: blocks shared
    for _ in range(4):
        orch.step()
    assert orch.engines[0].pstate.shared_blocks_saved() > 0, \
        "scenario exercised no sharing"
    # migrate ONLY rid 0; rid 1 keeps its claim on the shared blocks
    recs = orch.migrate_requests(0, 1, max_requests=1)
    assert len(recs) == 1 and recs[0].resumed and recs[0].rid == 0
    done = {r.rid: r.generated for r in orch.run_until_done()}
    assert done == ref
    assert orch.dropped == 0
    for e in orch.engines:
        assert e.pstate.blocks_in_use() == 0
        _check_invariants(e.pstate)
    snap = orch.snapshot()
    assert snap.prefix_hit_rate >= 0.0          # gauge surfaced
    assert orch.stats()["prefix_hit_rate"] > 0.0


def test_import_dedupes_resident_prefix_blocks(tiny):
    """Cross-instance dedupe: importing a payload whose carried prefix
    key is already RESIDENT in the destination cache aliases (increfs)
    the resident block instead of materializing a duplicate copy — and
    the aliased column behaves like any shared block (CoW on write,
    decref on release)."""
    cfg, _ = tiny
    L, KV, hd = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    rng = np.random.default_rng(2)
    toks = rng.integers(2, 100, size=9).astype(np.int32)  # 2 full blocks
    kv = jnp.asarray(rng.normal(size=(L, 9, KV, hd)), jnp.float32)

    def fresh_pool():
        st = PK.init_paged(cfg, 3, 12, block_size=4, dtype="float32",
                           max_len=32, prefix_cache=True)
        PK.allocate(st, 0, 9)
        st = PK.write_tokens(st, 0, kv, kv * 2)
        PK.register_prefix(st, 0, toks)
        return st

    src, dst = fresh_pool(), fresh_pool()   # dst already serves the prompt
    payload = PK.export_blocks(src, 0)
    used_before = dst.blocks_in_use()
    PK.import_blocks(dst, 1, payload)
    # only the partial tail materialized; the 2 full blocks aliased
    assert dst.dedup_imports == 2
    assert dst.blocks_in_use() == used_before + 1
    assert dst.shared_blocks_saved() == 2
    _check_invariants(dst)
    # aliased content is exactly the payload content
    want_k, _ = PK.gather_request(src, 0, 9)
    got_k, _ = PK.gather_request(dst, 1, 9)
    np.testing.assert_array_equal(np.asarray(want_k), np.asarray(got_k))
    # a write into an ALIASED column forks, leaving slot 0's view intact
    # (position 7 = last row of aliased block 1; the materialized tail at
    # column 2 is owned and would need no fork)
    assert PK.ensure_writable(dst, 1, 7, 1) == 1
    _check_invariants(dst)
    PK.free_slot(dst, 1)
    _check_invariants(dst)


def test_migration_dedupe_end_to_end_token_identical(tiny):
    """Orchestrator-level dedupe: migrate a stream whose system prompt
    is ALREADY resident at the destination (another stream with the same
    prefix lives there) — the import aliases instead of copying, pools
    stay consistent, and every stream is token-identical."""
    cfg, params = tiny
    rng = np.random.default_rng(13)
    sys_prompt = rng.integers(2, cfg.vocab_size, size=16).astype(np.int32)
    reqs = [RequestSpec(rid=i,
                        prompt=np.concatenate(
                            [sys_prompt,
                             rng.integers(2, cfg.vocab_size,
                                          size=4 + i).astype(np.int32)]),
                        max_tokens=8,
                        sampling=SamplingParams(temperature=0.8, top_k=16,
                                                seed=3 + i))
            for i in range(2)]
    ref = {}
    for r in reqs:
        e = Engine(cfg, params, max_batch=1, max_len=64,
                   cache_kind="paged", block_size=8)
        e.submit(r)
        ref[r.rid] = e.run_until_done()[0].generated

    orch = Orchestrator(cfg, params, n_instances=2, max_batch=2,
                        max_len=64, block_size=8, n_blocks=24,
                        telemetry_every=10_000)
    # same system prompt on BOTH instances: rid 0 on A, rid 1 on B
    for i, r in enumerate(reqs):
        orch._home[r.rid] = i
        orch.engines[i].submit(r)
    for _ in range(3):
        orch.step()
    in_use_before = orch.engines[1].pstate.blocks_in_use()
    recs = orch.migrate_requests(0, 1)
    assert len(recs) == 1 and recs[0].resumed
    st = orch.engines[1].pstate
    assert st.dedup_imports == 2            # both full sys-prompt blocks
    assert st.blocks_in_use() < in_use_before + recs[0].n_blocks
    _check_invariants(st)
    done = {r.rid: r.generated for r in orch.run_until_done()}
    assert done == ref
    assert orch.dropped == 0
    assert orch.stats()["dedup_imports"] == 2
    for e in orch.engines:
        assert e.pstate.blocks_in_use() == 0
        _check_invariants(e.pstate)


def test_hit_suffix_prefills_are_batched(tiny):
    """Prefix-hit admissions in one wave run ONE bucketed extend per
    (context, suffix) group — the hit-path analogue of the miss wave's
    pow2 buckets — instead of one extend per hit request; outputs still
    match sharing-off exactly."""
    cfg, params = tiny
    rng = np.random.default_rng(9)
    sys_prompt = rng.integers(2, cfg.vocab_size, size=24).astype(np.int32)
    # suffix lengths 6..8 share the pow2 bucket 8 AND the context bucket:
    # one wave => first request misses, three wave-mates hit as a group
    reqs = [RequestSpec(rid=i,
                    prompt=np.concatenate(
                        [sys_prompt,
                         rng.integers(2, cfg.vocab_size,
                                      size=5 + i).astype(np.int32)]),
                    max_tokens=4)
            for i in range(4)]
    on, _, eng = _run_engine(cfg, params, list(reqs),
                             share=True)
    off, _, _ = _run_engine(cfg, params, list(reqs),
                            share=False)
    assert on == off
    grouped = [(G, S) for G, S in eng._prefill_shapes if G >= 2 and S <= 16]
    assert grouped, (
        f"hit wave did not batch: prefill shapes {eng._prefill_shapes}")
    stats = eng.prefix_stats()
    assert stats["hits"] > 0
    assert eng.pstate.blocks_in_use() == 0
    _check_invariants(eng.pstate)


def test_snapshot_surfaces_sharing_gauges(tiny):
    """MetricsSnapshot carries prefix_hit_rate/blocks_saved while streams
    are live — the controller's vacancy signal reflects sharing."""
    cfg, params = tiny
    reqs = _shared_prompt_requests(cfg, 4, sys_len=16)
    orch = Orchestrator(cfg, params, n_instances=1, max_batch=4,
                        max_len=64, block_size=8, n_blocks=32,
                        telemetry_every=10_000)
    for r in reqs:
        orch.submit(r)
    for _ in range(3):
        orch.step()
    snap = orch.snapshot()
    assert snap.prefix_hit_rate > 0.0
    assert snap.blocks_saved > 0
    # the snapshot reads the EngineTelemetry mirrors, which must agree
    # with the engines' own counters
    tel = orch.telemetry[0]
    stats = orch.engines[0].prefix_stats()
    assert tel.prefix_hit_rate() == stats["hit_rate"]
    assert tel.blocks_saved == stats["blocks_saved_now"]
    assert orch.monitor is not None
    orch.monitor.record(snap)
    assert orch.monitor.prefix_hit_rate() == snap.prefix_hit_rate
    assert orch.monitor.blocks_saved_by_sharing() == snap.blocks_saved
    orch.run_until_done()
