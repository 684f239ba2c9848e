"""Production-feature extensions: paged KV, chunked prefill, sampling,
heterogeneous scale-up, gradient accumulation, traffic traces — plus
hypothesis property tests on attention causality."""
import dataclasses
import inspect
import itertools

import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings

from repro.configs import get_config
from repro.core.cluster import Cluster, Device, GB
from repro.core.plan import PlacementPlan
from repro.core.scale_up import scale_up_hetero
from repro.core.speedup import SpeedupModelConfig, speedup
from repro.kernels import paged_decode as PD
from repro.kernels.paged_decode import paged_decode_attention
from repro.models import transformer as T
from repro.serving import paged_kv as PK
from repro.serving.engine import Engine
from repro.serving.request import RequestSpec, SamplingParams
from repro.serving.workload import WorkloadConfig, generate_trace
from repro.training import optimizer as OPT
from repro.training import train as TR

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------- paged KV
def _filled_state(cfg, lens, block_size=8):
    state = PK.init_paged(cfg, max_batch=len(lens), n_blocks=64,
                          block_size=block_size, dtype="float32", max_len=256)
    rng = np.random.default_rng(0)
    L, KV, hd = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    for slot, n in enumerate(lens):
        PK.allocate(state, slot, n)
        state = PK.write_tokens(
            state, slot,
            jnp.asarray(rng.normal(size=(L, n, KV, hd)), jnp.float32),
            jnp.asarray(rng.normal(size=(L, n, KV, hd)), jnp.float32))
    return state


def _attend(q, k_pool, v_pool, layer, tables, lengths):
    """The kernel's output with nothing written (every ``new_pos`` -1)."""
    B, (KV, D) = q.shape[0], k_pool.shape[2::2]
    none = jnp.zeros((B, KV, D), k_pool.dtype)
    out, _, _ = paged_decode_attention(
        q, k_pool, v_pool, layer, tables, lengths, none, none,
        jnp.full((B,), -1, jnp.int32), interpret=True)
    return out


def test_paged_kernel_matches_ref():
    cfg = get_config("tinyllama-1.1b").reduced()
    state = _filled_state(cfg, [20, 7, 33])
    q = jax.random.normal(KEY, (3, cfg.num_kv_heads,
                                cfg.resolved_head_dim), jnp.float32)
    ref = PK.paged_attention_ref(q, state, [0, 1, 2], layer=0)
    out = _attend(
        q, state.k, state.v, 0, jnp.asarray(state.block_tables),
        jnp.asarray(state.lengths[:3]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


def test_paged_alloc_free_cycle():
    cfg = get_config("tinyllama-1.1b").reduced()
    state = _filled_state(cfg, [20, 7])
    used = state.blocks_in_use()
    assert used == -(-20 // 8) + -(-7 // 8)
    PK.free_slot(state, 0)
    assert state.blocks_in_use() == -(-7 // 8)
    assert 0.0 < state.utilization() <= 1.0
    with pytest.raises(PK.OutOfBlocks):
        PK.allocate(state, 0, 10_000)


def test_paged_gather_matches_written():
    cfg = get_config("tinyllama-1.1b").reduced()
    state = PK.init_paged(cfg, max_batch=1, n_blocks=16, block_size=8,
                          dtype="float32", max_len=64)
    L, KV, hd = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    k_new = jax.random.normal(KEY, (L, 13, KV, hd), jnp.float32)
    PK.allocate(state, 0, 13)
    state = PK.write_tokens(state, 0, k_new, k_new * 2)
    k, v = PK.gather_request(state, 0, 13)
    np.testing.assert_allclose(np.asarray(k), np.asarray(k_new), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(v), np.asarray(k_new * 2), rtol=1e-6)


def _raw_state(lens, *, n_blocks=16, bs=8, KV=2, hd=64, dtype=jnp.float32):
    """Pool built without a config — lets tests pick H != KV freely."""
    state = PK.PagedState(
        k=jnp.zeros((1, n_blocks, KV, bs, hd), dtype),   # KV-head-major
        v=jnp.zeros((1, n_blocks, KV, bs, hd), dtype),
        block_tables=np.full((len(lens), -(-max(lens) // bs) + 1), -1,
                             np.int32),
        lengths=np.zeros((len(lens),), np.int32),
        free=list(range(n_blocks)), block_size=bs)
    rng = np.random.default_rng(7)
    for slot, n in enumerate(lens):
        PK.allocate(state, slot, n)
        state = PK.write_tokens(
            state, slot,
            jnp.asarray(rng.normal(size=(1, n, KV, hd)), dtype),
            jnp.asarray(rng.normal(size=(1, n, KV, hd)), dtype))
    return state


@pytest.mark.parametrize("dtype,tol,H,KV,hd", [
    pytest.param(jnp.float32, 3e-5, 8, 2, 64, id="float32-3e-05"),
    pytest.param(jnp.bfloat16, 2e-2, 8, 2, 64, id="bfloat16-0.02"),
    pytest.param(jnp.float32, 3e-5, 8, 2, 128, id="float32-hd128"),
    pytest.param(jnp.bfloat16, 2e-2, 32, 4, 128, id="bfloat16-r8-hd128"),
])
def test_paged_kernel_gqa_matches_ref(dtype, tol, H, KV, hd):
    """H > KV: query heads grouped by KV head inside the kernel (R = 4,
    and R = 8 at Yi-1.5-9B's widths); head_dim 128 takes the chunked
    page copies, 64 the pipelined pages."""
    state = _raw_state([20, 7, 33], KV=KV, hd=hd, dtype=dtype)
    q = jax.random.normal(KEY, (3, H, hd), jnp.float32)
    ref = PK.paged_attention_ref(q.astype(dtype), state, [0, 1, 2], layer=0)
    out = _attend(
        q.astype(dtype), state.k, state.v, 0,
        jnp.asarray(state.block_tables), jnp.asarray(state.lengths))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_paged_kernel_ragged_block_boundaries():
    """Lengths at, just past, and far from block boundaries — plus an
    inactive (length 0) slot, which must yield exactly zeros."""
    bs = 8
    lens = [bs, 2 * bs, 1, 2 * bs + 1]
    state = _raw_state(lens, bs=bs, KV=4, hd=64)
    q = jax.random.normal(KEY, (4, 4, 64), jnp.float32)
    ref = PK.paged_attention_ref(q, state, [0, 1, 2, 3], layer=0)
    lengths = np.array(lens, np.int32)
    out = _attend(
        q, state.k, state.v, 0, jnp.asarray(state.block_tables),
        jnp.asarray(lengths))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)
    lengths[1] = 0  # deactivate a slot
    out0 = _attend(
        q, state.k, state.v, 0, jnp.asarray(state.block_tables),
        jnp.asarray(lengths))
    assert (np.asarray(out0[1]) == 0).all()


def _chunk_case(hd, dtype, *, H=32, KV=4, bs=16):
    """Rows one short of, at and one past a chunk of P pages, a single key
    and a row made inactive, over a table one column wider than the
    longest row needs (so its width is not a multiple of P; the entries
    past each row's length are -1)."""
    itemsize = jnp.dtype(dtype).itemsize
    P = PD.chunk_pages(bs, KV, hd, itemsize, 1 << 20)
    lens = [P * bs - 1, P * bs, P * bs + 1, 1, 5]
    state = _raw_state(lens, n_blocks=sum(-(-n // bs) for n in lens),
                       bs=bs, KV=KV, hd=hd, dtype=dtype)
    width = state.block_tables.shape[1]
    assert PD.chunk_pages(bs, KV, hd, itemsize, width) == P
    assert width % P != 0 and (state.block_tables < 0).any()
    q = jax.random.normal(KEY, (len(lens), H, hd), jnp.float32).astype(dtype)
    return state, q


@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_paged_kernel_chunk_boundaries(hd, dtype, tol):
    """Lengths P·bs - 1, P·bs, P·bs + 1 and 1 match the reference, and an
    inactive (length 0) row yields exactly zeros; head_dim 128 takes the
    chunked page copies, 64 the pipelined pages."""
    state, q = _chunk_case(hd, dtype)
    n = len(state.lengths)
    ref = PK.paged_attention_ref(q, state, range(n), layer=0)
    lengths = state.lengths.copy()
    lengths[-1] = 0
    out = _attend(
        q, state.k, state.v, 0, jnp.asarray(state.block_tables),
        jnp.asarray(lengths))
    np.testing.assert_allclose(np.asarray(out[:-1], np.float32),
                               np.asarray(ref[:-1], np.float32),
                               rtol=tol, atol=tol)
    assert (np.asarray(out[-1], np.float32) == 0).all()


@pytest.mark.parametrize("hd", [64, 128])
def test_paged_kernel_shard_invariant(hd):
    """Each quarter of the batch, run alone (as a degree-4 replicated
    layer maps the kernel over batch shards), gives bit for bit what the
    whole batch gives for those rows."""
    lens = [9, 700, 1, 513, 0, 33, 512, 40]      # row 4 inactive
    state = _raw_state(lens[:4] + [1] + lens[5:], bs=16, KV=4, hd=hd,
                       n_blocks=sum(-(-n // 16) for n in lens) + 1,
                       dtype=jnp.bfloat16)
    q = jax.random.normal(KEY, (8, 32, hd), jnp.float32).astype(jnp.bfloat16)
    args = (jnp.asarray(state.block_tables), jnp.asarray(lens, jnp.int32))
    full = _attend(q, state.k, state.v, 0, *args)
    for rows in (slice(0, 2), slice(2, 4), slice(4, 6), slice(6, 8)):
        part = _attend(
            q[rows], state.k, state.v, 0, *(a[rows] for a in args))
        assert np.array_equal(np.asarray(full[rows], np.float32),
                              np.asarray(part, np.float32))


def _layered_case(hd, dtype, *, L=3, H=32, KV=4, bs=16):
    """A pool of L layers, each with its own random contents, and rows: one
    with no token (inactive) first, then rows whose new tokens land on a
    row's first position, at offset bs - 1, on a block's first token, at
    the start of a chunk of P pages and mid block, another inactive row,
    and a row that writes but attends nothing (a replicated layer's row of
    another shard). Every row owns its blocks, and some blocks belong to
    no row."""
    itemsize = jnp.dtype(dtype).itemsize
    P = PD.chunk_pages(bs, KV, hd, itemsize, 1 << 20)
    new_pos = np.array([-1, 0, bs - 1, 2 * bs, P * bs, 3 * bs + 5, -1,
                        bs + 2], np.int32)
    lengths = np.where(new_pos >= 0, new_pos + 1, 0).astype(np.int32)
    lengths[-1] = 0                                  # writes, attends nothing
    held = [max(p, 0) // bs + 1 for p in new_pos]
    n_blocks = sum(held) + 5
    order = np.random.default_rng(3).permutation(n_blocks)
    tables = np.full((len(held), max(held) + 1), -1, np.int32)
    used = 0
    for row, n in enumerate(held):
        tables[row, :n] = order[used:used + n]
        used += n
    rng = np.random.default_rng(11)
    pool_k, pool_v = (jnp.asarray(rng.normal(size=(L, n_blocks, KV, bs, hd)),
                                  dtype) for _ in range(2))
    q = jax.random.normal(KEY, (len(held), H, hd), jnp.float32).astype(dtype)
    new_k, new_v = (jnp.asarray(rng.normal(size=(len(held), KV, hd)), dtype)
                    for _ in range(2))
    return (q, pool_k, pool_v, jnp.asarray(tables), jnp.asarray(lengths),
            (new_k, new_v), jnp.asarray(new_pos))


def _state_of(pool_k, pool_v, tables, lengths):
    return PK.PagedState(k=pool_k, v=pool_v, block_tables=np.asarray(tables),
                         lengths=np.asarray(lengths), free=[],
                         block_size=pool_k.shape[3])


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("hd", [64, 128])
def test_paged_kernel_reads_its_layer(hd, layer):
    """The layer index picks the layer: over a pool whose layers hold
    different contents, each layer's output matches the reference at
    that layer (head_dim 128: the chunked page copies; 64: the pipelined
    pages)."""
    q, pool_k, pool_v, tables, lengths, _, _ = _layered_case(hd, jnp.float32)
    out = _attend(q, pool_k, pool_v, layer, tables, lengths)
    ref = PK.paged_attention_ref(
        q, _state_of(pool_k, pool_v, tables, lengths), range(len(q)),
        layer=layer)
    attend = np.asarray(lengths) > 0
    np.testing.assert_allclose(np.asarray(out)[attend],
                               np.asarray(ref)[attend], rtol=3e-5, atol=3e-5)
    other = PK.paged_attention_ref(
        q, _state_of(pool_k, pool_v, tables, lengths), range(len(q)),
        layer=(layer + 1) % 3)
    assert not np.allclose(np.asarray(out)[attend],
                           np.asarray(other)[attend], atol=1e-2)


@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_paged_kernel_write_matches_scatter(hd, dtype, tol):
    """Each row's new token lands where the XLA scatter puts it, bit for
    bit — at a row's first position, at offsets 0 and bs - 1, on a
    block's first token and at a chunk's first page — and the row attends
    over it (write-then-attend); the inactive and the write-only rows
    yield zeros."""
    q, pool_k, pool_v, tables, lengths, new, new_pos = _layered_case(
        hd, dtype)
    layer = 1
    out, got_k, got_v = paged_decode_attention(
        q, pool_k, pool_v, layer, tables, lengths, *new, new_pos,
        interpret=True)
    want_k, want_v = (PD.scatter_new_tokens(p, x, layer, tables, new_pos)
                      for p, x in zip((pool_k, pool_v), new))
    assert np.array_equal(np.asarray(got_k), np.asarray(want_k))
    assert np.array_equal(np.asarray(got_v), np.asarray(want_v))
    ref = PK.paged_attention_ref(
        q, _state_of(want_k, want_v, tables, lengths), range(len(q)),
        layer=layer)
    attend = np.asarray(lengths) > 0
    np.testing.assert_allclose(np.asarray(out, np.float32)[attend],
                               np.asarray(ref, np.float32)[attend],
                               rtol=tol, atol=tol)
    assert (np.asarray(out, np.float32)[~attend] == 0).all()


@pytest.mark.parametrize("writes", [True, False], ids=["writes", "none"])
@pytest.mark.parametrize("hd", [64, 128])
def test_paged_kernel_write_leaves_the_rest(hd, writes):
    """The write touches exactly one token of one page per writing row, in
    the given layer: every other token of that page, every other page
    (the inactive rows' included) and every other layer keep their
    contents; a batch with no token to write leaves the pools as they
    were."""
    q, pool_k, pool_v, tables, lengths, new, new_pos = _layered_case(
        hd, jnp.bfloat16)
    if not writes:
        new_pos = jnp.full_like(new_pos, -1)
    layer, bs = 2, pool_k.shape[3]
    _, got_k, got_v = paged_decode_attention(
        q, pool_k, pool_v, layer, tables, lengths, *new, new_pos,
        interpret=True)
    written = np.zeros(pool_k.shape[:2] + (bs,), bool)    # [L, n_blocks, bs]
    tables, new_pos = np.asarray(tables), np.asarray(new_pos)
    for row, p in enumerate(new_pos):
        if p >= 0:
            written[layer, tables[row, p // bs], p % bs] = True
    assert written.sum() == (new_pos >= 0).sum()
    for got, old, x in ((got_k, pool_k, new[0]), (got_v, pool_v, new[1])):
        got, old = np.asarray(got, np.float32), np.asarray(old, np.float32)
        keep = ~written[:, :, None, :, None]
        assert np.array_equal(np.where(keep, got, 0), np.where(keep, old, 0))
        for row, p in enumerate(new_pos):
            if p >= 0:
                assert np.array_equal(
                    got[layer, tables[row, p // bs], :, p % bs],
                    np.asarray(x[row], np.float32))


def test_chunk_pages_from_shapes():
    """The chunk geometry is a function of shapes alone (no batch size,
    no option) and its double-buffered K and V fit v5e's default scoped
    VMEM (16 MiB) with room to spare."""
    assert list(inspect.signature(PD.chunk_pages).parameters) == [
        "block_size", "kv_heads", "head_dim", "itemsize", "table_width"]
    assert PD.chunk_pages(16, 4, 128, 2, 256) == 32   # Yi-1.5-9B: 512 keys
    assert PD.chunk_pages(16, 8, 128, 2, 256) == 16   # Mistral-7B: 1 MiB
    assert PD.chunk_pages(16, 4, 128, 2, 3) == 3      # the table's width
    assert PD.chunk_pages(16, 4, 128, 2, 1) == 1
    for bs, kv, hd, itemsize in itertools.product(
            (8, 16, 32, 64, 128), (1, 2, 4, 8, 16), (64, 128, 256), (2, 4)):
        pages = PD.chunk_pages(bs, kv, hd, itemsize, 4096)
        page = kv * bs * hd * itemsize
        assert pages * bs <= PD.TARGET_KEYS or pages == 1
        assert 2 * 2 * pages * page <= max(2 * PD.CHUNK_BYTES, 4 * page)
        assert 2 * 2 * pages * page <= 8 * 2 ** 20


def _run_engine(cfg, params, prompts, *, max_new=6, **kw):
    e = Engine(cfg, params, max_batch=2, max_len=64, **kw)
    for i, p in enumerate(prompts):
        e.submit(RequestSpec(rid=i, prompt=p, max_tokens=max_new))
    done = e.run_until_done()
    return {r.rid: r.generated for r in done}


def test_paged_engine_matches_dense_greedy():
    """Primary-path parity: the paged engine (batched same-length prefill,
    block-pool decode, on-device sampling) reproduces the dense engine's
    greedy outputs token for token — including ragged prompt lengths that
    cross block boundaries mid-generation."""
    cfg = get_config("tinyllama-1.1b").reduced()
    params = T.init_params(cfg, KEY, "float32")
    rng = np.random.default_rng(5)
    prompts = [rng.integers(2, cfg.vocab_size, size=s).astype(np.int32)
               for s in (8, 8, 5, 11)]  # two same-length -> batched prefill
    dense = _run_engine(cfg, params, prompts, cache_kind="dense")
    paged = _run_engine(cfg, params, prompts, cache_kind="paged",
                        block_size=8)
    assert paged == dense
    # the Pallas kernel path (interpret mode) agrees too
    kern = _run_engine(cfg, params, prompts[:2], max_new=3,
                       cache_kind="paged", block_size=8,
                       paged_attn_impl="kernel", interpret=True)
    assert kern == {k: v[:3] for k, v in dense.items() if k < 2}


def test_paged_kernel_engine_greedy_across_layers():
    """Greedy streams through the engine with the kernel's chunked side
    (head_dim 128), each new token written into its page in place, over
    three layers and prompts that cross block boundaries mid-generation,
    equal the dense engine's token for token."""
    cfg = dataclasses.replace(get_config("tinyllama-1.1b").reduced(),
                              num_layers=3, head_dim=128)
    params = T.init_params(cfg, KEY, "float32")
    rng = np.random.default_rng(9)
    prompts = [rng.integers(2, cfg.vocab_size, size=s).astype(np.int32)
               for s in (6, 13)]
    dense = _run_engine(cfg, params, prompts, max_new=5, cache_kind="dense")
    kern = _run_engine(cfg, params, prompts, max_new=5, cache_kind="paged",
                       block_size=8, paged_attn_impl="kernel",
                       interpret=True)
    assert kern == dense


def test_paged_engine_out_of_blocks_backpressure():
    """A pool too small for all requests at once: admission defers
    (requests wait in queue), decode pressure preempts — and every
    request still finishes with exactly the unconstrained outputs."""
    cfg = get_config("tinyllama-1.1b").reduced()
    params = T.init_params(cfg, KEY, "float32")
    rng = np.random.default_rng(6)
    prompts = [rng.integers(2, cfg.vocab_size, size=s).astype(np.int32)
               for s in (8, 8, 5, 11)]
    full = _run_engine(cfg, params, prompts, max_new=12,
                       cache_kind="paged", block_size=8)
    e = Engine(cfg, params, max_batch=2, max_len=64, cache_kind="paged",
               block_size=8, n_blocks=4)
    for i, p in enumerate(prompts):
        e.submit(RequestSpec(rid=i, prompt=p, max_tokens=12))
    waited = False
    done = []
    for _ in range(400):
        if e.queue and e.active:
            waited = True
        done += e.step() or []
        if not e.queue and not e.active:
            break
    assert waited, "pool was never under pressure"
    assert len(done) == len(prompts)
    assert {r.rid: r.generated for r in done} == full
    # a request larger than the whole pool is a hard error, not a hang —
    # and it must not take the rest of the admission wave down with it
    e2 = Engine(cfg, params, max_batch=2, max_len=64, cache_kind="paged",
                block_size=8, n_blocks=2)
    e2.submit(RequestSpec(rid=0, prompt=np.arange(2, 40, dtype=np.int32),
                      max_tokens=4))
    e2.submit(RequestSpec(rid=1, prompt=np.arange(2, 8, dtype=np.int32),
                      max_tokens=2))
    with pytest.raises(PK.OutOfBlocks):
        e2.run_until_done()
    done2 = e2.run_until_done()  # wave-mate survived the rejection
    assert [r.rid for r in done2] == [1]
    assert e2.pstate.blocks_in_use() == 0  # nothing leaked
    # a lone request whose GENERATION outgrows the pool is evicted with
    # truncated output (loud, but the engine stays serviceable)
    e3 = Engine(cfg, params, max_batch=1, max_len=64, cache_kind="paged",
                block_size=8, n_blocks=2)
    big = e3.submit(RequestSpec(rid=0,
                                prompt=np.arange(2, 10, dtype=np.int32),
                                max_tokens=30))
    with pytest.raises(PK.OutOfBlocks):
        e3.run_until_done()
    assert big.done and 0 < len(big.generated) < 30
    assert not e3.active and e3.pstate.blocks_in_use() == 0
    e3.submit(RequestSpec(rid=1, prompt=np.arange(2, 8, dtype=np.int32),
                      max_tokens=2))
    assert [r.rid for r in e3.run_until_done()] == [1]  # still serviceable
    # prompt == max_len would overflow the block-table row: clean
    # rejection (no IndexError, no leaked block, engine still serviceable)
    e4 = Engine(cfg, params, max_batch=2, max_len=32, cache_kind="paged",
                block_size=8)
    e4.submit(RequestSpec(rid=0, prompt=np.full(32, 3, np.int32),
                      max_tokens=4))
    e4.submit(RequestSpec(rid=1, prompt=np.full(31, 3, np.int32),  # just fits
                      max_tokens=4))
    with pytest.raises(PK.OutOfBlocks):
        e4.run_until_done()
    done4 = e4.run_until_done()
    assert [r.rid for r in done4] == [1]
    assert e4.pstate.blocks_in_use() == 0


# ------------------------------------------------------------------ sampling
def test_sampling_seeded():
    cfg = get_config("tinyllama-1.1b").reduced()
    params = T.init_params(cfg, KEY, "float32")
    prompt = np.arange(2, 10).astype(np.int32)
    gens = []
    for seed in (1, 1, 2):
        e = Engine(cfg, params, max_batch=1, max_len=64)
        e.submit(RequestSpec(rid=0, prompt=prompt, max_tokens=5,
                             sampling=SamplingParams(temperature=0.8,
                                                     top_k=16,
                                                     seed=seed)))
        gens.append(e.run_until_done()[0].generated)
    assert gens[0] == gens[1]
    assert gens[0] != gens[2]


# -------------------------------------------------------- hetero scale-up
def test_hetero_scale_up_prefers_fast_devices():
    # NOTE: under the exact Eq. 3 with honest units, a SINGLE replica never
    # pays at NVLink-1 bandwidth (the boundary comm exceeds one layer's
    # savings — the reason the paper's Alg. 1 sorts by continuity). A
    # 400 GB/s link (NVLink-4 class) makes the greedy first step viable.
    devices = [Device(0, compute_flops=312e12, mem_capacity=40 * GB),
               Device(1, compute_flops=312e12, mem_capacity=40 * GB),
               Device(2, compute_flops=78e12, mem_capacity=40 * GB)]
    cluster = Cluster(devices=devices, link_bandwidth=400 * GB)
    m = SpeedupModelConfig(d_model=5120, seq_len=256, batch_size=16)
    plan = scale_up_hetero(PlacementPlan.initial(16), cluster, model=m,
                           replica_size=605e6)
    assert speedup(plan, m, cluster) > 1.0
    fast = sum(reps.count(1) for reps in plan.replicas.values())
    slow = sum(reps.count(2) for reps in plan.replicas.values())
    assert fast >= slow  # Eq. 3 weights capacity; slow device helps less


def test_hetero_scale_up_slow_link_declines():
    """Exact-Eq.3 greedy correctly refuses replication when per-boundary
    communication exceeds per-layer compute savings (slow interconnect)."""
    cluster = Cluster.homogeneous(3, link_gbps=64)
    m = SpeedupModelConfig(d_model=5120, seq_len=256, batch_size=16)
    plan = scale_up_hetero(PlacementPlan.initial(16), cluster, model=m,
                           replica_size=605e6)
    assert plan.p == [1] * 16  # no replica pays for itself


# ------------------------------------------------------------- grad accum
def test_grad_accum_matches_full_batch():
    cfg = get_config("tinyllama-1.1b").reduced()
    params = T.init_params(cfg, KEY, "float32")
    ocfg = OPT.OptimizerConfig(lr=1e-3, warmup_steps=1, total_steps=10,
                               clip_norm=None)
    tokens = jax.random.randint(KEY, (8, 16), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens,
             "mask": jnp.ones((8, 16), jnp.float32)}
    # compare the accumulated GRADIENT against the full-batch gradient
    # (post-Adam params amplify fp noise through the rsqrt normalizer)
    loss_fn = TR.make_loss_fn(cfg)
    g_full = jax.grad(lambda p: loss_fn(p, batch)[0])(params)
    micros = jax.tree_util.tree_map(
        lambda x: x.reshape(4, 2, *x.shape[1:]), batch)
    g_acc = jax.tree_util.tree_map(lambda p: jnp.zeros_like(p), params)
    for i in range(4):
        mb = jax.tree_util.tree_map(lambda x: x[i], micros)
        gi = jax.grad(lambda p: loss_fn(p, mb)[0])(params)
        g_acc = jax.tree_util.tree_map(lambda a, b: a + b / 4, g_acc, gi)
    for a, b in zip(jax.tree_util.tree_leaves(g_full),
                    jax.tree_util.tree_leaves(g_acc)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)
    # and the jitted accumulating step runs + produces finite loss
    acc = TR.make_train_step_accum(cfg, ocfg, accum_steps=4)
    _, _, m2 = jax.jit(acc)(params, OPT.init_opt_state(params), batch)
    assert np.isfinite(float(m2["total_loss"]))


# ---------------------------------------------------------------- traces
@pytest.mark.parametrize("pattern", ["burst", "diurnal"])
def test_traffic_traces(pattern):
    wl = WorkloadConfig(rps=10, duration_s=30, seed=1)
    reqs = generate_trace(wl, pattern)
    assert len(reqs) > 100
    arr = np.array([r.arrival for r in reqs])
    mid = ((arr >= 10) & (arr < 20)).sum()
    edge = (arr < 10).sum()
    if pattern == "burst":
        assert mid > 2 * edge  # the spike is visible


# ----------------------------------------------------- causality property
@given(st.integers(0, 6), st.integers(0, 2 ** 31 - 1))
@settings(max_examples=8, deadline=None)
def test_attention_causality(perturb_pos, seed):
    """Perturbing token t must not change logits at positions < t."""
    cfg = get_config("tinyllama-1.1b").reduced()
    params = T.init_params(cfg, jax.random.PRNGKey(0), "float32")
    rng = np.random.default_rng(seed)
    toks = rng.integers(2, cfg.vocab_size, size=(1, 8)).astype(np.int32)
    toks2 = toks.copy()
    toks2[0, perturb_pos] = (toks2[0, perturb_pos] + 7) % cfg.vocab_size
    a, _, _ = T.forward(params, cfg, jnp.asarray(toks), mode="train")
    b, _, _ = T.forward(params, cfg, jnp.asarray(toks2), mode="train")
    if perturb_pos > 0:
        np.testing.assert_allclose(np.asarray(a[0, :perturb_pos]),
                                   np.asarray(b[0, :perturb_pos]),
                                   rtol=1e-5, atol=1e-5)
    assert not np.allclose(np.asarray(a[0, perturb_pos]),
                           np.asarray(b[0, perturb_pos]))
