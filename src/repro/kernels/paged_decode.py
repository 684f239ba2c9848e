"""Paged decode attention (Pallas): one new token per row against a
block-table KV pool — the vLLM paged-attention mechanism on TPU, with GQA
head grouping — that also writes each row's new token into its page.

Grid: (batch,). One grid step serves one row for every KV head at once.
The pool stays in HBM (``memory_space=pltpu.HBM``) whole, every layer of
it, in the serving pool's KV-HEAD-MAJOR layout ([L, n_blocks, KV, bs, D],
serving/paged_kv.py), so one page — a block's K (or V) of one layer for
every KV head, [KV, bs, D] — is contiguous there. The layer arrives by
scalar prefetch beside the block table and the lengths, and the kernel
copies ``pool.at[layer, block]``: the step's scan never slices a layer
out of the pool nor stacks one back. The kernel walks the row's block
table in chunks of P pages: each page is one async DMA into a VMEM chunk
[KV, P·bs, D] for K and one for V, and the chunks are double-buffered —
chunk c+1 is in flight while chunk c is computed, and a row's last chunk
starts the next row's first, so a grid step boundary never leaves the
DMA engine idle.

Write-then-attend, in place: the pools are aliased from input to output.
A row's new token lies in the last page of its context, which the last
chunk brings into VMEM anyway; the kernel merges the token into that VMEM
page, starts one DMA of the page back to the pool, attends over the
chunk (new token included) and waits for the DMA before its grid step
ends, so the buffer is free when the next row's chunks reuse it. A row
that writes but attends nothing (length 0; a replicated layer's rows of
other shards) copies its page in, merges and copies it back. A row with
no new token (``new_pos`` < 0) writes nothing. No two rows may write one
page, nor may a row read a page another row writes: the engine forks
shared blocks before the step (``paged_kv.ensure_writable``).

Early termination: the chunk loop runs ceil(length / (P·bs)) times (a
``lax.fori_loop`` with a dynamic trip count), and the last chunk copies
only the pages the row holds. A row pays HBM traffic and grid overhead
for its true context, never for the table's width; a row of length 0
runs no chunk and writes zeros. Keys past the length inside the last
chunk are masked by position (scores to -inf, values to zero), so stale
buffer contents never reach the output.

Online softmax across chunks, per KV head for the R = H/KV query heads
that share it, with f32 scores, statistics and accumulators: q·k takes
the operands in their storage dtype with f32 accumulation (products of
bf16 values are exact in f32) and applies the 1/sqrt(D) scale to the f32
scores; p·v runs in f32.

P comes from shapes alone (``chunk_pages``): about TARGET_KEYS keys or
CHUNK_BYTES of K+V a chunk, capped by the table's width. It never depends
on the batch size, so one batch shard's rows (``layers.apply_gqa_paged``
maps the kernel over batch shards under ``shard_map``) go through exactly
the arithmetic of the whole batch. The layer, the block table, the
lengths and the new tokens' positions arrive by scalar prefetch (SMEM).

A head_dim that is not a multiple of 128 lanes (TinyLlama's 64) is
padded to 128 lanes in HBM, and the TPU compiler refuses a DMA of a slice
of such an array. There the pages come in through the Pallas pipeline
instead: grid (batch, table width), one page of every KV head per grid
step, the page index clamped to the row's last page (the repeated DMA is
elided) and the fold skipped past the length. The fold is the same; the
new tokens go in first, by a second, small kernel that writes each into
its page in place through the same pipeline (``_write_pages``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")
TARGET_KEYS = 512            # keys a chunk aims to cover
CHUNK_BYTES = 1 << 20        # K + V bytes a chunk may hold (one buffer)
LANES = 128                  # a head_dim the page copies need a multiple of


def chunk_pages(block_size: int, kv_heads: int, head_dim: int,
                itemsize: int, table_width: int) -> int:
    """Pages (pool blocks) the kernel copies and computes per chunk:
    about TARGET_KEYS keys, at most CHUNK_BYTES of K+V, at most the
    table's width, at least one. Double-buffered K and V then take at
    most 2·CHUNK_BYTES of VMEM (or two pages each, where one page alone
    exceeds half of CHUNK_BYTES)."""
    page = kv_heads * block_size * head_dim * itemsize    # one page of K
    pages = min(TARGET_KEYS // block_size, CHUNK_BYTES // (2 * page))
    return max(1, min(pages, table_width))


def write_targets(block_tables, new_pos, block_size: int):
    """Each row's new-token page (its table entry at column
    ``new_pos // block_size``) and the position itself; rows with no
    token (``new_pos`` < 0), a column past the table or an unallocated
    entry get position -1 and write nothing."""
    col = jnp.maximum(new_pos, 0) // block_size
    width = block_tables.shape[1]
    blk = jnp.take_along_axis(block_tables,
                              jnp.minimum(col, width - 1)[:, None], 1)[:, 0]
    ok = (new_pos >= 0) & (col < width) & (blk >= 0)
    return blk, jnp.where(ok, new_pos, -1).astype(jnp.int32)


def scatter_new_tokens(pool, new, layer, block_tables, new_pos):
    """The XLA form of the kernel's write: ``pool[layer, blk, :, off] =
    new[b]`` for every row with a token (``new``: [B, KV, D]); the rest
    drop. The CPU path uses it, and it is the reference the kernel's
    write is held to."""
    n_blocks, bs = pool.shape[1], pool.shape[3]
    blk, pos = write_targets(block_tables, new_pos, bs)
    blk = jnp.where(pos >= 0, blk, n_blocks)                 # OOB -> dropped
    return pool.at[layer, blk, :, pos % bs].set(new.astype(pool.dtype),
                                                mode="drop")


def _start_row(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _fold_keys(q_ref, k_ref, v_ref, lead, start, length, acc_ref, m_ref,
               l_ref, *, scale):
    """Online-softmax update of every KV head's R query rows with keys
    ``start .. start+T-1`` (``k_ref[lead]``, ``v_ref[lead]``: [KV, T, D] in
    VMEM); keys at or past ``length`` are masked (scores to -inf, values
    to zero)."""
    kv_heads, keys, head_dim = k_ref.shape[-3:]
    key_pos = start + lax.broadcasted_iota(jnp.int32, (1, keys), 1)
    row_pos = start + lax.broadcasted_iota(jnp.int32, (keys, head_dim), 0)
    for h in range(kv_heads):
        q = q_ref[0, h]                                          # [R, D]
        k = k_ref[(*lead, h)]                                    # [T, D]
        dt = jnp.promote_types(q.dtype, k.dtype)
        s = lax.dot_general(
            q.astype(dt), k.astype(dt), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale          # [R, T]
        s = jnp.where(key_pos < length, s, NEG_INF)
        v = v_ref[(*lead, h)].astype(jnp.float32)                 # [T, D]
        v = jnp.where(row_pos < length, v, 0.0)

        m_prev = m_ref[h]                                        # [R, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[h] = m_new


def _finish_row(o_ref, acc_ref, l_ref):
    l = l_ref[...]                               # 0 only for a length-0 row
    o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(
        o_ref.dtype)


def _chunked_kernel(lyr_ref, tbl_ref, len_ref, pos_ref, q_ref, kn_ref,
                    vn_ref, k_hbm, v_hbm, o_ref, k_out, v_out, k_buf, v_buf,
                    sems, wsems, acc_ref, m_ref, l_ref, next_ref, *, pages,
                    scale):
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    keys = k_buf.shape[2]
    bs = keys // pages
    width = tbl_ref.shape[1]
    layer = lyr_ref[0]
    pos = pos_ref[b]                 # the new token's position, -1: none
    bufs = ((k_hbm, k_out, k_buf, kn_ref), (v_hbm, v_out, v_buf, vn_ref))

    def chunk_copies(row, c, slot, op):
        """Start (or wait for) the DMA of every page chunk ``c`` of
        ``row`` holds, into buffer ``slot``."""
        held = jnp.minimum(pl.cdiv(len_ref[row], bs), width) - c * pages

        def page(j, carry):
            blk = tbl_ref[row, c * pages + j]
            dst = pl.ds(pl.multiple_of(j * bs, bs), bs)
            for i, (hbm, _, buf, _) in enumerate(bufs):
                cp = pltpu.make_async_copy(hbm.at[layer, blk],
                                           buf.at[slot, :, dst],
                                           sems.at[i, slot])
                if op == "start":
                    cp.start()
                else:
                    cp.wait()
            return carry

        lax.fori_loop(0, jnp.minimum(held, pages), page, 0)

    def page_copy(slot, start, to_pool, op):
        """Start (or wait for) the DMA of the new token's page between
        the pool and the VMEM page at key ``start`` of buffer ``slot``."""
        blk = tbl_ref[b, pos // bs]
        for i, (hbm, out, buf, _) in enumerate(bufs):
            vmem = buf.at[slot, :, pl.ds(pl.multiple_of(start, bs), bs)]
            src, dst = ((vmem, out.at[layer, blk]) if to_pool
                        else (hbm.at[layer, blk], vmem))
            cp = pltpu.make_async_copy(src, dst, wsems.at[i])
            if op == "start":
                cp.start()
            else:
                cp.wait()

    def place_token(slot, start):
        """Merge the new token into row ``pos % bs`` of the VMEM page at
        key ``start`` of buffer ``slot``. The page is loaded and stored
        whole, since bf16 packs two rows to a sublane; the merge runs in
        f32, which holds every bf16 value exactly."""
        page = (slot, slice(None), pl.ds(pl.multiple_of(start, bs), bs))
        for _, _, buf, new_ref in bufs:
            old = buf[page].astype(jnp.float32)             # [KV, bs, D]
            rows = lax.broadcasted_iota(jnp.int32, old.shape, 1)
            new = new_ref[0].astype(jnp.float32)             # [KV, 1, D]
            buf[page] = jnp.where(rows == pos % bs, new, old).astype(
                buf.dtype)

    @pl.when(b == 0)
    def _first_row():
        next_ref[0] = 0          # 1: this row's first chunk is in flight
        next_ref[1] = 0          # the buffer slot of this row's first chunk

    length = len_ref[b]
    _start_row(acc_ref, m_ref, l_ref)

    @pl.when((length == 0) & (pos >= 0))
    def _write_only():
        # no chunk is in flight for a row of length 0: both slots are free
        slot = next_ref[1]
        page_copy(slot, 0, False, "start")
        page_copy(slot, 0, False, "wait")
        place_token(slot, 0)
        page_copy(slot, 0, True, "start")
        page_copy(slot, 0, True, "wait")

    @pl.when(length > 0)
    def _row():
        n_chunks = pl.cdiv(length, keys)
        slot0 = next_ref[1]
        last_slot = (slot0 + n_chunks - 1) % 2
        last_page = (pos - (n_chunks - 1) * keys) // bs * bs

        @pl.when(next_ref[0] == 0)
        def _start_first():
            chunk_copies(b, 0, slot0, "start")

        nxt = jnp.minimum(b + 1, n_rows - 1)
        next_live = (b + 1 < n_rows) & (len_ref[nxt] > 0)

        def chunk(c, carry):
            slot = (slot0 + c) % 2

            @pl.when(c + 1 < n_chunks)
            def _prefetch_own():
                chunk_copies(b, c + 1, 1 - slot, "start")

            @pl.when(c + 1 == n_chunks)
            def _prefetch_next_row():
                @pl.when(next_live)
                def _():
                    chunk_copies(nxt, 0, 1 - slot, "start")
                next_ref[0] = next_live.astype(jnp.int32)
                next_ref[1] = 1 - slot

            chunk_copies(b, c, slot, "wait")

            @pl.when((c + 1 == n_chunks) & (pos >= 0))
            def _write():
                place_token(slot, last_page)
                page_copy(slot, last_page, True, "start")

            _fold_keys(q_ref, k_buf, v_buf, (slot,), c * keys, length,
                       acc_ref, m_ref, l_ref, scale=scale)
            return carry

        lax.fori_loop(0, n_chunks, chunk, 0)

        @pl.when(pos >= 0)
        def _written():
            page_copy(last_slot, last_page, True, "wait")

    _finish_row(o_ref, acc_ref, l_ref)


def _page_kernel(lyr_ref, tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                 acc_ref, m_ref, l_ref, *, scale):
    b, blk = pl.program_id(0), pl.program_id(1)
    bs = k_ref.shape[-2]
    length = len_ref[b]

    @pl.when(blk == 0)
    def _init():
        _start_row(acc_ref, m_ref, l_ref)

    @pl.when(blk * bs < length)
    def _fold():
        _fold_keys(q_ref, k_ref, v_ref, (0, 0), blk * bs, length, acc_ref,
                   m_ref, l_ref, scale=scale)

    @pl.when(blk == pl.num_programs(1) - 1)
    def _fin():
        _finish_row(o_ref, acc_ref, l_ref)


def _page_write_kernel(lyr_ref, page_ref, pos_ref, first_ref, kn_ref,
                       vn_ref, k_in, v_in, k_out, v_out):
    b = pl.program_id(0)
    bs = k_in.shape[-2]
    for page_in, page_out, new_ref in ((k_in, k_out, kn_ref),
                                       (v_in, v_out, vn_ref)):
        @pl.when(first_ref[b] == 1)
        def _load():
            page_out[...] = page_in[...]

        @pl.when(pos_ref[b] >= 0)
        def _merge():
            old = page_out[0, 0].astype(jnp.float32)         # [KV, bs, D]
            rows = lax.broadcasted_iota(jnp.int32, old.shape, 1)
            new = new_ref[0].astype(jnp.float32)             # [KV, 1, D]
            page_out[0, 0] = jnp.where(rows == pos_ref[b] % bs, new,
                                       old).astype(page_out.dtype)


def _write_pages(k_pool, v_pool, lyr, block_tables, tokens, new_pos, *,
                 interpret):
    """The pipelined side's write, in place through the Pallas pipeline:
    grid (batch,), each step's input and output block one page of the
    layer. A row with no token maps to the page of the nearest row that
    writes (before it, else after it), so every page a run of rows maps
    to stays resident across the run, is loaded at the run's first step
    and written back once, and only the writing row merges its token.
    With no row writing, every row maps to block 0, written back as it
    was read."""
    B, (KV, bs, D) = new_pos.shape[0], k_pool.shape[2:]
    blk, pos = write_targets(block_tables, new_pos, bs)
    rows = jnp.arange(B)
    before = lax.cummax(jnp.where(pos >= 0, rows, -1))
    after = lax.cummin(jnp.where(pos >= 0, rows, B), reverse=True)
    src = jnp.where(before >= 0, before, after)
    page = jnp.where(src < B, blk[jnp.minimum(src, B - 1)], 0).astype(
        jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), bool), page[1:] != page[:-1]])
    pool = pl.BlockSpec((1, 1, KV, bs, D),
                        lambda b, lyr, page, *_: (lyr[0], page[b], 0, 0, 0))
    token = pl.BlockSpec((1, KV, 1, D), lambda b, *_: (b, 0, 0, 0))
    return pl.pallas_call(
        _page_write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(B,),
            in_specs=[token, token, pool, pool], out_specs=[pool, pool]),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype)
                   for p in (k_pool, v_pool)],
        input_output_aliases={6: 0, 7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="paged_decode_write",
    )(lyr, page, pos, first.astype(jnp.int32), *tokens, k_pool, v_pool)


def paged_decode_attention(q, k_pool, v_pool, layer, block_tables, lengths,
                           new_k, new_v, new_pos, *, interpret: bool = False):
    """q: [B, H, D] with H a multiple of KV (GQA: query heads are grouped
    by their KV head, no caller-side repeat); k/v_pool: [L, n_blocks, KV,
    bs, D] (the serving pool, every layer, in its native KV-head-major
    layout — each block's [KV, bs, D] page is contiguous); ``layer``: the
    layer to read and write (an int or an int32 scalar); block_tables:
    [B, max_blocks] int32 (entries < 0 treated as block 0 and masked by
    length); lengths: [B] int32 keys each row attends, its new token
    included (0 = no attention, output is zeros).

    new_k/new_v: [B, KV, D], the rows' new tokens, written at ``new_pos``
    [B] int32 (the token's position; < 0 writes nothing) into the layer's
    page before the row attends. A row that attends and writes has its
    token at ``lengths - 1``.

    Returns (out [B, H, D], k_pool, v_pool): the pools updated in place
    (aliased input to output; donate them to avoid a copy).
    """
    B, H, D = q.shape
    _, _, KV, bs, _ = k_pool.shape
    assert H % KV == 0, f"H={H} must be a multiple of KV={KV}"
    rep = H // KV
    width = block_tables.shape[1]
    scale = 1.0 / math.sqrt(D)
    _, new_pos = write_targets(block_tables, new_pos.astype(jnp.int32), bs)
    qg = q.reshape(B, KV, rep, D)            # query heads by their KV head
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)
    tbl = jnp.maximum(block_tables, 0).astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    stats = [pltpu.VMEM((KV, rep, D), jnp.float32),      # acc
             pltpu.VMEM((KV, rep, 1), jnp.float32),      # running max
             pltpu.VMEM((KV, rep, 1), jnp.float32)]      # running sum
    out_rows = jax.ShapeDtypeStruct((B, KV, rep, D), q.dtype)
    tokens = [x.astype(p.dtype).reshape(B, KV, 1, D)
              for x, p in ((new_k, k_pool), (new_v, v_pool))]

    if D % LANES == 0:
        pages = chunk_pages(bs, KV, D, k_pool.dtype.itemsize, width)
        kernel = functools.partial(_chunked_kernel, pages=pages, scale=scale)
        rows = pl.BlockSpec((1, KV, rep, D), lambda b, *_: (b, 0, 0, 0))
        token = pl.BlockSpec((1, KV, 1, D), lambda b, *_: (b, 0, 0, 0))
        pool = pl.BlockSpec(memory_space=pltpu.HBM)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(B,),
            in_specs=[rows, token, token, pool, pool],
            out_specs=[rows, pool, pool],
            scratch_shapes=[
                pltpu.VMEM((2, KV, pages * bs, D), k_pool.dtype),
                pltpu.VMEM((2, KV, pages * bs, D), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SemaphoreType.DMA((2,)),
                *stats,
                pltpu.SMEM((2,), jnp.int32),
            ])
        pools = [jax.ShapeDtypeStruct(p.shape, p.dtype)
                 for p in (k_pool, v_pool)]
        out, k_pool, v_pool = pl.pallas_call(
            kernel, grid_spec=grid_spec, out_shape=[out_rows, *pools],
            input_output_aliases={7: 1, 8: 2},   # the pools, in place
            # rows run in order: a row's last chunk starts the next row's
            # first
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret, name="paged_decode",
        )(lyr, tbl, lengths, new_pos, qg, *tokens, k_pool, v_pool)
        return out.reshape(B, H, D), k_pool, v_pool

    k_pool, v_pool = _write_pages(k_pool, v_pool, lyr, block_tables, tokens,
                                  new_pos, interpret=interpret)
    kernel = functools.partial(_page_kernel, scale=scale)

    def page(b, blk, lyr, tbl, ln):
        # clamp to the row's last page: later grid steps re-reference it
        # (DMA elided) and pl.when skips their compute
        last = jnp.maximum(pl.cdiv(ln[b], bs) - 1, 0)
        return (lyr[0], tbl[b, jnp.minimum(blk, last)], 0, 0, 0)

    rows = pl.BlockSpec((1, KV, rep, D), lambda b, blk, *_: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(B, width),
        in_specs=[rows, pl.BlockSpec((1, 1, KV, bs, D), page),
                  pl.BlockSpec((1, 1, KV, bs, D), page)],
        out_specs=rows, scratch_shapes=stats)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_rows,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name="paged_decode",
    )(lyr, tbl, lengths, qg, k_pool, v_pool)
    return out.reshape(B, H, D), k_pool, v_pool
