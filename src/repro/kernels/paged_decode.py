"""Paged decode attention (Pallas): one new token per row against a
block-table KV pool — the vLLM paged-attention mechanism on TPU, with GQA
head grouping.

Grid: (batch,). One grid step serves one row for every KV head at once.
The pool stays in HBM (``memory_space=pltpu.HBM``) in the serving pool's
KV-HEAD-MAJOR layout ([n_blocks, KV, bs, D], serving/paged_kv.py), so one
page — a block's K (or V) for every KV head, [KV, bs, D] — is contiguous
there. The kernel walks the row's block table in chunks of P pages: each
page is one async DMA into a VMEM chunk [KV, P·bs, D] for K and one for
V, and the chunks are double-buffered — chunk c+1 is in flight while
chunk c is computed, and a row's last chunk starts the next row's first,
so a grid step boundary never leaves the DMA engine idle.

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
the arithmetic of the whole batch. The block table and the lengths arrive
by scalar prefetch (SMEM).

A head_dim that is not a multiple of 128 lanes (TinyLlama's 64) is
padded to 128 lanes in HBM, and the TPU compiler refuses a DMA of a slice
of such an array. There the pages come in through the Pallas pipeline
instead: grid (batch, table width), one page of every KV head per grid
step, the page index clamped to the row's last page (the repeated DMA is
elided) and the fold skipped past the length. The fold is the same.
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


def _chunked_kernel(tbl_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
                    k_buf, v_buf, sems, acc_ref, m_ref, l_ref, next_ref,
                    *, pages, scale):
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    keys = k_buf.shape[2]
    bs = keys // pages
    width = tbl_ref.shape[1]

    def chunk_copies(row, c, slot, op):
        """Start (or wait for) the DMA of every page chunk ``c`` of
        ``row`` holds, into buffer ``slot``."""
        held = jnp.minimum(pl.cdiv(len_ref[row], bs), width) - c * pages

        def page(j, carry):
            blk = tbl_ref[row, c * pages + j]
            dst = pl.ds(pl.multiple_of(j * bs, bs), bs)
            for i, (hbm, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                cp = pltpu.make_async_copy(hbm.at[blk], buf.at[slot, :, dst],
                                           sems.at[i, slot])
                if op == "start":
                    cp.start()
                else:
                    cp.wait()
            return carry

        lax.fori_loop(0, jnp.minimum(held, pages), page, 0)

    @pl.when(b == 0)
    def _first_row():
        next_ref[0] = 0          # 1: this row's first chunk is in flight
        next_ref[1] = 0          # the buffer slot of this row's first chunk

    length = len_ref[b]
    _start_row(acc_ref, m_ref, l_ref)

    @pl.when(length > 0)
    def _row():
        n_chunks = pl.cdiv(length, keys)
        slot0 = next_ref[1]

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
            _fold_keys(q_ref, k_buf, v_buf, (slot,), c * keys, length,
                       acc_ref, m_ref, l_ref, scale=scale)
            return carry

        lax.fori_loop(0, n_chunks, chunk, 0)

    _finish_row(o_ref, acc_ref, l_ref)


def _page_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                 acc_ref, m_ref, l_ref, *, scale):
    b, blk = pl.program_id(0), pl.program_id(1)
    bs = k_ref.shape[2]
    length = len_ref[b]

    @pl.when(blk == 0)
    def _init():
        _start_row(acc_ref, m_ref, l_ref)

    @pl.when(blk * bs < length)
    def _fold():
        _fold_keys(q_ref, k_ref, v_ref, (0,), blk * bs, length, acc_ref,
                   m_ref, l_ref, scale=scale)

    @pl.when(blk == pl.num_programs(1) - 1)
    def _fin():
        _finish_row(o_ref, acc_ref, l_ref)


def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths, *,
                           interpret: bool = False):
    """q: [B, H, D] with H a multiple of KV (GQA: query heads are grouped
    by their KV head, no caller-side repeat); k/v_pool: [n_blocks, KV, bs,
    D] (the serving pool's native KV-head-major layout — each block's
    [KV, bs, D] page is contiguous); block_tables: [B, max_blocks] int32
    (entries < 0 treated as block 0 and masked by length); lengths: [B]
    int32 (0 = inactive slot, output is zeros). Returns [B, H, D].
    """
    B, H, D = q.shape
    _, KV, bs, _ = k_pool.shape
    assert H % KV == 0, f"H={H} must be a multiple of KV={KV}"
    rep = H // KV
    width = block_tables.shape[1]
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, KV, rep, D)            # query heads by their KV head
    tbl = jnp.maximum(block_tables, 0).astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    stats = [pltpu.VMEM((KV, rep, D), jnp.float32),      # acc
             pltpu.VMEM((KV, rep, 1), jnp.float32),      # running max
             pltpu.VMEM((KV, rep, 1), jnp.float32)]      # running sum

    if D % LANES == 0:
        pages = chunk_pages(bs, KV, D, k_pool.dtype.itemsize, width)
        kernel = functools.partial(_chunked_kernel, pages=pages, scale=scale)
        rows = pl.BlockSpec((1, KV, rep, D), lambda b, tbl, ln: (b, 0, 0, 0))
        pool = pl.BlockSpec(memory_space=pltpu.HBM)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B,),
            in_specs=[rows, pool, pool], out_specs=rows,
            scratch_shapes=[
                pltpu.VMEM((2, KV, pages * bs, D), k_pool.dtype),
                pltpu.VMEM((2, KV, pages * bs, D), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                *stats,
                pltpu.SMEM((2,), jnp.int32),
            ])
        # rows run in order: a row's last chunk starts the next row's first
        semantics = ("arbitrary",)
    else:
        kernel = functools.partial(_page_kernel, scale=scale)

        def page(b, blk, tbl, ln):
            # clamp to the row's last page: later grid steps re-reference
            # it (DMA elided) and pl.when skips their compute
            last = jnp.maximum(pl.cdiv(ln[b], bs) - 1, 0)
            return (tbl[b, jnp.minimum(blk, last)], 0, 0, 0)

        rows = pl.BlockSpec((1, KV, rep, D),
                            lambda b, blk, tbl, ln: (b, 0, 0, 0))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, width),
            in_specs=[rows, pl.BlockSpec((1, KV, bs, D), page),
                      pl.BlockSpec((1, KV, bs, D), page)],
            out_specs=rows, scratch_shapes=stats)
        semantics = ("arbitrary", "arbitrary")

    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, rep, D), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret, name="paged_decode",
    )(tbl, lengths, qg, k_pool, v_pool)
    return out.reshape(B, H, D)
