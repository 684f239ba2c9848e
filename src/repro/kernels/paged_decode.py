"""Paged decode attention (Pallas): one new token against a block-table KV
pool — the vLLM paged-attention mechanism on TPU, with GQA head grouping.

Grid: (batch · kv_heads, max_blocks); the block axis is sequential and
carries online-softmax state for the R = H/KV query heads that share each
KV head (the same flash-decoding layout as kernels/decode_attention.py).
The block table and per-request lengths arrive via scalar prefetch (SMEM)
and drive the K/V BlockSpec index maps — each grid step DMAs exactly one
pool block [block_size, D] for one KV head into VMEM. The serving pool
(serving/paged_kv.py) stores blocks KV-HEAD-MAJOR ([n_blocks, KV, bs, D]),
so that tile is contiguous in HBM and the kernel consumes the pool
natively — no whole-pool transpose per call.

Early termination: the index map clamps the block coordinate to the last
*valid* block of the request (ceil(length / block_size) - 1). Past that
point consecutive grid steps resolve to the same pool block, which the
Pallas pipeline dedups into a no-op DMA, and ``pl.when`` skips the compute
— so a short request pays HBM traffic and MXU time proportional to its
true context length, not to ``max_blocks``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _paged_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, bs, n_blk, kv_heads, scale):
    bk = pl.program_id(0)
    blk = pl.program_id(1)
    b = bk // kv_heads

    @pl.when(blk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]

    @pl.when(blk * bs < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale      # [R, D]
        k = k_ref[0, 0].astype(jnp.float32)           # [bs, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [R, bs]
        pos = blk * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        safe = jnp.where(jnp.isinf(m_new), 0.0, m_new)
        p = jnp.where(jnp.isinf(s), 0.0, jnp.exp(s - safe[:, None]))
        alpha = jnp.where(jnp.isinf(m_prev), 0.0, jnp.exp(m_prev - safe))
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot(p, v)
        m_ref[...] = m_new

    @pl.when(blk == n_blk - 1)
    def _fin():
        l = l_ref[...]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths, *,
                           interpret: bool = False):
    """q: [B, H, D] with H a multiple of KV (GQA: query heads are grouped
    by their KV head inside the kernel, no caller-side repeat);
    k/v_pool: [n_blocks, KV, bs, D] (the serving pool's native KV-head-
    major layout — each (block, kv-head) tile [bs, D] is contiguous);
    block_tables: [B, max_blocks] int32 (entries < 0 treated as block 0
    and masked by length); lengths: [B] int32 (0 = inactive slot, output
    is zeros). Returns [B, H, D].
    """
    B, H, D = q.shape
    n_blocks, KV, bs, _ = k_pool.shape
    assert H % KV == 0, f"H={H} must be a multiple of KV={KV}"
    rep = H // KV
    max_blocks = block_tables.shape[1]
    scale = 1.0 / math.sqrt(D)
    # group query heads by their kv head: [B*KV, R, D]
    qg = q.reshape(B, KV, rep, D).reshape(B * KV, rep, D)
    kp, vp = k_pool, v_pool                           # native layout
    tbl = jnp.maximum(block_tables, 0).astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)

    kernel = functools.partial(_paged_kernel, bs=bs, n_blk=max_blocks,
                               kv_heads=KV, scale=scale)

    def kv_index(bk, blk, tbl_ref, len_ref):
        b = bk // KV
        kv = bk % KV
        # clamp to the last valid block: pruned steps re-reference the same
        # pool block (DMA elided) and pl.when skips their compute.
        last = jnp.maximum((len_ref[b] + bs - 1) // bs - 1, 0)
        return (tbl_ref[b, jnp.minimum(blk, last)], kv, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * KV, max_blocks),
        in_specs=[
            pl.BlockSpec((1, rep, D), lambda bk, blk, tbl, ln: (bk, 0, 0)),
            pl.BlockSpec((1, 1, bs, D), kv_index),
            pl.BlockSpec((1, 1, bs, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, rep, D),
                               lambda bk, blk, tbl, ln: (bk, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rep, D), jnp.float32),
            pltpu.VMEM((rep,), jnp.float32),
            pltpu.VMEM((rep,), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * KV, rep, D), q.dtype),
        interpret=interpret, name="paged_decode",
    )(tbl, lengths, qg, kp, vp)
    return out.reshape(B, H, D)
