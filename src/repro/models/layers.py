"""Model primitives: norms, rotary embeddings, attention (GQA / MLA), MLPs.

Everything is pure-functional: parameters are nested dicts of ``jnp`` arrays,
initialised by ``init_*`` helpers and consumed by ``apply``-style functions.
Attention uses a chunked (flash-style) jnp path so that 32k-sequence prefill
lowers with a bounded live-score footprint — XLA does not flash-ify a naive
``softmax(QK^T)V`` on its own.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro.configs.base import ModelConfig

NEG_INF = -2.0 ** 20  # large-negative that is safe in bf16 softmax


# --------------------------------------------------------------------------- init
def dense_init(key, in_dim: int, out_shape, dtype) -> jnp.ndarray:
    """Truncated-normal fan-in init; out_shape may be a tuple (heads, dim)."""
    if isinstance(out_shape, int):
        out_shape = (out_shape,)
    scale = 1.0 / math.sqrt(in_dim)
    w = jax.random.truncated_normal(key, -2.0, 2.0, (in_dim, *out_shape), jnp.float32)
    return (w * scale).astype(dtype)


def stack_init(init_fn, key, n: int):
    """Initialise ``n`` stacked copies of a layer's params (leading dim n)."""
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


# --------------------------------------------------------------------------- norms
def init_norm(cfg: ModelConfig, dtype):
    p = {"scale": jnp.ones((cfg.d_model,), dtype)}
    if cfg.norm_kind == "layernorm":
        p["bias"] = jnp.zeros((cfg.d_model,), dtype)
    return p


def apply_norm(p, x, cfg: ModelConfig):
    xf = x.astype(jnp.float32)
    if cfg.norm_kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + cfg.norm_eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def rms_norm_dim(x, eps=1e-6):
    """Scale-free RMS norm over the last dim (for qk-norm)."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return y.astype(x.dtype)


# --------------------------------------------------------------------------- rope
def rope_freqs(dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x, positions, theta: float):
    """x: [..., S, H, D] (D even); positions: [..., S] int32."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                              # [D/2]
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, D/2]
    angles = angles[..., :, None, :]                          # broadcast over heads
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(positions, dim: int):
    """Classic transformer sinusoids; positions [..., S] -> [..., S, dim]."""
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ----------------------------------------------------------------- attention core
def _attn_mask(qpos, kpos, kv_len, window, causal):
    """Build [B,Qc,Sk] mask from per-batch positions.

    qpos: [B,Qc]; kpos: [B,Sk]; kv_len: [B] or None.
    """
    if causal:
        mask = kpos[:, None, :] <= qpos[:, :, None]
        if window is not None:
            mask &= kpos[:, None, :] > qpos[:, :, None] - window
    else:
        mask = jnp.ones((qpos.shape[0], qpos.shape[1], kpos.shape[1]), bool)
    if kv_len is not None:
        mask &= kpos[:, None, :] < kv_len[:, None, None]
    return mask


def _attend_block(q, k, v, qpos, kpos, kv_len, window, causal, softcap=0.0):
    """One (q-chunk x full-K) attention step with GQA grouping.

    q:[B,Qc,KV,R,D] k,v:[B,Sk,KV,D] -> out [B,Qc,KV,R,D]; fp32 softmax.
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqkrd,bskd->bkrqs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    mask = _attn_mask(qpos, kpos, kv_len, window, causal)
    s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkrqs,bskd->bqkrd", w, v.astype(jnp.float32))
    return out


def attention(q, k, v, *, q_positions=None, kv_len=None, window=None,
              causal=True, chunk=512, softcap=0.0, k_positions=None):
    """Chunked multi-head attention with GQA head grouping.

    q: [B,Sq,H,D]; k,v: [B,Sk,KV,D]. ``q_positions`` [B,Sq] are absolute
    positions of the queries (decode passes per-request cache offsets);
    default arange. ``kv_len`` [B] masks partially-filled caches.
    ``k_positions`` ([Sk] or [B,Sk]) overrides K positions (ring buffers).
    Returns [B,Sq,H,D] in q.dtype.
    """
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    Dv = v.shape[-1]  # MLA: v head dim differs from qk head dim
    R = H // KV
    qg = q.reshape(B, Sq, KV, R, D)
    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(Sq, dtype=jnp.int32), (B, Sq))
    if k_positions is None:
        kpos = jnp.broadcast_to(jnp.arange(Sk, dtype=jnp.int32), (B, Sk))
    else:
        kpos = jnp.broadcast_to(k_positions, (B, Sk)).astype(jnp.int32)

    if Sq <= chunk:
        out = _attend_block(qg, k, v, q_positions, kpos, kv_len, window,
                            causal, softcap)
        return out.reshape(B, Sq, H, Dv).astype(q.dtype)

    # pad Sq to a multiple of the chunk and scan over chunks
    nc = -(-Sq // chunk)
    pad = nc * chunk - Sq
    qg = jnp.pad(qg, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    qp = jnp.pad(q_positions, ((0, 0), (0, pad)), constant_values=-1)
    qg = qg.reshape(B, nc, chunk, KV, R, D).transpose(1, 0, 2, 3, 4, 5)
    qp = qp.reshape(B, nc, chunk).transpose(1, 0, 2)

    def step(_, qs):
        qc, qpc = qs
        return None, _attend_block(qc, k, v, qpc, kpos, kv_len, window,
                                   causal, softcap)

    _, out = jax.lax.scan(step, None, (qg, qp))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, nc * chunk, H, Dv)
    return out[:, :Sq].astype(q.dtype)


# ------------------------------------------------------------------ GQA attention
def init_gqa(cfg: ModelConfig, key, dtype):
    hd = cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, cfg.d_model, (cfg.num_heads, hd), dtype),
        "wk": dense_init(k2, cfg.d_model, (cfg.num_kv_heads, hd), dtype),
        "wv": dense_init(k3, cfg.d_model, (cfg.num_kv_heads, hd), dtype),
        "wo": dense_init(k4, cfg.num_heads * hd, cfg.d_model, dtype),
    }


def apply_gqa(p, x, cfg: ModelConfig, *, positions, cache=None, slots=None,
              k_positions=None, window=None, kv_override=None, causal=True,
              attend_fresh=False):
    """GQA/MQA attention with optional KV-cache write-then-attend.

    ``attend_fresh`` (prefill): attention runs over this call's own K/V —
    required for ring buffers, where the cache only retains the last W
    tokens but mid-sequence queries still need their full window — while
    the cache write at ``slots`` happens on the side.

    x: [B,S,d]; positions [B,S] absolute. ``cache`` is None (train) or a
    per-layer dict {"k": [B,M,KV,hd], "v": ...}; ``slots`` [B,S] are the
    cache rows to write (ring buffers pass ``positions % M``) and
    ``k_positions`` [B,M] the (already-updated) absolute position of each
    cache row (unwritten rows hold 2**30 so they mask out).
    ``kv_override``: (k, v) for cross-attention (ignores the cache path).
    Returns (out [B,S,d], new_cache_or_None).
    """
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if kv_override is not None:
        k, v = kv_override
        out = attention(q, k, v, causal=False, chunk=512)
        out = out.reshape(B, S, cfg.num_heads * hd)
        return jnp.einsum("bsh,hd->bsd", out, p["wo"]), None
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.use_qk_norm:
        q, k = rms_norm_dim(q), rms_norm_dim(k)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if cache is None:
        out = attention(q, k, v, q_positions=positions, window=window,
                        causal=causal, softcap=cfg.logit_softcap)
        new_cache = None
    else:
        bidx = jnp.arange(B, dtype=jnp.int32)[:, None]
        ck = cache["k"].at[bidx, slots].set(k, mode="drop")
        cv = cache["v"].at[bidx, slots].set(v, mode="drop")
        new_cache = {"k": ck, "v": cv}
        if attend_fresh:  # prefill: full fresh sequence, windowed mask
            out = attention(q, k, v, q_positions=positions, window=window,
                            causal=causal, softcap=cfg.logit_softcap)
        else:  # decode: attend the updated cache
            from repro.parallel.sharding import current_rules
            rules = current_rules()
            if (rules and rules.get("flash_decode") and S == 1
                    and rules.get("cache_seq")):
                # distributed flash-decoding over the seq-sharded cache
                # (beyond-paper optimization, EXPERIMENTS §Perf pair C)
                from repro.parallel.distributed_attention import flash_decode
                out = flash_decode(
                    q, ck, cv, positions,
                    jnp.broadcast_to(k_positions, (B, ck.shape[1])),
                    mesh=rules["mesh"], seq_axis=rules["cache_seq"],
                    batch_axis=rules.get("batch"), window=window,
                    softcap=cfg.logit_softcap)
            else:
                out = attention(q, ck, cv, q_positions=positions,
                                k_positions=k_positions, window=window,
                                causal=causal, softcap=cfg.logit_softcap)
    out = out.reshape(B, S, cfg.num_heads * hd)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"]), new_cache


def paged_kernel_serves(cfg: ModelConfig, window) -> bool:
    """Whether the Pallas paged-decode kernel computes this attention:
    plain causal GQA, no logit softcap, no sliding window."""
    return not cfg.logit_softcap and window is None


def apply_gqa_paged(p, x, cfg: ModelConfig, *, positions, pool_k, pool_v,
                    layer, block_tables, active, window=None,
                    impl="gather", interpret=False, batch_shard=None):
    """GQA decode attention against a paged block pool (write-then-attend).

    Single-token decode only: x [B,1,d]. ``pool_k/v`` are the WHOLE pools
    [L, n_blocks, KV, bs, hd] (KV-head-major — the Pallas kernel's native
    tile layout) and ``layer`` is this layer's index into them (an int or
    an int32 scalar): the layer is read and written where it lies, never
    sliced out. ``block_tables`` [B, max_blocks] int32 (-1 = unallocated,
    masked); ``positions`` [B,1] are the pre-write token counts (the new
    token lands at position ``positions[b,0]``); ``active`` [B] bool —
    inactive rows write nothing and attend over an empty, fully-masked
    context, producing garbage logits the engine ignores.

    ``impl``: "gather" writes the new tokens with an XLA scatter, then
    materializes the table's blocks with a batched gather and reuses the
    chunked fp32 attention (jit-friendly anywhere); "kernel" calls the
    Pallas paged-decode kernel (kernels/paged_decode.py), which writes
    each row's token into its page in place and whose HBM traffic stops
    at each request's true length. The kernel implements plain causal GQA
    only (``paged_kernel_serves``): asking it to serve a logit softcap or
    a sliding window raises. The Engine makes that choice once, at
    construction. ``batch_shard`` = (mesh, batch PartitionSpec) of a
    replicated layer: the compiler cannot partition a Pallas kernel, so
    the kernel is mapped over the batch shards there (``_shard_rows``).

    Returns (out [B,1,d], new_pool_k, new_pool_v).
    """
    from repro.kernels import paged_decode as PD
    B, S, _ = x.shape
    assert S == 1, "paged path is decode-only (one token per step)"
    hd = cfg.resolved_head_dim
    KV = pool_k.shape[2]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.use_qk_norm:
        q, k = rms_norm_dim(q), rms_norm_dim(k)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    pos = positions[:, 0].astype(jnp.int32)
    new_pos = jnp.where(active, pos, -1)          # inactive rows write nothing
    kv_len = jnp.where(active, pos + 1, 0).astype(jnp.int32)
    if impl == "kernel":
        if not paged_kernel_serves(cfg, window):
            raise ValueError("the paged decode kernel has no logit softcap "
                             "or sliding window; use impl='gather'")
        kernel = functools.partial(PD.paged_decode_attention,
                                   interpret=interpret)
        if batch_shard is not None:
            kernel = _shard_rows(kernel, *batch_shard)
        out, pool_k, pool_v = kernel(q[:, 0], pool_k, pool_v, layer,
                                     block_tables, kv_len, k[:, 0], v[:, 0],
                                     new_pos)
        out = out[:, None]
    else:
        pool_k = PD.scatter_new_tokens(pool_k, k[:, 0], layer, block_tables,
                                       new_pos)
        pool_v = PD.scatter_new_tokens(pool_v, v[:, 0], layer, block_tables,
                                       new_pos)
        tbl = jnp.maximum(block_tables, 0).astype(jnp.int32)
        # gathered blocks are [B, mb, KV, bs, hd]; only the gathered
        # context is re-laid token-major, never the whole pool
        kk = pool_k[layer][tbl].transpose(0, 1, 3, 2, 4).reshape(
            B, -1, KV, hd)
        vv = pool_v[layer][tbl].transpose(0, 1, 3, 2, 4).reshape(
            B, -1, KV, hd)
        out = attention(q, kk, vv, q_positions=positions, kv_len=kv_len,
                        k_positions=jnp.arange(kk.shape[1], dtype=jnp.int32),
                        window=window, causal=True,
                        softcap=cfg.logit_softcap)
    out = out.reshape(B, S, cfg.num_heads * hd)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"]), pool_k, pool_v


def _shard_rows(kernel, mesh, spec):
    """The paged kernel of a replicated layer, mapped over the batch
    shards of ``spec``. Each shard attends its own rows over the
    replicated pool, and every device writes EVERY row's new token (the
    rows come in gathered), so the pool's replicas stay identical: a
    shard's rows of other shards attend nothing (length 0) and only
    write."""
    axes = spec[0]
    if axes is None:                         # degree 1: one shard, all rows
        return kernel
    n = math.prod(mesh.shape[a] for a in axes)

    def local(q, pool_k, pool_v, layer, tables, kv_len, new_k, new_v,
              new_pos):
        rows = q.shape[0] // n
        shard = jax.lax.axis_index(axes)
        mine = jnp.arange(q.shape[0]) // rows == shard
        out, pool_k, pool_v = kernel(q, pool_k, pool_v, layer, tables,
                                     jnp.where(mine, kv_len, 0), new_k,
                                     new_v, new_pos)
        return (jax.lax.dynamic_slice_in_dim(out, shard * rows, rows),
                pool_k, pool_v)

    every = PartitionSpec()
    return jax.shard_map(local, mesh=mesh, in_specs=(every,) * 9,
                         out_specs=(PartitionSpec(axes), every, every),
                         check_vma=False)


def compute_cross_kv(p, enc_out):
    """Cross-attention K/V from encoder output (whisper decoder prefill)."""
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"])
    return k, v


# ------------------------------------------------------------------ MLA attention
def init_mla(cfg: ModelConfig, key, dtype):
    ks = jax.random.split(key, 6)
    H = cfg.num_heads
    qk_dim = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    p = {}
    if cfg.q_lora_rank:
        p["wq_a"] = dense_init(ks[0], cfg.d_model, cfg.q_lora_rank, dtype)
        p["wq_b"] = dense_init(ks[1], cfg.q_lora_rank, (H, qk_dim), dtype)
    else:
        p["wq"] = dense_init(ks[1], cfg.d_model, (H, qk_dim), dtype)
    p["wkv_a"] = dense_init(ks[2], cfg.d_model,
                            cfg.kv_lora_rank + cfg.qk_rope_head_dim, dtype)
    p["wk_b"] = dense_init(ks[3], cfg.kv_lora_rank, (H, cfg.qk_nope_head_dim), dtype)
    p["wv_b"] = dense_init(ks[4], cfg.kv_lora_rank, (H, cfg.v_head_dim), dtype)
    p["wo"] = dense_init(ks[5], H * cfg.v_head_dim, cfg.d_model, dtype)
    return p


def _mla_q(p, x, cfg, positions):
    if cfg.q_lora_rank:
        q = jnp.einsum("bsd,dr->bsr", x, p["wq_a"])
        q = jnp.einsum("bsr,rhk->bshk", q, p["wq_b"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope = q[..., : cfg.qk_nope_head_dim]
    q_rope = apply_rope(q[..., cfg.qk_nope_head_dim:], positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_latents(p, x, cfg, positions):
    ckr = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    c_kv = ckr[..., : cfg.kv_lora_rank]
    k_rope = apply_rope(ckr[..., None, cfg.kv_lora_rank:],
                        positions, cfg.rope_theta)[..., 0, :]
    return c_kv, k_rope


def apply_mla_prefill(p, x, cfg: ModelConfig, *, positions, cache=None,
                      slots=None, window=None):
    """MLA in the expanded (compute-friendly) form for train/prefill.

    cache: None or per-layer {"c": [B,M,r], "kr": [B,M,rope]}; latents of
    this call are written at ``slots``. Returns (out, new_cache_or_None).
    """
    B, S, _ = x.shape
    q_nope, q_rope = _mla_q(p, x, cfg, positions)
    c_kv, k_rope = _mla_latents(p, x, cfg, positions)
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["wk_b"])
    v = jnp.einsum("bsr,rhk->bshk", c_kv, p["wv_b"])
    H = cfg.num_heads
    k_rope_b = jnp.broadcast_to(k_rope[:, :, None, :],
                                (B, S, H, cfg.qk_rope_head_dim))
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([k_nope, k_rope_b], -1)
    out = attention(q, k, v, q_positions=positions, window=window)
    out = out.reshape(B, S, H * cfg.v_head_dim)
    out = jnp.einsum("bsh,hd->bsd", out, p["wo"])
    new_cache = None
    if cache is not None:
        bidx = jnp.arange(B, dtype=jnp.int32)[:, None]
        new_cache = {"c": cache["c"].at[bidx, slots].set(c_kv, mode="drop"),
                     "kr": cache["kr"].at[bidx, slots].set(k_rope, mode="drop")}
    return out, new_cache


def apply_mla_decode(p, x, cfg: ModelConfig, *, positions, cache, slots,
                     k_positions, window=None):
    """MLA decode in the ABSORBED form (DeepSeek-V2): attention runs in the
    latent space so the per-step cost is MQA-like over (r + rope) dims.

    cache: per-layer {"c": [B,M,r], "kr": [B,M,rope]} — new latents are
    written at ``slots`` BEFORE attending; ``k_positions`` [B,M] are the
    already-updated absolute row positions. Returns (out, new_cache).
    """
    B, S, _ = x.shape
    q_nope, q_rope = _mla_q(p, x, cfg, positions)
    c_new, kr_new = _mla_latents(p, x, cfg, positions)
    bidx = jnp.arange(B, dtype=jnp.int32)[:, None]
    cc = cache["c"].at[bidx, slots].set(c_new, mode="drop")
    ckr = cache["kr"].at[bidx, slots].set(kr_new, mode="drop")
    M = cc.shape[1]
    # absorb W_uk into q: q_lat [B,S,H,r]
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["wk_b"])
    scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    kpos = jnp.broadcast_to(k_positions, (B, M)).astype(jnp.int32)
    from repro.parallel.sharding import current_rules
    rules = current_rules()
    if rules and rules.get("flash_decode") and S == 1 \
            and rules.get("cache_seq"):
        # distributed flash-decoding in latent space (§Perf pair C family)
        from repro.parallel.distributed_attention import flash_decode_mla
        o_lat = flash_decode_mla(
            q_lat, q_rope, cc, ckr, positions, kpos,
            mesh=rules["mesh"], seq_axis=rules["cache_seq"],
            batch_axis=rules.get("batch"), window=window,
            qk_dim=cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
        o_lat = o_lat.astype(jnp.float32)
    else:
        s = (jnp.einsum("bshr,btr->bhst", q_lat.astype(jnp.float32),
                        cc.astype(jnp.float32))
             + jnp.einsum("bshk,btk->bhst", q_rope.astype(jnp.float32),
                          ckr.astype(jnp.float32))) * scale
        mask = _attn_mask(positions, kpos, None, window, True)
        s = jnp.where(mask[:, None, :, :], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bhst,btr->bshr", w, cc.astype(jnp.float32))
    out = jnp.einsum("bshr,rhk->bshk", o_lat.astype(x.dtype), p["wv_b"])
    out = out.reshape(B, S, cfg.num_heads * cfg.v_head_dim)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"]), {"c": cc, "kr": ckr}


# ------------------------------------------------------------------------- MLPs
def init_mlp(cfg: ModelConfig, key, dtype, d_ff: Optional[int] = None):
    d_ff = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    if cfg.ffn_kind == "gelu":
        return {"w_up": dense_init(k1, cfg.d_model, d_ff, dtype),
                "w_down": dense_init(k2, d_ff, cfg.d_model, dtype)}
    return {"w_gate": dense_init(k1, cfg.d_model, d_ff, dtype),
            "w_up": dense_init(k2, cfg.d_model, d_ff, dtype),
            "w_down": dense_init(k3, d_ff, cfg.d_model, dtype)}


def apply_mlp(p, x, cfg: ModelConfig):
    if cfg.ffn_kind == "gelu":
        h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, p["w_up"]))
        return jnp.einsum("bsf,fd->bsd", h, p["w_down"])
    act = (jax.nn.gelu if cfg.ffn_kind == "geglu" else jax.nn.silu)
    g = act(jnp.einsum("bsd,df->bsf", x, p["w_gate"]))
    h = g * jnp.einsum("bsd,df->bsf", x, p["w_up"])
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"])
