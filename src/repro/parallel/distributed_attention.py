"""Distributed flash-decoding over a sequence-sharded KV cache.

Beyond-paper optimization (EXPERIMENTS.md §Perf, pair C): when the KV cache
is sharded along its sequence dimension (the fallback for archs whose KV
heads don't divide the model axis — tinyllama kv=4, chameleon kv=8, arctic
kv=8, MLA latents), naive GSPMD lowering of ``softmax(qK^T)V`` all-reduces
full fp32 score rows per layer. The flash-decoding identity lets each shard
reduce its local slice to (m, l, o) — a per-head max, denominator and
weighted partial output — and combine with a single tiny ``psum``:

    o = Σ_shards exp(m_s - m*) · o_s / Σ_shards exp(m_s - m*) · l_s

Per-layer collective traffic drops from O(B·H·S_local) scores to
O(B·H·D) partials.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.layers import NEG_INF


def _local_partial(q, k, v, qpos, kpos, window, softcap):
    """Shard-local attention partials.

    q: [B,1,KV,R,D]; k,v: [B,Sl,KV,D]; qpos [B,1]; kpos [B,Sl].
    Returns m [B,KV,R], l [B,KV,R], o [B,KV,R,Dv] (fp32).
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqkrd,bskd->bkrqs", q.astype(jnp.float32),
                   k.astype(jnp.float32))[:, :, :, 0] * scale  # [B,KV,R,Sl]
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    qp = qpos[:, 0]                                           # [B]
    mask = kpos <= qp[:, None]                                # [B,Sl]
    if window is not None:
        mask &= kpos > qp[:, None] - window
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)                                   # [B,KV,R]
    p = jnp.exp(s - m[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkrs,bskd->bkrd", p, v.astype(jnp.float32))
    return m, l, o


def flash_decode(q, k_cache, v_cache, q_positions, k_positions, *,
                 mesh: Mesh, seq_axis: str = "model", batch_axis="data",
                 window=None, softcap=0.0):
    """Distributed flash-decoding.

    q: [B,1,H,D]; k/v_cache: [B,M,KV,Dk/Dv]; q_positions [B,1];
    k_positions [B,M]. Cache sharded: P(batch_axis, seq_axis, None, None).
    Returns [B,1,H,Dv] sharded P(batch_axis, None, None, None).
    """
    B, _, H, D = q.shape
    KV = k_cache.shape[2]
    R = H // KV
    qg = q.reshape(B, 1, KV, R, D)

    def kernel(qg, k, v, qp, kp):
        m, l, o = _local_partial(qg, k, v, qp, kp, window, softcap)
        m_max = jax.lax.pmax(m, seq_axis)                     # [B,KV,R]
        corr = jnp.exp(jnp.where(jnp.isfinite(m), m - m_max, NEG_INF))
        corr = jnp.where(jnp.isfinite(m), corr, 0.0)
        l_sum = jax.lax.psum(l * corr, seq_axis)
        o_sum = jax.lax.psum(o * corr[..., None], seq_axis)
        denom = jnp.where(l_sum == 0.0, 1.0, l_sum)
        return (o_sum / denom[..., None]).astype(q.dtype)     # [B,KV,R,Dv]

    bspec = batch_axis
    out = jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(P(bspec, None, None, None, None),
                  P(bspec, seq_axis, None, None),
                  P(bspec, seq_axis, None, None),
                  P(bspec, None),
                  P(bspec, seq_axis)),
        out_specs=P(bspec, None, None, None),
        check_vma=False,
    )(qg, k_cache, v_cache, q_positions, k_positions)
    Dv = v_cache.shape[-1]
    return out.reshape(B, 1, H, Dv)


def _mla_local_partial(q_lat, q_rope, c, kr, qpos, kpos, window, scale):
    """Shard-local absorbed-MLA partials.

    q_lat: [B,H,r]; q_rope: [B,H,ro]; c: [B,Sl,r]; kr: [B,Sl,ro];
    qpos [B,1]; kpos [B,Sl]. Returns m,l [B,H], o_lat [B,H,r] (fp32).
    """
    f32 = jnp.float32
    s = (jnp.einsum("bhr,btr->bht", q_lat.astype(f32), c.astype(f32))
         + jnp.einsum("bhk,btk->bht", q_rope.astype(f32),
                      kr.astype(f32))) * scale
    qp = qpos[:, 0]
    mask = kpos <= qp[:, None]
    if window is not None:
        mask &= kpos > qp[:, None] - window
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.where(jnp.isfinite(s), jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    o_lat = jnp.einsum("bht,btr->bhr", p, c.astype(f32))
    return m, l, o_lat


def flash_decode_mla(q_lat, q_rope, c_cache, kr_cache, q_positions,
                     k_positions, *, mesh: Mesh, seq_axis: str = "model",
                     batch_axis="data", window=None, qk_dim: int = 128):
    """Distributed flash-decoding in MLA's absorbed latent space.

    q_lat: [B,1,H,r]; q_rope: [B,1,H,ro]; c_cache: [B,M,r];
    kr_cache: [B,M,ro] — caches sharded P(batch, seq_axis, None).
    Returns o_lat [B,1,H,r] (multiply by W_uv outside).
    """
    B, _, H, r = q_lat.shape
    scale = 1.0 / math.sqrt(qk_dim)

    def kernel(ql, qr, c, kr, qp, kp):
        m, l, o = _mla_local_partial(ql[:, 0], qr[:, 0], c, kr, qp, kp,
                                     window, scale)
        m_max = jax.lax.pmax(m, seq_axis)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_max), 0.0)
        l_sum = jax.lax.psum(l * corr, seq_axis)
        o_sum = jax.lax.psum(o * corr[..., None], seq_axis)
        denom = jnp.where(l_sum == 0.0, 1.0, l_sum)
        return (o_sum / denom[..., None])[:, None].astype(q_lat.dtype)

    b = batch_axis
    return jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(P(b, None, None, None), P(b, None, None, None),
                  P(b, seq_axis, None), P(b, seq_axis, None),
                  P(b, None), P(b, seq_axis)),
        out_specs=P(b, None, None, None),
        check_vma=False,
    )(q_lat, q_rope, c_cache, kr_cache, q_positions, k_positions)
