"""Jit'd public wrappers for the Pallas kernels.

``interpret`` comes from the caller: False (the default) compiles the
kernel for the TPU, True runs its interpreted body, which is how the tests
exercise it on the CPU. There is no platform switch in here. Wrappers also
handle padding to block multiples and layout conversion from the model's
[B, S, H, D] convention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import decode_attention as _da
from repro.kernels import ssd_scan as _ssd


@functools.partial(jax.jit, static_argnames=("causal", "interpret"))
def flash_attention_bshd(q, k, v, *, causal: bool = True,
                         interpret: bool = False):
    """Model-layout flash attention: q [B,Sq,H,D], k/v [B,Sk,KV,D]."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    blk_q = min(_fa.DEFAULT_BLK_Q, max(16, Sq))
    blk_k = min(_fa.DEFAULT_BLK_K, max(16, Sk))
    pad_q = (-Sq) % blk_q
    pad_k = (-Sk) % blk_k
    qt = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    kt = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    vt = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    # padded K rows must not attend: causal masking handles pad at the end
    # only when pad_k rows sit beyond every real q position — enforce by
    # masking via an explicit large-negative trick: zero K rows attend with
    # score 0; instead we rely on causal mask (pad_q rows discarded) and
    # for non-causal pad_k must be 0.
    if not causal:
        assert pad_k == 0, "non-causal path requires block-aligned Sk"
    out = _fa.flash_attention(qt, kt, vt, causal=causal, blk_q=blk_q,
                              blk_k=blk_k, interpret=interpret)
    return out.transpose(0, 2, 1, 3)[:, :Sq]


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention_bshd(q, k, v, lengths, *, interpret: bool = False):
    """Decode: q [B,1,H,D], cache k/v [B,S,KV,D], lengths [B] -> [B,1,H,D]."""
    B, _, H, D = q.shape
    S = k.shape[1]
    blk_k = min(_da.DEFAULT_BLK_K, S)
    pad_k = (-S) % blk_k
    kt = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    vt = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    out = _da.decode_attention(q[:, 0], kt, vt, lengths, blk_k=blk_k,
                               interpret=interpret)
    return out[:, None]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, dt, A, B, C, chunk: int = 128, initial_state=None, *,
        interpret: bool = False):
    """SSD scan with the model's signature (see models/ssm.ssd_chunked).

    Falls back to the jnp reference when an initial state is supplied
    (incremental prefill continuation) — the kernel owns zero-state scans.
    """
    if initial_state is not None:
        from repro.models.ssm import ssd_chunked
        return ssd_chunked(x, dt, A, B, C, chunk, initial_state)
    L = x.shape[1]
    Q = min(chunk, L)
    pad = (-L) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    y, h = _ssd.ssd_scan(x, dt, A, B, C, chunk=Q,
                         interpret=interpret)
    return y[:, :L], h
