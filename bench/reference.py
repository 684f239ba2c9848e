"""The plain reference: a pre-norm GQA decoder (RMSNorm, rotary positions
with the half-split rotation, SwiGLU, untied head), as Mistral-7B and
Yi-1.5-9B publish it, in straightforward ``jax.numpy`` and float32 at
``highest`` matmul precision (a TPU otherwise rounds float32 matmul
inputs to bfloat16). It imports nothing of the serving program and reads
only the benchmark's own weights (``model.make_weights``).

One sequence is scored in chunks of ``CHUNK`` tokens against a float32
key/value buffer of the whole (padded) sequence, so memory stays bounded
at any length; causal masking by absolute position makes the chunked
pass equal to one full forward pass.

``precision="fp8"`` is the control: the same computation with every
weight, every matmul input and the key/value buffer rounded to float8
(e4m3, one scale per output channel, row or head vector), the precision
below the configuration's bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 512
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def fp8(x, axis):
    """Round ``x`` to float8 e4m3 with one absmax scale per slice along
    ``axis`` (the reduction axis of the matmul it feeds)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x [S, h, D]; rotate the halves (x1, x2) by pos * theta^(-2i/D)."""
    D = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = pos[:, None, None].astype(jnp.float32) * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


@functools.partial(jax.jit, static_argnames=("dm", "precision"))
def _chunk(w, kbuf, vbuf, tokens, start, targets, *, dm, precision):
    """Score CHUNK tokens starting at ``start``. Returns the updated
    buffers and, per row: the best logit, the logit of ``targets`` and the
    argmax token, all from float32 logits."""
    dm = dict(dm)
    eps, theta = dm["eps"], dm["theta"]
    H, KV, hd = dm["H"], dm["KV"], dm["hd"]
    R = H // KV
    lp = fp8 if precision == "fp8" else (lambda x, axis: x)
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    C = tokens.shape[0]
    pos = start + jnp.arange(C, dtype=jnp.int32)
    kpos = jnp.arange(kbuf.shape[1], dtype=jnp.int32)
    mask = kpos[None, :] <= pos[:, None]                       # [C, S]
    x = f32(w["embed"])[tokens]

    def layer(x, xs):
        lw, kb, vb = xs
        h = lp(_rms(x, f32(lw["norm1"]), eps), -1)
        q = jnp.einsum("sd,dhk->shk", h, lp(f32(lw["wq"]), 0))
        k = jnp.einsum("sd,dhk->shk", h, lp(f32(lw["wk"]), 0))
        v = jnp.einsum("sd,dhk->shk", h, lp(f32(lw["wv"]), 0))
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        kb = jax.lax.dynamic_update_slice(kb, lp(k, -1), (start, 0, 0))
        vb = jax.lax.dynamic_update_slice(vb, lp(v, -1), (start, 0, 0))
        qg = q.reshape(C, KV, R, hd)
        s = jnp.einsum("cgrd,sgd->gcrs", qg, kb) / np.sqrt(hd)
        s = jnp.where(mask[None, :, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("gcrs,sgd->cgrd", lp(p, -1), vb).reshape(C, H * hd)
        x = x + jnp.einsum("sh,hd->sd", lp(o, -1), lp(f32(lw["wo"]), 0))
        h = lp(_rms(x, f32(lw["norm2"]), eps), -1)
        g = jnp.einsum("sd,df->sf", h, lp(f32(lw["w_gate"]), 0))
        u = jnp.einsum("sd,df->sf", h, lp(f32(lw["w_up"]), 0))
        x = x + jnp.einsum("sf,fd->sd", lp(jax.nn.silu(g) * u, -1),
                           lp(f32(lw["w_down"]), 0))
        return x, (kb, vb)

    x, (kbuf, vbuf) = jax.lax.scan(layer, x, (w["layers"], kbuf, vbuf))
    h = lp(_rms(x, f32(w["final_norm"]), eps), -1)
    head = w["lm_head"] if "lm_head" in w else w["embed"].T
    logits = jnp.einsum("sd,dv->sv", h, lp(f32(head), 0))
    best = jnp.max(logits, -1)
    tgt = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
    return kbuf, vbuf, best, tgt, jnp.argmax(logits, -1).astype(jnp.int32)


def score(w, dm: dict, tokens, first: int, *, targets=None,
          precision: str = "float32", max_len: int = 0):
    """Teacher-force ``tokens`` (prompt + served tokens, 1-D) and return,
    for each position ``p >= first - 1`` that predicts a served token
    ``tokens[p + 1]``: the best logit, the logit of the token at that
    position in ``targets`` (default: the served tokens themselves) and
    the argmax, as float64/int arrays.

    ``max_len`` fixes the buffer length (rounded up to CHUNK) so every
    sequence of a run shares one compiled program."""
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    S = -(-max(n, max_len) // CHUNK) * CHUNK
    padded = np.zeros(S + 1, np.int32)
    padded[:n] = tokens
    nxt = padded[1:].copy()
    if targets is not None:
        nxt[first - 1:n - 1] = np.asarray(targets, np.int32)
    L, KV, hd = dm["L"], dm["KV"], dm["hd"]
    kbuf = jnp.zeros((L, S, KV, hd), jnp.float32)
    vbuf = jnp.zeros((L, S, KV, hd), jnp.float32)
    key = tuple(sorted(dm.items()))
    best, tgt, amax = [], [], []
    with jax.default_matmul_precision("highest"):
        for c0 in range(0, n - 1, CHUNK):
            kbuf, vbuf, b, t, a = _chunk(
                w, kbuf, vbuf, jnp.asarray(padded[c0:c0 + CHUNK]),
                jnp.int32(c0), jnp.asarray(nxt[c0:c0 + CHUNK]),
                dm=key, precision=precision)
            best.append(np.asarray(b, np.float64))
            tgt.append(np.asarray(t, np.float64))
            amax.append(np.asarray(a))
    sl = slice(first - 1, n - 1)
    return (np.concatenate(best)[sl], np.concatenate(tgt)[sl],
            np.concatenate(amax)[sl])
