"""A plain pre-norm GQA decoder: RMSNorm, rotary positions with the
half-split rotation, SwiGLU, an untied or tied output head, every layer
alike with full attention through the paged decode kernel. Mistral-7B and
Yi-1.5-9B publish this architecture.

A configuration file of this architecture holds the published
``config.json`` keys as they are run here (depth cut, widths as
published); ``model_config`` maps them onto the serving program's
``ModelConfig`` and refuses any other key of shape.

Weights are the benchmark's own: drawn from the run's seed on the device,
in the storage dtype, by one jitted call (``make_weights``). Their layout
is the one the program's parameter tree uses, so ``program_params`` only
re-nests it.

The plain reference (``score``) is straightforward ``jax.numpy`` in
float32 at ``highest`` matmul precision (a TPU otherwise rounds float32
matmul inputs to bfloat16). It imports nothing of the serving program and
reads only these weights. One sequence is scored in chunks of ``CHUNK``
tokens against a float32 key/value buffer of the whole (padded) sequence,
so memory stays bounded at any length; causal masking by absolute
position makes the chunked pass equal to one full forward pass.
``precision="fp8"`` is the control: the same computation with every
weight, every matmul input and the key/value buffer rounded to float8
(e4m3, one scale per output channel, row or head vector), the precision
below the configuration's bfloat16.

The operation counts are the least the computation needs: no padding,
recomputation or work on inactive batch rows, so a share of a peak built
on them can only understate how busy the chip was.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import weight_seed

# published config.json key -> ModelConfig field
HF_KEYS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "head_dim": "head_dim",
    "max_position_embeddings": "max_seq_len",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "dtype",
}
# keys that say nothing about the computation
ABOUT = {"arch", "source", "architectures", "reduced", "published",
         "assumed", "deployment"}


def dims(conf: dict) -> dict:
    """The sizes the benchmark's own code needs, under short names."""
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    return {"L": conf["num_hidden_layers"], "d": d, "H": h,
            "KV": conf["num_key_value_heads"],
            "hd": conf.get("head_dim") or d // h,
            "ff": conf["intermediate_size"], "V": conf["vocab_size"],
            "eps": conf["rms_norm_eps"], "theta": conf["rope_theta"],
            "tied": bool(conf.get("tie_word_embeddings", False)),
            "dtype": conf["torch_dtype"]}


def model_config(conf: dict, name: str):
    """The program's ``ModelConfig`` for a configuration file. A key this
    module does not map (experts, layer kinds, a window, latent
    attention, partial rotary, ...) is refused unless it is null, so no
    other architecture is served as this one."""
    from repro.configs.base import ModelConfig
    if conf.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{name}: only SwiGLU (silu) decoders are mapped")
    unmapped = sorted(k for k, v in conf.items() if v is not None
                      and k not in HF_KEYS and k not in ABOUT
                      and k != "hidden_act")
    if unmapped:
        raise ValueError(f"{name}: gqa_decoder does not map {unmapped}")
    fields = {HF_KEYS[k]: conf[k] for k in HF_KEYS if k in conf}
    fields.setdefault("head_dim", dims(conf)["hd"])
    return ModelConfig(name=name, family="dense", source=conf["source"],
                       attention_kind="gqa", ffn_kind="swiglu",
                       norm_kind="rmsnorm", sliding_window=None, **fields)


def make_weights(dm: dict, seed: int, device=None):
    """The model's weights from ``seed``: one jitted call on the device,
    in the storage dtype. Projections are N(0, 1/fan_in), the embedding
    N(0, 1/d), norm scales uniform in [0.5, 1.5] (so the norm weights
    matter). Layers are drawn one at a time under ``lax.map`` so no
    full-model float32 temporary is ever live."""
    dt = jnp.dtype(dm["dtype"])
    L, d, H, KV, hd, ff, V = (dm[k] for k in ("L", "d", "H", "KV", "hd",
                                              "ff", "V"))

    def normal(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dt)

    def scale(key):
        return jax.random.uniform(key, (d,), jnp.float32, 0.5, 1.5).astype(dt)

    def layer(key):
        k = jax.random.split(key, 9)
        return {"norm1": scale(k[0]), "norm2": scale(k[1]),
                "wq": normal(k[2], (d, H, hd), d),
                "wk": normal(k[3], (d, KV, hd), d),
                "wv": normal(k[4], (d, KV, hd), d),
                "wo": normal(k[5], (H * hd, d), H * hd),
                "w_gate": normal(k[6], (d, ff), d),
                "w_up": normal(k[7], (d, ff), d),
                "w_down": normal(k[8], (ff, d), ff)}

    def build(key):
        k = jax.random.split(key, 4)
        w = {"embed": normal(k[0], (V, d), d), "final_norm": scale(k[1]),
             "layers": jax.lax.map(layer, jax.random.split(k[2], L))}
        if not dm["tied"]:
            w["lm_head"] = normal(k[3], (d, V), d)
        return w

    out = None if device is None else jax.sharding.SingleDeviceSharding(device)
    return jax.jit(build, out_shardings=out)(
        jax.random.PRNGKey(weight_seed(seed)))


def program_params(w: dict, cfg):
    """Re-nest the benchmark's weights into the program's parameter tree
    (``models/transformer.init_params``'s layout; the vocabulary rows
    padded to ``cfg.padded_vocab`` with zeros, which the program masks)."""
    pad = cfg.padded_vocab - w["embed"].shape[0]

    def rows(x, axis):
        if not pad:
            return x
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        return jnp.pad(x, widths)

    ly = w["layers"]
    p = {"embed": rows(w["embed"], 0),
         "final_norm": {"scale": w["final_norm"]},
         "layers": {"norm1": {"scale": ly["norm1"]},
                    "attn": {k: ly[k] for k in ("wq", "wk", "wv", "wo")},
                    "norm2": {"scale": ly["norm2"]},
                    "mlp": {k: ly[k] for k in ("w_gate", "w_up",
                                               "w_down")}}}
    if "lm_head" in w:
        p["lm_head"] = rows(w["lm_head"], 1)
    return p


def n_params(dm: dict) -> int:
    d, H, KV, hd, ff, V, L = (dm[k] for k in ("d", "H", "KV", "hd", "ff",
                                              "V", "L"))
    per_layer = 2 * d + d * H * hd * 2 + 2 * d * KV * hd + 3 * d * ff
    return L * per_layer + d + V * d * (1 if dm["tied"] else 2)


# ---------------------------------------------------------------- reference

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


# ---------------------------------------------------------- operation counts

def matmul_flops_per_token(dm: dict) -> int:
    """Weight matmuls of one token through every layer (q, k, v, o and the
    three SwiGLU projections), without the output head."""
    d, H, KV, hd, ff = dm["d"], dm["H"], dm["KV"], dm["hd"], dm["ff"]
    per_layer = 2 * d * (H * hd + 2 * KV * hd) + 2 * H * hd * d + 6 * d * ff
    return dm["L"] * per_layer


def head_flops(dm: dict) -> int:
    """The output head for one row of logits."""
    return 2 * dm["d"] * dm["V"]


def attn_flops(dm: dict, keys: int) -> int:
    """Attention of one query token over ``keys`` keys in every layer:
    q.k scores and the probability-weighted sum of values."""
    return dm["L"] * 4 * dm["H"] * dm["hd"] * keys


def decode_token_flops(dm: dict, context: int) -> int:
    """One decode token whose attention spans ``context`` keys (itself
    included), with its row of logits."""
    return (matmul_flops_per_token(dm) + head_flops(dm)
            + attn_flops(dm, context))


def prefill_chunk_flops(dm: dict, start: int, n: int,
                        logits: bool) -> int:
    """Prompt tokens start .. start+n-1 (causal: token p attends p + 1
    keys), with one row of logits when the chunk ends the prompt."""
    keys = n * start + n * (n + 1) // 2
    return (n * matmul_flops_per_token(dm) + attn_flops(dm, keys)
            + (head_flops(dm) if logits else 0))


def paged_layers(dm: dict) -> int:
    """Every layer runs the paged decode kernel over the whole context."""
    return dm["L"]
