"""A test-only architecture module: a pre-norm GQA decoder (RMSNorm,
half-split rotary positions, SwiGLU) whose output head is its embedding.
It lives apart from ``bench/arch/`` so that a test can show a new
architecture is found and served from its own files, with nothing under
``bench/`` edited. Its reference scores a whole sequence in one pass,
float32 at ``highest`` precision; it has no fp8 control."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import weight_seed

KEYS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
        "num_attention_heads": "num_heads",
        "num_key_value_heads": "num_kv_heads", "intermediate_size": "d_ff",
        "vocab_size": "vocab_size", "head_dim": "head_dim",
        "max_position_embeddings": "max_seq_len", "rope_theta": "rope_theta",
        "rms_norm_eps": "norm_eps", "torch_dtype": "dtype"}
ABOUT = {"arch", "source", "tie_word_embeddings"}


def dims(conf):
    return {"L": conf["num_hidden_layers"], "d": conf["hidden_size"],
            "H": conf["num_attention_heads"],
            "KV": conf["num_key_value_heads"], "hd": conf["head_dim"],
            "ff": conf["intermediate_size"], "V": conf["vocab_size"],
            "eps": conf["rms_norm_eps"], "theta": conf["rope_theta"],
            "dtype": conf["torch_dtype"]}


def model_config(conf, name):
    from repro.configs.base import ModelConfig
    unmapped = sorted(set(conf) - set(KEYS) - ABOUT)
    if unmapped or conf.get("tie_word_embeddings") is not True:
        raise ValueError(f"{name}: tied_decoder does not map {unmapped}")
    return ModelConfig(name=name, family="dense", source=conf["source"],
                       attention_kind="gqa", ffn_kind="swiglu",
                       norm_kind="rmsnorm", sliding_window=None,
                       tie_embeddings=True,
                       **{KEYS[k]: conf[k] for k in KEYS})


def make_weights(dm, seed, device=None):
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
        k = jax.random.split(key, 3)
        return {"embed": normal(k[0], (V, d), d), "final_norm": scale(k[1]),
                "layers": jax.lax.map(layer, jax.random.split(k[2], L))}

    out = None if device is None else jax.sharding.SingleDeviceSharding(device)
    return jax.jit(build, out_shardings=out)(
        jax.random.PRNGKey(weight_seed(seed)))


def program_params(w, cfg):
    pad = cfg.padded_vocab - w["embed"].shape[0]
    ly = w["layers"]
    return {"embed": jnp.pad(w["embed"], ((0, pad), (0, 0))),
            "final_norm": {"scale": w["final_norm"]},
            "layers": {"norm1": {"scale": ly["norm1"]},
                       "attn": {k: ly[k] for k in ("wq", "wk", "wv", "wo")},
                       "norm2": {"scale": ly["norm2"]},
                       "mlp": {k: ly[k] for k in ("w_gate", "w_up",
                                                  "w_down")}}}


def n_params(dm):
    d, H, KV, hd, ff = (dm[k] for k in ("d", "H", "KV", "hd", "ff"))
    per_layer = 2 * d + 2 * d * H * hd + 2 * d * KV * hd + 3 * d * ff
    return dm["L"] * per_layer + d + dm["V"] * d


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    S, D = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


@functools.partial(jax.jit, static_argnames=("dm",))
def _logits(w, tokens, *, dm):
    dm = dict(dm)
    H, KV, hd, eps = dm["H"], dm["KV"], dm["hd"], dm["eps"]
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    S = tokens.shape[0]
    mask = jnp.tril(jnp.ones((S, S), bool))
    x = f32(w["embed"])[tokens]

    def layer(x, lw):
        h = _rms(x, f32(lw["norm1"]), eps)
        q = _rope(jnp.einsum("sd,dhk->shk", h, f32(lw["wq"])), dm["theta"])
        k = _rope(jnp.einsum("sd,dhk->shk", h, f32(lw["wk"])), dm["theta"])
        v = jnp.einsum("sd,dhk->shk", h, f32(lw["wv"]))
        k, v = jnp.repeat(k, H // KV, 1), jnp.repeat(v, H // KV, 1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
        o = jnp.einsum("hqk,khd->qhd", p, v).reshape(S, H * hd)
        x = x + o @ f32(lw["wo"])
        h = _rms(x, f32(lw["norm2"]), eps)
        g = jax.nn.silu(h @ f32(lw["w_gate"])) * (h @ f32(lw["w_up"]))
        return x + g @ f32(lw["w_down"]), None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    return _rms(x, f32(w["final_norm"]), eps) @ f32(w["embed"]).T


def score(w, dm, tokens, first, *, targets=None, precision="float32",
          max_len=0):
    if precision != "float32":
        raise ValueError(f"tied_decoder has no {precision} control")
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    padded = np.zeros(max(n, max_len), np.int32)
    padded[:n] = tokens
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(_logits(w, jnp.asarray(padded),
                                    dm=tuple(sorted(dm.items()))), np.float64)
    rows = logits[first - 1:n - 1]
    nxt = tokens[first:] if targets is None else np.asarray(targets)
    return (rows.max(-1), np.take_along_axis(rows, nxt[:, None], -1)[:, 0],
            rows.argmax(-1))


def decode_token_flops(dm, context):
    d, H, KV, hd, ff = (dm[k] for k in ("d", "H", "KV", "hd", "ff"))
    per_layer = (2 * d * (H * hd + 2 * KV * hd) + 2 * H * hd * d + 6 * d * ff
                 + 4 * H * hd * context)
    return dm["L"] * per_layer + 2 * d * dm["V"]


def prefill_chunk_flops(dm, start, n, logits):
    return sum(decode_token_flops(dm, start + i + 1) for i in range(n)) \
        - (n - bool(logits)) * 2 * dm["d"] * dm["V"]


def paged_layers(dm):
    return dm["L"]
