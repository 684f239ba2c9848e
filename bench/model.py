"""A configuration file, the model it describes, and its seeded weights.

A configuration file (``bench/configs/<name>.json``) holds the published
``config.json`` keys of a Llama-family decoder as it is run here (depth
cut, widths as published), plus ``source``, ``reduced`` (every key changed
from the source, with ``published`` giving the source's value), ``assumed``
(values the source does not state) and ``deployment`` (what the cut
stands for). ``model_config`` maps it onto the serving program's
``ModelConfig``.

Weights are the benchmark's own: drawn from the run's seed on the device,
in the storage dtype, by one jitted call (``make_weights``). Their layout
(``canonical`` below) is the one the program's parameter tree uses, so
``program_params`` only re-nests it; the plain reference in
``reference.py`` reads the same layout and imports nothing of the
program.
"""
from __future__ import annotations

import json
import math
import pathlib

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent

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


def load_config(name: str, root: pathlib.Path = BENCH) -> dict:
    with open(root / "configs" / f"{name}.json") as f:
        return json.load(f)


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
    """The program's ``ModelConfig`` for a configuration file: a plain
    pre-norm GQA decoder with SwiGLU, RMSNorm and rotary positions."""
    from repro.configs.base import ModelConfig
    if conf.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{name}: only SwiGLU (silu) decoders are mapped")
    if conf.get("sliding_window"):
        raise ValueError(f"{name}: a sliding window is not mapped")
    fields = {HF_KEYS[k]: conf[k] for k in HF_KEYS if k in conf}
    fields.setdefault("head_dim", dims(conf)["hd"])
    return ModelConfig(name=name, family="dense", source=conf["source"],
                       attention_kind="gqa", ffn_kind="swiglu",
                       norm_kind="rmsnorm", sliding_window=None, **fields)


def weight_seed(seed: int) -> int:
    """A 32-bit PRNG seed that depends on every bit of the run's seed."""
    return int(np.random.SeedSequence([int(seed), 0x77]).generate_state(1)[0])


def make_weights(dm: dict, seed: int, device=None):
    """The model's weights from ``seed``: one jitted call on the device,
    in the storage dtype. Projections are N(0, 1/fan_in), the embedding
    N(0, 1/d), norm scales uniform in [0.5, 1.5] (so the norm weights
    matter). Layers are drawn one at a time under ``lax.map`` so no
    full-model float32 temporary is ever live."""
    import jax
    import jax.numpy as jnp
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
    import jax.numpy as jnp
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


def check_layout(params, cfg):
    """Raise unless ``params`` has exactly the program's tree, shapes and
    dtypes for ``cfg`` (its own ``init_params``, traced for shapes only)."""
    import jax
    from repro.models import transformer as T
    want = jax.eval_shape(lambda k: T.init_params(cfg, k, cfg.dtype),
                          jax.random.PRNGKey(0))
    got = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), params)
    exp = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), want)
    if got != exp:
        raise ValueError(f"weights do not match the program's layout for "
                         f"{cfg.name}: {got} != {exp}")


def n_params(dm: dict) -> int:
    d, H, KV, hd, ff, V, L = (dm[k] for k in ("d", "H", "KV", "hd", "ff",
                                              "V", "L"))
    per_layer = 2 * d + d * H * hd * 2 + 2 * d * KV * hd + 3 * d * ff
    return L * per_layer + d + V * d * (1 if dm["tied"] else 2)
