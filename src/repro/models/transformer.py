"""Composable transformer stacks for every assigned architecture family.

One entry point per phase, uniform across families:

* ``init_params(cfg, key, dtype)``
* ``init_cache(cfg, batch, max_len, dtype)``      (serving state)
* ``forward(params, cfg, tokens, positions, ...)`` with ``mode`` in
  {"train", "prefill", "decode"} -> (logits, new_cache, aux_loss)

Layer stacks run under ``jax.lax.scan`` over stacked parameters so the HLO
stays O(1) in depth — required for the 512-partition dry-run to compile on
one CPU core. CoCoServe's *dynamic* per-layer placement path instead unrolls
layers (``unroll=True``) so each layer can carry its own sharding constraint
(see core/replication.py).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.parallel.sharding import lshard

BIG_POS = jnp.int32(2 ** 30)


def _dtype(dtype):
    return jnp.dtype(dtype) if not isinstance(dtype, str) else jnp.dtype(dtype)


# ======================================================================= init
def _init_attn(cfg, key, dtype):
    if cfg.attention_kind == "mla":
        return L.init_mla(cfg, key, dtype)
    return L.init_gqa(cfg, key, dtype)


def _init_decoder_layer(cfg: ModelConfig, key, dtype):
    """One layer of a dense/moe/vlm decoder (attention + mlp/moe)."""
    k1, k2 = jax.random.split(key)
    p = {"norm1": L.init_norm(cfg, dtype), "attn": _init_attn(cfg, k1, dtype),
         "norm2": L.init_norm(cfg, dtype)}
    if cfg.num_experts > 0:
        p["moe"] = MOE.init_moe(cfg, k2, dtype)
    else:
        p["mlp"] = L.init_mlp(cfg, k2, dtype)
    return p


def _init_mamba_layer(cfg: ModelConfig, key, dtype):
    return {"norm": L.init_norm(cfg, dtype),
            "mixer": SSM.init_mamba2(cfg, key, dtype)}


def _init_enc_layer(cfg: ModelConfig, key, dtype):
    k1, k2 = jax.random.split(key)
    return {"norm1": L.init_norm(cfg, dtype), "attn": L.init_gqa(cfg, k1, dtype),
            "norm2": L.init_norm(cfg, dtype), "mlp": L.init_mlp(cfg, k2, dtype)}


def _init_encdec_layer(cfg: ModelConfig, key, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"norm1": L.init_norm(cfg, dtype), "attn": L.init_gqa(cfg, k1, dtype),
            "norm_x": L.init_norm(cfg, dtype), "xattn": L.init_gqa(cfg, k2, dtype),
            "norm2": L.init_norm(cfg, dtype), "mlp": L.init_mlp(cfg, k3, dtype)}


def init_params(cfg: ModelConfig, key, dtype="bfloat16"):
    dtype = _dtype(dtype)
    keys = jax.random.split(key, 8)
    emb_scale = 1.0 / math.sqrt(cfg.d_model)
    params = {
        "embed": (jax.random.normal(keys[0], (cfg.padded_vocab, cfg.d_model),
                                    jnp.float32) * emb_scale).astype(dtype),
        "final_norm": L.init_norm(cfg, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(keys[1], cfg.d_model,
                                         cfg.padded_vocab, dtype)
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        params["layers"] = L.stack_init(
            lambda k: _init_decoder_layer(cfg, k, dtype), keys[2], cfg.num_layers)
    elif fam == "ssm":
        params["layers"] = L.stack_init(
            lambda k: _init_mamba_layer(cfg, k, dtype), keys[2], cfg.num_layers)
    elif fam == "hybrid":
        every = cfg.hybrid_attn_every
        nb, rem = divmod(cfg.num_layers, every)
        params["blocks"] = L.stack_init(
            lambda k: L.stack_init(
                lambda k2: _init_mamba_layer(cfg, k2, dtype), k, every),
            keys[2], nb)
        if rem:
            params["tail"] = L.stack_init(
                lambda k: _init_mamba_layer(cfg, k, dtype), keys[3], rem)
        params["shared"] = {
            "norm1": L.init_norm(cfg, dtype),
            "attn": L.init_gqa(cfg, keys[4], dtype),
            "norm2": L.init_norm(cfg, dtype),
            "mlp": L.init_mlp(cfg, keys[5],
                              dtype) if cfg.d_ff else None,
        }
        if params["shared"]["mlp"] is None:
            del params["shared"]["mlp"]
    elif fam == "audio":
        params["layers"] = L.stack_init(
            lambda k: _init_encdec_layer(cfg, k, dtype), keys[2], cfg.num_layers)
        params["encoder"] = {
            "layers": L.stack_init(lambda k: _init_enc_layer(cfg, k, dtype),
                                   keys[3], cfg.num_encoder_layers),
            "final_norm": L.init_norm(cfg, dtype),
        }
    else:
        raise ValueError(f"unknown family {fam}")
    return params


# ====================================================================== cache
def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype="bfloat16"):
    """Serving state. ``max_len`` is the cache capacity per request; sliding
    -window archs may pass ``min(logical_len, cfg.sliding_window)`` to get a
    ring buffer. SSM/hybrid caches are O(1) in sequence length."""
    dtype = _dtype(dtype)
    fam = cfg.family
    cache = {"length": jnp.zeros((batch,), jnp.int32)}
    hd = cfg.resolved_head_dim

    def kv(n_ctx, n_layers, kvh, d):
        return {"k": jnp.zeros((n_layers, batch, n_ctx, kvh, d), dtype),
                "v": jnp.zeros((n_layers, batch, n_ctx, kvh, d), dtype)}

    def ssm_state(n_layers_shape):
        P, N = cfg.ssm_head_dim, cfg.ssm_state
        gN = cfg.ssm_ngroups * N
        K1 = cfg.ssm_conv_dim - 1
        return {"conv_x": jnp.zeros((*n_layers_shape, batch, K1,
                                     cfg.ssm_d_inner), dtype),
                "conv_B": jnp.zeros((*n_layers_shape, batch, K1, gN), dtype),
                "conv_C": jnp.zeros((*n_layers_shape, batch, K1, gN), dtype),
                "ssd": jnp.zeros((*n_layers_shape, batch, cfg.ssm_heads, P, N),
                                 dtype)}

    if fam in ("dense", "moe", "vlm", "audio"):
        cache["positions"] = jnp.full((batch, max_len), BIG_POS, jnp.int32)
        if cfg.attention_kind == "mla":
            r, ro = cfg.kv_lora_rank, cfg.qk_rope_head_dim
            cache["layers"] = {
                "c": jnp.zeros((cfg.num_layers, batch, max_len, r), dtype),
                "kr": jnp.zeros((cfg.num_layers, batch, max_len, ro), dtype)}
        else:
            cache["layers"] = kv(max_len, cfg.num_layers, cfg.num_kv_heads, hd)
        if fam == "audio":
            cache["cross"] = kv(cfg.encoder_seq_len, cfg.num_layers,
                                cfg.num_kv_heads, hd)
    elif fam == "ssm":
        cache["layers"] = ssm_state((cfg.num_layers,))
    elif fam == "hybrid":
        every = cfg.hybrid_attn_every
        nb, rem = divmod(cfg.num_layers, every)
        cache["positions"] = jnp.full((batch, max_len), BIG_POS, jnp.int32)
        cache["blocks"] = ssm_state((nb, every))
        if rem:
            cache["tail"] = ssm_state((rem,))
        cache["shared"] = kv(max_len, nb, cfg.num_kv_heads, hd)
    return cache


# ================================================================= embeddings
def embed_tokens(params, cfg: ModelConfig, tokens):
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.scale_embed:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def unembed(params, cfg: ModelConfig, x):
    x = L.apply_norm(params["final_norm"], x, cfg)
    if cfg.tie_embeddings:
        logits = jnp.einsum("...d,vd->...v", x, params["embed"])
    else:
        logits = jnp.einsum("...d,dv->...v", x, params["lm_head"])
    logits = logits.astype(jnp.float32)
    if cfg.padded_vocab > cfg.vocab_size:  # mask padding rows
        pad = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
        logits = jnp.where(pad, L.NEG_INF, logits)
    return lshard(logits, "batch", None, "vocab") if logits.ndim == 3 else logits


# ============================================================== layer bodies
def _residual(x, h):
    """Residual add with an optional materialization barrier.

    Without the barrier, a TP partial output h feeds two consumers (the
    bf16 residual and the fp32 norm of the next sublayer) and GSPMD emits
    DUPLICATE all-reduces — one bf16 + one fp32 (measured: 3x fp32 + 1x
    bf16 per layer on chameleon prefill). The barrier forces one bf16
    reduction point. Enabled via the "residual_barrier" rule
    (EXPERIMENTS §Perf pair B).
    """
    from repro.parallel.sharding import current_rules
    rules = current_rules()
    out = x + h
    if rules and rules.get("residual_barrier"):
        out = jax.lax.optimization_barrier(out)
    return out


def _attn_sublayer(lp, x, cfg, positions, lcache, slots, kpos, mode, window):
    h = L.apply_norm(lp["norm1"], x, cfg)
    if cfg.attention_kind == "mla":
        if mode == "decode":
            h, nc = L.apply_mla_decode(lp["attn"], h, cfg, positions=positions,
                                       cache=lcache, slots=slots,
                                       k_positions=kpos, window=window)
        else:
            # train (lcache None) and prefill (expanded attention over the
            # fresh sequence; latents written into the cache at `slots`)
            h, nc = L.apply_mla_prefill(lp["attn"], h, cfg, positions=positions,
                                        cache=lcache, slots=slots, window=window)
    else:
        if lcache is not None:  # prefill: attend fresh; decode: attend cache
            h, nc = L.apply_gqa(lp["attn"], h, cfg, positions=positions,
                                cache=lcache, slots=slots, k_positions=kpos,
                                window=window,
                                attend_fresh=(mode == "prefill"))
        else:  # train
            h, nc = L.apply_gqa(lp["attn"], h, cfg, positions=positions,
                                window=window)
    h = lshard(h, "batch", "seq", None)
    return _residual(x, h), nc


def _mlp_sublayer(lp, x, cfg, dispatch):
    h = L.apply_norm(lp["norm2"], x, cfg)
    if "moe" in lp:
        h, aux = MOE.apply_moe(lp["moe"], h, cfg, dispatch=dispatch)
    else:
        h = L.apply_mlp(lp["mlp"], h, cfg)
        aux = jnp.float32(0.0)
    h = lshard(h, "batch", "seq", None)
    return _residual(x, h), aux


# ================================================================== forwards
def _stack_trees(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def _layer_slice(tree, i):
    return jax.tree_util.tree_map(lambda p: p[i], tree)


def _cache_meta(cache, positions):
    """slots [B,S] and updated kpos [B,M] for attention caches.

    Ring buffers (prefill longer than the cache) keep only the LAST M
    tokens: earlier tokens get the out-of-bounds slot M, which every cache
    scatter drops (``mode="drop"``) — avoiding duplicate-index scatters
    whose write order is undefined.
    """
    B, S = positions.shape
    M = cache["positions"].shape[1]
    slots = positions % M
    if S > M:
        keep = jnp.arange(S, dtype=jnp.int32)[None, :] >= S - M
        slots = jnp.where(keep, slots, M)  # M == out-of-bounds -> dropped
    bidx = jnp.arange(B, dtype=jnp.int32)[:, None]
    kpos = cache["positions"].at[bidx, slots].set(positions, mode="drop")
    return slots, kpos


def _forward_decoder(params, cfg, tokens, positions, cache, mode, dispatch,
                     remat, window, unroll, layer_hook, encoder_out=None,
                     last_idx=None):
    """dense / moe / vlm decoder and the whisper decoder (with cross-attn)."""
    has_cache = cache is not None
    is_audio = cfg.family == "audio"
    x = embed_tokens(params, cfg, tokens)
    if not cfg.use_rope:
        x = x + L.sinusoidal_positions(positions, cfg.d_model).astype(x.dtype)
    x = lshard(x, "batch", "seq", None)
    slots = kpos = None
    if has_cache:
        slots, kpos = _cache_meta(cache, positions)

    def body(carry, xs):
        x, aux = carry
        if is_audio:
            if mode == "train":
                lp = xs
                lc = None
                ckv = L.compute_cross_kv(lp["xattn"], encoder_out)
            elif mode == "prefill":
                lp, lc = xs
                ckv = L.compute_cross_kv(lp["xattn"], encoder_out)
            else:
                lp, lc, ckv = xs
                ckv = (ckv["k"], ckv["v"])
        else:
            lp, lc = xs if has_cache else (xs, None)
            ckv = None
        x, nc = _attn_sublayer(lp, x, cfg, positions, lc, slots, kpos, mode,
                               window)
        if is_audio:
            h = L.apply_norm(lp["norm_x"], x, cfg)
            h, _ = L.apply_gqa(lp["xattn"], h, cfg, positions=positions,
                               kv_override=ckv)
            x = x + h
        x, a = _mlp_sublayer(lp, x, cfg, dispatch)
        ys = nc
        if is_audio and mode == "prefill":
            ys = (nc, {"k": ckv[0], "v": ckv[1]})
        return (x, aux + a), ys

    if mode == "train" and remat:
        body = jax.checkpoint(body)

    new_cache = None
    if unroll:
        aux = jnp.float32(0.0)
        ncs = []
        for i in range(cfg.num_layers):
            lp = _layer_slice(params["layers"], i)
            lc = _layer_slice(cache["layers"], i) if has_cache else None
            if layer_hook is not None:
                x = layer_hook(i, x)
            if is_audio:
                if mode == "train":
                    xs = lp
                elif mode == "prefill":
                    xs = (lp, lc)
                else:
                    xs = (lp, lc, _layer_slice(cache["cross"], i))
            else:
                xs = (lp, lc) if has_cache else lp
            (x, aux), ys = body((x, aux), xs)
            ncs.append(ys)
        if has_cache:
            stacked = _stack_trees(ncs)
    else:
        if is_audio:
            if mode == "train":
                xs = params["layers"]
            elif mode == "prefill":
                xs = (params["layers"], cache["layers"])
            else:
                xs = (params["layers"], cache["layers"], cache["cross"])
        else:
            xs = (params["layers"], cache["layers"]) if has_cache \
                else params["layers"]
        (x, aux), stacked = jax.lax.scan(body, (x, jnp.float32(0.0)), xs)

    if has_cache:
        if is_audio and mode == "prefill":
            layers_c, cross_c = stacked
            new_cache = dict(cache, layers=layers_c, cross=cross_c,
                             positions=kpos,
                             length=positions[:, -1] + 1)
        else:
            new_cache = dict(cache, layers=stacked, positions=kpos,
                             length=positions[:, -1] + 1)

    if mode == "train":
        return unembed(params, cfg, x), None, aux
    if last_idx is not None:
        # per-row last REAL token (power-of-two padded prefill buckets:
        # causality keeps positions <= last_idx untouched by the padding)
        x_last = x[jnp.arange(x.shape[0]), last_idx]
    else:
        x_last = x[:, -1]
    return unembed(params, cfg, x_last), new_cache, aux


def forward_paged(params, cfg: ModelConfig, tokens, cache, *, window=None,
                  attn_impl="gather", interpret=False, layer_hook=None):
    """Single-token decode step against a PAGED KV pool (the Engine's
    primary decode path; see serving/paged_kv.py for the pool layout).

    tokens: [B, 1] int32. ``cache`` is the paged handle — a pytree of
    device arrays so the whole step jits with zero host syncs:

    * ``k``/``v``: [L, n_blocks, KV, bs, hd] shared block pools
      (KV-head-major — the decode kernel's native tile layout)
    * ``block_tables``: [B, max_blocks] int32 (-1 = unallocated; may be
      sliced to any prefix that covers every active request)
    * ``lengths``: [B] int32 tokens already in the pool per slot
    * ``active``: [B] bool (inactive slots decode garbage that is masked
      out of every pool write — the shape-stable static-batch trick)

    Positions are derived on device (new token sits at ``lengths[b]``).
    ``layer_hook`` (a core/replication.LayerHook) unrolls the stack so
    each layer can carry its own batch-sharding constraint — CoCoServe's
    per-layer replication degrees applied to the LIVE paged decode step;
    ``None`` keeps the O(1)-depth lax.scan.
    Returns (logits [B, Vpad], new_cache, aux_loss).
    """
    if not cfg.supports_paged_kv:
        raise ValueError(f"paged decode needs a GQA attention decoder "
                         f"(family={cfg.family}, attn={cfg.attention_kind})")
    lengths = cache["lengths"].astype(jnp.int32)
    active = cache["active"]
    positions = lengths[:, None]                       # [B, 1]
    x = embed_tokens(params, cfg, tokens)
    if not cfg.use_rope:
        x = x + L.sinusoidal_positions(positions, cfg.d_model).astype(x.dtype)
    x = lshard(x, "batch", "seq", None)

    def body(carry, xs, batch_shard=None):
        x, aux = carry
        lp, kl, vl = xs
        h = L.apply_norm(lp["norm1"], x, cfg)
        h, kl, vl = L.apply_gqa_paged(
            lp["attn"], h, cfg, positions=positions, pool_k=kl, pool_v=vl,
            block_tables=cache["block_tables"], active=active,
            window=window, impl=attn_impl, interpret=interpret,
            batch_shard=batch_shard)
        x = _residual(x, h)
        x, a = _mlp_sublayer(lp, x, cfg, "auto")
        return (x, aux + a), (kl, vl)

    if layer_hook is None:
        (x, aux), (nk, nv) = jax.lax.scan(
            body, (x, jnp.float32(0.0)),
            (params["layers"], cache["k"], cache["v"]))
    else:
        aux = jnp.float32(0.0)
        nks, nvs = [], []
        for i in range(cfg.num_layers):
            x = layer_hook(i, x)
            (x, aux), (kl, vl) = body(
                (x, aux), (_layer_slice(params["layers"], i),
                           cache["k"][i], cache["v"][i]),
                batch_shard=(layer_hook.mesh, layer_hook.batch_spec(i)))
            nks.append(kl)
            nvs.append(vl)
        nk, nv = jnp.stack(nks), jnp.stack(nvs)
    new_cache = dict(cache, k=nk, v=nv,
                     lengths=lengths + active.astype(jnp.int32))
    return unembed(params, cfg, x[:, -1]), new_cache, aux


def encode_audio(params, cfg: ModelConfig, frames):
    """Whisper-style encoder over precomputed (stubbed) frame embeddings."""
    B, S, _ = frames.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = frames + L.sinusoidal_positions(pos, cfg.d_model).astype(frames.dtype)
    x = lshard(x, "batch", None, None)

    def body(x, lp):
        h = L.apply_norm(lp["norm1"], x, cfg)
        h, _ = L.apply_gqa(lp["attn"], h, cfg, positions=pos, causal=False)
        x = x + h
        h = L.apply_norm(lp["norm2"], x, cfg)
        x = x + L.apply_mlp(lp["mlp"], h, cfg)
        return x, None

    x, _ = jax.lax.scan(body, x, params["encoder"]["layers"])
    return L.apply_norm(params["encoder"]["final_norm"], x, cfg)


def _forward_ssm(params, cfg, tokens, positions, cache, mode, remat):
    x = embed_tokens(params, cfg, tokens)
    x = lshard(x, "batch", None, None)
    has_cache = cache is not None

    def body(x, xs):
        lp, lc = xs if has_cache else (xs, None)
        h = L.apply_norm(lp["norm"], x, cfg)
        if mode == "decode" and x.shape[1] == 1:
            h, ns = SSM.apply_mamba2_decode(lp["mixer"], h, cfg, state=lc)
        else:  # train / prefill / multi-token extension (chunked prefill)
            h, ns = SSM.apply_mamba2(lp["mixer"], h, cfg, state=lc)
        h = lshard(h, "batch", None, None)
        return x + h, ns

    if mode == "train" and remat:
        body = jax.checkpoint(body)
    xs = (params["layers"], cache["layers"]) if has_cache else params["layers"]
    x, new_states = jax.lax.scan(body, x, xs)

    new_cache = None
    if has_cache:
        new_cache = dict(cache, layers=new_states,
                         length=positions[:, -1] + 1)
    if mode == "train":
        return unembed(params, cfg, x), None, jnp.float32(0.0)
    return unembed(params, cfg, x[:, -1]), new_cache, jnp.float32(0.0)


def _forward_hybrid(params, cfg, tokens, positions, cache, mode, remat,
                    window):
    every = cfg.hybrid_attn_every
    nb, rem = divmod(cfg.num_layers, every)
    has_cache = cache is not None
    x = embed_tokens(params, cfg, tokens)
    x = lshard(x, "batch", None, None)
    slots = kpos = None
    if has_cache:
        slots, kpos = _cache_meta(cache, positions)
    shared = params["shared"]

    def mamba_body(x, xs):
        lp, lc = xs if has_cache else (xs, None)
        h = L.apply_norm(lp["norm"], x, cfg)
        if mode == "decode" and x.shape[1] == 1:
            h, ns = SSM.apply_mamba2_decode(lp["mixer"], h, cfg, state=lc)
        else:
            h, ns = SSM.apply_mamba2(lp["mixer"], h, cfg, state=lc)
        return x + h, ns

    def block_body(x, xs):
        if has_cache:
            bp, bc, skv = xs
            inner_xs = (bp, bc)
        else:
            bp = xs
            inner_xs = bp
            skv = None
        x, new_states = jax.lax.scan(mamba_body, x, inner_xs)
        # shared attention (+ MLP) block — same params every application
        h = L.apply_norm(shared["norm1"], x, cfg)
        if has_cache:
            h, nkv = L.apply_gqa(shared["attn"], h, cfg, positions=positions,
                                 cache=skv, slots=slots, k_positions=kpos,
                                 window=window,
                                 attend_fresh=(mode == "prefill"))
        else:
            h, nkv = L.apply_gqa(shared["attn"], h, cfg, positions=positions,
                                 window=window)
        x = x + h
        if "mlp" in shared:
            h = L.apply_norm(shared["norm2"], x, cfg)
            x = x + L.apply_mlp(shared["mlp"], h, cfg)
        x = lshard(x, "batch", None, None)
        return x, (new_states, nkv) if has_cache else (new_states, None)

    if mode == "train" and remat:
        block_body = jax.checkpoint(block_body)

    if has_cache:
        xs = (params["blocks"], cache["blocks"], cache["shared"])
    else:
        xs = params["blocks"]
    x, ys = jax.lax.scan(block_body, x, xs)
    new_blocks, new_shared = ys if has_cache else (None, None)

    new_tail = None
    if rem:
        tail_xs = (params["tail"], cache["tail"]) if has_cache \
            else params["tail"]
        x, new_tail = jax.lax.scan(mamba_body, x, tail_xs)

    new_cache = None
    if has_cache:
        new_cache = dict(cache, blocks=new_blocks, shared=new_shared,
                         positions=kpos, length=positions[:, -1] + 1)
        if rem:
            new_cache["tail"] = new_tail
    if mode == "train":
        return unembed(params, cfg, x), None, jnp.float32(0.0)
    return unembed(params, cfg, x[:, -1]), new_cache, jnp.float32(0.0)


def forward(params, cfg: ModelConfig, tokens, positions=None, cache=None, *,
            mode="train", encoder_input=None, dispatch="auto", remat=False,
            window=None, unroll=False, layer_hook=None, last_idx=None):
    """Uniform entry point. tokens [B,S] int32; positions [B,S] absolute
    (default arange). Returns (logits, new_cache, aux_loss):
    train -> full-seq logits [B,S,Vpad]; prefill/decode -> last-token [B,Vpad].
    ``last_idx`` [B] (attention decoders, non-train) selects each row's
    last REAL token instead of column -1 — the per-row gather behind the
    engine's power-of-two padded prefill buckets.
    """
    if cache is not None and "block_tables" in cache:
        assert mode == "decode", "paged cache handles are decode-only"
        return forward_paged(params, cfg, tokens, cache, window=window,
                             layer_hook=layer_hook)
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        return _forward_decoder(params, cfg, tokens, positions, cache, mode,
                                dispatch, remat, window, unroll, layer_hook,
                                last_idx=last_idx)
    if fam == "audio":
        enc_out = None
        if mode in ("train", "prefill"):
            assert encoder_input is not None, "audio needs encoder frames"
            enc_out = encode_audio(params, cfg, encoder_input)
        return _forward_decoder(params, cfg, tokens, positions, cache, mode,
                                dispatch, remat, window, unroll, layer_hook,
                                encoder_out=enc_out, last_idx=last_idx)
    assert last_idx is None, f"last_idx unsupported for family {fam}"
    if fam == "ssm":
        return _forward_ssm(params, cfg, tokens, positions, cache, mode, remat)
    if fam == "hybrid":
        return _forward_hybrid(params, cfg, tokens, positions, cache, mode,
                               remat, window)
    raise ValueError(f"unknown family {fam}")
