"""Mixture-of-Experts layer: top-k routing with two dispatch paths.

* ``dense``  — every expert computes every token (used for tiny smoke configs,
  E <= 8; exact but E-times the FLOPs).
* ``scatter`` — Switch-Transformer-style capacity dispatch: tokens are
  scattered into a per-expert [E, C, d] buffer (position = rank within the
  expert via cumsum), experts run as one grouped einsum, results gathered
  back weighted by router probabilities. FLOPs ~= T·k·cf·(3·d·d_ff) — the
  honest active-parameter cost, which is what the roofline needs.

Expert weights live on the ``model`` mesh axis (expert parallelism); padding
experts (qwen2-moe: 60 -> 64) get their router logits masked to -inf so no
token ever routes to them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import dense_init

EXPERT_AXIS_PAD = 16  # pad expert count to a multiple of the model axis


def init_moe(cfg: ModelConfig, key, dtype):
    E = cfg.padded_experts(EXPERT_AXIS_PAD)
    ks = jax.random.split(key, 8)
    d, f = cfg.d_model, cfg.d_ff
    p = {
        "router": dense_init(ks[0], d, E, jnp.float32),
        "w_gate": jax.vmap(lambda k: dense_init(k, d, f, dtype))(
            jax.random.split(ks[1], E)),
        "w_up": jax.vmap(lambda k: dense_init(k, d, f, dtype))(
            jax.random.split(ks[2], E)),
        "w_down": jax.vmap(lambda k: dense_init(k, f, d, dtype))(
            jax.random.split(ks[3], E)),
    }
    if cfg.num_shared_experts:
        fs = cfg.num_shared_experts * f
        p["shared"] = {
            "w_gate": dense_init(ks[4], d, fs, dtype),
            "w_up": dense_init(ks[5], d, fs, dtype),
            "w_down": dense_init(ks[6], fs, d, dtype),
        }
    if cfg.moe_dense_residual:
        fr = cfg.dense_residual_d_ff
        kr = jax.random.split(ks[7], 3)
        p["residual"] = {
            "w_gate": dense_init(kr[0], d, fr, dtype),
            "w_up": dense_init(kr[1], d, fr, dtype),
            "w_down": dense_init(kr[2], fr, d, dtype),
        }
    return p


def _swiglu(x, w):
    h = jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])
    return h @ w["w_down"]


def route(p, x, cfg: ModelConfig):
    """Router: returns (weights [.., k], idx [.., k], aux_loss scalar)."""
    E = cfg.padded_experts(EXPERT_AXIS_PAD)
    logits = (x.astype(jnp.float32) @ p["router"])
    if E > cfg.num_experts:  # mask padding experts
        pad_mask = jnp.arange(E) >= cfg.num_experts
        logits = jnp.where(pad_mask, -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    weights = weights / jnp.clip(weights.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance auxiliary loss (fraction * mean-prob).
    k = cfg.num_experts_per_tok
    counts = jnp.zeros(logits.shape[:-1] + (E,), jnp.float32)
    for j in range(k):
        counts = counts + jax.nn.one_hot(idx[..., j], E)
    frac = counts.reshape(-1, E).mean(0)
    mean_prob = probs.reshape(-1, E).mean(0)
    aux = E * jnp.sum(frac * mean_prob) / k
    return weights.astype(x.dtype), idx, aux


def _moe_dense(p, x, weights, idx, cfg):
    """All-experts einsum; exact, only for tiny E."""
    E = cfg.padded_experts(EXPERT_AXIS_PAD)
    h = jax.nn.silu(jnp.einsum("bsd,edf->bsef", x, p["w_gate"]))
    h = h * jnp.einsum("bsd,edf->bsef", x, p["w_up"])
    out_e = jnp.einsum("bsef,efd->bsed", h, p["w_down"])
    k = cfg.num_experts_per_tok
    sel = jnp.zeros(x.shape[:-1] + (E,), x.dtype)
    for j in range(k):
        sel = sel + jax.nn.one_hot(idx[..., j], E, dtype=x.dtype) * weights[..., j:j + 1]
    return jnp.einsum("bsed,bse->bsd", out_e, sel)


def _moe_scatter(p, x, weights, idx, cfg, capacity_factor=1.25):
    """Capacity-based dispatch (Switch impl): scatter -> grouped mm -> gather."""
    B, S, d = x.shape
    E = cfg.padded_experts(EXPERT_AXIS_PAD)
    k = cfg.num_experts_per_tok
    T = B * S
    cap = int(max(1, round(T * k * capacity_factor / E)))
    cap = -(-cap // 8) * 8  # align
    xf = x.reshape(T, d)
    idx_f = idx.reshape(T, k)
    w_f = weights.reshape(T, k)

    # rank of each (token, slot) within its expert, slot-major
    buf = jnp.zeros((E, cap, d), x.dtype)
    out = jnp.zeros((T, d), jnp.float32)
    positions, keeps = [], []
    counts = jnp.zeros((E,), jnp.int32)
    for j in range(k):
        oh = jax.nn.one_hot(idx_f[:, j], E, dtype=jnp.int32)        # [T,E]
        pos_in = jnp.cumsum(oh, axis=0) - 1 + counts[None, :]       # [T,E]
        counts = counts + oh.sum(0)
        pos = jnp.take_along_axis(pos_in, idx_f[:, j:j + 1], 1)[:, 0]
        keep = pos < cap
        positions.append(jnp.where(keep, pos, cap - 1))
        keeps.append(keep)
        buf = buf.at[idx_f[:, j], positions[j]].add(
            jnp.where(keep[:, None], xf, 0).astype(x.dtype))

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    y = jnp.einsum("ecf,efd->ecd", h, p["w_down"])                  # [E,C,d]

    for j in range(k):
        gathered = y[idx_f[:, j], positions[j]].astype(jnp.float32)
        out = out + jnp.where(keeps[j][:, None], gathered, 0) * w_f[:, j:j + 1]
    return out.reshape(B, S, d).astype(x.dtype)


def apply_moe(p, x, cfg: ModelConfig, *, dispatch: str = "auto"):
    """Full MoE block: routed experts (+ shared experts, + dense residual).

    Returns (out, aux_loss).
    """
    from repro.parallel.sharding import current_rules
    rules = current_rules()
    weights, idx, aux = route(p, x, cfg)
    if dispatch == "auto":
        if (rules and rules.get("moe_a2a") and rules.get("experts")
                and cfg.num_experts > 8):
            dispatch = "expert_parallel"
        else:
            dispatch = "dense" if cfg.num_experts <= 8 else "scatter"
    if dispatch == "dense":
        out = _moe_dense(p, x, weights, idx, cfg)
    elif dispatch == "expert_parallel":
        out = _moe_expert_parallel(p, x, weights, idx, cfg, rules)
    else:
        out = _moe_scatter(p, x, weights, idx, cfg)
    if "shared" in p:
        out = out + _swiglu(x, p["shared"])
    if "residual" in p:
        out = out + _swiglu(x, p["residual"])
    return out, aux


# ===================================================================== a2a EP
def _moe_expert_parallel(p, x, weights, idx, cfg: ModelConfig, rules,
                         capacity_factor: float = 1.3):
    """Expert-parallel MoE via explicit all-to-all (beyond-paper
    optimization, EXPERIMENTS §Perf pair A).

    GSPMD's lowering of the scatter-based dispatch moves the full [E, C, d]
    buffer through collective-permutes every layer (~150 GB/device/layer on
    arctic train_4k). This shard_map implementation sends each token
    directly to the data-shard that owns its expert and back:
    2 x tokens·k·cf·d bytes per device per layer (fwd).

    Requires: experts sharded over `exp_axis` (= rules["experts"]), tokens
    batch-sharded over the same axis, d_ff sharded over rules["ffn"].
    """
    from jax.sharding import PartitionSpec as P

    mesh = rules["mesh"]
    exp_axis = rules.get("experts")
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_dest = sizes[exp_axis]
    E = cfg.padded_experts(EXPERT_AXIS_PAD)
    E_loc = E // n_dest
    k = cfg.num_experts_per_tok
    B, S, d = x.shape
    batch_spec = rules.get("batch")
    # per-shard token count (batch sharded over batch axes)
    n_batch = 1
    for a in (batch_spec if isinstance(batch_spec, tuple) else (batch_spec,)):
        if a:
            n_batch *= sizes.get(a, 1)
    T_loc = (B // n_batch) * S
    cap = int(max(8, round(T_loc * k * capacity_factor / n_dest)))
    cap = -(-cap // 8) * 8
    cap_e = int(max(8, round(n_dest * cap * 1.3 / E_loc)))
    cap_e = -(-cap_e // 8) * 8

    def block(xb, wb, idxb, wg, wu, wd):
        Bl, Sl, _ = xb.shape
        Tl = Bl * Sl
        xf = xb.reshape(Tl, d)
        w_f = wb.reshape(Tl, k).astype(jnp.float32)
        idx_f = idxb.reshape(Tl, k)
        dest = idx_f // E_loc                     # [Tl,k] destination shard
        e_loc = idx_f % E_loc                     # local expert id at dest
        # slot within each destination bucket (slot-major cumsum)
        send_x = jnp.zeros((n_dest, cap, d), xb.dtype)
        send_e = jnp.full((n_dest, cap), 0, jnp.int32)
        send_g = jnp.zeros((n_dest, cap), jnp.float32)
        send_src = jnp.full((n_dest, cap), 0, jnp.int32)
        counts = jnp.zeros((n_dest,), jnp.int32)
        tpos = jnp.arange(Tl, dtype=jnp.int32)
        keeps, poss, dests = [], [], []
        for j in range(k):
            oh = jax.nn.one_hot(dest[:, j], n_dest, dtype=jnp.int32)
            pos = (jnp.cumsum(oh, 0) - 1 + counts[None, :])
            counts = counts + oh.sum(0)
            pj = jnp.take_along_axis(pos, dest[:, j:j + 1], 1)[:, 0]
            keep = pj < cap
            pj = jnp.where(keep, pj, cap)        # cap == OOB -> dropped
            send_x = send_x.at[dest[:, j], pj].set(
                jnp.where(keep[:, None], xf, 0), mode="drop")
            send_e = send_e.at[dest[:, j], pj].set(e_loc[:, j], mode="drop")
            send_g = send_g.at[dest[:, j], pj].set(
                jnp.where(keep, w_f[:, j], 0.0), mode="drop")
            send_src = send_src.at[dest[:, j], pj].set(tpos, mode="drop")
            keeps.append(keep)
            poss.append(pj)
            dests.append(dest[:, j])
        # ---- exchange tokens with expert owners
        a2a = lambda t: jax.lax.all_to_all(t, exp_axis, 0, 0, tiled=False)  # noqa: E731
        rx = a2a(send_x)                          # [n_src, cap, d]
        re = a2a(send_e)
        rg = a2a(send_g)
        # ---- local expert compute (scatter into per-expert buckets)
        Tr = n_dest * cap
        rxf = rx.reshape(Tr, d)
        ref_ = re.reshape(Tr)
        rgf = rg.reshape(Tr)
        valid = (rgf > 0).astype(jnp.int32)       # unfilled slots are junk
        oh = jax.nn.one_hot(ref_, E_loc, dtype=jnp.int32) * valid[:, None]
        pos_in = jnp.cumsum(oh, 0) - 1
        pe = jnp.take_along_axis(pos_in, ref_[:, None], 1)[:, 0]
        keep_e = (pe < cap_e) & (rgf > 0)
        pe = jnp.where(keep_e, pe, cap_e)
        buf = jnp.zeros((E_loc, cap_e, d), xb.dtype)
        buf = buf.at[ref_, pe].set(jnp.where(keep_e[:, None], rxf, 0),
                                   mode="drop")
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
        h = h * jnp.einsum("ecd,edf->ecf", buf, wu)
        yb = jnp.einsum("ecf,efd->ecd", h, wd)    # partial over ffn shard
        # gather per-token outputs, then reduce the (smaller) token tensor
        y_tok = yb[ref_, jnp.minimum(pe, cap_e - 1)]
        y_tok = jnp.where(keep_e[:, None], y_tok, 0)
        if rules.get("ffn"):
            y_tok = jax.lax.psum(y_tok, rules["ffn"])
        y_tok = y_tok.astype(xb.dtype)
        ry = a2a(y_tok.reshape(n_dest, cap, d))   # back at the source shard
        # ---- combine at source
        out = jnp.zeros((Tl, d), jnp.float32)
        for j in range(k):
            got = ry[dests[j], jnp.minimum(poss[j], cap - 1)]
            got = jnp.where(keeps[j][:, None], got, 0)
            out = out + got.astype(jnp.float32) * w_f[:, j:j + 1]
        return out.reshape(Bl, Sl, d).astype(xb.dtype)

    bspec = batch_spec
    return jax.shard_map(
        block, mesh=mesh,
        in_specs=(P(bspec, None, None), P(bspec, None, None),
                  P(bspec, None, None),
                  P(exp_axis, None, rules.get("ffn")),
                  P(exp_axis, None, rules.get("ffn")),
                  P(exp_axis, rules.get("ffn"), None)),
        out_specs=P(bspec, None, None),
        check_vma=False,
    )(x, weights, idx, p["w_gate"], p["w_up"], p["w_down"])
