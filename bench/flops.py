"""Operations and bytes the algorithm needs, from shapes alone.

One function per count; each states what it counts. ``dm`` is
``model.dims(conf)``. Multiply-adds count as 2 operations. Nothing here
counts padding, recomputation or work on inactive batch rows: these are
the least the computation needs, so a share of a peak built on them can
only understate how busy the chip was, never overstate it.
"""
from __future__ import annotations


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


def paged_decode_cost(dm: dict, contexts, itemsize: int = 2):
    """One call of the paged decode attention kernel (one layer) over
    decoding rows whose contexts (keys, the new token included) are
    ``contexts``: ``(flops, bytes)``. Bytes are each row's keys and
    values read once, its query read and its output written; the
    block table and lengths are a few bytes a row and are left out."""
    H, KV, hd = dm["H"], dm["KV"], dm["hd"]
    keys = sum(int(c) for c in contexts)
    rows = len(contexts)
    flops = 4 * H * hd * keys
    nbytes = (2 * KV * hd * keys + 2 * H * hd * rows) * itemsize
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak operations per second and bytes over peak bandwidth."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
