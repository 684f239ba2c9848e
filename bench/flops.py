"""Operations and bytes of a kernel's work, from shapes alone.

One function per count; each states what it counts. ``dm`` is the
architecture module's ``dims(conf)``. Multiply-adds count as 2
operations. Nothing here counts padding, recomputation or work on
inactive batch rows: these are the least the computation needs, so a
share of a peak built on them can only understate how busy the chip was,
never overstate it. A whole model's counts are its architecture module's
(``bench/arch/``).
"""
from __future__ import annotations


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
