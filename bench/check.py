"""What decides ``correct``: the served tokens against the plain reference.

After the window closes and the server's state is freed, a sample of the
greedy requests that finished (drawn from the seed, the longest always
in it, and in a pod the longest that migrated between instances) is
teacher-forced through the architecture module's reference
(``score``) on weights drawn again from the same seed: prompt plus
served tokens, in float32. At each served position the gap ``best
reference logit - reference logit of the served token`` is 0 where the
server picked the reference's argmax and small where rounding flipped a
near tie; a wrong layer, cache entry or token makes it large. The
number compared is the widest gap over the sample.

Also compared, with limit 0: finished requests whose token count differs
from the ``max_tokens`` they asked for (no end-of-sequence token is
set, so each must stream exactly that many).
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value) and self.value <= self.limit)


def finished_greedy(records, w0: float) -> list:
    """Greedy requests that streamed to the end, the last token at or
    after the window opened."""
    return [r for r in records if r["done"] and r["temperature"] == 0.0
            and r["times"] and r["times"][-1] >= w0]


def sample(records, w0: float, k: int, seed: int, migrated=()) -> list:
    """``k`` of the finished greedy requests: the longest, the longest of
    those that migrated between instances (``migrated``: the server's
    stream ids, which the load generator records as ``server_id``) where
    there is one, and the rest drawn from the seed."""
    pool = sorted(finished_greedy(records, w0), key=lambda r: r["id"])
    if len(pool) <= k:
        return pool

    def longest(rs):
        return max(rs, key=lambda r: (len(r["tokens"]), -r["id"]))

    keep = [longest(pool)]
    moved = [r for r in pool if r.get("server_id") in migrated
             and r is not keep[0]]
    if moved:
        keep.append(longest(moved))
    rest = [r for r in pool if all(r is not x for x in keep)]
    rng = np.random.default_rng([int(seed), 7])
    pick = rng.choice(len(rest), size=k - len(keep), replace=False)
    return keep + [rest[i] for i in sorted(pick)]


def gaps(arch, w, dm: dict, prompts: dict, recs: List[dict], max_len: int,
         precision: str = "float32") -> np.ndarray:
    """Widest gap per request, by ``arch.score`` (the configuration's
    architecture module). ``precision="fp8"`` is the control: the
    gap, in the float32 reference, of the token the fp8 reference puts
    first at each position of the same sequences."""
    out = []
    for r in recs:
        prompt = prompts[r["id"]]
        seq = list(prompt) + list(r["tokens"])
        first = len(prompt)
        targets = None
        if precision != "float32":
            _, _, targets = arch.score(w, dm, seq, first,
                                       precision=precision, max_len=max_len)
        best, tgt, _ = arch.score(w, dm, seq, first, targets=targets,
                                  max_len=max_len)
        out.append(float(np.max(best - tgt)))
    return np.asarray(out)


def short_outputs(records) -> int:
    return sum(1 for r in records
               if r["done"] and len(r["tokens"]) != r["max_tokens"])


def checks(gaps, records, limit: float) -> List[Check]:
    """The numbers a run compares: the widest gap of ``gaps`` against the
    cell's ``limit``, and the short outputs against 0."""
    return [Check("max_logit_gap",
                  float(np.max(gaps)) if len(gaps) else float("inf"), limit),
            Check("short_outputs", short_outputs(records), 0)]


def correct(cs: List[Check]) -> bool:
    return bool(cs) and all(c.ok for c in cs)
