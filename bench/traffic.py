"""The one traffic generator: reads a mix's parameters
(``bench/traffic/<mix>.json``) and a cell's serving knobs
(``bench/cells/<cell>.json``) and draws every request of a run from the
seed.

Every seed gets the same work: the lengths, the arrival times and the
primed streams' progress are drawn from the mix's own ``shape_seed``; the
run's ``--seed`` draws the token ids, which requests are greedy and each
sampled request's seed. So two seeds differ in content, not in how much
there is to do or when it comes. (At the chat cell's rate a window holds
about eleven arrivals; permuting their order by seed moved the window's
output rate by a seventh between seeds.)

Two loops:

* ``open``: independent users; requests arrive at a fixed rate
  (``rate_rps`` in the cell file) whatever the server does. A mix may add
  ``bursts`` (``{"period_s": 20, "length_s": 8, "factor": 2.0}``): for
  ``length_s`` seconds of every ``period_s``, counted from the start of
  the lead-in and again from the start of the window, requests arrive at
  ``factor`` times the base rate (the shape of BurstGPT,
  arXiv:2401.17644). Arrival times stay drawn from the shape seed.
* ``closed``: ``clients`` callers that each wait for a reply before
  sending the next request.

Set-up first sends the warm-up groups (``warmup``), then: both start
from steady state. Set-up sends ``prime_streams`` (open; in
the cell file) or ``clients`` (closed) streams that are already under
way: each prompt carries the tokens already "generated" (fraction
``(i + 0.5) / n`` of its output, in a seeded order) and asks for the
rest, so the window opens with streams decoding at staggered context
lengths instead of all starting together. Once every primed stream has
its first token, a lead-in of ``lead_in_s`` seconds runs (open loop:
arrivals drawn from a different stream of the seed) and is set-up too:
it warms the shapes the cell's traffic uses. Then the window.
"""
from __future__ import annotations

import json
import pathlib

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
GREEDY = 0.0


def load(kind: str, name: str, root: pathlib.Path = BENCH) -> dict:
    """``kind`` is ``traffic`` or ``cells``."""
    with open(root / kind / f"{name}.json") as f:
        return json.load(f)


def lengths(rng, spec: dict, n: int) -> np.ndarray:
    """``n`` lengths, lognormal with the given median and sigma, rounded
    and clipped to [min, max]."""
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _shapes(mix: dict, knobs: dict, n: int, stream: int):
    """``n`` (prompt, output) pairs from the mix's shape seed; outputs
    clipped so prompt + output stays under the cell's ``max_len``."""
    rng = np.random.default_rng([mix["shape_seed"], stream])
    p = lengths(rng, mix["prompt_len"], n)
    o = lengths(rng, mix["output_len"], n)
    return p, np.minimum(o, knobs["max_len"] - 2 - p)


def _sampling(rng, mix: dict, n: int):
    """Per request: temperature (0 = greedy, exactly ``greedy_share`` of
    them) and a sampling seed."""
    greedy = np.zeros(n, bool)
    greedy[:int(round(mix["greedy_share"] * n))] = True
    greedy = rng.permutation(greedy)
    temps = np.where(greedy, GREEDY, mix["temperature"])
    return temps, rng.integers(0, 2 ** 31 - 1, size=n)


def _requests(rng, mix, knobs, vocab, p, o, phase, first_id):
    temps, seeds = _sampling(rng, mix, len(p))
    return [{"id": first_id + i, "phase": phase,
             "prompt": rng.integers(2, vocab, size=int(p[i])).tolist(),
             "max_tokens": int(o[i]),
             "temperature": float(temps[i]), "seed": int(seeds[i])}
            for i in range(len(p))]


def _load(mix, span: float):
    """The rate's breakpoints in [0, span] and the arrivals due by each,
    in seconds at the base rate (the cumulative intensity); without
    ``bursts``, None."""
    b = mix.get("bursts")
    if not b:
        return None
    period, length = float(b["period_s"]), float(b["length_s"])
    starts = np.arange(0.0, span, period)
    t = np.unique(np.clip(np.concatenate(
        [[0.0, span], starts, starts + length]), 0.0, span))
    inside = ((t[:-1] + t[1:]) / 2) % period < length
    rate = np.where(inside, float(b["factor"]), 1.0)
    return t, np.concatenate([[0.0], np.cumsum(rate * np.diff(t))])


def _count(rate: float, mix, span: float) -> int:
    """How many requests arrive in ``span`` seconds at the base ``rate``."""
    load = _load(mix, span)
    return int(round(rate * (span if load is None else load[1][-1])))


def _arrivals(mix, n: int, span: float, stream: int) -> np.ndarray:
    """``n`` Poisson arrival times in [0, span): uniform order statistics
    drawn from the shape seed, mapped through the inverse of the
    cumulative intensity where the mix has bursts."""
    srng = np.random.default_rng([mix["shape_seed"], stream])
    load = _load(mix, span)
    if load is None:
        return np.sort(srng.uniform(0.0, span, size=n))
    t, cum = load
    return np.interp(np.sort(srng.uniform(0.0, cum[-1], size=n)), cum, t)


def _prime(rng, mix, knobs, vocab, n: int) -> list:
    """``n`` streams already under way: stream i carries, in its prompt,
    the fraction ``(i + 0.5) / n`` (in an order drawn from the shape seed)
    of its output as tokens already generated, and asks for the rest."""
    p, o = _shapes(mix, knobs, n, 0)
    primes = _requests(rng, mix, knobs, vocab, p, o, "prime", 0)
    srng = np.random.default_rng([mix["shape_seed"], 5])
    frac = srng.permutation((np.arange(n) + 0.5) / n)
    for r, f in zip(primes, frac):
        done = min(int(f * r["max_tokens"]),
                   r["max_tokens"] - mix["min_remaining"])
        if done > 0:
            r["prompt"] += rng.integers(2, vocab, size=done).tolist()
            r["max_tokens"] -= done
    return primes


def warmup(mix: dict, knobs: dict, vocab: int, seed: int) -> list:
    """Groups of requests that set-up sends before anything else, one group
    at a time, so the server compiles (or loads from the compilation
    cache) the programs the cell's traffic will ask for, and not inside
    the window. Prompt lengths ``k * B + r`` (B = the cell's token budget)
    put a prompt's last prefill chunk at every power-of-two start bucket
    (k) with every power-of-two chunk size (r) the budget allows, once
    alone and once followed at once by a second prompt, queued behind it;
    lengths up to ``max_len`` also visit every decode table bucket. Each
    asks for two tokens, greedy and sampled.

    A cell file's ``warm_chunks`` (true) adds ``chunk_groups``: every
    chunk-prefill bucket that one or two chunks a step reach under
    open-loop traffic, for prompts up to the mix's longest."""
    B, cap = int(knobs["token_budget"]), int(knobs["max_len"]) - 4
    rng = np.random.default_rng([int(seed), 3])
    starts, seen = [], set()
    for k in range(cap // B + 1):
        bucket = 1 << max(k * B - 1, 0).bit_length() if k else 0
        if bucket not in seen:
            seen.add(bucket)
            starts.append(k * B)
    sizes = [16 << i for i in range(B.bit_length()) if 16 << i <= B]
    groups = []
    for pair in (False, True):
        for a in starts:
            for r in sizes:
                lens = [min(a + r, cap)] + ([B // 2] if pair else [])
                groups.append([{"prompt": rng.integers(2, vocab,
                                                       size=n).tolist(),
                                "max_tokens": 2, "temperature": t,
                                "seed": int(rng.integers(0, 2 ** 31 - 1))}
                               for n, t in zip(lens, (GREEDY, 0.8))])
    if knobs.get("warm_chunks"):
        longest = int(mix["prompt_len"]["max"])
        firsts = [16 << i for i in range(B.bit_length()) if 16 << i < B]
        for lens, temps in chunk_groups(B, firsts + [
                a for a in starts if 0 < a <= longest]):
            groups.append([{"prompt": rng.integers(2, vocab,
                                                   size=n).tolist(),
                            "max_tokens": 2, "temperature": t,
                            "seed": int(rng.integers(0, 2 ** 31 - 1))}
                           for n, t in zip(lens, temps)])
    return groups


def chunk_groups(B: int, starts) -> list:
    """``(prompt lengths, temperatures)`` of warm-up groups, each sent
    together to an idle server with the token budget ``B``, that run the
    chunk-prefill program at every bucket (chunks in the step, context,
    chunk) that open-loop traffic reaches with one or two chunks a step.
    A first chunk alone starts at 0 and fills what a whole prompt before
    it left of the budget: for every chunk bucket ``s`` from 16 to
    ``B / 2``, a whole prompt of ``B - s`` and then one that gets ``s``.
    A prompt's last chunk starts at ``a``: at a multiple of ``B`` after
    whole-budget chunks, or at any multiple of 16 after a partial first
    chunk that filled what the budget had left. For each ``a`` in
    ``starts`` and every chunk bucket ``s`` (1 token to ``B``):

    * ``a`` < ``B``: a lead prompt of ``2 B - a`` goes first and takes the
      budget for two steps; in the second the prompt A of ``a + r`` gets a
      partial first chunk of ``a``; in the third (the lead decodes one
      token) A's last chunk of ``r`` runs alone (context bucket of
      ``a``, chunk bucket ``s``). ``a`` >= ``B``: A alone, its chunks
      whole budgets; ``r`` < 16 only (``warmup``'s own groups run the
      rest);
    * the same, then a whole prompt and one more that gets a partial first
      chunk of ``n`` in the step of A's last chunk: two chunks whose
      larger is in bucket ``s`` (16 up); the last is greedy or sampled by
      turns (the step samples both rows).

    Every group arrives at an idle server whose first request holds the
    budget while the others queue, as ``warmup``'s pairs do."""
    sizes = [1 << i for i in range(B.bit_length()) if 1 << i <= B]
    out = [((B - s, B), (GREEDY, GREEDY)) for s in sizes if 16 <= s < B]
    for a in starts:
        lead = [2 * B - a] if a < B else []
        room = B - len(lead)          # the lead decodes in A's last step
        for i, s in enumerate(sizes):
            r = s if s < room else room // 2 + 16
            if a < B or s < 16:
                out.append((lead + [a + r], (GREEDY,) * (len(lead) + 1)))
            if s < 16:
                continue
            r = min(s, room // 2 + 16)
            w = room - r - s - 8      # a whole prompt that leaves ~s
            n = room - r - max(w, 0)
            n -= n % 16               # the partial chunk, block-aligned
            if n < 16 or max(r, n) > s or 2 * max(r, n) <= s:
                continue              # a budget this small cannot
            lens = lead + [a + r] + ([w] if w > 0 else []) + [n + 32]
            out.append((lens, (GREEDY,) * (len(lens) - 1)
                        + ((GREEDY, 0.8)[i % 2],)))
    return out


def generate(mix: dict, knobs: dict, vocab: int, seed: int,
             seconds: float) -> dict:
    """The loadgen's spec for one run (see ``loadgen.py``): the primed
    streams, then the lead-in and window traffic."""
    rng = np.random.default_rng([int(seed), 1])
    spec = {"mode": mix["loop"], "window_s": float(seconds),
            "lead_in_s": float(mix["lead_in_s"]),
            "grace_s": float(mix.get("grace_s", 60.0)),
            "warmup": warmup(mix, knobs, vocab, seed)}
    if mix["loop"] == "open":
        primes = _prime(rng, mix, knobs, vocab, int(knobs["prime_streams"]))
        rate = float(knobs["rate_rps"])
        lead = float(mix["lead_in_s"])
        n_lead = _count(rate, mix, lead)
        n_win = _count(rate, mix, seconds)
        lrng = np.random.default_rng([int(seed), 2])   # lead-in: own stream
        p, o = _shapes(mix, knobs, n_lead, 2)
        reqs = _requests(lrng, mix, knobs, vocab, p, o, "lead", len(primes))
        for r, t in zip(reqs, _arrivals(mix, n_lead, lead, 3)):
            r["due"] = float(t)
        p, o = _shapes(mix, knobs, n_win, 1)
        win = _requests(rng, mix, knobs, vocab, p, o, "window",
                        len(primes) + n_lead)
        for r, t in zip(win, _arrivals(mix, n_win, seconds, 4)):
            r["due"] = float(lead + t)
        spec["requests"] = primes + reqs + win
        spec["primes"] = [r["id"] for r in primes]
        return spec
    if mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    k = int(mix["clients"])
    per = int(mix["requests_per_client"])
    primes = _prime(rng, mix, knobs, vocab, k)
    p, o = _shapes(mix, knobs, k * per, 2)
    fresh = _requests(rng, mix, knobs, vocab, p, o, "fresh", k)
    spec["requests"] = primes + fresh
    spec["primes"] = [r["id"] for r in primes]
    spec["clients"] = [[primes[c]["id"]]
                       + [fresh[c * per + j]["id"] for j in range(per)]
                       for c in range(k)]
    return spec
