"""The traffic generator: deterministic per seed, the same work for every
seed, lengths as the mix file says."""
import numpy as np
import pytest

from bench import traffic as TF

CELLS = [("chat", "mistral-7b-v0.3.chat"), ("reasoning", "yi-1.5-9b.reasoning")]


def _gen(mix_name, cell, seed, seconds=51.0):
    mix, knobs = TF.load("traffic", mix_name), TF.load("cells", cell)
    return mix, knobs, TF.generate(mix, knobs, 32768, seed, seconds)


@pytest.mark.parametrize("mix_name,cell", CELLS)
def test_same_seed_same_requests(mix_name, cell):
    a = _gen(mix_name, cell, 2 ** 33 + 1)[2]
    b = _gen(mix_name, cell, 2 ** 33 + 1)[2]
    c = _gen(mix_name, cell, 2 ** 33 + 2)[2]
    assert a == b
    assert a["requests"] != c["requests"]


@pytest.mark.parametrize("mix_name,cell", CELLS)
def test_every_seed_gets_the_same_work(mix_name, cell):
    def work(spec):
        return [(len(r["prompt"]), r["max_tokens"], r["phase"], r.get("due"))
                for r in spec["requests"]]
    a, b = _gen(mix_name, cell, 5)[2], _gen(mix_name, cell, 6)[2]
    assert work(a) == work(b)
    assert a.get("clients") == b.get("clients")
    assert [r["prompt"] for r in a["requests"]] != \
        [r["prompt"] for r in b["requests"]]


@pytest.mark.parametrize("mix_name,cell", CELLS)
def test_lengths_follow_the_mix(mix_name, cell):
    mix, knobs = TF.load("traffic", mix_name), TF.load("cells", cell)
    rng = np.random.default_rng(0)
    for key in ("prompt_len", "output_len"):
        spec = mix[key]
        x = TF.lengths(rng, spec, 20000)
        assert x.min() >= spec["min"] and x.max() <= spec["max"]
        assert abs(np.median(x) / spec["median"] - 1) < 0.03
        inner = x[(x > spec["min"]) & (x < spec["max"])]
        # the interquartile range of a lognormal is 2 * 0.6745 sigma in logs
        q1, q3 = np.percentile(np.log(x), [25, 75])
        assert abs((q3 - q1) / (2 * 0.6745 * spec["sigma"]) - 1) < 0.1, inner
    spec = _gen(mix_name, cell, 9)[2]
    for r in spec["requests"]:
        assert len(r["prompt"]) + r["max_tokens"] <= knobs["max_len"] - 2
        assert r["max_tokens"] >= 1
    fresh = [r for r in spec["requests"] if r["phase"] != "prime"]
    greedy = sum(r["temperature"] == 0.0 for r in fresh)
    assert abs(greedy - mix["greedy_share"] * len(fresh)) <= 1 + len(fresh) // 50


def test_open_loop_rate_and_window():
    mix, knobs, spec = _gen("chat", "mistral-7b-v0.3.chat", 3, seconds=51.0)
    win = [r for r in spec["requests"] if r["phase"] == "window"]
    lead = [r for r in spec["requests"] if r["phase"] == "lead"]
    assert len(win) == round(knobs["rate_rps"] * 51.0)
    assert all(mix["lead_in_s"] <= r["due"] < mix["lead_in_s"] + 51.0
               for r in win)
    assert all(0 <= r["due"] < mix["lead_in_s"] for r in lead)
    assert len(spec["primes"]) == knobs["prime_streams"]


def test_closed_loop_primes_are_staggered():
    mix, knobs, spec = _gen("reasoning", "yi-1.5-9b.reasoning", 4)
    primes = [r for r in spec["requests"] if r["phase"] == "prime"]
    assert len(primes) == len(spec["clients"]) == mix["clients"]
    assert [c[0] for c in spec["clients"]] == spec["primes"]
    lens = sorted(len(r["prompt"]) for r in primes)
    # carried contexts spread over the trajectories, not all at the start
    assert lens[-1] > 4 * lens[0]
    assert all(r["max_tokens"] >= mix["min_remaining"] for r in primes)
