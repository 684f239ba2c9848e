"""The traffic generator: deterministic per seed, the same work for every
seed, lengths as the mix file says."""
import numpy as np
import pytest

from bench import traffic as TF

CELLS = [("chat", "mistral-7b-v0.3.chat"), ("reasoning", "yi-1.5-9b.reasoning"),
         ("chat-burst", "mistral-7b-v0.3.burst-scale")]
OPEN = [("chat", "mistral-7b-v0.3.chat"),
        ("chat-burst", "mistral-7b-v0.3.burst-scale")]


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


@pytest.mark.parametrize("mix_name,cell", OPEN)
def test_open_loop_rate_and_window(mix_name, cell):
    mix, knobs, spec = _gen(mix_name, cell, 3, seconds=51.0)
    win = [r for r in spec["requests"] if r["phase"] == "window"]
    lead = [r for r in spec["requests"] if r["phase"] == "lead"]
    b = mix.get("bursts")
    # seconds at the base rate: 51, plus (factor - 1) x the burst seconds
    extra = (b["factor"] - 1) * 3 * b["length_s"] if b else 0.0
    assert len(win) == round(knobs["rate_rps"] * (51.0 + extra))
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


def _dues(spec, phase):
    return np.array([r["due"] for r in spec["requests"]
                     if r["phase"] == phase])


def test_a_mix_without_bursts_draws_the_same_arrivals_as_before():
    """Uniform order statistics from the shape seed, as the generator drew
    them before bursts existed: the same bytes."""
    mix, knobs, spec = _gen("chat", "mistral-7b-v0.3.chat", 8, seconds=51.0)
    rate, lead = knobs["rate_rps"], float(mix["lead_in_s"])

    def before(n, span, stream):
        rng = np.random.default_rng([mix["shape_seed"], stream])
        return np.sort(rng.uniform(0.0, span, size=n))

    n_lead, n_win = int(round(rate * lead)), int(round(rate * 51.0))
    assert _dues(spec, "lead").tobytes() == before(n_lead, lead, 3).tobytes()
    assert _dues(spec, "window").tobytes() == \
        (lead + before(n_win, 51.0, 4)).tobytes()


@pytest.mark.parametrize("factor", [2.0, 3.0])
def test_bursts_multiply_the_rate_inside_them(factor):
    mix = dict(TF.load("traffic", "chat-burst"))
    mix["bursts"] = dict(mix["bursts"], factor=factor)
    period, length = mix["bursts"]["period_s"], mix["bursts"]["length_s"]
    span = 5 * period
    t = TF._arrivals(mix, 40000, span, 4)
    assert t.min() >= 0.0 and t.max() < span and np.all(np.diff(t) >= 0)
    inside = t % period < length
    rate_in = inside.sum() / (5 * length)
    rate_out = (~inside).sum() / (span - 5 * length)
    assert rate_in / rate_out == pytest.approx(factor, rel=0.05)
    assert TF._count(1.0, mix, span) == round(span + (factor - 1) * 5 * length)


def test_bursts_start_with_the_lead_in_and_with_the_window():
    mix, knobs, spec = _gen("chat-burst", "mistral-7b-v0.3.burst-scale", 9,
                            seconds=51.0)
    assert mix["bursts"] == {"period_s": 20, "length_s": 8, "factor": 2.0}
    # rate changes at 0, 8, 20, 28, 40, 48 s of the window; arrivals due by
    # each, in seconds at the base rate
    t, cum = TF._load(mix, 51.0)
    assert t.tolist() == [0, 8, 20, 28, 40, 48, 51]
    assert cum.tolist() == [0, 16, 28, 44, 56, 72, 75]
    t, cum = TF._load(mix, float(mix["lead_in_s"]))
    assert t.tolist() == [0, 8, 10] and cum.tolist() == [0, 16, 18]
    lead, rate = float(mix["lead_in_s"]), knobs["rate_rps"]
    assert np.array_equal(_dues(spec, "window"), lead + TF._arrivals(
        mix, int(round(rate * 75)), 51.0, 4))
    assert np.array_equal(_dues(spec, "lead"), TF._arrivals(
        mix, int(round(rate * 18)), lead, 3))


def _replay(B, groups):
    """Each group submitted at once to an idle paged engine with a budget
    of ``B`` tokens (blocks of 16) and served to its end; per group, the
    chunk-prefill programs it ran, as (chunks in the step, context bucket,
    chunk bucket)."""
    import jax

    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serving.engine import Engine
    from repro.serving.request import RequestSpec, SamplingParams

    cfg = get_config("tinyllama-1.1b").reduced()
    eng = Engine(cfg, T.init_params(cfg, jax.random.PRNGKey(0), "float32"),
                 max_batch=4, max_len=256, cache_kind="paged", block_size=16,
                 scheduler="budget", token_budget=B)
    seen, real = set(), eng._prefill_shared_batch

    def shared(slots, toks, ctxs, cb, Sb, n_real=None):
        seen.add((len(slots), cb, Sb))
        return real(slots, toks, ctxs, cb, Sb, n_real=n_real)

    eng._prefill_shared_batch = shared
    rng, rid, out = np.random.default_rng(0), 0, []
    for lens, temps in groups:
        seen.clear()
        for n, t in zip(lens, temps):
            rid += 1
            eng.submit(RequestSpec(
                rid=rid, prompt=rng.integers(2, 200, size=n, dtype=np.int32),
                max_tokens=2, sampling=SamplingParams(temperature=t,
                                                      seed=rid)))
        for _ in range(50):
            eng.step()
            if not (eng.queue or eng.active or eng.prefilling):
                break
        else:
            raise AssertionError(f"group {lens} unfinished")
        out.append(set(seen))
    return out


def test_chunk_groups_reach_every_chunk_bucket():
    B, starts = 64, [16, 32, 64, 128]
    seen = set().union(*_replay(B, TF.chunk_groups(B, starts)))
    sizes = (1, 2, 4, 8, 16, 32, 64)
    want = {(1, 1, r) for r in sizes[4:]}     # a first chunk alone
    want |= {(1, a, r) for a in starts for r in sizes if a < B or r < 16}
    want |= {(2, a, s) for a in starts for s in sizes[4:]}
    assert want <= seen, want - seen


def test_chunk_groups_each_reach_a_bucket_the_base_warmup_does_not():
    # warmup's own groups run first; every chunk group must then run a
    # chunk-prefill program that they did not
    B = 64
    knobs = {"token_budget": B, "max_len": 256}
    mix = {"prompt_len": {"max": 192}}
    base = TF.warmup(mix, knobs, 200, 1)
    chunked = TF.warmup(mix, {**knobs, "warm_chunks": True}, 200, 1)
    assert chunked[:len(base)] == base and len(chunked) > len(base)
    groups = [([len(r["prompt"]) for r in g], [r["temperature"] for r in g])
              for g in chunked]
    reached = _replay(B, groups)
    before = set().union(*reached[:len(base)])
    for g, keys in zip(groups[len(base):], reached[len(base):]):
        assert keys - before, (g, keys)
