"""``bench/run.py`` end to end on the CPU: it refuses to run without a
TPU, and a rehearsal of each cell at reduced width (the Pallas kernel in
interpret mode, through the real HTTP door, the real loadgen child and
the real check) reports the cell's metrics, no device metric, and comes
out correct. The check is shown to fail: under the fp8 control, with a
token altered where the decode step produces it, and with a decode step
that leaves the KV pool as it was. A pod of two instances, with an SLO
so tight that the controller migrates streams between them, is warmed up
on both, reads the steps of both and comes out correct."""
import json
import os
import subprocess
import sys

import jax
import pytest

from bench import check as CK
from bench import run as R
from repro.serving import engine as E

BENCH = json.load(open(R.ROOT / "BENCHMARK.json"))
SMALL = {"conf": {"num_hidden_layers": 3, "hidden_size": 256,
                  "num_attention_heads": 4, "num_key_value_heads": 1,
                  "head_dim": 64, "intermediate_size": 512,
                  "vocab_size": 512},
         "mix": {"prompt_len": {"median": 24, "sigma": 0.5, "min": 4,
                                "max": 64},
                 "output_len": {"median": 16, "sigma": 0.5, "min": 4,
                                "max": 32},
                 "lead_in_s": 0.5, "grace_s": 10, "clients": 4,
                 "requests_per_client": 3, "min_remaining": 2,
                 "check_requests": 8},
         "knobs": {"rate_rps": 4, "prime_streams": 2, "max_batch": 4,
                   "max_len": 128, "token_budget": 32, "pool_blocks": 64,
                   "max_queue": 32}}
KERNEL = {"paged_attn_impl": "kernel", "interpret": True}


# the pod cell is deferred (the program cannot hold its plan on the chip);
# its files stay, and its rehearsal keeps the pod path of the harness
# covered
BURST = {"name": "mistral-7b-v0.3.burst-scale", "config": "mistral-7b-v0.3",
         "traffic": "chat-burst", "chips": 4}
DEFERRED = {BURST["name"]: BURST}


def cell(name):
    return next((w for w in BENCH["workloads"] if w["name"] == name),
                None) or DEFERRED[name]


def rehearse(name, tmp_path, overrides=SMALL, seconds=3.0, **kw):
    return R.run_cell(BENCH, cell(name), 2 ** 33 + 7, seconds, False,
                      jax.devices()[0], out_dir=tmp_path,
                      overrides=overrides, engine_kw=KERNEL, **kw)


def test_refuses_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(R.BENCH / "run.py"),
                        "--workload", "yi-1.5-9b.reasoning", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=120,
                       cwd=tmp_path)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "needs 1 TPU chip" in p.stderr


@pytest.mark.parametrize("name", ["mistral-7b-v0.3.chat",
                                  "yi-1.5-9b.reasoning"])
def test_rehearsal(name, tmp_path):
    result, run, checks, extra = rehearse(name, tmp_path, control=True)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "checks"]
    want = {m["name"] for m in R.metric_names(BENCH, name, "end_to_end")}
    assert set(result["metrics"]) == want
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["memory_peak_bytes"] is None
    assert "busy_s" not in result["device"]
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert extra["gaps"], "nothing was checked"
    # the control (the reference in fp8) reads above the cell's limit, and
    # the harness's own comparison finds it not correct
    assert max(extra["control_gaps"]) > run.knobs["max_logit_gap"]
    assert not CK.correct(extra["control_checks"])


def test_pod_rehearsal(tmp_path):
    # two instances on the one CPU device; an SLO of 3 engine steps makes
    # every finished request a violation, so Alg. 2 migrates streams.
    # Every request greedy, so every one that migrated and finished can be
    # checked; a window of 6 s, as the first migration compiles programs
    # inside it, which takes seconds on a loaded CPU
    small = {**SMALL, "knobs": {**SMALL["knobs"], "instances": 2,
                                "slo_latency": 3.0},
             "mix": {**SMALL["mix"], "greedy_share": 1.0,
                     "bursts": {"period_s": 2, "length_s": 1,
                                "factor": 2.0}}}
    result, run, checks, extra = rehearse(BURST["name"], tmp_path, small,
                                          seconds=6.0)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert {s.instance for s in run.window_steps()} == {0, 1}
    assert R.read_metric("plan_changes", run) >= 1
    assert R.read_metric("migration_stall_ms", run) > 0
    moved = {e["rid"] for e in run.flight if e["kind"] == "migration"}
    sample = CK.sample(run.records, run.w0, run.mix["check_requests"],
                       run.seed, moved)
    assert any(r["server_id"] in moved for r in sample)
    assert {r.get("instance") for r in run.records if r["times"]} == {0, 1}


def test_pod_warm_up_serves_every_group_on_every_instance():
    class Pod:
        instances = [None] * 3

        def __init__(self):
            self.live, self.log = [], []

        def submit_to(self, idx, spec):
            self.live.append(spec)
            self.log.append((idx, len(spec.prompt), spec.max_tokens,
                             spec.sampling.temperature, spec.sampling.seed))

        def step(self):                   # one request finishes a step
            done, self.live = self.live[:1], self.live[1:]
            return done

    groups = [[{"prompt": [5] * n, "max_tokens": 2, "temperature": t,
                "seed": 10 * n} for n, t in ((g, 0.0), (g + 1, 0.8))]
              for g in (3, 7)]
    pod = Pod()
    R._warm_pod(pod, groups)
    # group by group, each whole group on every instance, served out
    assert pod.log == [(i, n, 2, t, 10 * n) for g in (3, 7)
                       for i in range(3) for n, t in ((g, 0.0), (g + 1, 0.8))]
    assert not pod.live


def _faulty_step(monkeypatch, fault):
    real = E._jitted_steps()

    def paged(*a, **k):
        toks, nk, nv = real[1](*a, **k)
        if fault == "token":
            return (toks + 1) % k["cfg"].vocab_size, nk, nv
        return toks, a[1], a[2]          # the pool left as it was

    monkeypatch.setattr(E, "_jitted_steps", lambda: (real[0], paged))


@pytest.mark.parametrize("fault", ["token", "state"])
def test_check_catches_a_broken_decode_step(fault, tmp_path, monkeypatch):
    _faulty_step(monkeypatch, fault)
    result, run, checks, extra = rehearse("mistral-7b-v0.3.chat", tmp_path)
    assert not result["correct"]
    assert max(extra["gaps"]) > run.knobs["max_logit_gap"]


def test_sweep_row_accounts_for_the_window(tmp_path, monkeypatch):
    # bench/sweep.py runs a cell at a given rate, as bench/run.py does it,
    # without the check and without grace; its row says what was due, what
    # came out, and what was still streaming when the window closed
    monkeypatch.syspath_prepend(str(R.BENCH))
    import sweep
    monkeypatch.setattr(sweep, "BENCH", tmp_path)     # its runs' output
    row, = sweep.sweep(BENCH, cell("mistral-7b-v0.3.chat"), [4.0],
                       [2 ** 33 + 9], 3.0, jax.devices()[0],
                       overrides=SMALL, engine_kw=KERNEL)
    assert row["rate_rps"] == 4.0 and row["due"] == 12
    assert row["output_tok_s"] > 0 and row["compiles_in_window"] >= 0
    assert 0 < row["decode_rows_max"] <= SMALL["knobs"]["max_batch"]
    started = row["due"] - row["no_token_in_window"]
    assert 0 < started and 0 <= row["streaming_at_close"] <= started
    # each answer still streaming owes at least one token
    assert row["owed_at_close_tok"] >= row["streaming_at_close"]
