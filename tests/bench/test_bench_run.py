"""``bench/run.py`` end to end on the CPU: it refuses to run without a
TPU, and a rehearsal of each cell at reduced width (the Pallas kernel in
interpret mode, through the real HTTP door, the real loadgen child and
the real check) reports the cell's metrics, no device metric, and comes
out correct. The check is shown to fail: under the fp8 control, with a
token altered where the decode step produces it, and with a decode step
that leaves the KV pool as it was."""
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


# the chat cell is deferred (not in BENCHMARK.json until its knee is swept
# on the chip); its files stay, and its rehearsal keeps the open loop covered
CHAT = {"name": "mistral-7b-v0.3.chat", "config": "mistral-7b-v0.3",
        "traffic": "chat", "chips": 1}


def cell(name):
    return next((w for w in BENCH["workloads"] if w["name"] == name), CHAT)


def rehearse(name, tmp_path, **kw):
    return R.run_cell(BENCH, cell(name), 2 ** 33 + 7, 3.0, False,
                      jax.devices()[0], out_dir=tmp_path, overrides=SMALL,
                      engine_kw=KERNEL, **kw)


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
