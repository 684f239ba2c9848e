"""Architecture modules (``bench/arch/``) chosen by the configuration
file: a configuration without one, or with an unknown one, fails at
load; the dense module refuses keys it does not map; the reasoning cell
reads as it did before the dense decoder became a module; and an
architecture that exists only as new files (a configuration and its
module) is found and served end to end."""
import json
import shutil

import jax
import pytest

from bench import check as CK
from bench import model as M
from bench import run as R
from bench import spans as S
from bench import trace_reduce as TR

DATA = R.ROOT / "tests" / "bench" / "data"
YI = M.load_config("yi-1.5-9b")
DENSE = M.arch(YI)


def test_both_configurations_are_the_dense_decoder():
    assert YI["arch"] == "gqa_decoder"
    assert M.arch(M.load_config("mistral-7b-v0.3")) is DENSE
    assert DENSE.__file__ == str(R.BENCH / "arch" / "gqa_decoder.py")


@pytest.mark.parametrize("arch", [None, "", "no_such_arch"])
def test_a_configuration_without_a_known_arch_fails_at_load(arch, tmp_path):
    (tmp_path / "configs").mkdir()
    conf = {k: v for k, v in YI.items() if k != "arch"}
    if arch is not None:
        conf["arch"] = arch
    with open(tmp_path / "configs" / "x.json", "w") as f:
        json.dump(conf, f)
    with pytest.raises(ValueError, match="architecture"):
        M.load_config("x", tmp_path)
    with pytest.raises(ValueError, match="architecture"):
        M.arch(conf, tmp_path)


@pytest.mark.parametrize("key,value", [
    ("num_experts", 64), ("num_local_experts", 8),
    ("layer_types", ["sliding_attention", "full_attention"]),
    ("sliding_window", 4096), ("q_lora_rank", 1536),
    ("partial_rotary_factor", 0.5)])
def test_the_dense_module_refuses_what_it_cannot_map(key, value):
    DENSE.model_config(YI, "yi")               # as published: mapped
    with pytest.raises(ValueError, match=key):
        DENSE.model_config({**YI, key: value}, "yi")


# --- the reasoning cell before and after the move ---------------------------
# A rehearsal of yi-1.5-9b.reasoning at reduced size on the CPU
# (test_bench_run's SMALL; seed 2**33 + 7), its raw run saved. The
# readings below were taken from it through the harness as it stood
# before the dense decoder became an architecture module (bench/model.py,
# bench/reference.py, bench/flops.py), with the trace below.
SMALL_CONF = {"num_hidden_layers": 3, "hidden_size": 256,
              "num_attention_heads": 4, "num_key_value_heads": 1,
              "head_dim": 64, "intermediate_size": 512, "vocab_size": 512}
BEFORE = {
    "admit_ms_max": 16.078609000032884,
    "device_idle_frac": 0.8672088082000753,
    "gen_late_max_ms": 0.002748000042629428,
    "hbm_peak_gb": None,
    "itl_p99_ms": 27.573204999953305,
    "kv_used_frac": 0.13984375,
    "migration_stall_ms": None,
    "output_tok_s": 66.66666666666667,
    "paged_decode_roofline": 0.002988503085692704,
    "plan_changes": 0,
    "preemptions": 0,
    "queue_wait_mean_ms": 16.10037780001221,
    "setup_s": 21.790894220999917,
    "step_compiles": 0,
    "step_host_ms_p50": 0.8512050001172611,
    "step_mfu": 0.0002979477874788494,
    "step_ms_p50": 9.845367000025362,
    "step_tokens_mean": 8.133333333333333,
    "token_gap_p95_ms": 26.137038000001667,
    "ttft_mean_ms": 46.94172989999288,
}
GAPS_BEFORE = [0.021658658981323242, 0.0, 0.0, 0.0, 0.0,
               0.0023598670959472656, 0.006950855255126953,
               0.010107755661010742]


def synthetic_trace(steps, w0, w1):
    """A trace of the window: each window step an ``engine_step`` span
    holding a kernel call (40% of the step, where it decodes) and another
    op (20%)."""
    ops, spans = [], []
    txt = '%cc = bf16[1] custom-call(), custom_call_target="tpu_custom_call"'
    for k, s in enumerate(steps):
        if s.idle or not (w0 <= s.t0 and s.t1 <= w1):
            continue
        a, b = (s.t0 - w0) * 1e9, (s.t1 - w0) * 1e9
        spans.append(("engine_step", a, b, {"step": k}))
        if s.decode_ctx:
            ops.append(TR.Op("jit__paged_step_impl", "cc", "custom-call",
                             txt, a + 0.1 * (b - a), a + 0.5 * (b - a)))
        ops.append(TR.Op("jit__paged_step_impl", "f", "fusion", "%f = ...",
                         a + 0.5 * (b - a), a + 0.7 * (b - a)))
    busy = {"/device:TPU:0": sum(o.t1 - o.t0 for o in ops)}
    gaps = [(sp[2], sp[2] + 1e6, "pump_push_streams") for sp in spans]
    return TR.Reduced((0.0, (w1 - w0) * 1e9), busy, ops, gaps, spans)


def test_the_reasoning_cell_reads_as_before_the_move():
    with open(DATA / "reasoning_raw.json") as f:
        raw = json.load(f)
    conf = {**YI, **SMALL_CONF}
    arch = M.arch(conf)
    dm = arch.dims(conf)
    steps = [S.StepRecord(*s) for s in raw["steps"]]
    for s in steps:
        s.prefill = [tuple(p) for p in s.prefill]
    run = R.Run(cell={"name": "yi-1.5-9b.reasoning", "chips": 1}, conf=conf,
                dm=dm, arch=arch, mix={}, knobs={}, seed=raw["seed"],
                seconds=3.0, setup_s=raw["setup_s"], w0=raw["w0"],
                w1=raw["w1"], t_end=raw["t_end"], records=raw["records"],
                steps=steps, traces=raw["traces"], flight=raw["flight"],
                peak=json.load(open(R.BENCH / "peaks.json"))["TPU v5 lite"],
                memory_peak_bytes=None,
                compiles_in_window=raw["compiles_in_window"],
                gc_in_window=raw["gc_in_window"],
                trace=synthetic_trace(steps, raw["w0"], raw["w1"]),
                trace_host=(raw["w0"], raw["w1"]))
    bench = json.load(open(R.ROOT / "BENCHMARK.json"))
    registered = {m["name"] for kind in ("end_to_end", "per_layer")
                  for m in R.metric_names(bench, "yi-1.5-9b.reasoning", kind)}
    assert registered <= set(BEFORE)
    got = {p.stem: R.read_metric(p.stem, run)
           for p in (R.BENCH / "metrics").glob("[a-z]*.py")}
    assert got == BEFORE                       # bit for bit, every reader
    recs = {r["id"]: r for r in raw["records"]}
    sample = CK.sample(raw["records"], raw["w0"], 8, raw["seed"])
    assert [str(r["id"]) for r in sample] == list(raw["prompts"])
    prompts = {int(k): v for k, v in raw["prompts"].items()}
    gaps = CK.gaps(arch, arch.make_weights(dm, raw["seed"]), dm, prompts,
                   [recs[i] for i in prompts], 128)
    # the same reference computation on the same CPU: the same numbers
    assert gaps.tolist() == GAPS_BEFORE
    assert max(gaps) == raw["checks"]["max_logit_gap"]["value"]


# --- a new architecture as new files ----------------------------------------

def test_a_new_architecture_is_served_from_its_files_alone(tmp_path):
    root = tmp_path / "bench"
    for d in ("arch", "configs", "traffic", "cells"):
        (root / d).mkdir(parents=True)
    shutil.copy(DATA / "tied_decoder.py", root / "arch" / "tied_decoder.py")
    shutil.copy(R.BENCH / "traffic" / "chat.json", root / "traffic")
    conf = {"arch": "tied_decoder", "source": "a test-only architecture",
            "num_hidden_layers": 2, "hidden_size": 128,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 64, "intermediate_size": 256, "vocab_size": 384,
            "max_position_embeddings": 256, "rope_theta": 10000.0,
            "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
            "torch_dtype": "float32"}
    with open(root / "configs" / "tied-tiny.json", "w") as f:
        json.dump(conf, f)
    with open(root / "cells" / "tied-tiny.chat.json", "w") as f:
        json.dump({"rate_rps": 4, "prime_streams": 2, "max_batch": 4,
                   "max_len": 128, "max_queue": 32, "token_budget": 32,
                   "govern_budget": False, "pool_blocks": 64,
                   "max_logit_gap": 0.3}, f)
    arch = M.arch(M.load_config("tied-tiny", root), root)
    assert arch.__file__ == str(root / "arch" / "tied_decoder.py")
    with pytest.raises(ValueError, match="sliding_window"):
        arch.model_config({**conf, "sliding_window": 64}, "tied-tiny")
    cell = {"name": "tied-tiny.chat", "config": "tied-tiny",
            "traffic": "chat", "chips": 1}
    mix = {"prompt_len": {"median": 24, "sigma": 0.5, "min": 4, "max": 64},
           "output_len": {"median": 16, "sigma": 0.5, "min": 4, "max": 32},
           "lead_in_s": 0.5, "grace_s": 10, "greedy_share": 1.0,
           "check_requests": 6}
    bench = json.load(open(R.ROOT / "BENCHMARK.json"))
    result, run, checks, extra = R.run_cell(
        bench, cell, 2 ** 33 + 11, 3.0, False, jax.devices()[0],
        out_dir=tmp_path / "out", overrides={"mix": mix},
        engine_kw={"paged_attn_impl": "kernel", "interpret": True},
        root=root)
    assert run.arch is arch and run.dm["V"] == 384
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert len(extra["gaps"]) == 6
    want = {m["name"] for m in R.metric_names(bench, cell["name"],
                                              "end_to_end")}
    assert set(result["metrics"]) == want
