"""chip_smoke.py's phases on the CPU at the reduced width, bfloat16 storage
and the paged-decode kernel in interpret mode: the same functions the chip
run calls, so a broken phase fails here before it costs chip time. The
script itself never falls back to the CPU: ``main`` refuses it."""
import dataclasses
import importlib.util
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as T

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MAX_TOKENS = 8


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def CS():
    return _load_smoke()


@pytest.fixture(scope="module")
def pod(CS):
    cfg = get_config(CS.ARCH).reduced()
    params = T.init_params(cfg, jax.random.PRNGKey(0), "bfloat16")
    args = CS.smoke_config(full_width=False, max_len=256, token_budget=64)
    orch = CS.phase_build(args, cfg, params, "bfloat16", interpret=True)
    yield cfg, params, args, orch
    orch.close()


def test_build_uses_serve_construction_with_kernel(pod):
    cfg, _, _, orch = pod
    assert len(orch.instances) == 2
    for eng in orch.engines:
        assert eng.paged_attn_impl == "kernel"
        assert eng._interpret
        assert eng.pstate.k.dtype == jax.numpy.bfloat16
        assert eng.sched.budgeted and eng.prefix_sharing
        assert eng.window is None


def test_serve_phase_over_http(CS, pod):
    """Eight completions over real HTTP: all complete, 0 dropped, a prefix
    hit, the long prompt chunked, /metrics parses (the phase checks)."""
    cfg, _, args, orch = pod
    out = CS.phase_serve(orch, cfg, args, max_tokens=MAX_TOKENS)
    assert out["wall_s"] >= out["first_s"] > 0
    assert orch.stats()["dropped"] == 0


def test_parity_phase(CS, pod):
    cfg, params, args, orch = pod
    out = CS.phase_parity(orch, cfg, params, "bfloat16",
                          prompt_len=2 * args.token_budget + 44)
    assert 0 < out["control"] and out["rel_err"] <= out["tol"]
    assert set(out["decode_err"]) == {"kernel", "gather"}
    assert max(out["decode_err"].values()) <= out["decode_tol"]
    assert out["kernel_vs_gather"] <= out["decode_tol"]


def test_module_ops_phase_drains_token_identically(CS, pod):
    cfg, _, _, orch = pod
    out = CS.phase_module_ops(orch, cfg, max_tokens=MAX_TOKENS)
    assert out["migrated"] >= 1
    assert orch.stats()["dropped"] == 0


def _prompt(cfg, n=40):
    return np.random.default_rng(0).integers(2, cfg.vocab_size, size=n)


def test_parity_tolerance_follows_dtype(CS, pod):
    """The tolerance is a multiple of the control's error: the rounding
    the storage dtype costs. Stored in float32 the control is exact."""
    cfg, params, _, _ = pod
    prompt = _prompt(cfg)
    p32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    ref = CS._reference_logits(params, cfg, [prompt])
    bf16 = CS.rel_l2(CS._dense_logits(params, cfg, [prompt]), ref)
    f32 = CS.rel_l2(CS._dense_logits(p32, cfg, [prompt]), ref)
    assert f32 == 0.0 < bf16 < 0.1


def test_parity_rejects_a_dropped_layer(CS, pod):
    """A forward that skips its last layer lands far outside the
    tolerance: the bound is tight enough to catch a lost layer."""
    cfg, params, _, _ = pod
    prompt = _prompt(cfg)
    ref = CS._reference_logits(params, cfg, [prompt])
    control = CS.rel_l2(CS._dense_logits(params, cfg, [prompt]), ref)
    short = dataclasses.replace(cfg, num_layers=cfg.num_layers - 1)
    cut = dict(params, layers=jax.tree_util.tree_map(lambda x: x[:-1],
                                                     params["layers"]))
    dropped = CS.rel_l2(CS._dense_logits(cut, short, [prompt]), ref)
    assert dropped > 10 * CS.PARITY_FACTOR * control


def test_main_refuses_cpu_backend(CS, capsys):
    assert CS.main([]) != 0
    out = capsys.readouterr().out.strip().splitlines()
    assert out and '"ok": true' not in out[-1]


def test_four_chip_phases_on_virtual_devices():
    """--chips 4's phases over four virtual CPU devices: each instance on
    its own device, degree-4 replication token-identical to degree 1
    with all-gathers in the step, and a cross-device drain."""
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \\
            "--xla_force_host_platform_device_count=4"
        import importlib.util, jax
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {os.path.join(ROOT, "chip_smoke.py")!r})
        CS = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(CS)
        from repro.configs import get_config
        from repro.models import transformer as T
        cfg = get_config(CS.ARCH).reduced()
        params = T.init_params(cfg, jax.random.PRNGKey(0), "bfloat16")
        args = CS.smoke_config(full_width=False, max_len=256,
                               token_budget=64)
        orch = CS.phase_build(args, cfg, params, "bfloat16",
                              interpret=True)
        CS.check_placement(orch)
        rep = CS.phase_replication(orch, cfg, max_tokens={MAX_TOKENS})
        CS.check_placement(orch)
        mig = CS.phase_module_ops(orch, cfg, src=1, degree=1,
                                  max_tokens={MAX_TOKENS})
        orch.close()
        print("FOUR_CHIP_OK", rep["collectives"], mig["migrated"])
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "FOUR_CHIP_OK" in out.stdout
