"""launch/serve.py around the chip: spawned engine servers refused on an
accelerator host before anything spawns, ``--http`` failing loudly when
the ingress pump dies, the persistent compile cache's location, the
full-width knobs, and the Engine's one-time choice of decode attention."""
import json
import logging
import os
import subprocess
import sys
import textwrap
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch import serve as SV
from repro.models import layers as L
from repro.models import transformer as T
from repro.serving.engine import Engine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("tinyllama-1.1b").reduced()
    return cfg, T.init_params(cfg, jax.random.PRNGKey(0), "float32")


# ------------------------------------------------ one process per chip
def _spawn_sites(cfg, params):
    from repro.launch.pod import Node, launch_pod, make_worker_factory
    from repro.serving.remote_engine import EngineProxy
    return {
        "serve --workers": lambda: SV.run(SV.ServeConfig(workers=2)),
        "inventory spawn node": lambda: launch_pod(
            cfg, params, [Node("127.0.0.1", 7101, spawn=True)]),
        "elastic remote factory": lambda: make_worker_factory(
            cfg, params, remote=True),
        "engine proxy": lambda: EngineProxy(cfg, params),
    }


@pytest.mark.parametrize("site", ["serve --workers", "inventory spawn node",
                                  "elastic remote factory", "engine proxy"])
def test_spawn_refused_on_accelerator_before_spawning(tiny, monkeypatch,
                                                      site):
    import multiprocessing
    cfg, params = tiny
    spawned = []
    monkeypatch.setattr(multiprocessing.context.SpawnProcess, "start",
                        lambda self: spawned.append(self))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="--instances"):
        _spawn_sites(cfg, params)[site]()
    assert spawned == []


# ------------------------------------------------------ --http failures
def test_http_exits_nonzero_when_the_pump_dies(monkeypatch):
    from repro.serving.orchestrator import Orchestrator

    def boom(self):
        raise RuntimeError("engine died")

    monkeypatch.setattr(Orchestrator, "snapshot", boom)
    t0 = time.monotonic()
    with pytest.raises(SystemExit, match="pump died"):
        SV.run(SV.ServeConfig(http=True, http_port=0, http_seconds=60,
                              instances=1))
    assert time.monotonic() - t0 < 60


def test_ingress_close_logs_what_it_swallowed(tiny, monkeypatch, caplog):
    from repro.serving.ingress import Ingress
    from repro.serving.orchestrator import Orchestrator
    cfg, params = tiny
    orch = Orchestrator(cfg, params, n_instances=1, max_batch=2, max_len=64)
    ing = Ingress(orch).start()

    async def broken():
        raise RuntimeError("shutdown broke")

    monkeypatch.setattr(ing, "_shutdown", broken)
    with caplog.at_level(logging.ERROR, logger="repro.serving.ingress"):
        ing.close()
    orch.close()
    assert any("did not complete cleanly" in r.getMessage()
               and r.exc_info for r in caplog.records)


# ------------------------------------------------------- compile cache
def _run_py(body: str, **env):
    full_env = {k: v for k, v in os.environ.items()
                if k != "JAX_COMPILATION_CACHE_DIR"}
    full_env.update(PYTHONPATH=os.path.join(ROOT, "src"),
                    JAX_PLATFORMS="cpu", **env)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                         capture_output=True, text=True, env=full_env,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


_COMPILE_ONCE = """
    import json, jax, jax.numpy as jnp
    from repro.launch import serve
    hits = []
    jax.monitoring.register_event_listener(
        lambda ev, **_: hits.append(ev)
        if ev == "/jax/compilation_cache/cache_hits" else None)
    path = serve.enable_compile_cache()
    jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.arange(8.0)).block_until_ready()
    print(json.dumps({"path": path, "hits": len(hits),
                      "config": jax.config.jax_compilation_cache_dir}))
"""


def test_compile_cache_lands_in_the_env_dir_and_hits_on_rerun(tmp_path):
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    first = json.loads(_run_py(_COMPILE_ONCE, **env).splitlines()[-1])
    assert first["path"] == str(tmp_path / "cache") == first["config"]
    assert first["hits"] == 0
    assert any((tmp_path / "cache").iterdir())
    second = json.loads(_run_py(_COMPILE_ONCE, **env).splitlines()[-1])
    assert second["hits"] >= 1


def test_compile_cache_defaults_to_the_checkout():
    out = _run_py("""
        import jax
        from repro.launch import serve
        print(serve.enable_compile_cache())
        print(jax.config.jax_compilation_cache_dir)
    """)
    path, config = out.splitlines()[-2:]
    assert path == config == os.path.join(ROOT, ".jax_cache")


# ---------------------------------------------------- full-width knobs
def test_full_width_flags_parse_and_defaults_stay_cpu_sized():
    d = SV.ServeConfig()
    assert (d.full_width, d.max_len) == (False, 128)
    args = SV.ServeConfig.from_args(["--full-width", "--max-len", "512"])
    assert args.full_width and args.max_len == 512
    with pytest.raises(ValueError, match="max_len"):
        SV.ServeConfig(max_len=1).validate()


def test_build_model_reduced_is_float32_and_bounds_max_len():
    cfg, params, dtype = SV.build_model(SV.ServeConfig())
    assert dtype == "float32" and cfg.num_layers == 2
    assert params["embed"].dtype == np.float32
    with pytest.raises(ValueError, match="max_seq_len"):
        SV.build_model(SV.ServeConfig(max_len=cfg.max_seq_len + 1))


# ------------------------------------------- decode attention choice
def test_engine_chooses_decode_attention_once(tiny):
    cfg, params = tiny
    eng = Engine(cfg, params, max_batch=2, max_len=64, cache_kind="paged")
    assert eng.paged_attn_impl == "gather"          # "auto" off the TPU
    from repro.serving.remote_engine import EngineServer
    assert EngineServer(eng).info()["paged_attn_impl"] == "gather"
    kern = Engine(cfg, params, max_batch=2, max_len=64, cache_kind="paged",
                  paged_attn_impl="kernel", interpret=True)
    assert kern.paged_attn_impl == "kernel"
    with pytest.raises(ValueError, match="cannot serve"):
        Engine(cfg, params, max_batch=2, max_len=64, cache_kind="paged",
               swa=True, paged_attn_impl="kernel")


def test_kernel_impl_refuses_window_inside_the_layer(tiny):
    cfg, params = tiny
    assert L.paged_kernel_serves(cfg, None)
    assert not L.paged_kernel_serves(cfg, 64)
    lp = jax.tree_util.tree_map(lambda p: p[0], params["layers"])["attn"]
    KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    jnp = jax.numpy
    pool = jnp.zeros((cfg.num_layers, 4, KV, 8, hd), jnp.float32)
    with pytest.raises(ValueError, match="softcap or sliding window"):
        L.apply_gqa_paged(lp, jnp.zeros((1, 1, cfg.d_model)), cfg,
                          positions=jnp.zeros((1, 1), jnp.int32),
                          pool_k=pool, pool_v=pool, layer=0,
                          block_tables=jnp.zeros((1, 2), jnp.int32),
                          active=jnp.ones((1,), bool), window=64,
                          impl="kernel", interpret=True)


def test_engine_places_weights_and_pool_on_its_device(tiny):
    cfg, params = tiny
    dev = jax.devices()[0]
    eng = Engine(cfg, params, max_batch=2, max_len=64, cache_kind="paged",
                 device=dev)
    leaves = jax.tree_util.tree_leaves((eng.params, eng.pstate.k,
                                        eng.pstate.v))
    assert all(x.devices() == {dev} and x.committed for x in leaves)
