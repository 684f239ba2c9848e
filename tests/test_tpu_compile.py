"""Compiles for a described TPU v5e (a 2x2 host) with no chip attached.

The serving path's kernels and step programs at TinyLlama-1.1B's published
widths, and the paged decode kernel at the benchmark cell's (Yi-1.5-9B's
attention: head_dim 128, the chunked page copies), compiled by the TPU
compiler from shapes alone: what the chip's
compiler would refuse (tiling, fast-memory limits, unpartitionable kernels,
programs that do not fit the device) fails here at no chip time. Depth is
cut to 2 layers to keep each compile near two seconds. Nothing runs, so
these say nothing about results or speed.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import replication as R
from repro.kernels.paged_decode import paged_decode_attention
from repro.models import transformer as T
from repro.serving import engine as E

V5E_HBM = 16 * 2 ** 30
B, N_BLOCKS, BS, TABLE = 8, 512, 16, 32    # batch, pool blocks, block, cols


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def cfg():
    """Published widths, depth cut to 2 layers."""
    return dataclasses.replace(get_config("tinyllama-1.1b"), num_layers=2)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _params(cfg, sharding):
    shapes = jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0), "bfloat16"))
    return jax.tree_util.tree_map(
        lambda s: _spec(s.shape, s.dtype, sharding), shapes)


def _pool(cfg, sharding):
    return _spec((cfg.num_layers, N_BLOCKS, cfg.num_kv_heads, BS,
                  cfg.resolved_head_dim), jnp.bfloat16, sharding)


def test_paged_decode_kernel_compiles_at_full_width(one_chip, cfg):
    H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    fn = jax.jit(paged_decode_attention)
    compiled = fn.lower(
        _spec((B, H, D), jnp.bfloat16, one_chip),
        _spec((N_BLOCKS, KV, BS, D), jnp.bfloat16, one_chip),
        _spec((N_BLOCKS, KV, BS, D), jnp.bfloat16, one_chip),
        _spec((B, TABLE), jnp.int32, one_chip),
        _spec((B,), jnp.int32, one_chip)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the kernel's name is its op's name, which the device trace shows
    assert "%paged_decode" in text


def test_paged_decode_kernel_compiles_at_cell_widths(one_chip):
    """Yi-1.5-9B's attention at the benchmark cell's shapes: 32 query and
    4 KV heads of 128, 64 rows over a 256-column table of 16-token blocks
    — the chunked page copies (head_dim a multiple of 128 lanes)."""
    B, H, KV, D, bs, table, n_blocks = 64, 32, 4, 128, 16, 256, 8800
    pool = _spec((n_blocks, KV, bs, D), jnp.bfloat16, one_chip)
    text = jax.jit(paged_decode_attention).lower(
        _spec((B, H, D), jnp.bfloat16, one_chip), pool, pool,
        _spec((B, table), jnp.int32, one_chip),
        _spec((B,), jnp.int32, one_chip)).compile().as_text()
    assert "tpu_custom_call" in text
    assert "%paged_decode" in text


def test_paged_decode_step_fits_one_chip(one_chip, cfg):
    """The engine's fused decode + sample step, with the kernel."""
    step = jax.jit(E._paged_step_impl,
                   static_argnames=("cfg", "window", "impl", "interp",
                                    "stochastic", "max_top_k", "degrees"),
                   donate_argnums=(1, 2),
                   compiler_options=E.STEP_COMPILER_OPTIONS)
    pool = _pool(cfg, one_chip)

    def vec(dtype):
        return _spec((B,), dtype, one_chip)

    compiled = step.lower(
        _params(cfg, one_chip), pool, pool,
        _spec((B, TABLE), jnp.int32, one_chip), vec(jnp.int32),
        vec(jnp.bool_), vec(jnp.int32), vec(jnp.float32), vec(jnp.int32),
        vec(jnp.uint32), vec(jnp.uint32), cfg=cfg, window=None,
        impl="kernel", interp=False, stochastic=True, max_top_k=40,
        degrees=None).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < V5E_HBM, total
    assert "tpu_custom_call" in compiled.as_text()


def test_chunk_prefill_compiles_at_full_width(one_chip, cfg):
    """One chunk bucket: 128 new tokens over a 256-token pool context."""
    G, ctx_blocks, Sb = 1, 256 // BS, 128
    cache_len = ctx_blocks * BS + Sb
    pool = _pool(cfg, one_chip)

    def ints(*shape):
        return _spec(shape, jnp.int32, one_chip)

    compiled = E._chunk_prefill_fn.lower(
        _params(cfg, one_chip), pool, pool, ints(G, ctx_blocks), ints(G, Sb),
        ints(G, Sb), ints(G, cache_len), ints(G), ints(G * Sb),
        ints(G * Sb), cfg=cfg, window=None, cache_len=cache_len,
        dtype="bfloat16").compile()
    assert compiled.memory_analysis().temp_size_in_bytes < V5E_HBM


@pytest.mark.parametrize("n_chips", [1, 4])
def test_replicated_decode_step_compiles(topo, cfg, n_chips):
    """The unrolled per-layer step of a replication plan at degree
    ``n_chips`` over a mesh of that many described chips; across four,
    the batch scatter/gather shows up as collectives."""
    mesh = R.replication_mesh(n_chips, devices=list(topo.devices))
    everywhere = NamedSharding(mesh, P())
    hook = R.LayerHook((n_chips,) * cfg.num_layers, mesh)
    pool = _pool(cfg, everywhere)

    def step(params, k, v, tables, lengths, active, tokens):
        handle = {"k": k, "v": v, "block_tables": tables,
                  "lengths": lengths, "active": active}
        logits, nc, _ = T.forward_paged(params, cfg, tokens, handle,
                                        attn_impl="kernel", layer_hook=hook)
        return logits, nc["k"], nc["v"]

    compiled = jax.jit(step, donate_argnums=(1, 2),
                       compiler_options=E.STEP_COMPILER_OPTIONS).lower(
        _params(cfg, everywhere), pool, pool,
        _spec((B, TABLE), jnp.int32, everywhere),
        _spec((B,), jnp.int32, everywhere),
        _spec((B,), jnp.bool_, everywhere),
        _spec((B, 1), jnp.int32, everywhere)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    collectives = R.count_collectives(text)
    if n_chips == 1:
        assert not collectives, collectives
    else:
        assert collectives.get("all-gather", 0) > 0, collectives


def test_paged_decode_kernel_maps_over_batch_shards_at_cell_widths(topo):
    """The chunked kernel as a degree-4 replicated layer runs it
    (``layers.apply_gqa_paged``): mapped over four batch shards of a
    described v5e:2x2 with the pool replicated, at the cell's widths."""
    B, H, KV, D, bs, table, n_blocks = 64, 32, 4, 128, 16, 256, 8800
    mesh = R.replication_mesh(4, devices=list(topo.devices))
    rows, everywhere = P(mesh.axis_names), P()
    kernel = jax.shard_map(paged_decode_attention, mesh=mesh,
                           in_specs=(rows, everywhere, everywhere, rows,
                                     rows),
                           out_specs=rows, check_vma=False)
    pool = _spec((n_blocks, KV, bs, D), jnp.bfloat16,
                 NamedSharding(mesh, everywhere))
    text = jax.jit(kernel).lower(
        _spec((B, H, D), jnp.bfloat16, NamedSharding(mesh, rows)), pool, pool,
        _spec((B, table), jnp.int32, NamedSharding(mesh, rows)),
        _spec((B,), jnp.int32, NamedSharding(mesh, rows))).compile().as_text()
    assert "tpu_custom_call" in text
    assert "%paged_decode" in text
