"""Compiles for a described TPU v5e (a 2x2 host) with no chip attached.

The serving path's kernels and step programs at TinyLlama-1.1B's published
widths, and the paged decode kernel and step at the benchmark cells'
(Yi-1.5-9B's and Mistral-7B's attention: head_dim 128, the chunked page
copies), compiled by the TPU compiler from shapes alone: what the chip's
compiler would refuse (tiling, fast-memory limits, unpartitionable kernels,
programs that do not fit the device) fails here at no chip time, and so
does a step that copies the KV pool. Depth is cut to 2 layers to keep each
compile within a few seconds. Nothing runs, so these say nothing about
results or speed.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""
import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import replication as R
from repro.kernels.paged_decode import paged_decode_attention
from repro.models import layers as L
from repro.models import transformer as T
from repro.serving import engine as E

V5E_HBM = 16 * 2 ** 30
B, N_BLOCKS, BS, TABLE = 8, 512, 16, 32    # batch, pool blocks, block, cols
# the benchmark cells' decode shapes: rows, pool blocks, table columns and
# the attention and model widths (bench/configs/, bench/cells/)
CELLS = {
    "yi-1.5-9b": dict(rows=64, n_blocks=8800, table=256, d_model=4096,
                      num_heads=32, num_kv_heads=4, d_ff=11008,
                      vocab_size=64000),
    "mistral-7b-v0.3": dict(rows=16, n_blocks=3800, table=256, d_model=4096,
                            num_heads=32, num_kv_heads=8, d_ff=14336,
                            vocab_size=32768),
}
STEP_TEMP_LIMIT = 64 * 2 ** 20


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


def _pool(cfg, sharding, n_blocks=N_BLOCKS):
    return _spec((cfg.num_layers, n_blocks, cfg.num_kv_heads, BS,
                  cfg.resolved_head_dim), jnp.bfloat16, sharding)


def _cell(name):
    """A cell's model at its published widths, depth cut to 2 layers, and
    its decode shapes (rows, pool blocks, table columns)."""
    shapes = dict(CELLS[name])
    rows, n_blocks, table = (shapes.pop(k) for k in
                             ("rows", "n_blocks", "table"))
    cfg = dataclasses.replace(get_config("tinyllama-1.1b"), num_layers=2,
                              head_dim=128, **shapes)
    return cfg, rows, n_blocks, table


_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
          "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
          "u64": 8}
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.+?) ([\w\-]+)\(")
_ARRAY = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
# ops that name a buffer another op made, or the loop state, and make none
_FORWARDING = {"parameter", "get-tuple-element", "tuple", "bitcast",
               "while", "conditional", "call", "opt-barrier"}


def _large_buffers(hlo_text, nbytes):
    """Instructions outside fused computations that make a buffer of
    ``nbytes`` or more (a copy, a slice, an update of the pool): all but
    the ops that only forward a buffer and the custom calls that update
    their operand in place (``output_to_operand_aliasing``)."""
    fused = set(re.findall(r" fusion\(.*?calls=%([\w.\-]+)", hlo_text))
    found, inside = [], None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            inside = head.group(1)
            continue
        op = _INSTRUCTION.match(line)
        if op is None or inside in fused:
            continue
        name, shape, opcode = op.groups()
        if opcode in _FORWARDING or (
                opcode == "custom-call"
                and "output_to_operand_aliasing" in line):
            continue
        size = max((_BYTES.get(dt, 4) * math.prod(
            int(d) for d in dims.split(",") if d)
            for dt, dims in _ARRAY.findall(shape)), default=0)
        if size >= nbytes:
            found.append(f"{name} ({opcode}, {size} bytes)")
    return found


def _kernel_args(sharding, B, H, KV, D, n_blocks, table, layers=2):
    """The kernel's arguments: q, the pools, the layer, the table, the
    lengths, the new tokens and their positions."""
    pool = _spec((layers, n_blocks, KV, BS, D), jnp.bfloat16, sharding)
    token = _spec((B, KV, D), jnp.bfloat16, sharding)
    rows = _spec((B,), jnp.int32, sharding)
    return (_spec((B, H, D), jnp.bfloat16, sharding), pool, pool,
            _spec((), jnp.int32, sharding),
            _spec((B, table), jnp.int32, sharding), rows, token, token, rows)


def _compile_kernel(sharding, *shapes):
    return jax.jit(paged_decode_attention, donate_argnums=(1, 2)).lower(
        *_kernel_args(sharding, *shapes)).compile()


def test_paged_decode_kernel_compiles_at_full_width(one_chip, cfg):
    compiled = _compile_kernel(one_chip, B, cfg.num_heads, cfg.num_kv_heads,
                               cfg.resolved_head_dim, N_BLOCKS, TABLE)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the kernel's name is its op's name, which the device trace shows
    assert "%paged_decode" in text


def test_paged_decode_kernel_compiles_at_cell_widths(one_chip):
    """Yi-1.5-9B's attention at the benchmark cell's shapes: 32 query and
    4 KV heads of 128, 64 rows over a 256-column table of 16-token blocks
    — the chunked page copies (head_dim a multiple of 128 lanes), with the
    in-place page write."""
    compiled = _compile_kernel(one_chip, 64, 32, 4, 128, 8800, 256)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "%paged_decode" in text
    assert "output_to_operand_aliasing" in text


def _compile_step(cfg, sharding, rows=B, n_blocks=N_BLOCKS, table=TABLE):
    """The engine's fused decode + sample step, with the kernel, its
    pools donated as the engine donates them."""
    step = jax.jit(E._paged_step_impl,
                   static_argnames=("cfg", "window", "impl", "interp",
                                    "stochastic", "max_top_k", "degrees"),
                   donate_argnums=(1, 2),
                   compiler_options=E.STEP_COMPILER_OPTIONS)
    pool = _pool(cfg, sharding, n_blocks)

    def vec(dtype):
        return _spec((rows,), dtype, sharding)

    return step.lower(
        _params(cfg, sharding), pool, pool,
        _spec((rows, table), jnp.int32, sharding), vec(jnp.int32),
        vec(jnp.bool_), vec(jnp.int32), vec(jnp.float32), vec(jnp.int32),
        vec(jnp.uint32), vec(jnp.uint32), cfg=cfg, window=None,
        impl="kernel", interp=False, stochastic=True, max_top_k=40,
        degrees=None).compile()


def test_paged_decode_step_fits_one_chip(one_chip, cfg):
    """The engine's fused decode + sample step, with the kernel."""
    compiled = _compile_step(cfg, one_chip)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < V5E_HBM, total
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_paged_decode_step_copies_no_pool(one_chip, cell):
    """At a benchmark cell's shapes the step never copies the KV pool: the
    scan carries it whole and the kernel writes each new token into its
    page in place. No op outside a fusion makes a buffer of one pool
    layer's size, and the step's temporaries stay small (one layer of
    one pool half is 144 MB at Yi-1.5-9B's 8,800 blocks)."""
    cfg, rows, n_blocks, table = _cell(cell)
    compiled = _compile_step(cfg, one_chip, rows, n_blocks, table)
    layer_bytes = n_blocks * cfg.num_kv_heads * BS * cfg.resolved_head_dim * 2
    assert _large_buffers(compiled.as_text(), layer_bytes) == []
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < STEP_TEMP_LIMIT, temp


def _compile_chunk_prefill(cfg, sharding, G, ctx_blocks, Sb, cache_len,
                           n_blocks=N_BLOCKS):
    """The engine's chunk-prefill program, the one paged prefill: ``G``
    rows of ``Sb`` new tokens over ``ctx_blocks`` gathered pool blocks,
    its pools donated as the engine donates them."""
    pool = _pool(cfg, sharding, n_blocks)

    def ints(*shape):
        return _spec(shape, jnp.int32, sharding)

    return E._chunk_prefill_fn.lower(
        _params(cfg, sharding), pool, pool, ints(G, ctx_blocks), ints(G, Sb),
        ints(G, Sb), ints(G, cache_len), ints(G), ints(G * Sb),
        ints(G * Sb), cfg=cfg, window=None, cache_len=cache_len,
        dtype="bfloat16").compile()


def test_chunk_prefill_compiles_at_full_width(one_chip, cfg):
    """One chunk bucket: 128 new tokens over a 256-token pool context."""
    G, ctx_blocks, Sb = 1, 256 // BS, 128
    compiled = _compile_chunk_prefill(cfg, one_chip, G, ctx_blocks, Sb,
                                      ctx_blocks * BS + Sb)
    assert compiled.memory_analysis().temp_size_in_bytes < V5E_HBM


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_miss_prefill_copies_at_most_one_pool_half(one_chip, cell):
    """A whole prompt that missed the prefix cache, at a benchmark cell's
    shapes: a chunk with no context (one gathered block, context bucket
    1) of 512 tokens. Its suffix scatter may re-lay out a pool half, as
    the eager write it replaced did, but its temporaries stay under one
    pool half plus 16 MB: a copy of the whole pool would fail here."""
    cfg, _, n_blocks, _ = _cell(cell)
    Sb = 512
    compiled = _compile_chunk_prefill(cfg, one_chip, 1, 1, Sb, 1 + Sb,
                                      n_blocks)
    half = (cfg.num_layers * n_blocks * cfg.num_kv_heads * BS
            * cfg.resolved_head_dim * 2)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < half + 16 * 2 ** 20, (temp, half)


def _compile_replicated(topo, cfg, n_chips, rows=B, n_blocks=N_BLOCKS,
                        table=TABLE):
    """The unrolled per-layer step of a replication plan at degree
    ``n_chips`` over a mesh of that many described chips, weights and
    pools replicated, the pools donated."""
    mesh = R.replication_mesh(n_chips, devices=list(topo.devices))
    everywhere = NamedSharding(mesh, P())
    hook = R.LayerHook((n_chips,) * cfg.num_layers, mesh)
    pool = _pool(cfg, everywhere, n_blocks)

    def step(params, k, v, tables, lengths, active, tokens):
        handle = {"k": k, "v": v, "block_tables": tables,
                  "lengths": lengths, "active": active}
        logits, nc, _ = T.forward_paged(params, cfg, tokens, handle,
                                        attn_impl="kernel", layer_hook=hook)
        return logits, nc["k"], nc["v"]

    return jax.jit(step, donate_argnums=(1, 2),
                   compiler_options=E.STEP_COMPILER_OPTIONS).lower(
        _params(cfg, everywhere), pool, pool,
        _spec((rows, table), jnp.int32, everywhere),
        _spec((rows,), jnp.int32, everywhere),
        _spec((rows,), jnp.bool_, everywhere),
        _spec((rows, 1), jnp.int32, everywhere)).compile()


@pytest.mark.parametrize("n_chips", [1, 4])
def test_replicated_decode_step_compiles(topo, cfg, n_chips):
    """The replicated step at TinyLlama's widths; across four chips, the
    batch scatter/gather shows up as collectives."""
    text = _compile_replicated(topo, cfg, n_chips).as_text()
    assert "tpu_custom_call" in text
    collectives = R.count_collectives(text)
    if n_chips == 1:
        assert not collectives, collectives
    else:
        assert collectives.get("all-gather", 0) > 0, collectives


def test_replicated_decode_step_copies_no_pool(topo):
    """At Yi-1.5-9B's cell shapes and degree 4 over four chips, the
    replicated step copies no pool either: each device writes every row's
    token into its replica in place."""
    cfg, rows, n_blocks, table = _cell("yi-1.5-9b")
    compiled = _compile_replicated(topo, cfg, 4, rows, n_blocks, table)
    layer_bytes = n_blocks * cfg.num_kv_heads * BS * cfg.resolved_head_dim * 2
    assert _large_buffers(compiled.as_text(), layer_bytes) == []
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < STEP_TEMP_LIMIT, temp


def test_paged_decode_kernel_maps_over_batch_shards_at_cell_widths(topo):
    """The chunked kernel as a degree-4 replicated layer runs it
    (``layers._shard_rows``): mapped over four batch shards of a described
    v5e:2x2 with the pool replicated, at the cell's widths; every device
    writes every row's token, into the pool in place."""
    mesh = R.replication_mesh(4, devices=list(topo.devices))
    sharded = L._shard_rows(paged_decode_attention, mesh,
                            P(mesh.axis_names))
    compiled = jax.jit(sharded, donate_argnums=(1, 2)).lower(*_kernel_args(
        NamedSharding(mesh, P()), 64, 32, 4, 128, 8800, 256)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "%paged_decode" in text
    layer_bytes = 8800 * 4 * BS * 128 * 2
    assert _large_buffers(text, layer_bytes) == []
