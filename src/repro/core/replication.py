"""Module replication on an SPMD mesh — the TPU adaptation of §3.1.

The paper replicates a layer onto extra GPUs and splits the batch between
replicas (hooks scatter inputs / all-gather outputs). Under GSPMD the same
dataflow is expressed as a *per-layer batch sharding constraint*: a layer
with parallelism degree p_i computes with its batch split p_i ways; entering
or leaving a replicated region makes XLA insert exactly the scatter /
all-gather the paper describes. Degrees are quantized to powers of two and
realized as prefixes of a factorized replication mesh (axes r0, r1, ...,
each of size 2) — DESIGN.md §2 records this assumption change.

``layer_hook_from_plan`` plugs into ``transformer.forward(unroll=True,
layer_hook=...)`` so each unrolled layer carries its own constraint. The
continuity property of Alg. 1 is therefore *observable*: plans with fewer
device-set changes lower to HLO with fewer resharding collectives
(``count_collectives`` below; asserted in tests/test_replication.py).
"""
from __future__ import annotations

import dataclasses
import functools
import math
import re
from typing import List, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.plan import PlacementPlan

COLLECTIVE_RE = re.compile(
    r'\b(all-gather(?:-start)?|all-reduce(?:-start)?|reduce-scatter|'
    r'all-to-all|collective-permute(?:-start)?)\b')


def replication_mesh(n_devices: int, devices=None) -> Mesh:
    """Factorized mesh: axes ("r0","r1",...) each of size 2. A single
    device degenerates to one axis of size 1 (every degree quantizes to 1
    and each constraint becomes a no-op P(None)) — the shape the live
    engine uses on 1-device hosts so the hook path still compiles."""
    import numpy as np
    devs = (devices if devices is not None else jax.devices())[:n_devices]
    if n_devices == 1:
        return Mesh(np.array(devs).reshape((1,)), ("r0",))
    k = int(math.log2(n_devices))
    assert 2 ** k == n_devices, "replication mesh needs a power-of-2 devices"
    arr = np.array(devs).reshape((2,) * k)
    return Mesh(arr, tuple(f"r{i}" for i in range(k)))


@functools.lru_cache(maxsize=1)
def default_replication_mesh() -> Mesh:
    """Replication mesh over the largest power-of-two prefix of the local
    devices — what Engine.apply_plan shards the live decode step over."""
    n = 1
    while n * 2 <= jax.device_count():
        n *= 2
    return replication_mesh(n)


def quantize_degrees(p: Sequence[int], n_devices: int) -> List[int]:
    """Round each p_i down to the nearest power of two <= n_devices."""
    out = []
    for pi in p:
        q = 1
        while q * 2 <= min(pi, n_devices):
            q *= 2
        out.append(q)
    return out


def batch_spec_for_degree(degree: int, mesh: Mesh) -> P:
    """Batch axis sharded over the first log2(degree) replication axes."""
    k = int(math.log2(degree))
    if k == 0:
        return P(None)
    axes = tuple(mesh.axis_names[:k])
    return P(axes)


@dataclasses.dataclass(frozen=True)
class LayerHook:
    """hook(i, x) -> x constrained to layer i's batch sharding, from an
    already-quantized degree tuple. The tuple is hashable, so the LIVE
    engine passes it as a static jit argument — changing the plan recompiles
    exactly the affected decode step, nothing else (the runtime face of
    ``layer_hook_from_plan``; see serving/engine.Engine.apply_plan).

    ``extra_dims``: trailing activation dims left unsharded ([B,S,d] -> 2).
    ``batch_spec(i)`` is layer i's batch PartitionSpec over ``mesh``: code
    inside a layer that the compiler cannot partition by itself — a Pallas
    kernel — maps itself over the batch shards with it.
    """
    degrees: Tuple[int, ...]
    mesh: Mesh
    extra_dims: int = 2

    def batch_spec(self, i: int) -> P:
        return batch_spec_for_degree(
            min(self.degrees[i], self.mesh.devices.size), self.mesh)

    def __call__(self, i: int, x):
        full = P(*(tuple(self.batch_spec(i)) + (None,) * self.extra_dims))
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, full))


def layer_hook_from_plan(plan: PlacementPlan, mesh: Mesh, *,
                         extra_dims: int = 2) -> LayerHook:
    """Returns hook(i, x) -> x constrained to the layer's batch sharding.

    ``extra_dims``: trailing activation dims left unsharded ([B,S,d] -> 2).
    """
    degrees = tuple(quantize_degrees(plan.p, mesh.devices.size))
    return LayerHook(degrees, mesh, extra_dims)


def count_collectives(hlo_text: str) -> dict:
    """Histogram of collective ops in an HLO dump (lowered/compiled text)."""
    out: dict = {}
    for mword in COLLECTIVE_RE.finditer(hlo_text):
        w = mword.group(1).replace("-start", "")
        out[w] = out.get(w, 0) + 1
    return out


def replicate_params_for_plan(params, mesh: Mesh):
    """Replicate parameters across the replication mesh (layer replication
    shares weights — every replica owns a copy, matching the paper's memory
    accounting in Table 2)."""
    sh = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), params)
