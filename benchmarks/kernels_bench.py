"""Kernel micro-bench: interpret-mode wall time (correctness harness shape;
TPU wall-times come from the same call sites on real hardware) plus the
analytic VMEM working-set check for the chosen BlockSpecs."""
import time

import jax
import jax.numpy as jnp

from repro.kernels import ops

VMEM_BYTES = 128 * 1024 * 1024  # v5e ~128 MiB VMEM


def _time(fn, *args, iters=3):
    fn(*args)  # compile/warm
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def run():
    rows = []
    key = jax.random.PRNGKey(0)
    # flash attention: vmem = blk_q*D + 2*blk_k*D + acc ~ fp32
    blkq = blkk = 128
    for D in (64, 128, 256):
        ws = (blkq * D + 2 * blkk * D) * 2 + (blkq * D + 2 * blkq) * 4
        assert ws < VMEM_BYTES
        q = jax.random.normal(key, (1, 256, 4, D), jnp.float32)
        k = jax.random.normal(key, (1, 256, 2, D), jnp.float32)
        us = _time(lambda a, b, c: ops.flash_attention_bshd(
            a, b, c, interpret=True), q, k, k)
        rows.append((f"flash_attention_D{D}", us,
                     f"vmem_ws={ws/1024:.0f}KiB"))
    # decode attention
    q = jax.random.normal(key, (4, 1, 8, 128), jnp.float32)
    kc = jax.random.normal(key, (4, 1024, 2, 128), jnp.float32)
    lens = jnp.full((4,), 1000, jnp.int32)
    us = _time(lambda a, b, c, l: ops.decode_attention_bshd(
        a, b, c, l, interpret=True),
               q, kc, kc, lens)
    rows.append(("decode_attention_S1024", us, "flash_decoding_grid"))
    # ssd
    x = jax.random.normal(key, (1, 512, 4, 64), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(key, (1, 512, 4)))
    A = -jnp.exp(jax.random.normal(key, (4,)) * 0.3)
    B = jax.random.normal(key, (1, 512, 1, 64)) * 0.3
    us = _time(lambda *a: ops.ssd(*a, chunk=128, interpret=True),
               x, dt, A, B, B)
    rows.append(("ssd_scan_L512", us, "chunked_dual_form"))
    for name, us, derived in rows:
        print(f"{name},{us:.0f},{derived}")
    return rows


if __name__ == "__main__":
    run()
