"""Per-kernel shape/dtype sweeps, allclose against the ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ref import (ref_decode_attention, ref_flash_attention,
                               ref_ssd)
from repro.kernels.ssd_scan import ssd_scan

K = [jax.random.PRNGKey(i) for i in range(4)]
TOL = {jnp.float32: dict(rtol=3e-5, atol=3e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KV,S,D", [
    (2, 4, 4, 256, 64),     # MHA
    (1, 8, 2, 128, 128),    # GQA
    (2, 4, 1, 256, 64),     # MQA
    (1, 2, 2, 512, 256),    # gemma-wide heads
])
def test_flash_attention(B, H, KV, S, D, dtype):
    q = jax.random.normal(K[0], (B, H, S, D), dtype)
    k = jax.random.normal(K[1], (B, KV, S, D), dtype)
    v = jax.random.normal(K[2], (B, KV, S, D), dtype)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = ref_flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KV,S,D", [
    (2, 8, 2, 256, 64),
    (3, 4, 4, 128, 128),
    (1, 16, 1, 384, 64),
])
def test_decode_attention(B, H, KV, S, D, dtype):
    q = jax.random.normal(K[0], (B, H, D), dtype)
    k = jax.random.normal(K[1], (B, KV, S, D), dtype)
    v = jax.random.normal(K[2], (B, KV, S, D), dtype)
    lengths = jnp.arange(1, B + 1, dtype=jnp.int32) * (S // (B + 1)) + 1
    out = decode_attention(q, k, v, lengths, interpret=True)
    ref = ref_decode_attention(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32])
@pytest.mark.parametrize("b,L,H,G,P,N,Q", [
    (2, 256, 4, 1, 64, 32, 64),
    (1, 128, 8, 2, 32, 16, 128),   # single-chunk
    (2, 512, 4, 4, 64, 64, 256),   # per-head groups
    (1, 256, 2, 1, 64, 128, 128),  # mamba2-780m-like state
])
def test_ssd_scan(b, L, H, G, P, N, Q, dtype):
    x = jax.random.normal(K[0], (b, L, H, P), dtype) * 0.5
    dt = jax.nn.softplus(jax.random.normal(K[1], (b, L, H), dtype))
    A = -jnp.exp(jax.random.normal(K[2], (H,), jnp.float32) * 0.3)
    B_ = jax.random.normal(K[3], (b, L, G, N), dtype) * 0.3
    C_ = jax.random.normal(K[0], (b, L, G, N), dtype) * 0.3
    y, h = ssd_scan(x, dt, A, B_, C_, chunk=Q, interpret=True)
    yr, hr = ref_ssd(x, dt, A, B_, C_)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=4e-4, atol=4e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               rtol=4e-4, atol=4e-4)


def test_ssd_matches_model_chunked_form():
    """Kernel vs the model's own chunked jnp path (a second oracle)."""
    from repro.models.ssm import ssd_chunked
    b, L, H, G, P, N = 1, 256, 4, 1, 32, 16
    x = jax.random.normal(K[0], (b, L, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(K[1], (b, L, H)))
    A = -jnp.exp(jax.random.normal(K[2], (H,)) * 0.3)
    B_ = jax.random.normal(K[3], (b, L, G, N)) * 0.3
    C_ = jax.random.normal(K[0], (b, L, G, N)) * 0.3
    y1, h1 = ssd_scan(x, dt, A, B_, C_, chunk=64, interpret=True)
    y2, h2 = ssd_chunked(x, dt, A, B_, C_, 64)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               rtol=3e-4, atol=3e-4)


def test_ops_wrappers_pad_paths():
    """Non-block-multiple shapes exercise the wrapper padding."""
    B, S, H, KV, D = 2, 200, 4, 2, 64
    q = jax.random.normal(K[0], (B, S, H, D))
    k = jax.random.normal(K[1], (B, S, KV, D))
    v = jax.random.normal(K[2], (B, S, KV, D))
    out = ops.flash_attention_bshd(q, k, v, interpret=True)
    ref = ref_flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                              v.transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)
    lens = jnp.array([150, 99], jnp.int32)
    qd = jax.random.normal(K[0], (B, 1, H, D))
    outd = ops.decode_attention_bshd(qd, k, v, lens, interpret=True)
    refd = ref_decode_attention(qd[:, 0], k.transpose(0, 2, 1, 3),
                                v.transpose(0, 2, 1, 3), lens)
    np.testing.assert_allclose(np.asarray(outd[:, 0]), np.asarray(refd),
                               rtol=3e-5, atol=3e-5)
