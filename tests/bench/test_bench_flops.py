"""Operation and byte counts against shapes worked out by hand: the
kernel's (``flops.py``) and the dense decoder's whole-model counts
(``arch/gqa_decoder.py``)."""
import pytest

from bench import flops as F
from bench import model as M

G = M.arch(M.load_config("mistral-7b-v0.3"))
MISTRAL = G.dims(M.load_config("mistral-7b-v0.3"))
YI = G.dims(M.load_config("yi-1.5-9b"))


def test_dims_of_the_configurations():
    assert M.arch(M.load_config("yi-1.5-9b")) is G
    assert G.paged_layers(MISTRAL) == G.paged_layers(YI) == 16
    assert (MISTRAL["H"] // MISTRAL["KV"], YI["H"] // YI["KV"]) == (4, 8)
    assert MISTRAL["hd"] == YI["hd"] == 128
    # 3.758 B and 3.29 B parameters at 16 layers
    assert G.n_params(MISTRAL) == 3_758_231_552
    assert G.n_params(YI) == 3_292_663_808


def test_matmul_and_head_flops():
    # Mistral layer: q 4096x4096, k and v 4096x1024, o 4096x4096,
    # gate/up/down 4096x14336: 218,103,808 weights -> 2 flops each
    assert G.matmul_flops_per_token(MISTRAL) == 16 * 2 * 218_103_808
    assert G.head_flops(YI) == 2 * 4096 * 64000


def test_attention_and_prefill_flops():
    # one query over 1000 keys: 4 * 32 heads * 128 * 1000 per layer
    assert G.attn_flops(MISTRAL, 1000) == 16 * 4 * 32 * 128 * 1000
    # a 3-token chunk from position 5 attends 6 + 7 + 8 keys
    assert G.prefill_chunk_flops(MISTRAL, 5, 3, False) == (
        3 * G.matmul_flops_per_token(MISTRAL) + G.attn_flops(MISTRAL, 21))
    assert G.prefill_chunk_flops(MISTRAL, 0, 1, True) == \
        G.decode_token_flops(MISTRAL, 1)


@pytest.mark.parametrize("dm,kv", [(MISTRAL, 8), (YI, 4)])
def test_paged_decode_bytes_at_r4_and_r8(dm, kv):
    flops, nbytes = F.paged_decode_cost(dm, [100, 300])
    assert flops == 4 * 32 * 128 * 400
    # K and V of 400 keys in kv heads, plus q in and out, bf16
    assert nbytes == (2 * kv * 128 * 400 + 2 * 32 * 128 * 2) * 2


def test_roofline_takes_the_slower_bound():
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert F.roofline_seconds(100, 50, peak) == 5.0
    assert F.roofline_seconds(1000, 5, peak) == 10.0
