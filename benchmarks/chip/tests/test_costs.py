"""Operation and byte counts against hand counts at small shapes."""
import pytest

from chipbench import costs
from families.dense_gqa import Dims

DM = Dims(L=2, d=64, H=4, KVH=2, Dh=16, f=96, V=100, eps=1e-5,
          theta=1e4)
PK = {"flops_per_s": 1e6, "hbm_bytes_per_s": 1e3}


def test_gemm_packed_hand_count():
    ops, byt = costs.gemm_packed(2, 64, 32)
    assert ops == 2 * 2 * 64 * 32
    # x f32 + packed codes (half a byte) + one E8M0 byte per 32 + y f32
    assert byt == 2 * 64 * 4 + 64 * 32 // 2 + 64 * 32 // 32 + 2 * 32 * 4


def test_decode_attention_hand_count():
    ops, byt = costs.decode_attention(DM, 10)
    # QK^T and PV: 2 * 2 * (H * Dh) * ctx per layer
    assert ops == DM.L * 4 * 64 * 10
    # K and V: ctx * kv_dim * (1 + 1/32) each; q in, out out in f32
    assert byt == pytest.approx(DM.L * (2 * 10 * 32 * (1 + 1 / 32)
                                        + 2 * 64 * 4))


def test_prefill_attention_hand_count():
    ops, _ = costs.prefill_attention(DM, 4, 3)
    # rows at positions 4, 5, 6 attend 5, 6, 7 keys
    assert ops == DM.L * 4 * 64 * (5 + 6 + 7)


def test_prompt_flops_sum_of_token_flops():
    assert costs.prompt_flops(DM, 9) == sum(costs.token_flops(DM, p)
                                            for p in range(9))


def test_roofline_takes_the_larger_bound():
    assert costs.roofline_s(2e6, 1e3, PK) == (2.0, "compute")
    assert costs.roofline_s(1e6, 3e3, PK) == (3.0, "memory")
