"""Roofline arithmetic against hand-worked shapes."""

import pytest

from perfbench import peaks


def test_unknown_device_kind_is_an_error_not_a_default():
    assert peaks.peaks_for("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


def test_paged_decode_cost_by_hand():
    # one slot, 1000 tokens of context, 32 q heads, 8 kv heads of 128, bf16:
    # K and V: 2 * 1000 * 8 * 128 * 2 B = 4_096_000 B; q in, o out: 2 * 32 * 128 * 2 B
    flops, nbytes = peaks.paged_decode_cost([1000], num_q_heads=32, num_kv_heads=8, head_dim=128)
    assert nbytes == 4_096_000 + 16_384
    assert flops == 4 * 1000 * 32 * 128          # QK^T and PV, 2 FLOPs a multiply-add
    two = peaks.paged_decode_cost([1000, 24], num_q_heads=32, num_kv_heads=8, head_dim=128)
    assert two[1] == nbytes + 2 * 24 * 8 * 128 * 2 + 16_384


def test_flash_forward_and_backward_by_hand():
    # B=2, S=2048, 16 heads of 256, causal: 4 * 2 * 2048^2 * 16 * 256 / 2
    flops, nbytes = peaks.flash_cost(2, 2048, num_q_heads=16, num_kv_heads=16, head_dim=256)
    assert flops == 4 * 2 * 2048 * 2048 * 16 * 256 / 2
    assert nbytes == 4 * (2 * 2048 * 16 * 256 * 2)            # q, k, v read, o written
    bflops, bbytes = peaks.flash_backward_cost(2, 2048, num_q_heads=16, num_kv_heads=16, head_dim=256)
    assert bflops == 2.5 * flops
    assert bbytes == 8 * (2 * 2048 * 16 * 256 * 2)            # q k v o do read, dq dk dv written
    full, _ = peaks.flash_cost(2, 2048, num_q_heads=16, num_kv_heads=16, head_dim=256, causal=False)
    assert full == 2 * flops


def test_roofline_share_names_its_bound():
    v5e = peaks.peaks_for("TPU v5 lite")
    share, bound = peaks.roofline_share_pct(197e12, 1.0, 2.0, v5e)      # 1 s of math in 2 s
    assert share == pytest.approx(50.0) and bound == "compute"
    share, bound = peaks.roofline_share_pct(1.0, 819e9, 4.0, v5e)       # 1 s of reads in 4 s
    assert share == pytest.approx(25.0) and bound == "memory"


def test_train_flops_per_token_is_bench_pys_arithmetic():
    # 6 (N - N_embed) + 6 L S H, per token
    got = peaks.train_flops_per_token(1_000_000, 100_000, num_layers=4, seq=2048, hidden=512)
    assert got == 6 * 900_000 + 6 * 4 * 2048 * 512
