"""The MLA kernels' operations and bytes against hand-worked shapes."""

import pytest

from perfbench import mla_costs, peaks


def test_absorbed_decode_cost_by_hand():
    # one slot, 1000 tokens, 16 heads, latent 512 + rope 64, bf16: the 576
    # values of each token ONCE = 1000 * 576 * 2 B; q in 16 * 576 * 2 B, the
    # latent output 16 * 512 * 2 B
    flops, nbytes = mla_costs.mla_decode_cost([1000], num_q_heads=16, latent_dim=512, rope_dim=64)
    assert nbytes == 1000 * 1152 + 16 * 576 * 2 + 16 * 512 * 2
    assert flops == 2 * 1000 * 16 * (576 + 512)
    two = mla_costs.mla_decode_cost([1000, 24], num_q_heads=16, latent_dim=512, rope_dim=64)
    assert two[0] == flops + 2 * 24 * 16 * 1088 and two[1] == nbytes + 24 * 1152 + 16 * 1088 * 2


def test_absorbed_decode_is_memory_bound_on_a_v5e_at_16_heads():
    flops, nbytes = mla_costs.mla_decode_cost([28000], num_q_heads=16, latent_dim=512, rope_dim=64)
    v5e = peaks.peaks_for("TPU v5 lite")
    assert nbytes / v5e["hbm_bytes_per_s"] > flops / v5e["flops_bf16"]
    # ... at about 30 operations a byte, 16 times what one head's K and V read gives
    assert flops / nbytes == pytest.approx(2 * 16 * 1088 / 1152, rel=0.01)


def test_materialised_prefill_cost_by_hand():
    # 8192 tokens, 16 heads, q.k over 192 channels and values of 128, causal:
    # 2 * S^2 * H * (192 + 128) / 2
    flops, nbytes = mla_costs.mla_prefill_cost(8192, num_q_heads=16, qk_dim=192, v_dim=128)
    assert flops == 8192 * 8192 * 16 * 320
    assert nbytes == 8192 * 16 * (192 + 192 + 128 + 128) * 2
    # the same as flash_cost where v is as wide as q and k
    same, same_bytes = mla_costs.mla_prefill_cost(2048, num_q_heads=16, qk_dim=256, v_dim=256)
    want = peaks.flash_cost(1, 2048, num_q_heads=16, num_kv_heads=16, head_dim=256)
    assert (same, same_bytes) == want


def test_a_padded_value_head_is_not_counted():
    padded, _ = mla_costs.mla_prefill_cost(4096, num_q_heads=16, qk_dim=192, v_dim=192)
    needed, _ = mla_costs.mla_prefill_cost(4096, num_q_heads=16, qk_dim=192, v_dim=128)
    assert needed / padded == pytest.approx(320 / 384)
