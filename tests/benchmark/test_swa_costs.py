"""The cost functions of attention in a stack of window and full layers:
needed work only."""

import json
import os

from perfbench import peaks, swa_costs

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HEADS = dict(num_q_heads=48, num_kv_heads=8, head_dim=128)


def test_decode_reads_the_window_in_a_window_layer_and_everything_in_a_full_one():
    ctx = [100, 4096, 30000]
    flops, nbytes = swa_costs.swa_decode_cost(ctx, window=4096, **HEADS)
    kept = 100 + 4096 + 4096
    assert flops == 2.0 * kept * 48 * 256
    assert nbytes == kept * 4096 + 3 * 2 * 48 * 128 * 2            # 4096 B a token: K and V of 8 heads of 128
    full = swa_costs.swa_decode_cost(ctx, window=None, **HEADS)
    assert full == (2.0 * sum(ctx) * 48 * 256, sum(ctx) * 4096 + 3 * 2 * 48 * 128 * 2)
    # a context past the window costs a window layer what one at the window does
    assert swa_costs.swa_decode_cost([4096], window=4096, **HEADS) == swa_costs.swa_decode_cost([10**6], window=4096, **HEADS)


def test_prefill_counts_the_pairs_inside_the_band():
    seq = 16384
    assert swa_costs.band_pairs(seq, 4096) == 4096 * 4097 / 2 + (seq - 4096) * 4096     # 58.7 M
    assert swa_costs.band_pairs(seq, None) == seq * (seq + 1) / 2                         # 134 M
    assert 0.43 < swa_costs.band_pairs(seq, 4096) / swa_costs.band_pairs(seq, None) < 0.45
    assert swa_costs.band_pairs(1000, 4096) == swa_costs.band_pairs(1000, None) == 1000 * 1001 / 2
    flops, nbytes = swa_costs.swa_prefill_cost(seq, window=4096, **HEADS)
    assert flops == 2.0 * 48 * 256 * swa_costs.band_pairs(seq, 4096)
    assert nbytes == seq * (2 * 48 + 2 * 8) * 128 * 2


def test_the_kernels_bounds_on_the_v5e():
    table = peaks.peaks_for("TPU v5 lite")
    flops, nbytes = swa_costs.swa_decode_cost([8192] * 8, window=4096, **HEADS)
    share, bound = peaks.roofline_share_pct(flops, nbytes, 1e-3, table)
    assert bound == "memory" and 0 < share < 100                    # 6 operations a byte
    flops, nbytes = swa_costs.swa_prefill_cost(16384, window=4096, **HEADS)
    assert peaks.roofline_share_pct(flops, nbytes, 1.0, table)[1] == "compute"


def test_the_costs_read_the_geometry_the_family_gives():
    from perfbench.families import afmoe as family

    with open(os.path.join(ROOT, "perfbench", "configs", "trinity-large-serve.json")) as f:
        g = family.geometry(json.load(f)["model"])
    assert (g["window_layers"], g["full_layers"], g["window"], g["expert_layers"]) == (4, 1, 4096, 4)
    flops, nbytes = swa_costs.layers_cost(swa_costs.swa_decode_cost, g, [9146])
    q_io = 2 * 48 * 128 * 2
    assert nbytes == 4 * (4096 * 4096 + q_io) + (9146 * 4096 + q_io)
    assert flops == 2.0 * 48 * 256 * (4 * 4096 + 9146)
    flops, _ = swa_costs.layers_cost(swa_costs.swa_prefill_cost, g, 16384)
    assert flops == 2.0 * 48 * 256 * (4 * swa_costs.band_pairs(16384, 4096) + swa_costs.band_pairs(16384, None))
