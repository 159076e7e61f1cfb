"""The cost functions of the sparse-attention kernels, from shapes."""

import json
import os

import pytest

from perfbench import dsa_costs, peaks

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
KEYE = dict(num_q_heads=32, num_kv_heads=4, head_dim=128)
INDEX = dict(index_heads=16, index_dim=64)


def test_index_scores_read_128_bytes_a_token():
    flops, nbytes = dsa_costs.index_decode_cost([10_000], **INDEX)
    assert flops == 2 * 10_000 * 16 * 64
    assert nbytes == 10_000 * (128 + 4) + 16 * 65 * 2
    twice = dsa_costs.index_decode_cost([10_000, 10_000], **INDEX)
    assert twice == (2 * flops, 2 * nbytes)


def test_sparse_attention_reads_the_selected_tokens_and_no_others():
    short = dsa_costs.sparse_decode_cost([1500], topk=2048, **KEYE)
    long = dsa_costs.sparse_decode_cost([25_000], topk=2048, **KEYE)
    longer = dsa_costs.sparse_decode_cost([30_000], topk=2048, **KEYE)
    assert long == longer                                   # past topk the context does not matter
    assert short[1] == 1500 * 2048 + 2 * 32 * 128 * 2       # 2048 B a token: K and V of 4 heads of 128
    assert long[1] == 2048 * 2048 + 2 * 32 * 128 * 2
    assert long[0] == 2 * 2048 * 32 * 256
    # a dense read of the same context would be 12 x the bytes
    assert 25_000 * 2048 / long[1] > 12


def test_selected_pairs_is_the_sum_of_min_t_plus_1_topk():
    for seq, topk in ((1, 4), (4, 4), (5, 4), (100, 16), (3000, 2048)):
        assert dsa_costs.selected_pairs(seq, topk) == sum(min(t + 1, topk) for t in range(seq))


def test_prefill_counts_selected_pairs_and_every_causal_index_score():
    seq = 12_288
    flops, nbytes = dsa_costs.sparse_prefill_cost(seq, topk=2048, **KEYE, **INDEX)
    attend = 2 * 32 * 256 * dsa_costs.selected_pairs(seq, 2048)
    scores = 2 * 16 * 64 * seq * (seq + 1) / 2
    assert flops == attend + scores
    dense = 2 * 32 * 256 * seq * (seq + 1) / 2
    assert attend < 0.31 * dense                             # what a dense kernel does beyond the need
    assert nbytes == seq * ((2 * 32 + 2 * 4) * 128 + 17 * 64) * 2
    # up to topk tokens this IS dense causal attention
    assert dsa_costs.sparse_prefill_cost(2048, topk=2048, **KEYE, **INDEX)[0] == (
        (2 * 32 * 256 + 2 * 16 * 64) * 2048 * 2049 / 2)


def test_decode_kernels_are_memory_bound_on_the_v5e():
    chip = peaks.peaks_for("TPU v5 lite")
    for flops, nbytes in (dsa_costs.index_decode_cost([13_000] * 8, **INDEX),
                          dsa_costs.sparse_decode_cost([13_000] * 8, topk=2048, **KEYE)):
        share, bound = peaks.roofline_share_pct(flops, nbytes, 1e-3, chip)
        assert bound == "memory" and 0 < share < 100


def test_the_costs_read_the_geometry_the_family_gives():
    from perfbench.families import keye_vl2

    with open(os.path.join(ROOT, "perfbench", "configs", "keye-vl2-30b-a3b-serve.json")) as f:
        g = keye_vl2.geometry(json.load(f)["model"])
    assert (g["index_heads"], g["index_dim"], g["index_topk"]) == (16, 64, 2048)
    assert (g["num_q_heads"], g["num_kv_heads"], g["head_dim"]) == (32, 4, 128)
    assert dsa_costs.sparse_decode_cost(
        [5000], num_q_heads=g["num_q_heads"], num_kv_heads=g["num_kv_heads"], head_dim=g["head_dim"],
        topk=g["index_topk"])[1] == pytest.approx(2048 * 2048 + 16384)
