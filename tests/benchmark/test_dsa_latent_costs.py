"""The cost functions of sparse attention over LATENTS: needed work only."""

import json
import os

from perfbench import dsa_costs, dsa_latent_costs, peaks

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
GLM = dict(num_q_heads=64, latent_dim=512, rope_dim=64, topk=2048)


def test_decode_reads_the_selected_latents_and_no_others():
    flops, nbytes = dsa_latent_costs.sparse_latent_decode_cost([100, 2048, 30000], **GLM)
    kept = 100 + 2048 + 2048
    assert flops == 2.0 * kept * 64 * (576 + 512)
    assert nbytes == kept * 1152 + 3 * 64 * (576 + 512) * 2            # 1152 B a token: what it HOLDS, not its tile
    # a context past topk costs what one at topk does
    assert dsa_latent_costs.sparse_latent_decode_cost([2048], **GLM) == dsa_latent_costs.sparse_latent_decode_cost([10**6], **GLM)


def test_prefill_counts_selected_pairs_and_every_causal_index_score():
    kw = dict(num_q_heads=64, qk_dim=256, v_dim=256, index_heads=32, index_dim=128, topk=2048)
    seq = 16384
    flops, nbytes = dsa_latent_costs.sparse_latent_prefill_cost(seq, **kw)
    pairs = dsa_costs.selected_pairs(seq, 2048)
    assert pairs == 2048 * 2049 / 2 + (seq - 2048) * 2048
    assert flops == 2.0 * 64 * 512 * pairs + 2.0 * 32 * 128 * seq * (seq + 1) / 2
    assert nbytes == seq * 2 * 64 * 512 * 2 + seq * 33 * 128 * 2
    dense = 2.0 * 64 * 512 * seq * (seq + 1) / 2
    assert flops < 0.45 * dense                                          # a kernel doing dense work reads low
    short, _ = dsa_latent_costs.sparse_latent_prefill_cost(1000, **kw)
    assert short == 2.0 * (64 * 512 + 32 * 128) * 1000 * 1001 / 2        # under topk: every causal pair


def test_the_decode_kernel_is_memory_bound_on_the_v5e():
    flops, nbytes = dsa_latent_costs.sparse_latent_decode_cost([8192] * 8, **GLM)
    table = peaks.peaks_for("TPU v5 lite")
    share, bound = peaks.roofline_share_pct(flops, nbytes, 1e-3, table)
    assert bound == "memory" and 0 < share < 100
    # 64 heads on ONE row: 121 operations a byte, still under the v5e's ridge (~240)
    assert 100 < flops / nbytes < 130


def test_the_costs_read_the_geometry_the_family_gives():
    from perfbench.families import glm_moe_dsa as family

    with open(os.path.join(ROOT, "perfbench", "configs", "glm-5-serve.json")) as f:
        g = family.geometry(json.load(f)["model"])
    assert dsa_latent_costs.sparse_latent_decode_cost(
        [5000], num_q_heads=g["num_q_heads"], latent_dim=g["latent_dim"], rope_dim=g["rope_dim"],
        topk=g["index_topk"])[1] == 2048 * 1152 + 64 * 1088 * 2
    assert dsa_costs.index_decode_cost([5000], index_heads=g["index_heads"], index_dim=g["index_dim"])[1] == (
        5000 * 256 + 5000 * 4 + 32 * 129 * 2)                              # 256 B of index key a token
