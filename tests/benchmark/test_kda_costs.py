"""``perfbench/kda_costs.py`` on hand-made shapes: the recurrence's needed work
(the state once in and once out a decode step, ``6 H d^2`` operations a token),
independent of the kernel's chunk size; its bounds on the v5e; the geometry the
family gives the readers."""

import importlib
import json
import os

from perfbench import kda_costs, peaks, swa_costs

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HEADS = dict(heads=64, head_dim=128)
STATE = 64 * 128 * 128 * 4          # 4 MiB


def test_a_decode_step_moves_the_state_once_in_and_once_out():
    flops, nbytes = kda_costs.kda_decode_cost(3, **HEADS)
    vectors = 64 * 128 * (4 * 2 + 4) + 64 * 4       # q, k, v, o in bf16, g in float32, beta a head
    assert nbytes == 3 * (2 * STATE + vectors) and flops == 3 * 6.0 * 64 * 128 * 128
    assert kda_costs.kda_decode_cost(0, **HEADS) == (0.0, 0.0)      # a slot that takes no token is no needed work
    share, bound = peaks.roofline_share_pct(flops, nbytes, 1e-3, peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory" and 0 < share < 100                      # 0.75 operations a byte


def test_a_prompt_costs_its_valid_tokens_whatever_the_chunk():
    flops, nbytes = kda_costs.kda_prefill_cost(3000, **HEADS)
    assert flops == 6.0 * 3000 * 64 * 128 * 128
    assert nbytes == 3000 * kda_costs.token_vector_bytes(64, 128) + STATE     # the state written once
    # twice the tokens, twice the work: no term in a chunk size or a bucket
    f2, _ = kda_costs.kda_prefill_cost(6000, **HEADS)
    assert f2 == 2 * flops
    _, bound = peaks.roofline_share_pct(flops, nbytes, 1e-3, peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory"            # 6 d / 12 = 64 operations a byte of q, k, v, g, o: under the v5e's 240


def test_a_slots_state_is_four_mib_and_the_taps():
    assert kda_costs.slot_state_bytes(heads=64, head_dim=128, taps=3) == STATE + 3 * 24576 * 2 == 4341760
    assert kda_costs.slot_state_bytes(heads=4, head_dim=16, taps=3, act_bytes=4) == 4 * 16 * 16 * 4 + 3 * 192 * 4


def test_the_costs_read_the_geometry_the_family_gives():
    with open(os.path.join(ROOT, "perfbench", "configs", "solar-open2-250b-serve.json")) as f:
        model = json.load(f)["model"]
    g = importlib.import_module("perfbench.families.solar_open2").geometry(model)
    assert (g["num_layers"], g["expert_layers"], g["recurrent_layers"], g["full_layers"], g["window_layers"]) == (8, 8, 6, 2, 0)
    assert (g["kda_heads"], g["kda_head_dim"], g["vocab_size"]) == (64, 128, 24576)
    assert kda_costs.kda_decode_cost(1, heads=g["kda_heads"], head_dim=g["kda_head_dim"])[1] > 2 * STATE
    # the GQA layers through the window-and-full stack's costs with NO window layer, unedited
    flops, nbytes = swa_costs.layers_cost(swa_costs.swa_decode_cost, g, [1000])
    one = swa_costs.swa_decode_cost([1000], num_q_heads=64, num_kv_heads=8, head_dim=128, window=None)
    assert (flops, nbytes) == (2 * one[0], 2 * one[1])
