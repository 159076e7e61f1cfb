"""CPU rehearsal of ``chip_smoke.py``: each row of its ``PHASES`` table,
imported, ONCE, at a tiny size, on 1 and on 4 virtual devices, with the
Pallas kernels interpreted.

The platform is the only thing this rehearsal cannot have. So, from HERE and
not through an option of the script: ``backend.on_tpu`` is patched to take
every branch the program takes on the chip (``auto`` → flash, fused paged
decode, flash-decode), the kernels run interpreted (conftest's session
switch), ``main()``'s platform assertion is not reached, and the checks named
``kernel_*`` — "a compiled Pallas kernel is in the program", which only a TPU
lowering can make true — are expected False. Every other check must pass,
but for what only the chip's clock or the chip's compiler can say (``ROWS``).
"""

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Tuple

import jax
import jax.numpy as jnp
import pytest

from neuronx_distributed_tpu.kernels import backend
from neuronx_distributed_tpu.models.llama import LlamaConfig

_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "chip_smoke.py"
)
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
chip_smoke = importlib.util.module_from_spec(_spec)
sys.modules["chip_smoke"] = chip_smoke  # dataclasses resolve their module by name
_spec.loader.exec_module(chip_smoke)


def _tiny(config_name):
    """The ``model`` group of the benchmark's CPU stand-in of a configuration."""
    path = os.path.join(os.path.dirname(_PATH), "tests", "benchmark", "data", "configs", config_name + ".json")
    with open(path) as f:
        return json.load(f)["model"]


# Llama-2-7B's structure (MHA, gated MLP, remat + scanned layers for
# training) shrunk to test size; 1024 cache columns so the row-cache pass
# crosses FLASH_DECODE_MIN_CONTEXT as the chip run does
TINY = LlamaConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
    num_heads=8, num_kv_heads=8, max_seq_len=1024, dtype=jnp.float32,
)
TRAIN = chip_smoke.TrainSize(config=TINY, batch=4, seq=128, steps=3)
# 12 new tokens = 2 decode chunks: a decode program that compiles twice
# (the engine's one-program invariant) shows here, not only on the chip
SERVE = chip_smoke.ServeSize(
    config=TINY, max_seq_len=1024, slots=4, prompt_lens=(20, 40, 70, 130),
    new_tokens=12, row_slots=2, row_prompt_lens=(12, 30), row_new_tokens=12,
    logit_tol=1e-3,  # fp32 serving against the fp32 reference
)


@dataclasses.dataclass(frozen=True)
class Row:
    """A phase's rehearsal: its tiny size, the names of the checks it must
    return (the parent's, letter for letter) and which of them may read
    False off a TPU. ``kernel_*`` MUST read False here; a name ending in one
    of ``chips_to_say`` may read either way."""

    size: object
    names: Tuple[str, ...]
    chips_to_say: Tuple[str, ...] = ()
    devices: int = 1


ROWS = {
    "train": Row(TRAIN, (
        "train_steps_taken", "train_finite", "train_one_compile", "train_loss_near_ln_vocab",
        "train_loss_matches_xla", "train_gnorm_matches_xla", "train_resolved_flash", "kernel_train_step")),
    "serve": Row(SERVE, (
        "serve_paged_clean_run", "serve_paged_one_decode_program", "serve_row_clean_run",
        "serve_row_one_decode_program", "serve_paged_matches_reference", "serve_row_matches_reference",
        "serve_several_prefill_buckets", "serve_resolved_fused", "serve_row_resolved_flash_decode",
        "kernel_serve_programs", "kernel_serve_row_decode")),
    # DeepSeek-V2's structure at the CPU stand-in's size; float32, so the
    # tolerances are the rounding of a float32 matmul's summation order
    "mla": Row(chip_smoke.MlaSize(
        model=_tiny("deepseek-v2-lite-serve"), max_seq_len=512, slots=2, prompt_lens=(300, 128, 40),
        tail=32, new_tokens=12, kernel_contexts=(70, 200, 33), kernel_calls=2, logit_tol=1e-3, typical_tol=1e-4,
        gap_tol=1e-3), (
        "mla_matches_reference", "mla_resolved_latent_fused", "mla_cache_is_latent_sized",
        "mla_float8_latent_is_caught", "mla_dropped_rope_channel_is_caught", "kernel_mla_programs")),
    # Keye-VL-2.0's structure at the CPU stand-in's size (32 columns kept);
    # float32, so the tolerances are a float32 matmul's summation order; the
    # float8 control is held to the selection limit, the halved one to all
    "dsa": Row(chip_smoke.DsaSize(
        model=_tiny("keye-vl2-30b-a3b-serve"), max_seq_len=512, slots=2, prompt_lens=(300, 128, 40),
        tail=32, new_tokens=12, kernel_contexts=(70, 200, 33), kernel_cursor=260,
        kernel_calls=2, kernel_prefill=512, forward_bucket=512, forward_prompts=(512, 300), kernel_tol=1e-4,
        logit_tol=1e-3, typical_tol=1e-4, selected_tol=1e-3, index_near_tie=1e-3, gap_tol=1e-3), (
        "dsa_index_kernel_matches_jnp", "dsa_bisection_selects_what_top_k_selects",
        "dsa_sparse_kernel_matches_jnp", "dsa_mask_kernel_keeps_the_einsums_columns",
        "dsa_prefill_forward_equals_pr49s_bit_for_bit", "dsa_prefill_forward_blocks_past_the_prompt_are_zero",
        "dsa_matches_reference", "dsa_selects_the_references_columns", "dsa_resolved_sparse_fused",
        "dsa_cache_is_indexed_sized", "dsa_float8_index_keys_are_caught", "dsa_1024_kept_is_caught",
        "kernel_dsa_programs"), chips_to_say=("_bit_for_bit",)),
    # GLM-5's structure at the CPU stand-in's size (32 columns kept, 8 of 16
    # experts held); float32, so the tolerances are a float32 matmul's summation order
    "glm": Row(chip_smoke.GlmSize(
        model=_tiny("glm-5-serve"), max_seq_len=512, slots=2, prompt_lens=(300, 128, 40),
        tail=32, new_tokens=12, kernel_contexts=(70, 200, 20), kernel_calls=2, forward_bucket=512,
        forward_prompts=(512, 300), kernel_tol=1e-4, logit_tol=1e-3, typical_tol=1e-4, selected_tol=1e-3,
        attn_tol=1e-4, routed_tol=1e-4, latent_tol=1e-5, gap_tol=1e-3, near_tie=0.0), (
        "glm_sparse_latent_kernel_matches_jnp", "glm_prefill_forward_equals_pr49s_bit_for_bit",
        "glm_prefill_forward_blocks_past_the_prompt_are_zero", "glm_matches_reference",
        "glm_selects_the_references_columns", "glm_attention_routed_sum_and_cache_alone_match_reference",
        "glm_resolved_sparse_latent_fused", "glm_cache_is_a_tile_and_an_index_key",
        "glm_float8_index_keys_are_caught", "glm_1024_kept_is_caught", "glm_float8_latent_is_caught",
        "glm_bias_in_the_weights_is_caught", "kernel_glm_programs"), chips_to_say=("_bit_for_bit",)),
    # (each of dsa, glm and trinity: the prefill's grouped forward alone against
    # PR 49's first, at a bucket of 512; on the CPU, in float32, the two may differ
    # in the last bit, as the flash forward's bodies below)
    # ``--only trinity`` at the CPU stand-in's size (a window of 32 under
    # contexts of 200, page 8, 4 of 16 experts held); float32, so the
    # tolerances are a float32 matmul's summation order and every control is caught
    "trinity": Row(chip_smoke.TrinitySize(
        model=_tiny("trinity-large-serve"), max_seq_len=512, slots=3, page=8, prompt_lens=(200, 90, 40), tail=32,
        new_tokens=12, pool_tokens=10, forward_bucket=512, forward_prompts=(512, 300), kernel_calls=2,
        logit_tol=1e-3, typical_tol=1e-4, attn_tol=1e-4, routed_tol=1e-4, cache_tol=1e-5, gap_tol=1e-3, near_tie=0.0), (
        "trinity_prefill_forward_equals_pr49s_bit_for_bit", "trinity_prefill_forward_blocks_past_the_prompt_are_zero",
        "trinity_matches_reference", "trinity_window_full_routed_and_pool_alone_match_reference",
        "trinity_frees_pages_behind_the_window_and_leaks_none", "trinity_cursor_jumps_leave_gap_columns",
        "trinity_resolved_paged_walk_fused", "trinity_cache_is_k_and_v_of_every_kv_head",
        "trinity_no_window_is_caught", "trinity_half_window_is_caught", "trinity_missing_gate_is_caught",
        "trinity_rotary_on_the_full_layer_is_caught", "trinity_float8_cache_is_caught",
        "trinity_bias_in_the_weights_is_caught", "kernel_trinity_programs"), chips_to_say=("_bit_for_bit",)),
    # ``--only zaya`` at the CPU stand-in's size (3 layers, contexts of 150 in a row of 512, page 8);
    # float32, so the tolerances are a float32 matmul's summation order and every control is caught
    "zaya": Row(chip_smoke.ZayaSize(
        model=_tiny("zaya1-8b-serve"), max_seq_len=512, slots=3, page=8, prompt_lens=(150, 70, 20),
        new_tokens=12, block_tol=1e-4, state_tol=1e-4, gap_tol=1e-3, near_tie=0.0), (
        "zaya_matches_reference", "zaya_two_blocks_alone_match_reference", "zaya_cursor_jumps_leave_gap_columns",
        "zaya_leaks_no_page", "zaya_resolved_paged_walk_fused",
        "zaya_cache_is_a_kib_a_token_and_the_state_five_and_a_quarter_a_slot", "zaya_no_convolution_is_caught",
        "zaya_unshifted_value_head_is_caught", "zaya_float8_is_caught", "zaya_no_router_state_is_caught",
        "kernel_zaya_programs")),
    # ``--only solar`` at the CPU stand-in's size (4 layers: GQA, linear, linear, GQA; contexts of 150 in a row of
    # 512, page 8); float32, so the tolerances are a float32 matmul's summation order and every control is caught
    "solar": Row(chip_smoke.SolarSize(
        model=_tiny("solar-open2-250b-serve"), max_seq_len=512, slots=3, page=8, prompt_lens=(150, 70, 20),
        new_tokens=12, linear_tol=1e-4, full_tol=1e-4, gap_tol=1e-3, near_tie=0.0), (
        "solar_matches_reference", "solar_two_mixers_alone_match_reference", "solar_cursor_jumps_leave_gap_columns",
        "solar_leaks_no_page", "solar_resolved_paged_walk_fused",
        "solar_cache_is_four_kib_a_token_and_the_state_four_mib_a_slot",
        "solar_decode_chunk_copies_no_array_of_the_states_size", "solar_decay_off_is_caught",
        "solar_beta_without_its_factor_is_caught", "solar_no_convolution_is_caught", "solar_no_output_gate_is_caught",
        "solar_float8_state_is_caught", "solar_float8_is_caught", "solar_rotary_on_the_gqa_layer_is_caught", "solar_no_gqa_gate_is_caught",
        "kernel_solar_programs"), chips_to_say=("_copies_no_array_of_the_states_size",)),
    # ``--only ouro`` at the CPU stand-in's size (2 layers x 3 passes, MHA; contexts of 150 in a row of 512, page 8,
    # a prefix of 64 tokens shared); float32, so the tolerance is a float32 matmul's summation order
    "ouro": Row(chip_smoke.OuroSize(
        model=_tiny("ouro-2.6b-serve"), max_seq_len=512, slots=2, page=8, prompt_lens=(150, 40), shared_tokens=64,
        new_tokens=12, gap_tol=1e-3), (
        "ouro_matches_reference", "ouro_shared_cache_is_caught", "ouro_float8_is_caught",
        "ouro_cursor_jump_leaves_gap_columns", "ouro_prefix_hit_maps_every_passes_pages_and_copies_nothing",
        "ouro_resolved_paged_walk_fused", "ouro_cache_is_a_node_a_layer_a_pass", "kernel_ouro_programs")),
    # ``--only moe`` at a tiny size: every comparison must hold; which form is
    # FASTER is the chip's to say (an interpreted kernel's time says nothing)
    "moe": Row(chip_smoke.MoeSize(
        shapes=(("tiny", 8, 128, 256, 2, 8), ("tiny-odd", 16, 128, 384, 4, 4)),
        tokens=(1, 8), calls=1, dtype="float32", routed_tol=1e-4,
        # a prefill's layer: every expert held, and 4 of 16 held
        prefill=(("tiny", 8, 128, 256, 2, None, 64, (20, 50)), ("tiny-held", 16, 128, 384, 4, 4, 64, (20, 50))),
        prefill_calls=1, sampled_rows=16), (
        "moe_tiny_stream_matches_jnp", "moe_tiny_ragged_dot_matches_jnp",
        "moe_tiny_float8_weights_are_caught", "moe_tiny_a_dropped_expert_is_caught",
        "moe_tiny-odd_stream_matches_jnp", "moe_tiny-odd_ragged_dot_matches_jnp",
        "moe_tiny-odd_float8_weights_are_caught", "moe_tiny-odd_a_dropped_expert_is_caught",
        "moe_tiny_stream_wins_where_the_rule_takes_it", "moe_tiny-odd_stream_wins_where_the_rule_takes_it",
        "moe_tiny_prefill_content_rows_match_jnp", "moe_tiny_prefill_content_rows_match_the_parents_form",
        "moe_tiny_prefill_padded_rows_are_zero", "moe_tiny_prefill_is_blind_to_its_padding",
        "moe_tiny_prefill_full_bucket_costs_no_more",
        "moe_tiny_prefill_emptiest_prompt_costs_less",
        "moe_tiny-held_prefill_content_rows_match_jnp", "moe_tiny-held_prefill_content_rows_match_the_parents_form",
        "moe_tiny-held_prefill_padded_rows_are_zero", "moe_tiny-held_prefill_is_blind_to_its_padding",
        "moe_tiny-held_prefill_full_bucket_costs_no_more",
        "moe_tiny-held_prefill_emptiest_prompt_costs_less"),
        chips_to_say=("_wins_where_the_rule_takes_it", "_costs_no_more", "_costs_less")),
    # ``--only walk`` at a tiny size (rows of 32 pages of 8 tokens, one block
    # a row; a window of 40; a second shape with no window layer): both kinds
    # of layer against the float32 einsum, under a random block table and
    # under the one a PagedCacheManager deals. The times are the chip's to say
    "walk": Row(chip_smoke.WalkSize(
        shapes=(chip_smoke.WalkShape("tiny", 4, 2, 16, 256, (134, 78, 30), 157, window=40, window_pages=16),
                chip_smoke.WalkShape("narrow", 2, 1, 16, 256, (134, 62), 157)),
        page=8, calls=1, dtype="float32", tol=1e-5),
        tuple(f"walk_{shape}_{table}_table_matches_the_float32_einsum"
              for shape in ("tiny_window", "tiny_full", "narrow_full") for table in ("random", "dealt"))),
    # ``--only runahead`` on the Trinity stand-in (window pages freed behind a
    # projected cursor): the probe's two chained calls with no profiler session
    # (a pytest worker opens none), then both passes, 6 requests over 2 slots.
    # What only the chip's clock says: that the second call comes back while the
    # first runs, that the device leaves no hole, and the shorter wall
    "runahead": Row(chip_smoke.RunAheadSize(
        model=_tiny("trinity-large-serve"), max_seq_len=512, slots=2, prompt_len=40, probe_pairs=2, requests=6,
        answers=(24, 64), trace=False), (
        "runahead_second_call_returns_while_the_first_runs_on_the_chips_clock",
        "runahead_device_leaves_no_hole_between_chained_chunks_on_the_chips_clock",
        "runahead_streams_equal_one_chunk_at_a_time", "runahead_engages_with_every_slot_held",
        "runahead_leaks_no_page", "runahead_chunk_wall_is_shorter_on_the_chips_clock"),
        chips_to_say=("_on_the_chips_clock",)),
    # ``--only flash`` at a tiny size (blocks of 128; the padding's edge
    # inside a block, on a boundary and absent; GQA; a narrower value head; a
    # call that keeps residuals): every comparison. The times are the chip's.
    # On the CPU the interior body may differ from the edge body in the last
    # bit (the compiler contracts a multiply-add in one and not in the other)
    "flash": Row(chip_smoke.FlashSize(
        shapes=(("tiny edge inside", 2, 2, 48, 32, 1, 512, 300, False),
                ("tiny gqa on a boundary", 4, 2, 32, 32, 1, 512, 256, False),
                ("tiny train", 2, 2, 32, 32, 2, 256, 256, True)),
        rows=64, calls=1, dtype="float32", tol=1e-4), (
        "flash_0_content_rows_match_float32", "flash_0_padded_rows_are_zero",
        "flash_0_equals_the_stage_triangle_only_bit_for_bit",
        "flash_0_equals_the_stage_padded_rows_bit_for_bit", "flash_1_content_rows_match_float32",
        "flash_1_padded_rows_are_zero", "flash_1_equals_the_stage_triangle_only_bit_for_bit",
        "flash_1_equals_the_stage_padded_rows_bit_for_bit", "flash_2_content_rows_match_float32",
        "flash_2_padded_rows_are_zero", "flash_2_equals_the_stage_triangle_only_bit_for_bit",
        "flash_2_equals_the_stage_padded_rows_bit_for_bit"), chips_to_say=("_bit_for_bit",)),
    "tp_train": Row(TRAIN, (
        "tp_train_loss_matches_one_device", "tp_train_gnorm_matches_one_device", "tp_train_params_split",
        "kernel_tp_train_step"), devices=4),
    "tp_serve": Row(SERVE, (
        "tp_serve_one_device_clean_run", "tp_serve_one_device_one_decode_program", "tp_serve_clean_run",
        "tp_serve_one_decode_program", "tp_serve_one_device_matches_reference",
        "tp_serve_matches_reference", "tp_serve_resolved_fused", "tp_serve_params_split",
        "tp_serve_kv_split", "kernel_tp_serve_programs"), devices=4),
    # float32 here: saving in place of computing twice changes no bit
    "remat": Row(chip_smoke.RematSize(
        model=_tiny("codegen2-7b-train-tp4"), batch=4, seq=64, steps=2, loss_tol=0.0), (
        "remat_policies_give_the_same_losses", "remat_save_nothing_is_recorded_empty",
        "remat_named_saves_are_recorded"), devices=4),
}


@pytest.fixture(autouse=True)
def _as_on_the_chip(monkeypatch):
    monkeypatch.setattr(backend, "on_tpu", lambda: True)


def _assert_only_kernel_checks_fail(checks, chips_to_say=()):
    judged = {name: ok for name, ok in checks.items() if not (chips_to_say and name.endswith(chips_to_say))}
    failed = {name for name, ok in judged.items() if not ok}
    kernel = {name for name in judged if name.startswith("kernel_")}
    assert failed == kernel, (
        f"checks that failed: {sorted(failed - kernel)}; kernel checks that "
        f"passed without a TPU lowering: {sorted(kernel - failed)}"
    )


@pytest.mark.parametrize("name", list(chip_smoke.PHASES))
def test_phase_rehearsal(name):
    """One row of ``PHASES`` alone, reached as ``main()`` reaches it (a row of
    the table with no row here fails by name)."""
    row = ROWS[name]
    checks = chip_smoke.run([name], 0, jax.devices()[:row.devices], {name: row.size})
    assert sorted(checks) == sorted(row.names)
    _assert_only_kernel_checks_fail(checks, row.chips_to_say)


def test_train_then_serve_in_one_process():
    """Train, then serve in ONE process, as ``main()`` runs them: the train
    phase's global mesh must not leak into the mesh-free engine (the tiny
    Llama only; every other serving phase drops the mesh as ``serve`` does)."""
    checks = chip_smoke.run(["train", "serve"], 0, jax.devices()[:1], {"train": TRAIN, "serve": SERVE})
    assert sorted(checks) == sorted(ROWS["train"].names + ROWS["serve"].names)
    _assert_only_kernel_checks_fail(checks)


def test_default_runs_are_the_tables_rows():
    """What ``main()`` runs with no ``--only`` (no model built), and what
    ``--only`` may name."""
    assert chip_smoke.default_run(1) == ["train", "serve", "mla", "dsa", "glm"]
    assert chip_smoke.default_run(4) == ["tp_train", "tp_serve", "remat"]
    only = [name for name, phase in chip_smoke.PHASES.items() if phase.only]
    assert only == ["mla", "dsa", "glm", "moe", "trinity", "zaya", "solar", "ouro", "walk", "runahead", "flash"]
    for name in only:
        assert chip_smoke.parse_args(["--only", name]).only == name
        assert chip_smoke.PHASES[name].help and chip_smoke.PHASES[name].chips == 1
    assert chip_smoke.parse_args([]).only == "all" and chip_smoke.parse_args(["--chips", "4"]).chips == 4
    with pytest.raises(SystemExit):
        chip_smoke.parse_args(["--only", "train"])


def test_main_refuses_without_a_tpu(capsys):
    """No accelerator: non-zero exit and no result line."""
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == "" and "needs a TPU" in out.err
