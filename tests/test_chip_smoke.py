"""CPU rehearsal of ``chip_smoke.py``: its phases, imported, at a tiny size,
on 1 and on 4 virtual devices, with the Pallas kernels interpreted.

The platform is the only thing this rehearsal cannot have. So, from HERE and
not through an option of the script: ``backend.on_tpu`` is patched to take
every branch the program takes on the chip (``auto`` → flash, fused paged
decode, flash-decode), the kernels run interpreted (conftest's session
switch), ``main()``'s platform assertion is not reached, and the checks named
``kernel_*`` — "a compiled Pallas kernel is in the program", which only a TPU
lowering can make true — are expected False. Every other check must pass.
"""

import importlib.util
import os
import sys

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


@pytest.fixture(autouse=True)
def _as_on_the_chip(monkeypatch):
    monkeypatch.setattr(backend, "on_tpu", lambda: True)


def _assert_only_kernel_checks_fail(checks):
    failed = {name for name, ok in checks.items() if not ok}
    kernel = {name for name in checks if name.startswith("kernel_")}
    assert kernel, "every phase reports on its compiled kernels"
    assert failed == kernel, (
        f"checks that failed: {sorted(failed - kernel)}; kernel checks that "
        f"passed without a TPU lowering: {sorted(kernel - failed)}"
    )


def _tiny_mla():
    import json

    path = os.path.join(os.path.dirname(_PATH), "tests", "benchmark", "data",
                        "configs", "deepseek-v2-lite-serve.json")
    with open(path) as f:
        return json.load(f)["model"]


# DeepSeek-V2's structure at the CPU stand-in's size; float32, so the
# tolerances are the rounding of a float32 matmul's summation order
MLA = chip_smoke.MlaSize(
    model=_tiny_mla(), max_seq_len=512, slots=2, prompt_lens=(300, 128, 40),
    tail=32, new_tokens=12, logit_tol=1e-3, typical_tol=1e-4, gap_tol=1e-3,
)


def _tiny_dsa():
    import json

    path = os.path.join(os.path.dirname(_PATH), "tests", "benchmark", "data",
                        "configs", "keye-vl2-30b-a3b-serve.json")
    with open(path) as f:
        return json.load(f)["model"]


# Keye-VL-2.0's structure at the CPU stand-in's size (32 columns kept);
# float32, so the tolerances are a float32 matmul's summation order; the
# float8 control is held to the selection limit, the halved one to all
DSA = chip_smoke.DsaSize(
    model=_tiny_dsa(), max_seq_len=512, slots=2, prompt_lens=(300, 128, 40),
    tail=32, new_tokens=12, kernel_contexts=(70, 200, 33), kernel_cursor=260,
    kernel_calls=2, kernel_prefill=512, kernel_tol=1e-4, logit_tol=1e-3, typical_tol=1e-4,
    selected_tol=1e-3, index_near_tie=1e-3, gap_tol=1e-3,
)


def _tiny_glm():
    import json

    path = os.path.join(os.path.dirname(_PATH), "tests", "benchmark", "data", "configs", "glm-5-serve.json")
    with open(path) as f:
        return json.load(f)["model"]


# GLM-5's structure at the CPU stand-in's size (32 columns kept, 8 of 16
# experts held); float32, so the tolerances are a float32 matmul's summation order
GLM = chip_smoke.GlmSize(
    model=_tiny_glm(), max_seq_len=512, slots=2, prompt_lens=(300, 128, 40),
    tail=32, new_tokens=12, kernel_contexts=(70, 200, 20), kernel_calls=2, kernel_tol=1e-4,
    logit_tol=1e-3, typical_tol=1e-4, selected_tol=1e-3, attn_tol=1e-4, routed_tol=1e-4, latent_tol=1e-5, gap_tol=1e-3,
    near_tie=0.0,
)


def test_one_chip_run_rehearsal():
    """Train, serve, then the MLA model in ONE process, exactly as ``main()``
    runs them (the train phase's global mesh must not leak into the
    mesh-free engines)."""
    _assert_only_kernel_checks_fail(
        chip_smoke.one_chip(0, jax.devices()[:1], TRAIN, SERVE, MLA, dsa=DSA, glm=GLM)
    )


def test_mla_phase_alone_rehearsal():
    _assert_only_kernel_checks_fail(
        chip_smoke.one_chip(0, jax.devices()[:1], TRAIN, SERVE, MLA, only="mla")
    )


def test_dsa_phase_alone_rehearsal():
    _assert_only_kernel_checks_fail(
        chip_smoke.one_chip(0, jax.devices()[:1], TRAIN, SERVE, MLA, only="dsa", dsa=DSA)
    )


def test_glm_phase_alone_rehearsal():
    _assert_only_kernel_checks_fail(
        chip_smoke.one_chip(0, jax.devices()[:1], TRAIN, SERVE, MLA, only="glm", glm=GLM)
    )


def _tiny_trinity():
    import json

    path = os.path.join(os.path.dirname(_PATH), "tests", "benchmark", "data", "configs", "trinity-large-serve.json")
    with open(path) as f:
        return json.load(f)["model"]


def test_trinity_phase_alone_rehearsal():
    """``--only trinity`` at the CPU stand-in's size (a window of 32 under
    contexts of 200, page 8, 4 of 16 experts held); float32, so the
    tolerances are a float32 matmul's summation order and every control is
    caught."""
    trinity = chip_smoke.TrinitySize(
        model=_tiny_trinity(), max_seq_len=512, slots=3, page=8, prompt_lens=(200, 90, 40), tail=32,
        new_tokens=12, pool_tokens=10, logit_tol=1e-3, typical_tol=1e-4, attn_tol=1e-4, routed_tol=1e-4,
        cache_tol=1e-5, gap_tol=1e-3, near_tie=0.0,
    )
    _assert_only_kernel_checks_fail(
        chip_smoke.one_chip(0, jax.devices()[:1], TRAIN, SERVE, MLA, only="trinity", trinity=trinity)
    )


def test_moe_phase_alone_rehearsal():
    """``--only moe`` at a tiny size: every comparison must hold; which form
    is FASTER is the chip's to say (an interpreted kernel's time says nothing)."""
    moe = chip_smoke.MoeSize(
        shapes=(("tiny", 8, 128, 256, 2, 8), ("tiny-odd", 16, 128, 384, 4, 4)),
        tokens=(1, 8), calls=1, dtype="float32", routed_tol=1e-4)
    checks = chip_smoke.one_chip(0, jax.devices()[:1], TRAIN, SERVE, MLA, only="moe", moe=moe)
    compared = {name: ok for name, ok in checks.items() if not name.endswith("_wins_where_the_rule_takes_it")}
    assert len(compared) == 8 and len(checks) == 10
    assert all(compared.values()), sorted(name for name, ok in compared.items() if not ok)


def test_walk_phase_alone_rehearsal():
    """``--only walk`` at a tiny size (rows of 32 pages of 8 tokens, one block
    a row; a window of 40 under contexts that start mid-page): both kinds of
    layer against the float32 einsum. The times are the chip's to say."""
    walk = chip_smoke.WalkSize(
        q_heads=4, kv_heads=2, head_dim=16, window=40, max_seq_len=256, page=8, window_pages=8,
        contexts=(131, 77, 30), cursor=157, calls=1, dtype="float32", tol=1e-5)
    checks = chip_smoke.one_chip(0, jax.devices()[:1], TRAIN, SERVE, MLA, only="walk", walk=walk)
    assert sorted(checks) == ["walk_full_matches_the_float32_einsum", "walk_window_matches_the_float32_einsum"]
    assert all(checks.values()), checks


def test_four_chip_run_rehearsal():
    import json

    path = os.path.join(os.path.dirname(_PATH), "tests", "benchmark", "data",
                        "configs", "codegen2-7b-train-tp4.json")
    with open(path) as f:
        # float32 here: saving in place of computing twice changes no bit
        remat = chip_smoke.RematSize(model=json.load(f)["model"], batch=4, seq=64, steps=2, loss_tol=0.0)
    _assert_only_kernel_checks_fail(
        chip_smoke.four_chips(0, jax.devices()[:4], TRAIN, SERVE, remat)
    )


def test_main_refuses_without_a_tpu(capsys):
    """No accelerator: non-zero exit and no result line."""
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == "" and "needs a TPU" in out.err


def test_flash_phase_alone_rehearsal():
    """``--only flash`` at a tiny size (blocks of 128; the padding's edge
    inside a block, on a boundary and absent; GQA; a narrower value head; a
    call that keeps residuals): every comparison. The times are the chip's."""
    flash = chip_smoke.FlashSize(
        shapes=(("tiny edge inside", 2, 2, 48, 32, 1, 512, 300, False),
                ("tiny gqa on a boundary", 4, 2, 32, 32, 1, 512, 256, False),
                ("tiny train", 2, 2, 32, 32, 2, 256, 256, True)),
        rows=64, calls=1, dtype="float32", tol=1e-4)
    checks = chip_smoke.one_chip(0, jax.devices()[:1], TRAIN, SERVE, MLA, only="flash", flash=flash)
    assert len(checks) == 3 * 4
    # on the CPU the interior body may differ from the edge body in the last
    # bit (the compiler contracts a multiply-add in one and not in the other)
    assert all(ok for name, ok in checks.items() if "_bit_for_bit" not in name), checks
