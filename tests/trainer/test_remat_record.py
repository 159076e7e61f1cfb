"""The trainer records what the model's remat policy keeps through the
backward pass of the train program it built: ``programs.resolved["remat"]``
and gauge ``train_remat_saved_bytes``."""

import jax
import numpy as np
import pytest

from neuronx_distributed_tpu.models.codegen import CodeGenForCausalLM, tiny_codegen
from neuronx_distributed_tpu.models.llama import LlamaForCausalLM, tiny_llama
from neuronx_distributed_tpu.parallel import mesh as mesh_lib
from neuronx_distributed_tpu.trainer import OptimizerConfig
from neuronx_distributed_tpu.trainer.loop import Trainer

BATCH, SEQ = 8, 16


def _fit(model, steps=2, **optimizer):
    rng = np.random.default_rng(0)

    def data():
        while True:
            ids = rng.integers(0, 256, (BATCH, SEQ + 1)).astype(np.int32)
            yield {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}

    trainer = Trainer(model=model, optimizer_config=OptimizerConfig(**optimizer), handle_signals=False)
    out = trainer.fit(data(), jax.random.PRNGKey(0), max_steps=steps)
    return trainer, float(out["loss"])


def _recorded(trainer):
    snapshot = trainer.programs.snapshot(analyze=False)["resolved"]["remat"]
    assert snapshot == trainer.programs.resolved["remat"]
    return snapshot, trainer.programs.registry.get("train_remat_saved_bytes").value


@pytest.mark.parametrize("tp,sp", [(1, False), (4, True)], ids=["tp1", "tp4_sp"])
def test_trainer_records_the_named_saves_and_their_bytes_a_chip(tp, sp):
    mesh_lib.initialize_model_parallel(tensor_model_parallel_size=tp)
    cfg = tiny_codegen()
    assert cfg.remat_policy == "mlp_up+attn"
    # float32 values a layer, over the 8 devices of the mesh
    up = cfg.num_layers * BATCH * SEQ * cfg.intermediate_size * 4 // 8
    qkv = cfg.num_layers * 3 * BATCH * SEQ * cfg.hidden_size * 4 // 8
    losses = {}
    for policy, names, want in (("mlp_up+attn", ["attn_qkv", "mlp_up"], up + qkv),
                                ("mlp_up", ["mlp_up"], up), (None, [], 0)):
        trainer, losses[policy] = _fit(CodeGenForCausalLM(
            tiny_codegen(sequence_parallel=sp, remat=True, remat_policy=policy)))
        assert _recorded(trainer) == (names, want)
    assert losses["mlp_up+attn"] == losses["mlp_up"] == losses[None]    # two steps: the update was the same


def test_trainer_records_nothing_for_a_model_without_remat_or_names():
    mesh_lib.initialize_model_parallel(tensor_model_parallel_size=2)
    trainer, _ = _fit(CodeGenForCausalLM(tiny_codegen(remat=False)), steps=1)
    assert _recorded(trainer) == ([], 0)
    # Llama's policies keep by primitive: nothing carries a name
    trainer, _ = _fit(LlamaForCausalLM(tiny_llama(remat=True, remat_policy="dots"), attention_impl="xla"), steps=1)
    assert _recorded(trainer) == ([], 0)


def test_bytes_are_one_microbatchs_under_gradient_accumulation():
    mesh_lib.initialize_model_parallel(tensor_model_parallel_size=2)
    cfg = tiny_codegen(remat=True)
    trainer, _ = _fit(CodeGenForCausalLM(cfg), steps=1, grad_accum_steps=2)
    want = cfg.num_layers * (BATCH // 2) * SEQ * (cfg.intermediate_size + 3 * cfg.hidden_size) * 4 // 8
    assert _recorded(trainer) == (["attn_qkv", "mlp_up"], want)
