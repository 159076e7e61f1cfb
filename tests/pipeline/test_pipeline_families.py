"""Pipeline adapters beyond Llama (round-2 coverage #15: "Mixtral/NeoX/BERT
still cannot pipeline"; reference: NxDPPModel wraps arbitrary models)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from neuronx_distributed_tpu.models.gpt_neox import (
    GPTNeoXForCausalLM,
    tiny_gpt_neox,
)
from neuronx_distributed_tpu.models.mixtral import (
    MixtralForCausalLM,
    tiny_mixtral,
)
from neuronx_distributed_tpu.parallel import mesh as mesh_lib
from neuronx_distributed_tpu.pipeline.gpt_neox import (
    gpt_neox_params_to_pipeline,
    gpt_neox_pipeline_engine,
    pipeline_params_to_gpt_neox,
)
from neuronx_distributed_tpu.pipeline.mixtral import (
    mixtral_params_to_pipeline,
    mixtral_pipeline_engine,
    pipeline_params_to_mixtral,
)
from neuronx_distributed_tpu.pipeline.model import microbatch

B, S, M = 8, 16, 4


def _assert_tree_close(got, want, atol):
    flat_w = jax.tree_util.tree_flatten_with_path(want)[0]
    flat_g = jax.tree_util.tree_flatten_with_path(got)[0]
    assert len(flat_w) == len(flat_g)
    for (path, vw), (_, vg) in zip(flat_w, flat_g):
        np.testing.assert_allclose(
            np.asarray(vg), np.asarray(vw), atol=atol,
            err_msg=jax.tree_util.keystr(path),
        )


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_gpt_neox_pipeline_matches_monolith(schedule, in_child_process):
    if schedule == "1f1b" and in_child_process:    # pp=2 x tp=2: see conftest.in_child_process
        return in_child_process(__file__, "test_gpt_neox_pipeline_matches_monolith", schedule, None)
    mesh_lib.initialize_model_parallel(
        tensor_model_parallel_size=2, pipeline_model_parallel_size=2
    )
    cfg = tiny_gpt_neox(num_layers=4)
    model = GPTNeoXForCausalLM(cfg)
    key = jax.random.PRNGKey(0)
    ids = jax.random.randint(jax.random.fold_in(key, 1), (B, S), 0, cfg.vocab_size)
    labels = jnp.roll(ids, -1, 1)
    params = meta.unbox(jax.jit(model.init)(key, ids))
    engine = gpt_neox_pipeline_engine(cfg, num_microbatches=M, schedule=schedule)
    pp_params = gpt_neox_params_to_pipeline(params, engine)
    batch_mb = microbatch({"input_ids": ids, "labels": labels}, M)

    def mono_loss(p):
        return model.loss(p, ids, labels)

    ref_loss, g_ref = jax.jit(jax.value_and_grad(mono_loss))(params)
    if schedule == "1f1b":
        loss, grads = jax.jit(engine.value_and_grad)(pp_params, batch_mb)
    else:
        loss, grads = jax.jit(jax.value_and_grad(engine.loss_fn))(pp_params, batch_mb)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    _assert_tree_close(pipeline_params_to_gpt_neox(grads, engine), g_ref, atol=5e-5)


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_mixtral_pipeline_matches_monolith_no_aux(schedule, in_child_process):
    """Exact parity with aux coefficients 0 (aux is per-microbatch under PP,
    see pipeline/mixtral.py docstring)."""
    if schedule == "1f1b" and in_child_process:    # pp=2 x tp=2: see conftest.in_child_process
        return in_child_process(__file__, "test_mixtral_pipeline_matches_monolith_no_aux", schedule, None)
    mesh_lib.initialize_model_parallel(
        tensor_model_parallel_size=2, pipeline_model_parallel_size=2
    )
    cfg = tiny_mixtral(
        scan_layers=True, num_layers=2,
        router_aux_loss_coef=0.0, router_z_loss_coef=0.0, max_seq_len=S,
    )
    model = MixtralForCausalLM(cfg, attention_impl="xla")
    key = jax.random.PRNGKey(0)
    ids = jax.random.randint(jax.random.fold_in(key, 1), (B, S), 0, cfg.vocab_size)
    labels = jnp.roll(ids, -1, 1)
    params = meta.unbox(jax.jit(model.init)(key, ids))
    engine = mixtral_pipeline_engine(
        cfg, num_microbatches=M, attention_impl="xla", schedule=schedule
    )
    pp_params = mixtral_params_to_pipeline(params, engine)
    batch_mb = microbatch({"input_ids": ids, "labels": labels}, M)

    def mono_loss(p):
        return model.loss(p, ids, labels)

    ref_loss, g_ref = jax.jit(jax.value_and_grad(mono_loss))(params)
    if schedule == "1f1b":
        loss, grads = jax.jit(engine.value_and_grad)(pp_params, batch_mb)
    else:
        loss, grads = jax.jit(jax.value_and_grad(engine.loss_fn))(pp_params, batch_mb)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    _assert_tree_close(pipeline_params_to_mixtral(grads, engine), g_ref, atol=5e-5)


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_mixtral_pipeline_aux_losses(schedule):
    """With nonzero coefficients the loss equals CE + mean-over-microbatches
    aux (computed per-mb by a monolithic golden), and router grads flow."""
    mesh_lib.initialize_model_parallel(pipeline_model_parallel_size=2)
    cfg = tiny_mixtral(
        scan_layers=True, num_layers=2, router_aux_loss_coef=0.05,
        router_z_loss_coef=0.01, max_seq_len=S,
    )
    model = MixtralForCausalLM(cfg, attention_impl="xla")
    key = jax.random.PRNGKey(0)
    ids = jax.random.randint(jax.random.fold_in(key, 1), (B, S), 0, cfg.vocab_size)
    labels = jnp.roll(ids, -1, 1)
    params = meta.unbox(jax.jit(model.init)(key, ids))
    engine = mixtral_pipeline_engine(
        cfg, num_microbatches=M, attention_impl="xla", schedule=schedule
    )
    pp_params = mixtral_params_to_pipeline(params, engine)
    batch_mb = microbatch({"input_ids": ids, "labels": labels}, M)

    # golden: per-microbatch CE sums / total weight + mean-over-mb aux
    from neuronx_distributed_tpu.parallel.losses import parallel_cross_entropy

    ce_sum, aux_sum = 0.0, 0.0
    for m in range(M):
        mb_ids = ids[m * (B // M) : (m + 1) * (B // M)]
        mb_lab = labels[m * (B // M) : (m + 1) * (B // M)]
        logits, aux = model.apply(params, mb_ids)
        ce_sum += float(parallel_cross_entropy(logits, mb_lab).sum())
        aux_sum += float(
            cfg.router_aux_loss_coef * aux["load_balancing_loss"]
            + cfg.router_z_loss_coef * aux["router_z_loss"]
        )
    want = ce_sum / float(labels.size) + aux_sum / M

    if schedule == "1f1b":
        loss, grads = jax.jit(engine.value_and_grad)(pp_params, batch_mb)
    else:
        loss, grads = jax.jit(jax.value_and_grad(engine.loss_fn))(pp_params, batch_mb)
    np.testing.assert_allclose(float(loss), want, rtol=1e-5)
    router_g = jax.tree_util.tree_flatten_with_path(grads)[0]
    router_leaves = [
        np.abs(np.asarray(v)).sum()
        for p, v in router_g
        if "router" in jax.tree_util.keystr(p)
    ]
    assert router_leaves and all(g > 0 for g in router_leaves)
