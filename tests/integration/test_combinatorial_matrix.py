"""Combinatorial parallelism matrix (reference:
``test/integration/combinatorial_tests/`` — the config-driven
TP×SP×PP×ZeRO1 sweep over a tiny-depth Llama, extended here with the CP, EP
and interleaved-PP axes the TPU stack adds).

The invariant swept is stronger than "it runs": with identical params and
data, the FIRST train-step loss must equal the unsharded baseline's for every
layout — parallelism is a layout change, never a math change. (The round-2
blockwise-EP regression at ep=2/tp=1 would have failed exactly this.)

Wall-time budget: one tiny model + one step per combo; the whole matrix must
stay under ~5 min on the 8-device CPU mesh (VERDICT round-2 item #10).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from neuronx_distributed_tpu.models.llama import LlamaForCausalLM, tiny_llama
from neuronx_distributed_tpu.models.mixtral import (
    MixtralForCausalLM,
    tiny_mixtral,
)
from neuronx_distributed_tpu.parallel import mesh as mesh_lib
from neuronx_distributed_tpu.parallel.losses import parallel_cross_entropy
from neuronx_distributed_tpu.trainer import (
    OptimizerConfig,
    build_train_step,
    create_train_state,
    make_optimizer,
    shard_batch,
)

B, S = 8, 32


def _llama_cfg(**over):
    return tiny_llama(max_seq_len=S, **over)


def _llama_data():
    ids = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, 256)
    return {"input_ids": ids, "labels": jnp.roll(ids, -1, 1)}


def _llama_baseline(llama_data):
    """Unsharded golden: params + first-step loss."""
    mesh_lib.destroy_model_parallel()
    cfg = _llama_cfg(scan_layers=True)
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    params = meta.unbox(jax.jit(model.init)(jax.random.PRNGKey(0),
                                            llama_data["input_ids"]))

    def loss_fn(p):
        logits = model.apply(p, llama_data["input_ids"])
        return parallel_cross_entropy(logits, llama_data["labels"]).mean()

    loss = float(jax.jit(loss_fn)(params))
    # host copy: device_put aliases matching-sharding buffers, and the donated
    # train step would delete them out from under the next combo
    return jax.device_get(params), loss


@pytest.fixture(scope="module")
def llama_data():
    return _llama_data()


@pytest.fixture(scope="module")
def llama_baseline(llama_data):
    """Computed once per module."""
    return _llama_baseline(llama_data)


# (tp, sp, pp, zero1, cp, schedule)
LLAMA_MATRIX = [
    (2, False, 1, False, 1, None),
    (2, True, 1, True, 1, None),
    (4, True, 1, False, 1, None),
    (4, False, 1, True, 1, None),
    (1, False, 2, False, 1, "gpipe"),
    (2, True, 2, True, 1, "1f1b"),
    (2, False, 2, True, 1, "interleaved"),
    (1, False, 4, True, 1, "1f1b"),
    (2, False, 1, True, 2, None),  # cp: ring-attention training path
]


def _llama_matrix_in_a_child(*case):
    data = _llama_data()
    test_llama_matrix(data, _llama_baseline(data), *case, in_child_process=None)


@pytest.mark.parametrize("tp,sp,pp,zero1,cp,schedule", LLAMA_MATRIX)
def test_llama_matrix(llama_data, llama_baseline, tp, sp, pp, zero1, cp, schedule, in_child_process):
    if (tp, pp, cp, schedule) == (2, 2, 1, "1f1b") and in_child_process:
        # two independent collectives in flight on the 8-device mesh: see conftest.in_child_process
        return in_child_process(__file__, "_llama_matrix_in_a_child", tp, sp, pp, zero1, cp, schedule)
    base_params, base_loss = llama_baseline
    mesh_lib.destroy_model_parallel()
    mesh_lib.initialize_model_parallel(
        tensor_model_parallel_size=tp,
        pipeline_model_parallel_size=pp,
        context_parallel_size=cp,
    )
    cfg = _llama_cfg(scan_layers=True, sequence_parallel=sp)
    impl = "auto" if cp > 1 else "xla"
    model = LlamaForCausalLM(cfg, attention_impl=impl)
    optimizer = make_optimizer(OptimizerConfig(zero1=zero1))

    if pp > 1:
        from neuronx_distributed_tpu.pipeline.llama import (
            LlamaPipelineAdapter,
            llama_params_to_pipeline,
        )

        # per-microbatch rows must divide dp; M=4 when it fits, else fewer
        dp = mesh_lib.get_data_parallel_size()
        M = min(4, max(1, B // dp))
        adapter = LlamaPipelineAdapter(
            config=cfg, num_microbatches=M, attention_impl=impl,
            schedule=schedule, num_chunks=2 if schedule == "interleaved" else 1,
        )
        state, step, engine = adapter.build_state_and_step(
            model, optimizer, jax.random.PRNGKey(0), llama_data["input_ids"],
            zero1=zero1,
        )
        # same params as the baseline, re-laid-out
        state = state.replace(
            params=jax.device_put(
                llama_params_to_pipeline({"params": base_params["params"]}, engine),
                jax.tree.map(lambda x: x.sharding, state.params),
            )
        )
        batch = adapter.prepare_batch(llama_data)
    else:
        state, p_sh, s_sh = create_train_state(
            model, optimizer, jax.random.PRNGKey(0), llama_data["input_ids"],
            zero1=zero1,
        )
        state = state.replace(params=jax.device_put(base_params, p_sh))
        step = build_train_step(model, optimizer, p_sh, s_sh)
        batch = shard_batch(llama_data)

    state, metrics = step(state, batch)
    np.testing.assert_allclose(float(metrics["loss"]), base_loss, rtol=2e-4)
    assert float(metrics["grad_norm"]) > 0


# --- MoE: the EP axis (incl. the ep>1/tp=1 blockwise case that regressed) ----

MIXTRAL_MATRIX = [
    ("blockwise", 2, 1, True),
    ("blockwise", 2, 2, False),
    ("capacity_factor", 2, 2, True),
    ("all_experts", 4, 1, False),
]


@pytest.fixture(scope="module")
def mixtral_data():
    ids = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0, 256)
    return {"input_ids": ids, "labels": jnp.roll(ids, -1, 1)}


@pytest.fixture(scope="module")
def mixtral_baseline(mixtral_data):
    mesh_lib.destroy_model_parallel()
    out = {}
    for strategy in {s for s, *_ in MIXTRAL_MATRIX}:
        cfg = tiny_mixtral(
            max_seq_len=S, expert_strategy=strategy,
            capacity_factor=4.0 if strategy == "capacity_factor" else None,
        )
        model = MixtralForCausalLM(cfg, attention_impl="xla")
        params = meta.unbox(
            jax.jit(model.init)(jax.random.PRNGKey(0), mixtral_data["input_ids"])
        )
        loss = float(
            jax.jit(lambda p, m=model: m.loss(
                p, mixtral_data["input_ids"], mixtral_data["labels"]
            ))(params)
        )
        out[strategy] = (jax.device_get(params), loss)  # see llama_baseline
    return out


@pytest.mark.parametrize("strategy,ep,tp,zero1", MIXTRAL_MATRIX)
def test_mixtral_matrix(mixtral_data, mixtral_baseline, strategy, ep, tp, zero1):
    base_params, base_loss = mixtral_baseline[strategy]
    mesh_lib.destroy_model_parallel()
    mesh_lib.initialize_model_parallel(
        tensor_model_parallel_size=tp, expert_model_parallel_size=ep
    )
    cfg = tiny_mixtral(
        max_seq_len=S, expert_strategy=strategy,
        capacity_factor=4.0 if strategy == "capacity_factor" else None,
    )
    model = MixtralForCausalLM(cfg, attention_impl="xla")
    optimizer = make_optimizer(OptimizerConfig(zero1=zero1))

    def loss_fn(p, batch):
        return model.loss(p, batch["input_ids"], batch["labels"])

    state, p_sh, s_sh = create_train_state(
        model, optimizer, jax.random.PRNGKey(0), mixtral_data["input_ids"],
        zero1=zero1,
    )
    state = state.replace(params=jax.device_put(base_params, p_sh))
    step = build_train_step(model, optimizer, p_sh, s_sh, loss_fn=loss_fn)
    state, metrics = step(state, shard_batch(mixtral_data))
    np.testing.assert_allclose(float(metrics["loss"]), base_loss, rtol=2e-4)
    assert float(metrics["grad_norm"]) > 0


# --- round-5 widening (VERDICT r4 next #8): joint cp×pp, interleaved C=4,
# --- packed segments, quantized serving, LoRA, dcn-hybrid layout -------------

LLAMA_MATRIX_R5 = [
    # joint cp × pp (ring attention inside pipeline stages)
    (2, False, 2, True, 2, "1f1b"),
    (1, False, 2, False, 2, "gpipe"),
]


@pytest.mark.parametrize("tp,sp,pp,zero1,cp,schedule", LLAMA_MATRIX_R5)
def test_llama_matrix_r5(llama_data, llama_baseline, tp, sp, pp, zero1, cp,
                         schedule):
    test_llama_matrix(llama_data, llama_baseline, tp, sp, pp, zero1, cp, schedule, in_child_process=None)


def test_llama_interleaved_c4(llama_data):
    """Interleaved virtual-pipeline at C=4 (8 layers, pp=2 → 8 virtual
    stages of one layer): first-step loss equals the unsharded baseline."""
    from neuronx_distributed_tpu.pipeline.llama import (
        LlamaPipelineAdapter,
        llama_params_to_pipeline,
    )

    mesh_lib.destroy_model_parallel()
    cfg = _llama_cfg(scan_layers=True, num_layers=8)
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    params = meta.unbox(jax.jit(model.init)(jax.random.PRNGKey(0),
                                            llama_data["input_ids"]))

    def loss_fn(p):
        logits = model.apply(p, llama_data["input_ids"])
        return parallel_cross_entropy(logits, llama_data["labels"]).mean()

    base_loss = float(jax.jit(loss_fn)(params))
    base_params = jax.device_get(params)

    mesh_lib.initialize_model_parallel(
        tensor_model_parallel_size=1, pipeline_model_parallel_size=2
    )
    dp = mesh_lib.get_data_parallel_size()
    M = min(4, max(1, B // dp))
    adapter = LlamaPipelineAdapter(
        config=cfg, num_microbatches=M, attention_impl="xla",
        schedule="interleaved", num_chunks=4,
    )
    optimizer = make_optimizer(OptimizerConfig(zero1=True))
    state, step, engine = adapter.build_state_and_step(
        model, optimizer, jax.random.PRNGKey(0), llama_data["input_ids"],
        zero1=True,
    )
    state = state.replace(
        params=jax.device_put(
            llama_params_to_pipeline({"params": base_params["params"]}, engine),
            jax.tree.map(lambda x: x.sharding, state.params),
        )
    )
    state, metrics = step(state, adapter.prepare_batch(llama_data))
    np.testing.assert_allclose(float(metrics["loss"]), base_loss, rtol=2e-4)


def test_packed_segments_row(llama_data, llama_baseline):
    """Packed-document training (segment_ids + per-doc positions + boundary
    loss mask) is layout-invariant: tp=4+sp loss equals unsharded."""
    from neuronx_distributed_tpu.trainer.trainer import default_loss_fn

    base_params, _ = llama_baseline
    seg = np.zeros((B, S), np.int32)
    seg[:, S // 2:] = 1  # two documents per row
    batch = {
        **llama_data,
        "segment_ids": jnp.asarray(seg),
        "loss_mask": jnp.asarray(
            (seg[:, :] == np.roll(seg, -1, 1)).astype(np.float32)
        ),
    }
    mesh_lib.destroy_model_parallel()
    cfg = _llama_cfg(scan_layers=True)
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    golden = float(default_loss_fn(model, base_params, batch))

    mesh_lib.initialize_model_parallel(tensor_model_parallel_size=4)
    cfg_sp = _llama_cfg(scan_layers=True, sequence_parallel=True)
    model_sp = LlamaForCausalLM(cfg_sp, attention_impl="xla")
    optimizer = make_optimizer(OptimizerConfig(zero1=True))
    state, p_sh, s_sh = create_train_state(
        model_sp, optimizer, jax.random.PRNGKey(0), batch["input_ids"],
        zero1=True,
    )
    state = state.replace(params=jax.device_put(base_params, p_sh))
    step = build_train_step(model_sp, optimizer, p_sh, s_sh)
    state, metrics = step(state, shard_batch(batch))
    np.testing.assert_allclose(float(metrics["loss"]), golden, rtol=2e-4)


# --- quantized serving rows: same quantized tree, every layout, identical
# --- logits ------------------------------------------------------------------

QUANT_MATRIX = [
    ("int8", False, 2),
    ("int8", True, 2),   # native int8 MXU matmul path
    ("f8e4m3", False, 4),
]


@pytest.mark.parametrize("qdtype,int8_mxu,tp", QUANT_MATRIX)
def test_quantized_serving_matrix(llama_data, qdtype, int8_mxu, tp):
    from neuronx_distributed_tpu.quantization.config import (
        QuantizationConfig,
        QuantizedDtype,
    )
    from neuronx_distributed_tpu.quantization.utils import quantize_param_tree

    mesh_lib.destroy_model_parallel()
    qcfg = QuantizationConfig(
        quantized_dtype=QuantizedDtype(qdtype), use_int8_matmul=int8_mxu
    )
    cfg = _llama_cfg(scan_layers=False)
    fmodel = LlamaForCausalLM(cfg, attention_impl="xla")
    fparams = meta.unbox(
        jax.jit(fmodel.init)(jax.random.PRNGKey(0), llama_data["input_ids"])
    )
    qparams = quantize_param_tree(fparams, qcfg)
    qmodel = LlamaForCausalLM(
        dataclasses.replace(cfg, quantization=qcfg), attention_impl="xla"
    )
    golden = np.asarray(
        qmodel.apply(qparams, llama_data["input_ids"]), np.float32
    )
    mesh_lib.initialize_model_parallel(tensor_model_parallel_size=tp)
    sharded = np.asarray(
        jax.jit(lambda p, i: qmodel.apply(p, i))(qparams, llama_data["input_ids"]),
        np.float32,
    )
    np.testing.assert_allclose(sharded, golden, atol=2e-4)


LORA_MATRIX = [(2, False), (2, True), (4, False)]


@pytest.mark.parametrize("tp,sp", LORA_MATRIX)
def test_lora_matrix(llama_data, llama_baseline, tp, sp):
    """Adapter-only training is layout-invariant: the LoRA loss (frozen base
    + merged adapters) at tp/sp equals the unsharded LoRA loss."""
    from neuronx_distributed_tpu.modules.lora import (
        LoraConfig,
        init_lora_params,
        lora_train_loss_fn,
    )

    base_params, _ = llama_baseline
    lcfg = LoraConfig(r=4)
    mesh_lib.destroy_model_parallel()
    cfg = _llama_cfg(scan_layers=True)
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    lora = init_lora_params(base_params, lcfg, jax.random.PRNGKey(7))
    # make B nonzero so the adapters actually contribute
    lora = jax.tree.map(lambda x: x + 0.01, lora)

    def base_loss(p, batch):
        logits = model.apply(p, batch["input_ids"])
        return parallel_cross_entropy(logits, batch["labels"]).mean()

    loss_fn = lora_train_loss_fn(base_params, lcfg, base_loss)
    golden = float(jax.jit(loss_fn)(lora, llama_data))

    mesh_lib.initialize_model_parallel(tensor_model_parallel_size=tp)
    cfg_s = _llama_cfg(scan_layers=True, sequence_parallel=sp)
    model_s = LlamaForCausalLM(cfg_s, attention_impl="xla")

    def base_loss_s(p, batch):
        logits = model_s.apply(p, batch["input_ids"])
        return parallel_cross_entropy(logits, batch["labels"]).mean()

    loss_fn_s = lora_train_loss_fn(base_params, lcfg, base_loss_s)
    grad_fn = jax.jit(jax.value_and_grad(loss_fn_s))
    v, g = grad_fn(lora, llama_data)
    np.testing.assert_allclose(float(v), golden, rtol=2e-4)
    # adapter-only grads exist and are finite
    leaves = jax.tree.leaves(g)
    assert leaves and all(bool(jnp.isfinite(x).all()) for x in leaves)


def test_dcn_hybrid_grid_layout(llama_data):
    """The dcn-hybrid mesh keeps the DCN-crossing axis OUTERMOST (only DP
    traffic crosses the slow links): with dcn_data_parallel_size=2 on 8
    devices, the edp axis's device blocks partition into the two 'slices'
    (contiguous halves of the virtual device list, which is how
    create_hybrid_device_mesh lays out slices)."""
    mesh_lib.destroy_model_parallel()
    mesh_lib.initialize_model_parallel(
        tensor_model_parallel_size=2,
        dcn_data_parallel_size=2,
    )
    try:
        mesh = mesh_lib.get_mesh()
        devs = np.asarray(mesh.devices)
        # axes (pp, edp, ep, cp, tp) → edp is dim 1
        assert mesh.shape[mesh_lib.EDP_AXIS] == 4
        ids = np.vectorize(lambda d: d.id)(devs)
        edp_axis = list(mesh.axis_names).index(mesh_lib.EDP_AXIS)
        moved = np.moveaxis(ids, edp_axis, 0).reshape(4, -1)
        # the first two edp groups must live entirely in slice 0 (ids 0-3)
        # and the last two in slice 1 (ids 4-7): DP is the DCN axis
        slice_of = moved // 4
        for row in slice_of:
            assert (row == row[0]).all(), (
                f"edp group spans slices: {moved.tolist()}"
            )
        # and a dp-axis collective still compiles + runs on this grid
        x = shard_batch(llama_data)["input_ids"]
        total = int(jax.jit(lambda a: a.sum())(x))
        assert total == int(np.asarray(llama_data["input_ids"]).sum())
    finally:
        mesh_lib.destroy_model_parallel()
