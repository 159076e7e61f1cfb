"""What a rematerialised layer keeps (``modules/remat.py``): a named save
changes no number and takes the matmuls it names out of the backward pass; a
name outside a policy changes no lowered program; Llama's policies go through
the same table."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from neuronx_distributed_tpu.inference.generate import serving_clones
from neuronx_distributed_tpu.kernels import backend
from neuronx_distributed_tpu.models.codegen import CodeGenForCausalLM, tiny_codegen
from neuronx_distributed_tpu.models.llama import LlamaForCausalLM, tiny_llama
from neuronx_distributed_tpu.modules import attention
from neuronx_distributed_tpu.modules.attention import ParallelMLP
from neuronx_distributed_tpu.modules.remat import ATTN_QKV, MLP_UP, remat_layer_cls, saved_by_name
from neuronx_distributed_tpu.parallel import mesh as mesh_lib
from neuronx_distributed_tpu.parallel.sharding import param_shardings

B, S = 2, 16
NOTHING = dict(remat=True, remat_policy=None)
NAMED = dict(remat=True, remat_policy="mlp_up")
DEFAULT = dict(remat=True)                     # CodeGenConfig's default: "mlp_up+attn"
POLICIES = {"no_remat": dict(remat=False), "save_nothing": NOTHING, "mlp_up": NAMED,
            "mlp_up+attn": DEFAULT}


def _ids():
    key = jax.random.PRNGKey(3)
    return (jax.random.randint(key, (B, S), 0, 256),
            jax.random.randint(jax.random.fold_in(key, 1), (B, S), 0, 256))


def _loss_and_grads(model, ids, labels):
    """Loss, gradients and the backward's ``dot_general`` count of ``model``
    on the mesh that is set up, from the same seeded weights."""
    boxed = jax.jit(model.init)(jax.random.PRNGKey(0), ids)
    params = jax.device_put(meta.unbox(boxed), param_shardings(boxed))

    def loss(p):
        return model.loss(p, ids, labels)

    value, grads = jax.jit(jax.value_and_grad(loss))(params)
    dots = str(jax.make_jaxpr(jax.grad(loss))(params)).count("dot_general")
    return np.asarray(value), jax.tree.map(np.asarray, grads), dots


@pytest.mark.parametrize("tp,sp", [(1, False), (4, True)], ids=["tp1", "tp4_sp"])
def test_codegen_named_saves_change_no_number_and_take_matmuls_out(tp, sp):
    assert tiny_codegen().remat_policy == "mlp_up+attn"
    mesh_lib.initialize_model_parallel(tensor_model_parallel_size=tp)
    ids, labels = _ids()
    got = {
        name: _loss_and_grads(
            CodeGenForCausalLM(tiny_codegen(sequence_parallel=sp, **over)), ids, labels)
        for name, over in POLICIES.items()
    }
    loss, grads, _ = got["save_nothing"]
    for other in ("mlp_up", "mlp_up+attn", "no_remat"):
        assert loss == got[other][0]
        for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(got[other][1])):
            np.testing.assert_array_equal(a, b)
    layers = tiny_codegen().num_layers
    dots = {name: run[2] for name, run in got.items()}
    assert dots["save_nothing"] - dots["mlp_up"] == layers          # the up-projection
    assert dots["mlp_up"] - dots["mlp_up+attn"] == 3 * layers       # q, k and v's projections
    assert dots["mlp_up+attn"] > dots["no_remat"]       # the softmax's product is still run again


def test_the_flash_forward_is_not_run_twice_under_the_default_policy(monkeypatch):
    """With the Pallas kernel (interpreted here) as the attention, the default
    policy keeps its outputs: one kernel call a layer fewer, the same numbers."""
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    mesh_lib.initialize_model_parallel(tensor_model_parallel_size=1, devices=jax.devices()[:1])
    key = jax.random.PRNGKey(3)
    ids = jax.random.randint(key, (B, 128), 0, 256)
    got = {}
    for name in ("save_nothing", "mlp_up", "mlp_up+attn"):
        model = CodeGenForCausalLM(tiny_codegen(max_seq_len=128, **POLICIES[name]))
        params = jax.jit(model.init)(jax.random.PRNGKey(0), ids)

        def loss(p):
            return model.loss(p, ids, ids)

        value, grads = jax.jit(jax.value_and_grad(loss))(params)
        kernels = str(jax.make_jaxpr(jax.grad(loss))(params)).count("pallas_call")
        got[name] = (np.asarray(value), jax.tree.map(np.asarray, grads), kernels)
    layers = tiny_codegen().num_layers
    assert got["save_nothing"][2] == got["mlp_up"][2] == 4 * layers    # forward, forward again, dq, dk + dv
    assert got["mlp_up+attn"][2] == 3 * layers
    for name in ("mlp_up", "mlp_up+attn"):
        assert got[name][0] == got["save_nothing"][0]
        for a, b in zip(jax.tree.leaves(got[name][1]), jax.tree.leaves(got["save_nothing"][1])):
            np.testing.assert_array_equal(a, b)


def _residuals(over):
    """What the backward pass keeps beside the arguments: ``[(shape, why)]``
    (the list ``jax.ad_checkpoint.print_saved_residuals`` prints)."""
    from jax._src.ad_checkpoint import saved_residuals

    ids, labels = _ids()
    model = CodeGenForCausalLM(tiny_codegen(**over))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)
    return [(aval.shape, why)
            for aval, why in saved_residuals(lambda p: model.loss(p, ids, labels), params)
            if "argument" not in why]


def test_the_named_tensor_is_kept_once_a_layer_and_no_other_matmul_output():
    cfg = tiny_codegen()
    kept = _residuals(NAMED)
    up = [why for shape, why in kept if shape == (B, S, cfg.intermediate_size) and "ParallelMLP" in why]
    assert len(up) == cfg.num_layers, kept
    assert not [why for _, why in kept if "dot_general" in why], kept
    # beside them: what "save nothing" keeps, the blocks' inputs and what lies outside the blocks
    nothing = _residuals(NOTHING)
    assert sorted(kept) == sorted(nothing + [r for r in kept if r[1] in up])
    # the default keeps q, k and v beside it, each once a layer, and still no matmul's own output
    more = _residuals(DEFAULT)
    heads = (B, S, cfg.num_heads, cfg.head_dim_)
    qkv = [r for r in more if r[0] == heads and "ParallelSelfAttention" in r[1]]
    assert len(qkv) == 3 * cfg.num_layers, more
    assert not [why for _, why in more if "dot_general" in why], more
    assert sorted(more) == sorted(kept + qkv)


def test_remat_policy_none_still_saves_nothing():
    cfg = tiny_codegen()
    assert not [why for _, why in _residuals(NOTHING) if "ParallelMLP" in why or "ParallelSelfAttention" in why]
    ids, labels = _ids()
    up = cfg.num_layers * B * S * cfg.intermediate_size * 4
    qkv = cfg.num_layers * 3 * B * S * cfg.hidden_size * 4
    for over, want in ((NOTHING, {}), (dict(remat=False), {}), (NAMED, {MLP_UP: up}),
                       (DEFAULT, {MLP_UP: up, ATTN_QKV: qkv})):
        model = CodeGenForCausalLM(tiny_codegen(**over))
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)
        assert saved_by_name(lambda p: model.loss(p, ids, labels), params) == want


def _lowered_texts():
    """The lowered text of CodeGen's prefill and decode programs and of a
    gated ``ParallelMLP``, as the tree has them."""
    model = CodeGenForCausalLM(tiny_codegen())
    ids, mask = jnp.zeros((1, 32), jnp.int32), jnp.ones((1, 32), bool)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)
    pre, dec = serving_clones(model)

    def prefill(p, i, m):
        return pre.apply(p, i, padding_mask=m, mutable=["cache"])

    cache = jax.eval_shape(lambda p, i, m: prefill(p, i, m)[1]["cache"], params, ids, mask)

    def decode(p, c, tok):
        return dec.apply({**p, "cache": c}, tok, mutable=["cache"])

    mlp = ParallelMLP(hidden_size=64, intermediate_size=256, activation="silu", use_bias=False, glu=True)
    x = jnp.zeros((2, 8, 64), jnp.float32)
    mlp_params = jax.eval_shape(mlp.init, jax.random.PRNGKey(0), x)
    return {
        "codegen.prefill": jax.jit(prefill).lower(params, ids, mask).as_text(),
        "codegen.decode": jax.jit(decode).lower(params, cache, ids[:, :1]).as_text(),
        "parallel_mlp.glu": jax.jit(mlp.apply).lower(mlp_params, x).as_text(),
    }


@pytest.fixture(scope="module")
def lowered_pairs():
    with_name = _lowered_texts()
    assert "name" in str(jax.make_jaxpr(     # the name IS in the traced program
        lambda x: ParallelMLP(hidden_size=8, intermediate_size=16).init(jax.random.PRNGKey(0), x))(
            jnp.zeros((1, 8))))
    with mock.patch.object(attention, "checkpoint_name", lambda x, name: x):
        return with_name, _lowered_texts()


@pytest.mark.parametrize("program", ["codegen.prefill", "codegen.decode", "parallel_mlp.glu"])
def test_a_name_outside_a_policy_changes_no_lowered_program(lowered_pairs, program):
    with_name, without = lowered_pairs
    assert "dot_general" in with_name[program]
    assert with_name[program] == without[program]


@pytest.mark.parametrize("scan", [False, True], ids=["loop", "scan"])
@pytest.mark.parametrize("policy", [None, "dots", "dots_saveable"])
def test_llama_policies_through_the_shared_table(policy, scan):
    """Each of Llama's policies is the ``jax.checkpoint_policies`` entry it
    always named, now from ``modules/remat.py``: the same numbers as no remat,
    and a backward that runs fewer matmuls again the more it saves."""
    ids, labels = _ids()
    mesh_lib.initialize_model_parallel(tensor_model_parallel_size=1)

    def run(**over):
        model = LlamaForCausalLM(tiny_llama(scan_layers=scan, **over), attention_impl="xla")
        boxed = jax.jit(model.init)(jax.random.PRNGKey(0), ids)
        params = meta.unbox(boxed)

        def loss(p):
            return model.loss(p, ids, labels)

        value, grads = jax.jit(jax.value_and_grad(loss))(params)
        return np.asarray(value), grads, str(jax.make_jaxpr(jax.grad(loss))(params)).count("dot_general")

    loss, grads, dots = run(remat=True, remat_policy=policy)
    plain_loss, plain_grads, plain_dots = run(remat=False)
    np.testing.assert_allclose(loss, plain_loss, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(plain_grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-7)
    nothing_dots = run(remat=True, remat_policy=None)[2]
    assert (dots == nothing_dots) if policy is None else (plain_dots <= dots < nothing_dots)


def test_the_table_refuses_a_policy_it_does_not_hold():
    assert remat_layer_cls(ParallelMLP, False, "no such policy") is ParallelMLP
    with pytest.raises(KeyError):
        remat_layer_cls(ParallelMLP, True, "no such policy")
