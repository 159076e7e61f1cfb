"""What is particular to the latent (MLA) cache kind on the serving path (the
contract it shares with the other kinds: ``test_cache_kinds.py``): the logit
check against the plain reference's full forward fails on a lower-precision
latent; an int8 latent pool; a K/V cache reads its own bytes; the other
models' programs unchanged."""

import hashlib
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference.generate import chunked_decode_step, serving_clones
from neuronx_distributed_tpu.models.deepseek_v2 import DeepseekV2ForCausalLM, tiny_deepseek_v2
from neuronx_distributed_tpu.modules.attention import PAGED_LEAVES, cache_bytes_per_token_layer
from neuronx_distributed_tpu.quantization import QuantConfig
from neuronx_distributed_tpu.serving import PagedCacheManager
from tests.models.jitted import through_the_cache
from tests.serving.test_cache_kinds import TOLERANCE, built, serve

PS = 16


@pytest.fixture(scope="module")
def setup():
    return tuple(built("latent"))


def _decode_logits(model, params, ids, split, latent_dtype=None):
    """Prefill ``ids[:, :split]``, then decode the rest a token at a time
    through the row cache; ``latent_dtype`` rounds the cached latent and
    rotated key to that type first (a lower-precision cache)."""
    prefill, decode = serving_clones(model)
    _, cache = through_the_cache(prefill, params, ids[:, :split])
    if latent_dtype is not None:
        cache = jax.tree_util.tree_map_with_path(
            lambda path, a: a.astype(latent_dtype).astype(a.dtype) if path[-1].key in PAGED_LEAVES else a,
            cache)
    rows = []
    for t in range(split, ids.shape[1]):
        (logits, _), cache = through_the_cache(decode, {**params, "cache": cache}, ids[:, t:t + 1])
        rows.append(logits[:, 0])
    return jnp.stack(rows, axis=1)


def test_the_logit_check_fails_on_a_lower_precision_latent(setup):
    """Decode logits against the reference's full forward: within the
    tolerance as computed, far outside it when the cached latent is first
    rounded to bfloat16, the nearest precision below this configuration's
    float32 (and further with an fp8 latent)."""
    cfg, model, params, _, ref = setup
    ids = jax.random.randint(jax.random.PRNGKey(5), (1, 48), 1, cfg.vocab_size)
    want = np.asarray(ref.logits(np.asarray(ids)))[:, 40:]
    gap = lambda dtype: float(np.abs(np.asarray(_decode_logits(model, params, ids, 40, dtype)) - want).max())  # noqa: E731
    assert gap(None) <= TOLERANCE
    assert gap(jnp.bfloat16) > 10 * TOLERANCE
    assert gap(jnp.float8_e4m3fn) > gap(jnp.bfloat16)


def test_an_int8_latent_pool_serves_through_the_gather_transport(setup):
    """The quantized pool's walkers handle the latent leaves as they handle
    k/v (a scale sibling each); its stream stays the reference's at these
    margins, which is why the precision check above is made in logits."""
    _, model, params, prompts, ref = setup
    eng, toks = serve(model, params, prompts, kv_page_size=PS,
                      quantize=QuantConfig(weights=None, kv="int8"))
    names = {p[-1].key for p, _ in jax.tree_util.tree_flatten_with_path(eng.cache.cache["pool"])[0]}
    assert {"k", "k_scale", "k_pe", "k_pe_scale"} <= names and "v" not in names
    assert all(len(t) == 12 for t in toks)
    assert cache_bytes_per_token_layer(eng.cache.cache) == (32 + 8) * 1 + 2 * 4 / PS


def test_a_kv_cache_reads_its_own_bytes():
    from neuronx_distributed_tpu.models.llama import LlamaForCausalLM, tiny_llama

    cfg = tiny_llama(num_layers=2, hidden_size=32, num_heads=4, num_kv_heads=2)
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    ids = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)
    _, state = model.clone(mode="prefill").apply(params, ids, mutable=["cache"])
    assert cache_bytes_per_token_layer(state["cache"]) == 2 * 2 * 8 * 4   # k and v, 2 heads of 8, f32


# --- the other models' programs ------------------------------------------------------

# sha256 of the jaxpr text (addresses masked) of tiny Mixtral's and CodeGen's
# prefill and paged decode chunk, both transports, taken on the PARENT commit
# of the PR that added the latent cache (4823593): what that PR generalised
# (KVCache's leaves, the pool pairing, the fused decode, the flash kernel's
# value head size) must leave these programs exactly as they were. The two
# ``decode.fused`` digests were taken anew by the PR that made the fused chunk
# gather its write window instead of the logical view (PR 27); that PR left
# the other four, prefill and the ``gather`` chunk, as they are. Mixtral's two
# ``decode`` digests were taken anew by the PR that made the expert layers sow
# ``hit_experts`` / ``routed_rows`` into a decode chunk (PR 33: the model names
# ``chunk_stats``, so the chunk sums them and has a seventh output); that PR
# left ``mixtral.prefill`` and CodeGen's three as they are. All four ``decode``
# digests were taken anew by the PR that put the chunk's sampler behind one
# conditional on "some emitting row samples" (PR 36: ``sample_per_row`` takes
# ``kept=~done`` and branches round the sort); that PR left both ``prefill``
# digests as they are (a prefill samples nothing). Both ``prefill`` digests were
# taken anew by the PR that applies a prefill's head to the last position alone
# in every model (PR 49: one slice in front of the head, logits of one row);
# that PR left all four ``decode`` digests as they are. ``mixtral.prefill`` was
# taken anew by the PR that hands a prefill's ``padding_mask`` to its expert
# layers as their row mask (PR 55: the padded rows' slots sort last and get no
# expert); that PR left Mixtral's two ``decode`` digests (a decode step passes
# no mask) and CodeGen's three (no expert layer) as they are.
PARENT_PROGRAMS = {
    "mixtral.prefill": "940c6b4d2de17d46dda5c3a62c0dea9252a021a744badb942132a5ee722a9c18",
    "mixtral.decode.gather": "3dd48b7bb2d393f00deb7568a1c77dc997d3743775d913a32f7f90d29d1f5f83",
    "mixtral.decode.fused": "3d166644826da274dc5f22bb35072967bd480d62c1aeb3903b898c56b6e9feb0",
    "codegen.prefill": "0b3585bbf36fd58d4a6ad7a7b57a066ce3bcbbacb5b96577efd67747f619d91b",
    "codegen.decode.gather": "26031da023b7a3167fd7da7206124f5efad09ea86c03cde92890e6da20ec99dd",
    "codegen.decode.fused": "414b5c2ac9b6016838e6863ceecf99ba70debfc0aefabf9d6c1270a2152ce969",
}


def _model_program_texts(name, model):
    """``{name.prefill, name.decode.gather, name.decode.fused}``: the jaxpr
    text of a tiny model's prefill and paged decode chunk, both transports.

    Taken with ``checkpoint_name`` as the identity it lowers to: PR 40 named
    ``ParallelMLP``'s up-projection output for the trainer's remat policy, a
    ``name`` equation in every jaxpr that holds a dense MLP and nothing in the
    lowered program (``tests/models/test_remat_policy.py`` holds the lowered
    texts equal), so the digests below still say "everything else is the
    parent's"."""
    from unittest import mock

    from neuronx_distributed_tpu.modules import attention

    with mock.patch.object(attention, "checkpoint_name", lambda x, name: x):
        return _program_texts_of(name, model)


def _program_texts_of(name, model):
    out = {}
    length = model.config.max_seq_len
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    pre, dec = serving_clones(model)
    ids, mask = jnp.zeros((1, 32), jnp.int32), jnp.ones((1, 32), bool)

    def prefill(p, i, m):
        return pre.apply(p, i, padding_mask=m, mutable=["cache"])

    out[f"{name}.prefill"] = str(jax.make_jaxpr(prefill)(params, ids, mask))
    row = jax.eval_shape(lambda p, i, m: prefill(p, i, m)[1]["cache"], params, ids, mask)

    def pool_of(row):
        mgr = PagedCacheManager(4, length, 16)
        mgr.allocate_from(row)
        return mgr.cache

    paged = jax.eval_shape(pool_of, row)
    state = dict(
        tok=jnp.zeros((4,), jnp.int32), keys=jnp.zeros((4, 2), jnp.uint32),
        active=jnp.ones((4,), bool), remaining=jnp.full((4,), 5, jnp.int32),
        temp=jnp.zeros((4,), jnp.float32), topk=jnp.zeros((4,), jnp.int32),
        topp=jnp.ones((4,), jnp.float32), eos=jnp.full((4,), -1, jnp.int32))
    for mode in ("gather", "fused"):
        fn = chunked_decode_step(dec, 4, length, page_size=16, paged_attention=mode)
        out[f"{name}.decode.{mode}"] = str(jax.make_jaxpr(fn)(params, paged, state))
    return out


def _digest(text):
    return hashlib.sha256(re.sub(r"0x[0-9a-f]+", "0x", text).encode()).hexdigest()


def _program_texts():
    from neuronx_distributed_tpu.models.codegen import CodeGenForCausalLM, tiny_codegen
    from neuronx_distributed_tpu.models.mixtral import MixtralForCausalLM, tiny_mixtral

    return {
        **_model_program_texts("mixtral", MixtralForCausalLM(tiny_mixtral(), attention_impl="xla")),
        **_model_program_texts("codegen", CodeGenForCausalLM(tiny_codegen())),
    }


@pytest.mark.parametrize("program", list(PARENT_PROGRAMS))
def test_mixtral_and_codegen_programs_are_the_parents(program):
    if not hasattr(test_mixtral_and_codegen_programs_are_the_parents, "texts"):
        test_mixtral_and_codegen_programs_are_the_parents.texts = _program_texts()
    text = test_mixtral_and_codegen_programs_are_the_parents.texts[program]
    digest = hashlib.sha256(re.sub(r"0x[0-9a-f]+", "0x", text).encode()).hexdigest()
    assert digest == PARENT_PROGRAMS[program], json.dumps({program: digest})


# sha256 of the same three programs of tiny DeepSeek-V2 (this file's model),
# taken on the PARENT commit (07a904c) of the PR that joined K and V into one
# leaf of the INDEXED cache (PR 31): a new name in ``PAGED_LEAVES`` and a new
# sparse decode kernel must leave the programs of a model with no such leaf
# exactly as they were. The two ``decode`` digests: anew with PR 33's counters,
# as Mixtral's above, and anew again with PR 36's sampler branch;
# ``deepseek.prefill``: anew with PR 49's head on the last position alone, as
# Mixtral's and CodeGen's above; that PR left the two ``decode`` digests.
# ``deepseek.decode.fused``: anew with PR 52's latent decode kernel, which
# fetches a run of adjacent pages with one copy (the kernel is in the program;
# ``gather`` and the prefill, which hold no such kernel, are as they were).
# ``deepseek.prefill``: anew with PR 55's row mask, as Mixtral's above; that PR
# left the two ``decode`` digests.
DEEPSEEK_PARENT_PROGRAMS = {
    "deepseek.prefill": "5997180a69883294c11f38add31c8780e4b8669c7052ba10c23694ddb52b7c11",
    "deepseek.decode.gather": "277cce6135272958e7f21a7520375984ab6fcf96160e46688f9604f6fdadcc09",
    "deepseek.decode.fused": "8950beef88173af760551c358b5977d44e8cede7d6f51d6a9e10362c167d26a9",
}


@pytest.fixture(scope="module")
def deepseek_program_texts():
    return _model_program_texts("deepseek", DeepseekV2ForCausalLM(tiny_deepseek_v2(), attention_impl="xla"))


@pytest.mark.parametrize("program", list(DEEPSEEK_PARENT_PROGRAMS))
def test_deepseek_programs_are_the_parents(deepseek_program_texts, program):
    digest = _digest(deepseek_program_texts[program])
    assert digest == DEEPSEEK_PARENT_PROGRAMS[program], json.dumps({program: digest})
