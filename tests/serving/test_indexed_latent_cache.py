"""What is particular to the indexed LATENT cache kind (a token's latent and
rotated key joined in one leaf, and one index key) on the serving path (the
contract it shares with the other kinds: ``test_cache_kinds.py``): the rows
the held experts computed read back with each chunk; per-page fingerprints
and ``reset_cache_slot``; and the programs of the model with the OTHER
indexed cache unchanged."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import GenerationConfig
from neuronx_distributed_tpu.inference.generate import serving_clones
from neuronx_distributed_tpu.models.glm_moe_dsa import GlmMoeDsaForCausalLM, tiny_glm_moe_dsa
from neuronx_distributed_tpu.modules.attention import PAGED_LEAVES, reset_cache_slot
from neuronx_distributed_tpu.serving import ServingEngine
from tests.serving.test_cache_kinds import built, serve
from tests.serving.test_latent_cache import _digest, _model_program_texts

PS = 16


@pytest.fixture(scope="module")
def setup():
    return tuple(built("indexed_latent"))


def test_the_chunk_reads_back_the_rows_its_held_experts_computed(setup):
    """``held_rows`` / ``routed_rows`` on ``nxd.step.decode.readback``: one
    pair of int32 a chunk, summed on the device over the chunk's steps and
    the two sparse layers; the served model and no other has them."""
    cfg, model, params, prompts, _ = setup
    eng = ServingEngine(model, params, decode_chunk_size=4, num_slots=2, kv_page_size=PS, prefix_cache=None)
    fn = eng._nonspec_chunk()
    # budgets past the chunk the admitting step reads back and the one it may have called ahead
    gcfg = GenerationConfig(max_new_tokens=16, temperature=0.0)
    for i, p in enumerate(prompts[:2]):
        eng.submit(p, gcfg, key=jax.random.PRNGKey(i))
    while eng.has_work and not any(eng._active):
        eng.step()
    out = fn(eng._params, eng.cache.take(), eng._state)
    assert len(out) == 7                                              # six, and the model's own counters
    held, routed = (int(v) for v in out[6])
    steps = int(out[4])
    sparse_layers = cfg.num_layers - cfg.first_k_dense
    assert routed == steps * sparse_layers * 2 * cfg.top_k            # slots x top-k a layer a step
    assert 0 < held < routed
    assert eng._decode_model.chunk_stats == ("held_rows", "routed_rows")
    whole = GlmMoeDsaForCausalLM(dataclasses.replace(cfg, held_experts=None), attention_impl="xla")
    assert serving_clones(whole)[1].chunk_stats == ()


def test_page_fingerprints_see_both_leaves_and_a_freed_slot_attends_nothing(setup):
    from neuronx_distributed_tpu.utils.fingerprint import cache_fingerprint

    _, model, params, prompts, _ = setup
    eng, _ = serve(model, params, prompts[:2], new_tokens=4, kv_page_size=PS)
    pool = eng.cache.cache["pool"]
    base = np.asarray(cache_fingerprint(pool))
    for name in ("kv", "k_idx"):
        def flip(path, leaf, name=name):
            return leaf.at[1, 0, 0, 0].add(1.0) if path[-1].key == name and "layers_0" in str(path) else leaf
        changed = np.asarray(cache_fingerprint(jax.tree_util.tree_map_with_path(flip, pool)))
        assert not np.array_equal(changed, base), name
    # reset_cache_slot on a row collection of this kind: validity cleared, storage left
    _, state = model.clone(mode="prefill").apply(params, jnp.asarray(prompts[1][None, :32]), mutable=["cache"])
    row = jax.tree.map(lambda a: jnp.concatenate([a, a]) if a.ndim else a, state["cache"])
    freed = reset_cache_slot(row, 1)
    for path, leaf in jax.tree_util.tree_flatten_with_path(freed)[0]:
        if path[-1].key == "kv_valid":
            assert bool(leaf[0, :32].all()) and not bool(leaf[1].any())
        elif path[-1].key in PAGED_LEAVES:
            assert bool(jnp.abs(leaf[1, :32]).sum() > 0)


# sha256 of the jaxpr text of tiny Keye-VL-2.0's prefill and paged decode
# chunk, both transports, taken on the PARENT commit (4eea0cf) of the PR that
# added the indexed latent cache (PR 32): `_fused_sparse_decode` grew a latent
# form, `chunked_decode_step` a model's own counters and the router a
# selection bias; the model with the OTHER indexed cache must not notice.
# (Mixtral's, CodeGen's and DeepSeek-V2's nine are in test_latent_cache.py.)
# The two ``decode`` digests were taken anew by PR 33, whose expert layers sow
# ``hit_experts`` / ``routed_rows`` into a decode chunk; ``keye.prefill`` is
# the parent's, and so are GLM-5's three at the end of this file (a layer told which experts it
# holds sows what it sowed). PR 36 (the chunk's sampler behind one conditional
# on "some emitting row samples", ``utils/sampling.sample_per_row``) took the
# two ``decode`` digests anew, here and in GLM-5's below; both ``prefill``
# digests stand. PR 52 took the two ``decode.fused`` digests anew: the
# index-score kernel in them fetches a run of adjacent pages with one copy
# (``gather`` and the prefills hold no such kernel and stand). PR 55 took both
# ``prefill`` digests anew, here and in GLM-5's below: a prefill hands its
# ``padding_mask`` to its expert layers as their row mask (the padded rows'
# slots count as absent); all four ``decode`` digests stand (a decode step
# passes no mask).
KEYE_PARENT_PROGRAMS = {
    "keye.prefill": "a632140741590a63e5ecb3ca0536e769735af79774348ecb674d35fdefa91ee8",
    "keye.decode.gather": "745243e191fa1af397416893de4bfb8335a4040b9dd4c285bd488fb3be53102b",
    "keye.decode.fused": "8e8214201d09ea8949783edf8ea7baa35303e75ea284bf806e4b8b7406dbc9f1",
}


@pytest.fixture(scope="module")
def keye_program_texts():
    from neuronx_distributed_tpu.models.keye_vl2 import KeyeVL2ForCausalLM, tiny_keye_vl2

    return _model_program_texts("keye", KeyeVL2ForCausalLM(tiny_keye_vl2(max_seq_len=128), attention_impl="xla"))


@pytest.mark.parametrize("program", list(KEYE_PARENT_PROGRAMS))
def test_keye_programs_are_the_parents(keye_program_texts, program):
    digest = _digest(keye_program_texts[program])
    assert digest == KEYE_PARENT_PROGRAMS[program], json.dumps({program: digest})


# The same three programs of tiny GLM-5 with a share of its experts held,
# taken on the PARENT commit (97eb835) of the PR that gave the other expert
# layers their counters and a streamed decode form (PR 33): a layer told which
# experts it holds sows and multiplies what it did. (The two ``decode`` digests:
# anew with PR 36's sampler branch, see Keye's above.)
GLM_PARENT_PROGRAMS = {
    "glm.prefill": "fb584abfb098ed77ffb847b4f062e301607636f4d7ae0ec4b3c08c7457f6f5db",
    "glm.decode.gather": "cff08709ba7f506ae7f3a9aab3660044fd2a0b55a54ae56da5ca2a99c10ea39b",
    "glm.decode.fused": "20cacc2f819978158c65edc1e186d67d3340e1b89c362f958c3925217c8309f4",
}


@pytest.fixture(scope="module")
def glm_program_texts():
    return _model_program_texts("glm", GlmMoeDsaForCausalLM(
        tiny_glm_moe_dsa(max_seq_len=128, held_experts=(2, 4)), attention_impl="xla"))


@pytest.mark.parametrize("program", list(GLM_PARENT_PROGRAMS))
def test_glm_programs_are_the_parents(glm_program_texts, program):
    digest = _digest(glm_program_texts[program])
    assert digest == GLM_PARENT_PROGRAMS[program], json.dumps({program: digest})
