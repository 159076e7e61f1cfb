"""A prefill's padded rows get no expert (PR 55), through ``ServingEngine`` on
the CPU, for each MoE cache kind of the contract suite (``test_cache_kinds.py``
``KINDS``): one prompt served in its power-of-two bucket and in a bucket that
is its own length gives one float32 stream, and the first token's span
carries what the expert layers' dispatch kept and was given."""

import dataclasses

import jax
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import GenerationConfig
from neuronx_distributed_tpu.observability import tracing
from neuronx_distributed_tpu.serving import ServingEngine
from neuronx_distributed_tpu.utils.timeline import Timeline
from tests.serving.test_cache_kinds import KINDS, built

PROMPT, NEW_TOKENS, CHUNK = 37, 12, 4


def _serve(model, params, prompt, path):
    engine = ServingEngine(model, params, num_slots=2, prefix_cache=None, decode_chunk_size=CHUNK,
                           timeline=Timeline(str(path)))
    req = engine.submit(prompt, GenerationConfig(max_new_tokens=NEW_TOKENS, temperature=0.0), key=jax.random.PRNGKey(0))
    engine.run()
    firsts = [e["args"] for e in engine.timeline._events
              if e["name"] == tracing.STEP_FIRST_TOKEN and e.get("ph") == "X"]
    prefills = [e["args"] for e in engine.timeline._events if e["name"] == tracing.STEP_PREFILL and e.get("ph") == "X"]
    return list(req.tokens), firsts, prefills


@pytest.mark.parametrize("kind", [name for name in KINDS if name != "looped"])     # Ouro has no expert layer
def test_one_prompt_gives_one_stream_whatever_its_bucket_and_the_span_counts_the_rows(kind, tmp_path):
    b = built(kind)
    prompt = np.random.default_rng(1).integers(1, b.cfg.vocab_size, size=PROMPT).astype(np.int32)
    expert_layers = sum(1 for path, _ in jax.tree_util.tree_flatten_with_path(b.params)[0]
                        if any(getattr(k, "key", None) == "experts" for k in path)
                        and any(getattr(k, "key", None) == "up_proj" for k in path))
    assert expert_layers >= 1 and b.model.prefill_stats == ("moe_live_rows", "moe_rows")
    # a row of 64 leaves the 64 bucket no room for the answer: the bucket is the prompt's own length
    exact = b.cls(dataclasses.replace(b.cfg, max_seq_len=64), attention_impl="xla")
    streams = {}
    for name, model, bucket in (("padded", b.model, 64), ("exact", exact, PROMPT)):
        streams[name], firsts, prefills = _serve(model, b.params, prompt, tmp_path / f"{name}.json")
        assert [p["padded"] for p in prefills] == [bucket]
        assert firsts == [{"rid": firsts[0]["rid"], "moe_live_rows": PROMPT * expert_layers,
                           "moe_rows": bucket * expert_layers}]
    assert len(streams["padded"]) == NEW_TOKENS and streams["padded"] == streams["exact"]
