"""No fallback hides the device (kernels/backend.py): a kernel is interpreted
only where a test asks, a requested kernel never turns into a reference, and
what ``"auto"`` resolved to is readable from the engine's and the trainer's
program ledger."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import GenerationConfig
from neuronx_distributed_tpu.kernels import backend
from neuronx_distributed_tpu.kernels.flash_attention import flash_attention
from neuronx_distributed_tpu.models.llama import LlamaForCausalLM, tiny_llama
from neuronx_distributed_tpu.serving import EngineHealth, RequestState, ServingEngine


def test_interpretation_is_the_tests_request_not_the_platforms(monkeypatch):
    assert backend.INTERPRET is True  # conftest's session switch
    assert backend.interpret_mode(None) is True
    assert backend.interpret_mode(False) is False
    monkeypatch.setattr(backend, "INTERPRET", False)  # any non-test process
    assert backend.interpret_mode(None) is False  # ... even on this CPU
    assert backend.interpret_mode(True) is True


def test_flash_kernel_raises_uninterpreted_off_tpu(monkeypatch):
    q = jnp.ones((1, 64, 2, 32), jnp.float32)
    monkeypatch.setattr(backend, "INTERPRET", False)
    with pytest.raises(ValueError, match="interpret mode"):
        flash_attention(q, q, q)
    with pytest.raises(ValueError, match="interpret mode"):
        jax.grad(lambda q: flash_attention(q, q, q).sum())(q)


def test_auto_resolves_by_platform_and_mesh(monkeypatch):
    assert backend.resolve_attention_impl("auto") == "xla"  # this CPU
    assert backend.resolve_attention_impl("auto", cp=2) == "ring"
    for name in ("flash", "xla", "ring", "ulysses"):
        assert backend.resolve_attention_impl(name, cp=2) == name
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    assert backend.resolve_attention_impl("auto") == "flash"


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_llama(num_layers=1, max_seq_len=1024)
    model = LlamaForCausalLM(cfg, attention_impl="auto")
    ids = jnp.ones((1, 8), jnp.int32)
    return cfg, model, model.init(jax.random.PRNGKey(0), ids)


def _resolved(engine):
    ledger = engine.programs.snapshot(analyze=False)["resolved"]
    # ... and through the metrics snapshot an operator scrapes
    scraped = engine.metrics.snapshot(analyze_programs=False)["programs"]
    assert scraped["resolved"] == ledger
    return ledger


def test_engine_snapshot_records_what_auto_resolved_to(tiny, monkeypatch):
    cfg, model, params = tiny
    paged = dict(num_slots=2, kv_page_size=16)
    assert _resolved(ServingEngine(model, params, **paged)) == {
        "attention": "xla", "decode_attention": "einsum",
        "paged_attention": "gather",
    }
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    assert _resolved(ServingEngine(model, params, **paged)) == {
        "attention": "flash", "decode_attention": "paged_fused",
        "paged_attention": "fused",
    }
    assert _resolved(ServingEngine(model, params, num_slots=2)) == {
        "attention": "flash", "decode_attention": "flash_decode",
        "paged_attention": "none",
    }
    short = LlamaForCausalLM(
        tiny_llama(num_layers=1, max_seq_len=512), attention_impl="xla"
    )
    assert _resolved(ServingEngine(short, params, num_slots=2)) == {
        "attention": "xla", "decode_attention": "einsum",
        "paged_attention": "none",
    }


def test_fused_engine_never_silently_serves_through_gather(tiny, monkeypatch):
    """``paged_attention="fused"`` off the TPU, uninterpreted: the decode
    chunk cannot lower, the engine retries and HALTS for cause. It used to
    answer — through the gather reference, reporting itself fused."""
    cfg, model, params = tiny
    monkeypatch.setattr(backend, "INTERPRET", False)
    engine = ServingEngine(
        LlamaForCausalLM(cfg, attention_impl="xla"), params, num_slots=2,
        kv_page_size=16, paged_attention="fused", sleep_fn=lambda s: None,
    )
    req = engine.submit(
        np.arange(1, 9, dtype=np.int32),
        GenerationConfig(max_new_tokens=4, temperature=0.0),
        key=jax.random.PRNGKey(0),
    )
    engine.run()
    assert engine.health() is EngineHealth.HALTED
    assert "interpret mode" in engine.halt_reason
    assert req.state is not RequestState.DONE
