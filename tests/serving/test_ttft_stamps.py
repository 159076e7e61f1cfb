"""Where the engine stamps a request (ISSUE 24): the first token AFTER the
readback that waits for its prefill, the admission at the start of THAT
request's prefill, not at the top of the step that admitted it. The clock
below moves only inside a prefill, so whatever a stamp includes is exact."""

import jax
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import GenerationConfig
from neuronx_distributed_tpu.models.llama import LlamaForCausalLM, tiny_llama
from neuronx_distributed_tpu.observability import SLOSpec
from neuronx_distributed_tpu.serving import ServingEngine

PREFILL_S = 1.0


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_llama()
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    params = model.init(jax.random.PRNGKey(1), np.ones((1, 8), np.int32))
    return model, params


def _engine(model, params, **kw):
    """An engine whose clock advances by ``PREFILL_S`` inside every prefill
    program's call and nowhere else."""
    clock = {"t": 0.0}
    engine = ServingEngine(model, params, num_slots=2, prefix_cache=None,
                           time_fn=lambda: clock["t"], **kw)
    real = engine._prefill_fn

    def slow_prefill_fn(padded_len):
        fn = real(padded_len)

        def call(*args):
            clock["t"] += PREFILL_S
            return fn(*args)

        return call

    engine._prefill_fn = slow_prefill_fn
    return engine, clock


def test_ttft_includes_the_prefill_and_the_second_request_waits_for_the_first(setup):
    model, params = setup
    engine, clock = _engine(model, params)
    gcfg = GenerationConfig(max_new_tokens=4, temperature=0.0)
    # the longer prompt prefills first (longest-prefill-first)
    first = engine.submit(np.arange(1, 12, dtype=np.int32), gcfg, key=jax.random.PRNGKey(1))
    second = engine.submit(np.arange(1, 5, dtype=np.int32), gcfg, key=jax.random.PRNGKey(2))
    engine.step()                                  # both admitted in ONE step
    a, b = (engine.metrics.request_snapshot(r.rid) for r in (first, second))
    assert a["queue_wait"] == 0.0 and a["ttft"] == PREFILL_S
    assert b["queue_wait"] == PREFILL_S            # it waited for the first one's prefill
    assert b["ttft"] == 2 * PREFILL_S
    assert first.admit_time == 0.0 and first.first_token_time == PREFILL_S
    assert second.admit_time == PREFILL_S and second.first_token_time == 2 * PREFILL_S
    engine.run()
    snap = engine.metrics.snapshot()
    assert snap["mean_ttft"] == 1.5 * PREFILL_S    # and so does what the engine exports
    assert snap["ttft_p50_s"] >= 0.95 * PREFILL_S  # (log buckets: exact to the bucket)


def test_slo_attainment_rests_on_the_honest_ttft(setup):
    """A TTFT bound between nothing and one prefill: met by the old stamp
    (taken before the prefill), violated by the honest one."""
    model, params = setup
    engine, _ = _engine(model, params, slo={"chat": SLOSpec(ttft_p99_s=PREFILL_S / 2)})
    req = engine.submit(np.arange(1, 9, dtype=np.int32),
                        GenerationConfig(max_new_tokens=3, temperature=0.0),
                        key=jax.random.PRNGKey(3), tenant="chat")
    engine.run()
    slo = engine.metrics.snapshot()["slo"]
    assert (slo["attained"], slo["violated"]) == (0, 1)
    assert slo["violation_reasons"] == {"chat": {"latency": 1}}
    assert engine.metrics.request_snapshot(req.rid)["slo_attained"] is False
