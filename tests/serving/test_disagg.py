"""Disaggregated prefill/decode (ISSUE 14): dedicated prefill workers hand
finished contexts to the decode engine as PAGE-TABLE handoffs — zero KV
bytes moved on the shared-pool path (``PageAllocator.copy_bytes == 0``,
the acceptance pin), an explicit charged copy on the distinct-pool
export/import fallback — with streams bit-identical to solo ``generate()``
through every topology and every fault fallback.

Tier budget (the PR 5 precedent): the acceptance core — shared-pool
zero-copy handoff, the handoff-failure fallback chaos, validation — stays
tier-1; distinct pools / worker-death / pacing / deadline variants are
``slow`` (the suite runs within ~30s of the verify wall without them)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import GenerationConfig, generate
from neuronx_distributed_tpu.models.llama import LlamaForCausalLM, tiny_llama
from neuronx_distributed_tpu.serving import (
    DisaggregatedServer,
    FaultInjector,
    RequestState,
    ServingEngine,
)


@pytest.fixture(scope="module")
def setup():
    # small-but-real geometry: 2 layers keep every mesh/handoff
    # compile under the tier-1 budget while heads/kv-heads still
    # exercise the tp sharding rules (8 q heads, 4 kv heads)
    cfg = tiny_llama(num_layers=2, hidden_size=32,
                     intermediate_size=96, vocab_size=128)
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    ids = jax.random.randint(jax.random.PRNGKey(0), (1, 8), 1, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(1), ids)
    return cfg, model, params


def _solo(model, params, prompt, key, gcfg):
    toks = np.asarray(
        generate(model, params, jnp.asarray(prompt)[None], key, gcfg)
    )[0].tolist()
    if gcfg.eos_token_id is not None and gcfg.eos_token_id in toks:
        toks = toks[: toks.index(gcfg.eos_token_id) + 1]
    return toks


def _engine(model, params, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("decode_chunk_size", 2)
    kw.setdefault("prefix_cache", None)
    kw.setdefault("kv_page_size", 8)
    return ServingEngine(model, params, **kw)


def _mixed_workload(cfg, n=5):
    rng = np.random.RandomState(13)
    prompts = [
        rng.randint(1, cfg.vocab_size, size=rng.randint(4, 14)).astype(
            np.int32
        )
        for _ in range(n)
    ]
    gcfgs = [
        GenerationConfig(max_new_tokens=5 + (i % 3), temperature=0.0)
        if i % 2 == 0
        else GenerationConfig(
            max_new_tokens=6, temperature=0.9, top_k=19, top_p=0.95
        )
        for i in range(n)
    ]
    keys = [jax.random.PRNGKey(900 + i) for i in range(n)]
    return prompts, gcfgs, keys


def test_shared_pool_handoff_zero_copy_bit_identical(setup):
    """The acceptance pin: contexts move prefill→decode by block-table
    mapping with ``copy_bytes == 0``; greedy AND sampled streams equal
    solo; the decode engine never self-admits; one decode program."""
    cfg, model, params = setup
    prompts, gcfgs, keys = _mixed_workload(cfg)
    refs = [
        _solo(model, params, p, k, c)
        for p, k, c in zip(prompts, keys, gcfgs)
    ]
    engine = _engine(model, params)
    server = DisaggregatedServer(engine, n_workers=2)
    reqs = [
        server.submit(p, c, key=k)
        for p, c, k in zip(prompts, gcfgs, keys)
    ]
    server.run()
    for i, (req, ref) in enumerate(zip(reqs, refs)):
        assert req.state is RequestState.DONE
        assert req.tokens == ref, f"request {i} diverged through handoff"
    assert server.stats["handoffs"] == len(prompts)
    assert server.stats["coupled_fallbacks"] == 0
    assert engine.cache.alloc.copy_bytes == 0
    assert engine.external_prefill
    assert engine.decode_compilations == 1


@pytest.mark.slow
def test_distinct_pools_import_is_a_charged_copy(setup):
    """Different prefill/decode pools: the export/import fallback moves
    the context by an explicit device transfer — streams identical,
    ``copy_bytes`` charged (the accounting that proves the shared path
    moved nothing)."""
    cfg, model, params = setup
    prompts, gcfgs, keys = _mixed_workload(cfg, n=3)
    refs = [
        _solo(model, params, p, k, c)
        for p, k, c in zip(prompts, keys, gcfgs)
    ]
    engine = _engine(model, params)
    server = DisaggregatedServer(engine, n_workers=1, shared_pool=False)
    reqs = [
        server.submit(p, c, key=k)
        for p, c, k in zip(prompts, gcfgs, keys)
    ]
    server.run()
    for req, ref in zip(reqs, refs):
        assert req.state is RequestState.DONE
        assert req.tokens == ref
    assert server.stats["imported_contexts"] == 3
    assert engine.cache.alloc.copy_bytes > 0


@pytest.mark.slow
def test_prefills_per_step_bounds_prefill_between_chunks(setup):
    """The TPOT-isolation knob: with a backlog of queued prompts, one
    server step runs AT MOST ``prefills_per_step`` worker prefills — a
    coupled engine would admit the whole selection round inline."""
    cfg, model, params = setup
    prompts, gcfgs, keys = _mixed_workload(cfg, n=4)
    engine = _engine(model, params, num_slots=4)
    server = DisaggregatedServer(engine, n_workers=1, prefills_per_step=1)
    for p, c, k in zip(prompts, gcfgs, keys):
        server.submit(p, c, key=k)
    server.step()
    assert server.stats["prefills"] == 1
    server.step()
    assert server.stats["prefills"] == 2
    server.run()
    assert server.stats["prefills"] == 4


@pytest.mark.chaos
def test_handoff_failure_falls_back_to_coupled_prefill(setup):
    """``FaultInjector.fail_handoff``: the page-table transfer fails →
    staged pages release (leak-checked by the conftest invariant), the
    request prefills COUPLED on the decode engine, streams bit-identical,
    zero tokens lost."""
    cfg, model, params = setup
    prompts, gcfgs, keys = _mixed_workload(cfg, n=4)
    refs = [
        _solo(model, params, p, k, c)
        for p, k, c in zip(prompts, keys, gcfgs)
    ]
    inj = FaultInjector().fail_handoff(at=0, times=2)
    engine = _engine(model, params)
    server = DisaggregatedServer(engine, n_workers=1, fault_injector=inj)
    reqs = [
        server.submit(p, c, key=k)
        for p, c, k in zip(prompts, gcfgs, keys)
    ]
    server.run()
    assert inj.counters["handoff_failures"] == 2
    assert server.stats["handoff_failures"] == 2
    assert server.stats["coupled_fallbacks"] == 2
    tokens_lost = sum(
        1 for req, ref in zip(reqs, refs) if req.tokens != ref
    )
    assert tokens_lost == 0
    assert all(r.state is RequestState.DONE for r in reqs)


@pytest.mark.chaos
@pytest.mark.slow
def test_prefill_worker_death_degrades_to_coupled_engine(setup):
    """A worker whose prefill keeps failing leaves the rotation; losing
    the LAST worker flips the engine back to full self-admission — the
    topology degrades to a coupled engine, never to an outage. Streams
    bit-identical throughout."""
    cfg, model, params = setup
    prompts, gcfgs, keys = _mixed_workload(cfg, n=4)
    refs = [
        _solo(model, params, p, k, c)
        for p, k, c in zip(prompts, keys, gcfgs)
    ]
    inj = FaultInjector().fail_prefill(at=0, times=None)
    engine = _engine(model, params)
    server = DisaggregatedServer(engine, n_workers=1, fault_injector=inj)
    reqs = [
        server.submit(p, c, key=k)
        for p, c, k in zip(prompts, gcfgs, keys)
    ]
    server.run()
    assert server.stats["worker_failures"] == 1
    assert len(server.workers) == 0
    assert not engine.external_prefill  # coupled mode from here on
    for req, ref in zip(reqs, refs):
        assert req.state is RequestState.DONE
        assert req.tokens == ref


@pytest.mark.slow
def test_pending_handoff_respects_deadline(setup):
    """A request whose deadline passes while its prefilled context awaits
    handoff sheds (TIMED_OUT) and its staged pages release — no page can
    leak behind a dead deadline (conftest leak check)."""
    cfg, model, params = setup
    clock = [0.0]
    engine = _engine(model, params, time_fn=lambda: clock[0])
    server = DisaggregatedServer(engine, n_workers=1)
    gcfg = GenerationConfig(max_new_tokens=6, temperature=0.0)
    req = server.submit(
        np.arange(1, 9, dtype=np.int32), gcfg,
        key=jax.random.PRNGKey(0), deadline_s=5.0,
    )
    # let the worker prefill (request becomes pending-handoff), then jump
    # the clock past the deadline BEFORE the next handoff attempt
    server._run_prefills(clock[0])
    assert len(server._pending) == 1
    clock[0] = 100.0
    server.step()
    assert req.state is RequestState.TIMED_OUT
    assert not server._pending
    assert not server.has_work


@pytest.mark.chaos
@pytest.mark.slow
def test_recovery_voids_pending_handoff_without_leaks(setup):
    """Review regression (findings on the recovery x pending-handoff
    race): a dispatch failure's pool recovery VOIDS a staged context
    awaiting handoff. The next handoff attempt must (a) not double-deref
    the voided pages (release_staged is void-safe), (b) not leak the
    acquired slot (admit_staged frees it on a failed map), and (c) fall
    back to coupled prefill — every stream still completes bit-identical
    and the slot count is intact."""
    cfg, model, params = setup
    prompts, gcfgs, keys = _mixed_workload(cfg, n=3)
    refs = [
        _solo(model, params, p, k, c)
        for p, k, c in zip(prompts, keys, gcfgs)
    ]
    inj = FaultInjector().fail_dispatch(at=1, times=1)
    engine = _engine(model, params, num_slots=1, fault_injector=inj)
    server = DisaggregatedServer(engine, n_workers=1)
    reqs = [
        server.submit(p, c, key=k)
        for p, c, k in zip(prompts, gcfgs, keys)
    ]
    # drive until a prefilled context is PENDING handoff (slot busy) and
    # the injected dispatch failure's recovery has voided it
    server.run()
    assert inj.counters["dispatch_failures"] == 1
    assert server.stats["handoff_failures"] >= 1  # the voided handoff
    assert server.stats["coupled_fallbacks"] >= 1
    for req, ref in zip(reqs, refs):
        assert req.state is RequestState.DONE
        assert req.tokens == ref
    # the failed handoff's slot rejoined the rotation
    assert engine.cache.free_slots == engine.num_slots
    engine.cache.check()


def test_disagg_validation(setup):
    cfg, model, params = setup
    row_engine = ServingEngine(
        model, params, num_slots=2, prefix_cache=None
    )
    with pytest.raises(ValueError, match="PAGED"):
        DisaggregatedServer(row_engine)
    draft = LlamaForCausalLM(
        tiny_llama(num_layers=1, hidden_size=32, intermediate_size=96,
                   vocab_size=128),
        attention_impl="xla",
    )
    ids = jax.random.randint(jax.random.PRNGKey(0), (1, 8), 1, cfg.vocab_size)
    d_params = draft.init(jax.random.PRNGKey(7), ids)
    spec_engine = ServingEngine(
        model, params, num_slots=2, prefix_cache=None, kv_page_size=8,
        draft_model=draft, draft_params=d_params,
    )
    with pytest.raises(ValueError, match="speculative"):
        DisaggregatedServer(spec_engine)
