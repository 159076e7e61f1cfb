"""Deterministic traffic replay (ISSUE 11 tentpole d + determinism pin).

The generator's arrival tape must be BYTE-identical for the same seed
(no wall clock, no process-global RNG, no dict-order dependence), and a
full in-process replay must yield an identical SLO report — that property
is what makes the harness a judge for scheduler/cache changes."""

import dataclasses
import json

import numpy as np
import pytest

from neuronx_distributed_tpu.observability import SLOSpec
from neuronx_distributed_tpu.serving.traffic import (
    Arrival,
    TenantProfile,
    VirtualClock,
    generate_tape,
    replay,
    tape_bytes,
)


def _tenants(arrival="poisson"):
    return [
        TenantProfile("chat", rate_rps=2.0, arrival=arrival,
                      workload="chat", priority="interactive",
                      burst_factor=4.0, burst_period_s=4.0,
                      burst_duty=0.25),
        TenantProfile("docs", rate_rps=0.8, arrival=arrival,
                      workload="longdoc", priority="batch"),
    ]


# --- generator ----------------------------------------------------------------


def test_same_seed_byte_identical_tape():
    a = generate_tape(_tenants(), duration_s=20.0, seed=11, vocab_size=512)
    b = generate_tape(_tenants(), duration_s=20.0, seed=11, vocab_size=512)
    assert tape_bytes(a) == tape_bytes(b)
    assert len(a) > 10
    c = generate_tape(_tenants(), duration_s=20.0, seed=12, vocab_size=512)
    assert tape_bytes(a) != tape_bytes(c)  # the seed actually matters


def test_bursty_tape_byte_identical_and_different_from_poisson():
    a = generate_tape(_tenants("bursty"), duration_s=20.0, seed=11,
                      vocab_size=512)
    b = generate_tape(_tenants("bursty"), duration_s=20.0, seed=11,
                      vocab_size=512)
    assert tape_bytes(a) == tape_bytes(b)
    p = generate_tape(_tenants("poisson"), duration_s=20.0, seed=11,
                      vocab_size=512)
    assert tape_bytes(a) != tape_bytes(p)


def test_tenant_streams_independent():
    """Adding a tenant never perturbs another's arrivals (independent
    seeded streams — the property that makes tenant-mix sweeps A/B-able)."""
    solo = generate_tape([_tenants()[0]], duration_s=20.0, seed=11,
                         vocab_size=512)
    both = generate_tape(_tenants(), duration_s=20.0, seed=11,
                         vocab_size=512)
    chat_of_both = [a for a in both if a.tenant == "chat"]
    assert tape_bytes(solo) == tape_bytes(chat_of_both)


def test_tape_sorted_and_well_formed():
    tape = generate_tape(_tenants("bursty"), duration_s=30.0, seed=3,
                         vocab_size=128)
    times = [a.t for a in tape]
    assert times == sorted(times)
    for a in tape:
        assert 0.0 <= a.t < 30.0
        assert all(1 <= t < 128 for t in a.prompt)
        assert a.max_new_tokens >= 1
        assert a.tenant in ("chat", "docs")
    # both workload shapes present with their length signatures
    chat_lens = [len(a.prompt) for a in tape if a.tenant == "chat"]
    docs_lens = [len(a.prompt) for a in tape if a.tenant == "docs"]
    assert chat_lens and docs_lens
    assert max(chat_lens) <= 16 and min(docs_lens) >= 24


def test_bursty_is_actually_burstier():
    """The diurnal square wave concentrates arrivals: the busiest
    period-sized window of the bursty tape beats poisson's by a wide
    margin at the same off-peak rate."""
    def peak_window(tape, w):
        times = [a.t for a in tape]
        return max(
            (sum(1 for t in times if lo <= t < lo + w)
             for lo in np.arange(0.0, 60.0, w / 4)),
            default=0,
        )

    tp = TenantProfile("t", rate_rps=2.0, arrival="poisson")
    tb = dataclasses.replace(tp, arrival="bursty", burst_factor=6.0,
                             burst_period_s=8.0, burst_duty=0.25)
    poisson = generate_tape([tp], duration_s=60.0, seed=5, vocab_size=64)
    bursty = generate_tape([tb], duration_s=60.0, seed=5, vocab_size=64)
    assert peak_window(bursty, 2.0) > 1.5 * peak_window(poisson, 2.0)


def test_generator_validation():
    with pytest.raises(ValueError):
        TenantProfile("x", rate_rps=0.0)
    with pytest.raises(ValueError):
        TenantProfile("x", arrival="fractal")
    with pytest.raises(ValueError):
        TenantProfile("x", workload="video")
    with pytest.raises(ValueError):
        TenantProfile("x", arrival="bursty", burst_duty=1.5)
    with pytest.raises(ValueError):
        generate_tape([TenantProfile("a"), TenantProfile("a")], 10.0)
    with pytest.raises(ValueError):
        generate_tape([TenantProfile("a")], 0.0)


# --- replay -------------------------------------------------------------------


@pytest.fixture(scope="module")
def setup():
    import jax

    from neuronx_distributed_tpu.models.llama import (
        LlamaForCausalLM,
        tiny_llama,
    )

    cfg = tiny_llama()
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    ids = jax.random.randint(jax.random.PRNGKey(0), (1, 8), 1, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(1), ids)
    return cfg, model, params


_SLO = {
    "chat": SLOSpec(ttft_p99_s=0.15, tpot_p99_s=0.05),
    "docs": SLOSpec(ttft_p99_s=1.00, tpot_p99_s=0.10),
}


def _replay_once(model, params, cfg, tape, **engine_kw):
    from neuronx_distributed_tpu.serving import ServingEngine

    clock = VirtualClock()
    engine = ServingEngine(
        model, params, num_slots=2, decode_chunk_size=4,
        prefix_cache=None, slo=_SLO, time_fn=clock,
        sleep_fn=lambda s: None, **engine_kw,
    )
    return replay(engine, tape, clock, step_dt=0.05)


def test_replay_report_shape_and_keys(setup):
    cfg, model, params = setup
    tape = generate_tape(_tenants(), duration_s=3.0, seed=7,
                         vocab_size=cfg.vocab_size)
    report = _replay_once(model, params, cfg, tape)
    assert set(report["tenants"]) == {"chat", "docs"}
    for row in report["tenants"].values():
        for key in ("submitted", "completed", "ttft_p50_s", "ttft_p99_s",
                    "tpot_p50_s", "tpot_p99_s", "sheds", "timed_out",
                    "rejects", "attainment", "goodput_tok_s"):
            assert key in row, key
    assert report["replay"]["submitted"] == len(tape)
    assert report["replay"]["truncated"] is False
    assert report["completed"] == len(tape)
    assert report["slo"]["attained"] + report["slo"]["violated"] == len(tape)
    json.dumps(report)  # artifact-ready


def test_replay_requires_the_virtual_clock(setup):
    cfg, model, params = setup
    from neuronx_distributed_tpu.serving import ServingEngine

    engine = ServingEngine(model, params, num_slots=2, prefix_cache=None)
    with pytest.raises(ValueError, match="time_fn"):
        replay(engine, [], VirtualClock())


@pytest.mark.parametrize("scheduling", ["fifo", "slo"])
def test_same_seed_identical_slo_report(setup, scheduling):
    """THE determinism pin: same seed ⇒ byte-identical tape AND an
    identical SLO report across two in-process replays — wall-clock or
    dict-order leaks anywhere in the pipeline fail here, under the FIFO
    scheduler and under the SLO policy (which reads the tracker it
    reports from)."""
    cfg, model, params = setup
    tapes = [
        generate_tape(_tenants("bursty"), duration_s=3.0, seed=9,
                      vocab_size=cfg.vocab_size)
        for _ in range(2)
    ]
    assert tape_bytes(tapes[0]) == tape_bytes(tapes[1])
    r1 = _replay_once(model, params, cfg, tapes[0], scheduling=scheduling)
    r2 = _replay_once(model, params, cfg, tapes[1], scheduling=scheduling)
    assert json.dumps(r1, sort_keys=True) == json.dumps(r2, sort_keys=True)
    # keys deterministic AND ordered deterministically (insertion order
    # is tenant-sorted, so even non-sort_keys serialization matches)
    assert json.dumps(r1) == json.dumps(r2)


@pytest.mark.slow
def test_same_seed_identical_report_full_replay(setup):
    """Slow full-scale variant: a longer two-tenant bursty tape with
    deadlines (sheds exercised), replayed twice — reports identical."""
    cfg, model, params = setup
    tenants = [
        dataclasses.replace(_tenants("bursty")[0], rate_rps=4.0,
                            deadline_s=2.0),
        _tenants("bursty")[1],
    ]
    tapes = [
        generate_tape(tenants, duration_s=12.0, seed=21,
                      vocab_size=cfg.vocab_size)
        for _ in range(2)
    ]
    assert tape_bytes(tapes[0]) == tape_bytes(tapes[1])
    r1 = _replay_once(model, params, cfg, tapes[0])
    r2 = _replay_once(model, params, cfg, tapes[1])
    assert json.dumps(r1, sort_keys=True) == json.dumps(r2, sort_keys=True)
    assert r1["replay"]["steps"] > 20


def test_overload_rejects_and_sheds_attributed(setup):
    """Open-loop under a bounded queue: arrivals beyond capacity REJECT
    (attributed per tenant, counted as SLO violations) instead of
    backpressuring the generator — the open-loop property."""
    cfg, model, params = setup
    tenants = [
        TenantProfile("chat", rate_rps=30.0, workload="chat",
                      priority="interactive", queue_timeout_s=0.3),
    ]
    tape = generate_tape(tenants, duration_s=2.0, seed=3,
                         vocab_size=cfg.vocab_size)
    assert len(tape) > 20
    report = _replay_once(model, params, cfg, tape, max_queue=4)
    rep = report["replay"]
    assert rep["submitted"] + rep["rejected"] == len(tape)
    row = report["tenants"]["chat"]
    assert row["rejects"] == rep["rejected"]
    # every arrival is accounted: finished, shed, or rejected
    assert (
        row["completed"] + row["sheds"] + row["rejects"] == len(tape)
    )
    if rep["rejected"]:
        assert report["slo"]["violation_reasons"]["chat"]["reject"] == (
            rep["rejected"]
        )


def test_unplaceable_arrival_does_not_kill_the_replay(setup):
    """Review regression: an arrival the engine can NEVER place (here:
    footprint over max_tokens_in_flight) fails at the door with
    ValueError BEFORE any metrics record — the replay must attribute it
    as a reject for its tenant and keep going, not crash and lose the
    whole report."""
    from neuronx_distributed_tpu.serving import ServingEngine

    cfg, model, params = setup
    tape = generate_tape(_tenants(), duration_s=3.0, seed=7,
                         vocab_size=cfg.vocab_size)
    assert any(a.tenant == "docs" for a in tape)
    clock = VirtualClock()
    engine = ServingEngine(
        model, params, num_slots=2, decode_chunk_size=4,
        prefix_cache=None, slo=_SLO, time_fn=clock,
        sleep_fn=lambda s: None,
        # chat fits (<= 16 prompt + <= 20 new), every longdoc request
        # (>= 24 prompt + >= 16 new) is permanently unplaceable
        max_tokens_in_flight=38,
    )
    report = replay(engine, tape, clock, step_dt=0.05)
    rep = report["replay"]
    n_docs = sum(1 for a in tape if a.tenant == "docs")
    assert rep["unplaceable"] == n_docs
    assert rep["submitted"] + rep["rejected"] + rep["unplaceable"] == len(tape)
    assert report["tenants"]["docs"]["rejects"] == n_docs
    assert report["slo"]["violation_reasons"]["docs"]["reject"] == n_docs
    # the placeable tenant's traffic is untouched
    assert report["tenants"]["chat"]["completed"] == (
        sum(1 for a in tape if a.tenant == "chat")
    )
