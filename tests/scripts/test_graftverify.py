"""graftverify: IR-level verification of ledgered programs
(scripts/graftverify/).

Covers the check catalog at the unit level (a dropped donation by
dtype-mismatch MUST flag, a pruned-unused donation must NOT, a compiled-in
host callback flags, the recompile-hazard cross-check flags, waivers
suppress with a mandatory reason), the baseline ratchet mechanics, and the
ISSUE 15 acceptance pins on a REAL paged TP-sharded ServingEngine:
100% of declared donations aliased (or provably pruned-unused), zero
transfer ops, the tp∈{2,4} per-decode-chunk all-reduce wire-byte table
derived STATICALLY from the lowered IR, and the EQuARX quantized-ring
ratio ≥ 3.9x vs exact psum asserted from that static table — not a bench.

Enumeration contract: ``ProgramLedger.programs()`` and a full ``verify``
run trigger ZERO XLA compiles and ZERO device→host syncs (lowering is a
trace), pinned here by patching ``Lowered.compile`` and counting
``jax.device_get``.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import GenerationConfig
from neuronx_distributed_tpu.models.llama import LlamaForCausalLM, tiny_llama
from neuronx_distributed_tpu.observability.programs import ProgramLedger
from neuronx_distributed_tpu.parallel import mesh as mesh_lib
from neuronx_distributed_tpu.parallel.quantized_collectives import (
    QuantizedAllReduceConfig,
)
from neuronx_distributed_tpu.scripts.graftlint import baseline as baseline_mod
from neuronx_distributed_tpu.scripts.graftverify import (
    runner as gv_runner,
)
from neuronx_distributed_tpu.scripts.graftverify import ir as gv_ir
from neuronx_distributed_tpu.serving import RequestState, ServingEngine


def rules_of(report):
    return sorted({f.rule for f in report.findings})


def verify_nb(ledger, **kw):
    return gv_runner.verify({"t": ledger}, use_baseline=False, **kw)


# --- unit: donation aliasing (GV01) -------------------------------------------


def test_clean_donation_aliases_and_counts():
    led = ProgramLedger()
    fn = led.wrap("upd", jax.jit(
        lambda s, x: (s + x, x * 2.0), donate_argnums=(0,)
    ))
    fn(jnp.zeros((4,), jnp.float32), jnp.ones((4,), jnp.float32))
    rep = verify_nb(led)
    assert rep.findings == []
    st = rep.stats()
    assert st["programs_checked"] == 1
    assert st["donations_declared"] == 1
    assert st["donations_aliased"] == 1
    assert st["donations_dropped"] == 0


def test_injected_dropped_donation_flags_gv01():
    """The acceptance fixture: a donated leaf that NO output can take —
    jax silently drops the donation, graftverify must flag it."""
    led = ProgramLedger()

    def f(state, x):
        # state["c"] is int32[8] and USED, but every output is float32[4].
        # jax pairs a donor with an output of its aval, else hands it to
        # XLA (``jax.buffer_donor``) when an unpaired output has its SIZE;
        # 8 elements match neither, so the buffer is copied each dispatch
        return state["a"] + x, state["c"][:4].astype(jnp.float32) * 2

    fn = led.wrap("bad", jax.jit(f, donate_argnums=(0,)))
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # jax's own dropped-donation note
        fn(
            {"a": jnp.zeros((4,), jnp.float32),
             "c": jnp.zeros((8,), jnp.int32)},
            jnp.ones((4,), jnp.float32),
        )
    rep = verify_nb(led)
    assert rules_of(rep) == ["GV01"]
    (v,) = rep.findings
    assert "int32" in v.message and "<t/bad>" == v.path
    st = rep.stats()
    assert st["donations_dropped"] == 1


def test_pruned_unused_donation_is_not_a_drop():
    """A donated leaf the program never reads is PRUNED by pjit
    (keep_unused=False): the buffer is freed, nothing is copied — it must
    count as pruned, never as the GV01 bug (the paged_admit index-leaf
    false positive this distinction was built for)."""
    led = ProgramLedger()

    def f(state, x):
        return state["a"] + x  # state["b"] donated but untouched

    fn = led.wrap("pruned", jax.jit(f, donate_argnums=(0,)))
    fn(
        {"a": jnp.zeros((4,), jnp.float32),
         "b": jnp.zeros((8,), jnp.float32)},
        jnp.ones((4,), jnp.float32),
    )
    rep = verify_nb(led)
    assert rep.findings == []
    st = rep.stats()
    assert st["donations_declared"] == 2
    assert st["donations_aliased"] == 1
    assert st["donations_pruned"] == 1
    assert st["donations_dropped"] == 0


# --- unit: transfer census (GV02) ---------------------------------------------


def test_compiled_in_callback_flags_gv02():
    led = ProgramLedger()

    def f(x):
        jax.debug.callback(lambda v: None, x)
        return x + 1

    fn = led.wrap("cb", jax.jit(f))
    fn(jnp.ones((4,), jnp.float32))
    rep = verify_nb(led)
    assert "GV02" in rules_of(rep)
    assert any("callback" in v.message for v in rep.findings)
    assert rep.stats()["transfer_ops"] >= 1


def test_sharding_markers_are_not_transfers():
    led = ProgramLedger()
    fn = led.wrap("plain", jax.jit(lambda x: x * 3.0))
    fn(jnp.ones((4,), jnp.float32))
    rep = verify_nb(led)
    assert rep.stats()["transfer_ops"] == 0
    assert rep.findings == []


# --- unit: dispatch-key stability (GV04) --------------------------------------


def test_recompile_with_identical_avals_flags_gv04():
    """A python-float dispatch then a committed-array dispatch share one
    shape/dtype signature but compile twice (weak_type flip) — the GL03
    hazard observed at the cache layer."""
    led = ProgramLedger()
    fn = led.wrap("wk", jax.jit(lambda x: x * 2))
    fn(jnp.float32(1.0))  # committed f32[] (weak_type=False) — compile 1
    fn(jnp.array(1.0))  # weak f32[] — compile 2, SAME aval skeleton
    info = led.programs()["wk"]
    assert info.compiles == 2 and len(info.variants) == 1
    rep = verify_nb(led)
    assert "GV04" in rules_of(rep)


def test_waiver_suppresses_with_reason_and_gv00_without():
    led = ProgramLedger()
    fn = led.wrap("wk", jax.jit(lambda x: x * 2))
    fn(jnp.float32(1.0))
    fn(jnp.array(1.0))
    rep = verify_nb(
        led, waivers={"wk": {"GV04": "intentional weak-type probe"}}
    )
    assert rep.findings == [] and len(rep.suppressed) == 1
    rep2 = verify_nb(led, waivers={"wk": {"GV04": "  "}})
    assert "GV00" in rules_of(rep2) and "GV04" in rules_of(rep2)


# --- unit: baseline ratchet ---------------------------------------------------


def test_baseline_ratchet_add_then_stale(tmp_path):
    led = ProgramLedger()

    def f(state, x):
        return state["a"] + x, state["c"][:4].astype(jnp.float32)

    fn = led.wrap("bad", jax.jit(f, donate_argnums=(0,)))
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        fn(
            {"a": jnp.zeros((4,), jnp.float32),
             "c": jnp.zeros((8,), jnp.int32)},
            jnp.ones((4,), jnp.float32),
        )
    bl = tmp_path / "gv_baseline.json"
    rep = gv_runner.verify({"t": led}, baseline_path=str(bl))
    assert rep.failed and len(rep.diff.new) == 1
    gv_runner.write_baseline(str(bl), rep)
    rep2 = gv_runner.verify({"t": led}, baseline_path=str(bl))
    assert not rep2.failed and len(rep2.diff.grandfathered) == 1
    # the program is fixed → the baseline entry is STALE and the run fails
    # until regenerated (debt only shrinks consciously)
    led2 = ProgramLedger()
    fixed = led2.wrap("bad", jax.jit(
        lambda s, x: (s["a"] + x, s["c"] + 1), donate_argnums=(0,)
    ))
    fixed(
        {"a": jnp.zeros((4,), jnp.float32),
         "c": jnp.zeros((8,), jnp.int32)},
        jnp.ones((4,), jnp.float32),
    )
    rep3 = gv_runner.verify({"t": led2}, baseline_path=str(bl))
    assert rep3.failed and len(rep3.diff.stale) == 1


def test_baseline_scopes_do_not_cross_contaminate(tmp_path):
    """Pinning one workload configuration's findings (--tp 2) must not
    make another configuration's run (--tp 1) fail with stale entries —
    one baseline file holds each scope's slice independently, and the
    same fingerprint pinned under two scopes stays two entries."""
    led = ProgramLedger()

    def f(state, x):
        return state["a"] + x, state["c"][:4].astype(jnp.float32)

    fn = led.wrap("bad", jax.jit(f, donate_argnums=(0,)))
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        fn(
            {"a": jnp.zeros((4,), jnp.float32),
             "c": jnp.zeros((8,), jnp.int32)},
            jnp.ones((4,), jnp.float32),
        )
    bl = tmp_path / "gv_baseline.json"
    rep_tp2 = gv_runner.verify(
        {"t": led}, baseline_path=str(bl), scope="tp2"
    )
    assert rep_tp2.failed
    gv_runner.write_baseline(str(bl), rep_tp2, scope="tp2")
    # tp1 sees NEITHER a grandfathered match NOR a stale entry from tp2:
    # its own finding is new (fails), the tp2 slice is invisible
    rep_tp1 = gv_runner.verify(
        {"t": led}, baseline_path=str(bl), scope="tp1"
    )
    assert len(rep_tp1.diff.new) == 1 and not rep_tp1.diff.stale
    # pinning tp1 too leaves both slices live (same raw fingerprint,
    # two scoped entries) and both runs clean
    gv_runner.write_baseline(str(bl), rep_tp1, scope="tp1")
    assert not gv_runner.verify(
        {"t": led}, baseline_path=str(bl), scope="tp1"
    ).failed
    assert not gv_runner.verify(
        {"t": led}, baseline_path=str(bl), scope="tp2"
    ).failed


def test_checked_in_baseline_is_empty():
    import os

    from neuronx_distributed_tpu.scripts.graftverify.core import (
        DEFAULT_BASELINE_NAME,
    )

    root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    ))
    entries = baseline_mod.load(os.path.join(root, DEFAULT_BASELINE_NAME))
    assert entries == {}


# --- unit: collective table arithmetic ----------------------------------------


@pytest.fixture(autouse=True)
def fresh_mesh():
    mesh_lib.destroy_model_parallel()
    yield
    mesh_lib.destroy_model_parallel()


def test_collective_table_ring_model():
    """The per-kind wire model against a hand-built shard_map program:
    one f32 psum of n elements over R ranks must read 2*(R-1)/R * 4n
    bytes; an int8 permute reads its payload once."""
    from jax.experimental.shard_map import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    devs = jax.devices()[:4]
    mesh = Mesh(np.array(devs).reshape(4), ("tp",))

    def body(x):
        return jax.lax.psum(x, "tp")

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=P("tp"), out_specs=P()))
    low = fn.lower(jnp.ones((1024,), jnp.float32))
    table = gv_ir.collective_table(low)
    row = table["by_kind"]["all_reduce"]
    # per-shard operand: 256 elements f32 → ring moves 2*(3)/4 * 1024B
    assert row["ops"] == 1 and row["elements"] == 256
    assert row["payload_bytes"] == 1024
    assert row["wire_bytes"] == 2 * 3 * 1024 // 4

    def body2(x):
        q = jnp.clip(x, -127, 127).astype(jnp.int8)
        q = jax.lax.ppermute(
            q, "tp", [(i, (i + 1) % 4) for i in range(4)]
        )
        return q.astype(jnp.float32)

    fn2 = jax.jit(shard_map(
        body2, mesh=mesh, in_specs=P("tp"), out_specs=P("tp"),
        check_rep=False,
    ))
    t2 = gv_ir.collective_table(fn2.lower(jnp.ones((1024,), jnp.float32)))
    row2 = t2["by_kind"]["collective_permute"]
    assert row2["ops"] == 1 and row2["payload_bytes"] == 256  # int8
    assert row2["wire_bytes"] == 256


# --- integration: real ServingEngine ------------------------------------------

# num_slots x hidden_size = 1024: the row-parallel reduction's element
# count is divisible by ranks*block_size at tp∈{2,4} — zero ring padding,
# so the static ratio is exactly the EQuARX 4/(1+4/256)=3.938. hidden=128
# keeps the XLA compiles inside the tier-1 budget.
_H = 128
_SLOTS = 8
_CHUNK = 2
_ROUTED_ELEMS = _SLOTS * _H  # one routed reduce = (slots, 1, hidden) f32


@pytest.fixture(scope="module")
def comms_model():
    cfg = tiny_llama(num_layers=2, hidden_size=_H,
                     intermediate_size=3 * _H, vocab_size=128)
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    ids = jax.random.randint(jax.random.PRNGKey(0), (1, 8), 1, cfg.vocab_size)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), ids)
    return cfg, model, params


@pytest.fixture(scope="module")
def tiny_model():
    cfg = tiny_llama()
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    ids = jax.random.randint(jax.random.PRNGKey(0), (1, 8), 1, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(1), ids)
    return cfg, model, params


def _drive(engine, cfg, n_req=1, new_tokens=2):
    rng = np.random.RandomState(3)
    gcfg = GenerationConfig(max_new_tokens=new_tokens, temperature=0.0)
    reqs = []
    for i in range(n_req):
        reqs.append(engine.submit(
            rng.randint(1, cfg.vocab_size, size=6).astype(np.int32),
            gcfg, key=jax.random.PRNGKey(i),
        ))
    engine.run()
    assert all(r.state is RequestState.DONE for r in reqs)
    return reqs


def _tp_engine(model, params, tp, quantized, paged):
    mesh_lib.destroy_model_parallel()
    return ServingEngine(
        model, params, num_slots=_SLOTS, decode_chunk_size=_CHUNK,
        prefix_cache=None, tp=tp,
        kv_page_size=8 if paged else None,
        tp_comms=QuantizedAllReduceConfig(enabled=quantized),
    )


def _quant_ring_bytes_per_reduce(tp):
    """Closed-form per-rank wire bytes of ONE quantized-ring reduction of
    _ROUTED_ELEMS f32 elements (no padding by construction): int8 payload
    both phases + blockwise f32 scales."""
    chunk = _ROUTED_ELEMS // tp
    hops = 2 * (tp - 1)
    return hops * chunk + hops * (chunk // 256) * 4


def _exact_ring_bytes_per_reduce(tp):
    return 2 * (tp - 1) * _ROUTED_ELEMS * 4 // tp


def _routed_detail(table, tp):
    """The (slots x hidden) f32 all_reduce rows of a decode-chunk table —
    the row-parallel reductions plus the one same-shaped residual."""
    return [
        d for d in table["detail"]
        if d["kind"] == "all_reduce" and d["elements"] == _ROUTED_ELEMS
        and d["elt_bytes"] == 4 and d["ranks"] == tp
    ]


# per decode chunk: 2 routed row-parallel reductions per step (one per
# transformer layer) — the tp_comms scope replaces exactly these with the
# quantized ring — plus ONE residual reduction of the same (slots x
# hidden) shape that stays an exact psum in both modes (measured: exact
# ops = 2*chunk+1 at chunk∈{2,4}, quant rings = 2*chunk, residual 1)
_ROUTED_OPS = 2 * _CHUNK


def _assert_routed_table(table, tp):
    """The per-decode-chunk all-reduce byte table pin: every reduce is
    exactly (slots x hidden) f32 moving the ring-model bytes, 2*chunk
    routed + 1 residual."""
    (row,) = _routed_detail(table, tp)
    assert row["ops"] == _ROUTED_OPS + 1, table["detail"]
    assert row["wire_bytes"] == _exact_ring_bytes_per_reduce(tp), row
    assert table["by_kind"]["all_reduce"]["ops"] == _ROUTED_OPS + 1


@pytest.mark.slow  # the tp=2 mesh compile bill (tier-1 budget, PR 5/13
# lean-core policy): the exact byte/donation models stay tier-1 via
# test_collective_table_ring_model and test_clean_donation_aliases_and_counts;
# tp streams via test_multichip.py
def test_tp2_engine_donations_tables_and_static_ratio(comms_model):
    """THE tp=2 acceptance pin, on one real paged engine pair:

    * 100% of declared donations across EVERY ledgered program reach the
      IR (aliased / mesh-deferred / provably pruned-unused), zero
      transfer ops, decode/paged/slot programs individually verified;
    * the per-decode-chunk all-reduce byte table matches the ring
      arithmetic exactly (detail rows identified by element count);
    * the EQuARX quantized ring moves >= 3.9x fewer wire bytes than the
      exact psum, asserted from the two STATIC tables — not a bench.
    """
    cfg, model, params = comms_model
    exact = _tp_engine(model, params, 2, quantized=False, paged=True)
    _drive(exact, cfg, n_req=2)
    rep = verify_nb(exact.programs)
    st = rep.stats()
    assert st["variants_uncaptured"] == 0
    assert not any(a.lower_errors for a in rep.audits)
    assert st["donations_declared"] > 0
    assert st["donations_dropped"] == 0
    assert (
        st["donations_aliased"] + st["donations_deferred"]
        + st["donations_pruned"]
        == st["donations_declared"]
    )
    assert st["donations_deferred"] > 0  # the tp engine really defers
    assert st["transfer_ops"] == 0
    assert rules_of(rep) in ([], ["GV03"])
    for name in ("decode_chunk", "paged_admit", "slot_write", "slot_clear"):
        audit = rep.audit(name)
        assert audit is not None and audit.variants, name
        for v in audit.variants:
            assert v.donations["dropped"] == [], (name, v.donations)

    te = rep.audit("decode_chunk").collective_table
    assert set(te["by_kind"]) == {"all_reduce"}
    _assert_routed_table(te, 2)

    quant = _tp_engine(model, params, 2, quantized=True, paged=False)
    _drive(quant, cfg)
    rep_q = verify_nb(quant.programs)
    assert rep_q.stats()["donations_dropped"] == 0
    tq = rep_q.audit("decode_chunk").collective_table
    assert {"collective_permute", "all_gather"} <= set(tq["by_kind"])
    ring_quant = (
        tq["by_kind"]["collective_permute"]["wire_bytes"]
        + tq["by_kind"]["all_gather"]["wire_bytes"]
    )
    assert ring_quant == _ROUTED_OPS * _quant_ring_bytes_per_reduce(2)
    # quantized mode replaces the routed psums: only the ONE residual
    # (slots x hidden) f32 reduce survives in the quant table
    (residual,) = _routed_detail(tq, 2)
    assert residual["ops"] == 1, tq["detail"]
    routed_exact = _ROUTED_OPS * _exact_ring_bytes_per_reduce(2)
    ratio = routed_exact / ring_quant
    assert ratio >= 3.9, f"static EQuARX ratio {ratio:.3f} < 3.9 at tp=2"
    # the ratchet basis is stable: a second lowering renders identically
    assert gv_ir.stable_table_basis(te) == gv_ir.stable_table_basis(
        verify_nb(exact.programs).audit("decode_chunk").collective_table
    )


@pytest.mark.slow  # the tp=4 mesh compile bill — the test_multichip
# precedent: the exact byte/donation models stay tier-1 via the unit
# tests above; both tp engine legs run in the full (slow-inclusive) suite
def test_tp4_engine_byte_table_and_static_ratio(comms_model):
    """The tp=4 leg: one exact engine pins the per-decode-chunk
    all-reduce byte table from the IR; the quantized side of the >= 3.9x
    ratio comes from the ring's closed-form byte arithmetic over the SAME
    pinned element counts (still static — no bench, and no second
    engine's compile bill)."""
    cfg, model, params = comms_model
    exact = _tp_engine(model, params, 4, quantized=False, paged=False)
    _drive(exact, cfg)
    rep = verify_nb(exact.programs)
    st = rep.stats()
    assert st["donations_dropped"] == 0 and st["transfer_ops"] == 0
    te = rep.audit("decode_chunk").collective_table
    assert set(te["by_kind"]) == {"all_reduce"}
    _assert_routed_table(te, 4)
    ratio = (
        _ROUTED_OPS * _exact_ring_bytes_per_reduce(4)
    ) / (_ROUTED_OPS * _quant_ring_bytes_per_reduce(4))
    assert ratio >= 3.9, f"static EQuARX ratio {ratio:.3f} < 3.9 at tp=4"


@pytest.mark.slow  # heavy spec-engine verify run (tier-1 budget,
# PR 5/13 lean-core policy): donation aliasing stays tier-1 via
# test_clean_donation_aliases_and_counts and
# test_injected_dropped_donation_flags_gv01
def test_speculative_engine_donations_all_aliased(tiny_model):
    """The spec chunk donates BOTH caches + slot state; every declared
    donation must reach the IR (mesh-free engine → exact
    tf.aliasing_output accounting), and the draft programs are
    transfer-free like the target's."""
    mesh_lib.destroy_model_parallel()
    cfg, model, params = tiny_model
    draft_cfg = tiny_llama(num_layers=1)
    draft = LlamaForCausalLM(draft_cfg, attention_impl="xla")
    ids = jax.random.randint(jax.random.PRNGKey(0), (1, 8), 1, cfg.vocab_size)
    d_params = draft.init(jax.random.PRNGKey(2), ids)
    engine = ServingEngine(
        model, params, num_slots=2, decode_chunk_size=2, prefix_cache=None,
        draft_model=draft, draft_params=d_params, gamma=2,
    )
    _drive(engine, cfg)
    rep = verify_nb(engine.programs)
    st = rep.stats()
    assert st["donations_dropped"] == 0 and st["transfer_ops"] == 0
    spec = rep.audit("spec_decode_chunk")
    assert spec is not None and spec.variants
    (v,) = spec.variants
    assert v.donations["dropped"] == []
    # both caches and the slot state donate: a large declared set, all
    # accounted aliased or pruned
    assert len(v.donations["declared"]) > 4
    assert not v.transfers


@pytest.fixture(scope="module")
def tiny_engine(tiny_model):
    """ONE mesh-free paged engine shared by the enumeration and
    host-sync-budget tests (each engine build is an XLA compile bill the
    tier-1 budget feels)."""
    cfg, model, params = tiny_model
    mesh_lib.destroy_model_parallel()
    engine = ServingEngine(
        model, params, num_slots=2, decode_chunk_size=4,
        prefix_cache=None, kv_page_size=8,
    )
    _drive(engine, cfg)
    return cfg, engine


@pytest.mark.slow  # heavy engine-enumeration verify run (tier-1 budget,
# PR 5/13 lean-core policy): verify-on-a-live-ledger (trace, never a
# compile) stays tier-1 via test_gv05_manifest_coverage_missing_stale_and_clean
# and test_gv05_prewarm_replays_do_not_fake_coverage
def test_enumeration_zero_compiles_zero_syncs(tiny_engine, monkeypatch):
    """ProgramLedger.programs() enumeration AND a full graftverify run
    re-trace but never compile and never sync: Lowered.compile is patched
    to raise, device_get counted, transfers guarded."""
    cfg, engine = tiny_engine
    led = engine.programs
    compiles_before = {
        name: info.compiles for name, info in led.programs().items()
    }

    from jax._src import stages as jax_stages

    def _boom(self, *a, **k):
        raise AssertionError("graftverify must never compile")

    monkeypatch.setattr(jax_stages.Lowered, "compile", _boom)

    calls = {"n": 0}
    real_get = jax.device_get

    def counting_get(x):
        calls["n"] += 1
        return real_get(x)

    monkeypatch.setattr(jax, "device_get", counting_get)
    # enumeration: pure host metadata under a transfer guard
    with jax.transfer_guard_device_to_host("disallow"):
        infos = led.programs()
        total = sum(i.dispatches + i.compiles for i in infos.values())
        assert total > 0
        names = [v.signature for i in infos.values() for v in i.variants]
        assert names
    rep = verify_nb(led)  # full verify: lowers (traces) every variant
    assert rep.stats()["variants_checked"] > 0
    assert calls["n"] == 0, "verification must not sync"
    compiles_after = {
        name: info.compiles for name, info in led.programs().items()
    }
    assert compiles_after == compiles_before


@pytest.mark.slow  # heavy in-process budget A/B (tier-1 budget, PR 5/13
# lean-core policy): the host-sync budget pins themselves stay tier-1 in
# tests/serving/test_host_sync.py
def test_host_sync_budgets_with_graftverify_in_process(tiny_engine):
    """ISSUE 15 acceptance: the pinned budgets (submit=1, admission=2,
    steady chunk=1) hold with a graftverify enumeration+verify having run
    in-process against the live engine's ledger."""
    cfg, engine = tiny_engine  # programs already warm
    rep = verify_nb(engine.programs)
    assert rep.stats()["variants_checked"] > 0

    class _SyncCounter:
        def __init__(self):
            self.calls = 0
            self._real = jax.device_get

        def __enter__(self):
            jax.device_get = self._counting
            return self

        def __exit__(self, *exc):
            jax.device_get = self._real

        def _counting(self, x):
            self.calls += 1
            return self._real(x)

    prompt = np.arange(1, 7, dtype=np.int32)
    gcfg = GenerationConfig(max_new_tokens=12, temperature=0.0)
    with _SyncCounter() as c:
        req = engine.submit(prompt, gcfg, key=jax.random.PRNGKey(7))
    assert c.calls == 1
    with _SyncCounter() as c:
        engine.step()  # admission + first chunk
    assert c.calls == 2
    with _SyncCounter() as c:
        engine.step()  # steady chunk
    assert c.calls == 1
    engine.run()
    assert req.state is RequestState.DONE


# --- CLI ----------------------------------------------------------------------


def test_cli_explain_and_select_validation(capsys):
    from neuronx_distributed_tpu.scripts.graftverify import cli

    assert cli.main(["--explain", "GV01"]) == 0
    assert "donation" in capsys.readouterr().out
    assert cli.main(["--explain", "GV99"]) == 2
    assert cli.main(["--select", "GVXX"]) == 2
    assert cli.main(["--tp", "0"]) == 2
    assert cli.main(["--tp-comms", "quant"]) == 2  # needs --tp > 1


@pytest.mark.slow  # heavy CLI end-to-end run (tier-1 budget, PR 5/13
# lean-core policy): CLI arg handling stays tier-1 via
# test_cli_explain_and_select_validation, the clean-repo contract via
# test_checked_in_baseline_is_empty
def test_cli_reference_workload_clean(capsys, tmp_path):
    """The CLI's tp=1 reference workload runs clean against an EMPTY
    baseline (the checked-in contract) and reports the verified-donation
    census in its summary line."""
    from neuronx_distributed_tpu.scripts.graftverify import cli

    mesh_lib.destroy_model_parallel()
    bl = tmp_path / "empty.json"
    rc = cli.main(["--baseline", str(bl), "--json"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "0 dropped" in out or '"donations_dropped": 0' in out
    payload = json.loads(out[: out.rindex("}") + 1])
    assert payload["stats"]["donations_dropped"] == 0
    assert payload["stats"]["transfer_ops"] == 0


# --- GV05: manifest coverage (AOT) --------------------------------------------


def test_gv05_manifest_coverage_missing_stale_and_clean(tmp_path):
    """GV05 arms only when a manifest is passed: a runtime-dispatched
    program absent from it flags missing-from-manifest; a manifest name
    no audited ledger knows flags stale; a manifest regenerated from the
    ledger is clean both ways. Accepts the object or a saved path."""
    from neuronx_distributed_tpu.inference import aot

    led = ProgramLedger()
    f = led.wrap("f", jax.jit(lambda x: x + 1))
    f(jnp.zeros(4))

    # clean: object form and saved-path form
    assert rules_of(verify_nb(led, select={"GV05"}, manifest=led.manifest())) == []
    path = led.manifest().save(str(tmp_path))
    assert rules_of(verify_nb(led, select={"GV05"}, manifest=path)) == []

    # missing: dispatched at runtime, absent from the prewarm manifest
    nb = verify_nb(led, select={"GV05"}, manifest=aot.ProgramManifest({}, {}))
    assert rules_of(nb) == ["GV05"]
    [v] = nb.findings
    assert v.snippet == "f:missing-from-manifest" and v.path == "<t/f>"

    # stale: manifest names a program no audited ledger knows
    m = led.manifest()
    m.programs["ghost"] = []
    nb = verify_nb(led, select={"GV05"}, manifest=m)
    assert [v.snippet for v in nb.findings] == ["ghost:stale-manifest-entry"]
    assert nb.findings[0].path == "<manifest/ghost>"

    # unarmed (no manifest) and deselected: GV05 stays silent
    assert rules_of(verify_nb(led, select={"GV05"})) == []
    nb = verify_nb(led, select={"GV01"}, manifest=aot.ProgramManifest({}, {}))
    assert rules_of(nb) == []


def test_gv05_prewarm_replays_do_not_fake_coverage():
    """dispatches excludes prewarm replays by construction (the ledger
    routes them to prewarm_dispatches), so a prewarm-only program demands
    nothing — and the first REAL dispatch starts demanding coverage."""
    from neuronx_distributed_tpu.inference import aot

    led = ProgramLedger()
    g = led.wrap("g", jax.jit(lambda x: x * 2))
    with led.prewarming():
        g(jnp.zeros(3))
    empty = aot.ProgramManifest({}, {})
    assert rules_of(verify_nb(led, select={"GV05"}, manifest=empty)) == []
    g(jnp.zeros(3))  # runtime traffic
    nb = verify_nb(led, select={"GV05"}, manifest=empty)
    assert [v.snippet for v in nb.findings] == ["g:missing-from-manifest"]
