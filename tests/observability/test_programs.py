"""Device-efficiency observability (ISSUE 12): the compiled-program ledger,
HBM accounting, and their graceful degradation on the CPU backend.

Pins, in order of load-bearing-ness:

* the ledger snapshot SCHEMA on this container — cost analysis is REAL
  (``Lowered.cost_analysis`` works on CPU), memory analysis degrades to
  explicit ``"unavailable"`` markers unless opted into, device peaks are
  ``"unavailable"`` (unknown CPU kind) — never a crash, never a skewed
  number;
* recompile accumulation — a program registered twice (the engine's lazy
  fallback rebuild, a second ``fit()``) accumulates into ONE record
  instead of double-counting or resetting;
* determinism — two identical engine runs produce byte-identical
  ``snapshot()["programs"]``/``["hbm"]`` projections once wall-clock
  fields are excluded (``include_timing=False``);
* the HBM ledger's resident accounting + ``plan()`` capacity math.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.observability import (
    HBMLedger,
    MetricsRegistry,
    ProgramLedger,
    UNAVAILABLE,
    device_peaks,
    record_device_memory,
    tree_nbytes,
)


# --- ProgramLedger unit level -------------------------------------------------


def test_wrap_counts_dispatches_and_detects_compiles():
    led = ProgramLedger()
    f = led.wrap("mm", jax.jit(lambda x: (x @ x).sum()))
    x = jnp.ones((16, 16))
    f(x).block_until_ready()
    assert f.last_call_compiled
    f(x)
    assert not f.last_call_compiled
    rec = led.record("mm")
    assert rec.dispatches == 2 and rec.compiles == 1
    assert rec.compile_wall_s > 0.0


def test_every_call_runs_inside_one_program_span_with_its_two_names(monkeypatch):
    """``nxd.program``: one span a call through the one ``span`` primitive,
    the ledger's name and the module's fixed when the program is wrapped, no
    timeline and no step ledger (a bare profiler annotation); a raising call
    closes it, and still counts its compile."""
    from neuronx_distributed_tpu.observability import programs, tracing

    seen = []

    class Recording:
        def __init__(self, name, **stats):
            self.row = [name, stats, None]

        def __enter__(self):
            seen.append(self.row)
            self.row[2] = "open"
            return self

        def __exit__(self, kind, *_):
            self.row[2] = "closed" if kind is None else f"closed on {kind.__name__}"
            return False

    real = tracing.span
    kwargs = []

    def span(name, *args, **kw):
        kwargs.append((args, {k: v for k, v in kw.items() if k in ("ledger", "annotation")}))
        return real(name, *args, annotation=Recording, **kw)

    monkeypatch.setattr(programs.tracing, "span", span)
    led = ProgramLedger()

    def chunk_fn(x, fail=False):
        if fail:
            raise ValueError("traced and refused")
        return x * 2

    f = led.wrap("decode_chunk", jax.jit(chunk_fn, static_argnums=(1,)))
    f(jnp.ones(4))
    f(jnp.ones(4))
    with pytest.raises(ValueError):
        f(jnp.ones(4), True)
    stats = {"program": "decode_chunk", "module": "jit_chunk_fn"}
    assert seen == [[tracing.PROGRAM, stats, "closed"]] * 2 + [[tracing.PROGRAM, stats, "closed on ValueError"]]
    assert kwargs == [((), {})] * 3                     # no timeline, no step ledger
    assert led.record("decode_chunk").dispatches == 2
    # with no session the real primitive hands back the profiler's own annotation
    assert type(real(tracing.PROGRAM, **stats)) is jax.profiler.TraceAnnotation
    # the module's name as JAX spells it: what is no word character becomes "_", the last ones go
    assert programs.module_name(jax.jit(lambda x: x)) == "jit__lambda"
    assert programs.module_name(object()) == ""
    g = led.wrap("lam", jax.jit(lambda x: x + 1))
    assert "HloModule jit__lambda," in g.lower(1.0).compile().as_text()


def test_cost_analysis_schema_on_this_container():
    """Cost analysis is AVAILABLE on this CPU (lowered.cost_analysis);
    memory analysis stays UNAVAILABLE without the opt-in — the explicit
    degradation contract, pinned."""
    led = ProgramLedger()
    f = led.wrap("mm", jax.jit(lambda x, y: x @ y, donate_argnums=(0,)))
    f(jnp.ones((32, 32)), jnp.ones((32, 32)))
    entry = led.snapshot()["by_program"]["mm"]
    assert isinstance(entry["flops_per_dispatch"], float)
    assert entry["flops_per_dispatch"] > 0
    assert isinstance(entry["bytes_per_dispatch"], float)
    assert entry["cost_source"] == "lowered.cost_analysis"
    assert entry["donated_argnums"] == [0]
    assert isinstance(entry["arithmetic_intensity"], float)
    assert entry["flops_total"] == entry["flops_per_dispatch"]
    # memory analysis needs an AOT compile the default never pays for
    assert all(v == UNAVAILABLE for v in entry["memory"].values())


def test_memory_analysis_opt_in_pins_container_gaps():
    """memory_analysis=True pays one AOT compile per signature and gets
    real argument/output/temp/alias/peak bytes on this CPU."""
    led = ProgramLedger(memory_analysis=True)
    f = led.wrap("mm", jax.jit(lambda x: jnp.tanh(x @ x)))
    f(jnp.ones((32, 32)))
    mem = led.snapshot()["by_program"]["mm"]["memory"]
    assert isinstance(mem["argument_bytes"], int)
    assert isinstance(mem["output_bytes"], int) and mem["output_bytes"] > 0
    assert isinstance(mem["temp_bytes"], int)
    assert isinstance(mem["alias_bytes"], int)
    assert isinstance(mem["peak_bytes"], int) and mem["peak_bytes"] > 0


def test_recompile_accumulates_never_double_counts():
    """A program registered twice (recompile / lazy rebuild) shares ONE
    record: dispatches sum across both proxies, compiles count each real
    XLA compile, and the snapshot shows one entry."""
    led = ProgramLedger()
    a = led.wrap("step", jax.jit(lambda x: x + 1))
    b = led.wrap("step", jax.jit(lambda x: x + 1))
    x = jnp.ones((4,))
    a(x), a(x), b(x), b(x), b(x)
    rec = led.record("step")
    assert rec.dispatches == 5
    assert rec.compiles == 2  # two distinct jit objects each compiled once
    snap = led.snapshot()
    assert list(snap["by_program"]) == ["step"]
    assert snap["totals"]["dispatches"] == 5


def test_multi_signature_program_reports_variants():
    led = ProgramLedger()
    f = led.wrap("poly", jax.jit(lambda x: x * 2))
    f(jnp.ones((4,)))
    f(jnp.ones((8,)))
    entry = led.snapshot()["by_program"]["poly"]
    assert entry["variants"] == 2
    # per-dispatch cost is undefined across signatures — explicit, not 0
    assert entry["flops_per_dispatch"] == UNAVAILABLE
    assert len(entry["variant_cost"]) == 2


def test_compile_detection_survives_raising_dispatch():
    """Review fix: a compile-then-execution-failure warms the pjit cache,
    so the retry never trips the cache-size delta — the compile must be
    noted in the failing call's finally or the program's signature (and
    all cost analysis) is lost for the process lifetime."""

    class FakeJit:
        def __init__(self):
            self.n = 0

        def _cache_size(self):
            return self.n

        def __call__(self, *args):
            self.n = 1  # the compile happened...
            raise RuntimeError("device OOM")  # ...then execution died

    led = ProgramLedger()
    prog = led.wrap("oomer", FakeJit())
    with pytest.raises(RuntimeError):
        prog(jnp.ones((4,)))
    rec = led.record("oomer")
    assert rec.compiles == 1  # the compile was seen despite the raise
    assert rec.dispatches == 0  # but a failed call is not a dispatch
    assert prog.last_call_compiled
    assert len(rec.variants) == 1  # signature captured for later analysis
    # the (now warm) retry succeeds and counts normally, no double compile
    FakeJit.__call__ = lambda self, *a: a[0]
    prog(jnp.ones((4,)))
    assert rec.compiles == 1 and rec.dispatches == 1


def test_untrackable_callable_degrades_to_dispatch_counts():
    led = ProgramLedger()
    f = led.wrap("plain", lambda x: x + 1)
    assert f(1) == 2
    entry = led.snapshot()["by_program"]["plain"]
    assert entry["dispatches"] == 1 and entry["compiles"] == 0
    assert entry["flops_per_dispatch"] == UNAVAILABLE


def test_observe_wall_derives_roofline_fields():
    led = ProgramLedger()
    f = led.wrap("mm", jax.jit(lambda x: x @ x))
    f(jnp.ones((64, 64)))
    led.observe_wall("mm", 0.002)
    entry = led.snapshot()["by_program"]["mm"]
    assert entry["wall"]["count"] == 1
    flops = entry["flops_per_dispatch"]
    assert entry["achieved_flops_p50"] == pytest.approx(
        flops / entry["wall"]["p50_s"]
    )
    # unknown CPU peaks -> MFU/bandwidth degrade explicitly
    assert entry["mfu_p50"] == UNAVAILABLE
    assert entry["hbm_bw_util_p50"] == UNAVAILABLE


def test_device_peaks_unknown_on_cpu():
    p = device_peaks()
    assert p["flops"] == UNAVAILABLE
    assert p["hbm_bytes_per_s"] == UNAVAILABLE
    assert "unknown" in p["source"]


def test_ledger_prometheus_families_labeled_by_program():
    reg = MetricsRegistry()
    led = ProgramLedger(registry=reg, prefix="serving")
    f = led.wrap("mm", jax.jit(lambda x: x @ x))
    f(jnp.ones((8, 8)))
    text = reg.prometheus_text()
    assert 'serving_program_dispatches{program="mm"} 1' in text
    assert 'serving_program_compiles{program="mm"} 1' in text
    # lazily-resolved flops gauge exports the real compiler number
    assert 'serving_program_flops{program="mm"}' in text


# --- HBM ledger ---------------------------------------------------------------


def test_hbm_residents_plan_and_container_degradation():
    hbm = HBMLedger()
    hbm.add_resident("params", {"w": jnp.ones((64, 64), jnp.float32)})
    hbm.add_resident(
        "pages", lambda: 8 * 1024, unit_bytes=1024, count=8, unit="page"
    )
    snap = hbm.snapshot()
    assert snap["residents"]["params"]["bytes"] == 64 * 64 * 4
    assert snap["residents"]["pages"] == {
        "bytes": 8192, "unit_bytes": 1024, "unit": "page", "count": 8
    }
    assert snap["resident_bytes_total"] == 64 * 64 * 4 + 8192
    # CPU memory_stats has no limit: every device-derived field degrades
    for key in ("bytes_limit", "bytes_in_use", "utilization",
                "unaccounted_bytes"):
        assert snap[key] == UNAVAILABLE
    # no budget + no limit -> explicit unavailable, never a guess
    assert hbm.plan()["budget_bytes"] == UNAVAILABLE
    # explicit budget -> exact unit math
    plan = hbm.plan(budget_bytes=snap["resident_bytes_total"] + 10 * 1024)
    assert plan["free_bytes"] == 10 * 1024
    assert plan["fits"]["pages"]["additional"] == 10
    assert plan["fits"]["pages"]["max_total"] == 18


def test_tree_nbytes_survives_donation_metadata():
    x = jnp.ones((32, 32))
    n = tree_nbytes({"x": x})
    f = jax.jit(lambda t: {"x": t["x"] + 1}, donate_argnums=(0,))
    f({"x": x})
    assert x.is_deleted()
    assert tree_nbytes({"x": x}) == n  # aval metadata, no buffer touch


def test_record_device_memory_utilization_gauge():
    """Satellite: bytes_limit + a memory_utilization fraction per device;
    backends omitting the limit skip the fraction quietly."""

    class _Dev:
        def __init__(self, stats):
            self._stats = stats

        def memory_stats(self):
            return self._stats

    devs = [
        _Dev({"bytes_in_use": 50, "peak_bytes_in_use": 75,
              "bytes_limit": 200}),
        _Dev({"bytes_in_use": 10}),  # no limit -> no fraction
        _Dev(None),  # no stats at all -> skipped entirely
    ]
    reg = MetricsRegistry()
    orig = jax.local_devices
    jax.local_devices = lambda: devs
    try:
        reported = record_device_memory(reg)
    finally:
        jax.local_devices = orig
    assert reported == 2
    assert reg.get("device0_bytes_limit").value == 200
    assert reg.get("device0_memory_utilization").value == pytest.approx(0.25)
    assert reg.get("device1_bytes_in_use").value == 10
    assert reg.get("device1_memory_utilization") is None


# --- engine integration -------------------------------------------------------


@pytest.fixture(scope="module")
def engine_setup():
    from neuronx_distributed_tpu.models.llama import (
        LlamaForCausalLM,
        tiny_llama,
    )

    cfg = tiny_llama()
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    ids = jax.random.randint(
        jax.random.PRNGKey(0), (1, 8), 1, cfg.vocab_size
    )
    params = model.init(jax.random.PRNGKey(1), ids)
    return cfg, model, params


def _run_engine(model, params, kv_page_size=None, kv_num_pages=None):
    from neuronx_distributed_tpu.inference import GenerationConfig
    from neuronx_distributed_tpu.serving import ServingEngine

    engine = ServingEngine(
        model, params, num_slots=2, decode_chunk_size=4,
        kv_page_size=kv_page_size, kv_num_pages=kv_num_pages,
    )
    gcfg = GenerationConfig(max_new_tokens=8, temperature=0.0)
    for i in range(2):
        engine.submit(
            np.arange(1 + i, 7 + i, dtype=np.int32), gcfg,
            key=jax.random.PRNGKey(7 + i),
        )
    engine.run()
    return engine


def test_engine_snapshot_carries_programs_and_hbm(engine_setup):
    cfg, model, params = engine_setup
    engine = _run_engine(model, params)
    snap = engine.metrics.snapshot()
    by = snap["programs"]["by_program"]
    # the serving hot programs are all ledgered
    for name in ("decode_chunk", "prefill[8]", "slot_write", "first_token",
                 "cache_admit"):
        assert name in by, name
    dc = by["decode_chunk"]
    assert dc["dispatches"] >= 2 and dc["compiles"] == 1
    assert isinstance(dc["flops_per_dispatch"], float)
    assert dc["donated_argnums"] != UNAVAILABLE
    # roofline: measured chunk walls (compile chunk excluded) yield
    # achieved FLOPs even without device peaks
    assert dc["wall"]["count"] >= 1
    assert isinstance(dc["achieved_flops_p50"], float)
    assert dc["mfu_p50"] == UNAVAILABLE  # unknown CPU peak, pinned
    # HBM: residents accounted, device fields degrade on CPU
    hbm = snap["hbm"]
    assert hbm["residents"]["params"]["bytes"] == tree_nbytes(params)
    assert hbm["residents"]["kv_cache"]["bytes"] > 0
    assert hbm["bytes_limit"] == UNAVAILABLE
    # plan() in slot units off an explicit budget
    plan = engine.hbm.plan(budget_bytes=hbm["resident_bytes_total"] * 2)
    assert plan["fits"]["kv_cache"]["additional"] >= 1


def test_engine_snapshot_deterministic_across_identical_runs(engine_setup):
    """Acceptance pin: snapshot()["programs"]/["hbm"] are deterministic
    across two identical runs on this container once wall-clock fields
    are excluded (include_timing=False drops them)."""
    cfg, model, params = engine_setup
    a = _run_engine(model, params)
    b = _run_engine(model, params)
    pa = json.dumps(a.programs.snapshot(include_timing=False), sort_keys=True)
    pb = json.dumps(b.programs.snapshot(include_timing=False), sort_keys=True)
    assert pa == pb
    ha = json.dumps(a.hbm.snapshot(), sort_keys=True)
    hb = json.dumps(b.hbm.snapshot(), sort_keys=True)
    assert ha == hb
    # and the streams the ledgered engines produced are identical too
    assert a.metrics.decode_tokens == b.metrics.decode_tokens


def test_paged_engine_accounts_pages(engine_setup):
    cfg, model, params = engine_setup
    engine = _run_engine(model, params, kv_page_size=8, kv_num_pages=16)
    snap = engine.metrics.snapshot()
    pages = snap["hbm"]["residents"]["kv_pages"]
    assert pages["bytes"] > 0 and pages["unit"] == "page"
    assert pages["unit_bytes"] > 0
    assert pages["count"] == engine.cache.alloc.capacity
    # paged admission programs are ledgered under their own names
    assert "paged_admit" in snap["programs"]["by_program"]
    plan = engine.hbm.plan(
        budget_bytes=snap["hbm"]["resident_bytes_total"]
        + 4 * pages["unit_bytes"]
    )
    assert plan["fits"]["kv_pages"]["additional"] == 4


def test_model_builder_trace_records_aot_programs():
    """The inference builder's lower().compile() path records cost AND
    memory eagerly (the Compiled is in hand — zero extra compiles), and
    routed calls dispatch-count through the ledger."""
    from neuronx_distributed_tpu.inference.model_builder import ModelBuilder

    led = ProgramLedger()
    builder = ModelBuilder()
    builder.add(
        "logits", lambda x: x @ jnp.ones((8, 8)),
        bucket_args=[(jnp.ones((4, 8)),), (jnp.ones((16, 8)),)],
        bucket_dim=0,
    )
    model = builder.trace(programs=led)
    model("logits", jnp.ones((3, 8)))
    snap = led.snapshot()["by_program"]
    assert set(snap) == {"logits[4]", "logits[16]"}
    e = snap["logits[4]"]
    assert e["compiles"] == 1 and e["dispatches"] == 1
    assert isinstance(e["flops_per_dispatch"], float)
    # memory analysis rode the already-compiled executable for free
    assert isinstance(e["memory"]["argument_bytes"], int)
    assert isinstance(e["memory"]["peak_bytes"], int)


def test_trainer_ledger_and_halt_extras(tmp_path):
    """Trainer side: train_step ledgered with real compiler FLOPs, the
    HBM ledger carries params/opt_state, and a halt post-mortem carries
    both as flat tables that survive the depth-3 redaction."""
    from neuronx_distributed_tpu.models.llama import (
        LlamaForCausalLM,
        tiny_llama,
    )
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib
    from neuronx_distributed_tpu.trainer.loop import Trainer

    if not mesh_lib.model_parallel_is_initialized():
        mesh_lib.initialize_model_parallel()
    cfg = tiny_llama()

    def batches(n=50, bs=8, seq=16):
        key = jax.random.PRNGKey(0)
        for i in range(n):
            ids = jax.random.randint(
                jax.random.fold_in(key, i), (bs, seq), 0, cfg.vocab_size
            )
            yield {"input_ids": ids, "labels": jnp.roll(ids, -1, 1)}

    trainer = Trainer(model=LlamaForCausalLM(cfg, attention_impl="xla"))
    trainer.fit(batches(), jax.random.PRNGKey(1), max_steps=3)
    entry = trainer.programs.snapshot()["by_program"]["train_step"]
    assert entry["dispatches"] == 3 and entry["compiles"] == 1
    assert isinstance(entry["flops_per_dispatch"], float)
    hbm = trainer.hbm.halt_summary()
    assert hbm["resident_params_bytes"] > 0
    assert hbm["resident_opt_state_bytes"] > 0
    assert hbm["bytes_limit"] == UNAVAILABLE
    # graftverify closes the training side of the ISSUE 15 acceptance:
    # the train step's declared donations all reach the lowered IR
    # (aliased, deferred-to-XLA under the mesh, or pruned-unused), and
    # the program is transfer-free
    from neuronx_distributed_tpu.scripts.graftverify import verify

    rep = verify({"training": trainer.programs}, use_baseline=False)
    st = rep.stats()
    assert st["variants_checked"] >= 1
    assert st["donations_declared"] > 0
    assert st["donations_dropped"] == 0
    assert st["transfer_ops"] == 0
    assert not any(f.rule in ("GV01", "GV02") for f in rep.findings)


# --- programs() public enumeration (ISSUE 15) ---------------------------------


def test_programs_enumeration_api():
    """programs() is the supported surface for external verifiers:
    read-only views with counts and per-variant lazy lower() handles —
    graftverify iterates this, never the private records."""
    led = ProgramLedger()
    f = led.wrap("mm", jax.jit(lambda x, y: x @ y, donate_argnums=(0,)))
    f(jnp.ones((8, 8)), jnp.ones((8, 8)))
    f(jnp.ones((8, 8)), jnp.ones((8, 8)))
    infos = led.programs()
    assert list(infos) == ["mm"]
    info = infos["mm"]
    assert info.dispatches == 2 and info.compiles == 1
    (var,) = info.variants
    assert var.captured
    low = var.lower()
    # the Lowered is the real thing: declared donation visible on it
    donated = [
        a.donated for a in jax.tree_util.tree_leaves(low.args_info)
    ]
    assert donated == [True, False]


def test_variant_lower_survives_cost_analysis():
    """ensure() consumes `pending` for the memoized cost analysis; the
    enumeration handle must still lower AFTERWARDS (the abstract call is
    retained past analysis) — snapshot() then programs().lower() is the
    graftverify-after-bench ordering."""
    led = ProgramLedger()
    f = led.wrap("mm", jax.jit(lambda x: (x @ x).sum()))
    f(jnp.ones((8, 8)))
    snap = led.snapshot()  # runs the deferred analysis
    assert isinstance(
        snap["by_program"]["mm"]["flops_per_dispatch"], float
    )
    (var,) = led.programs()["mm"].variants
    low = var.lower()
    assert low is not None and hasattr(low, "compiler_ir")


def test_programs_enumeration_zero_compiles_and_syncs(monkeypatch):
    """The ISSUE 15 regression pin at the unit level: enumeration touches
    ONLY host metadata — no XLA compile (Lowered.compile patched to
    raise), no device_get, and it holds under a device->host transfer
    guard. Even variant.lower() is a pure trace."""
    led = ProgramLedger()
    f = led.wrap("mm", jax.jit(lambda x: x * 2))
    f(jnp.ones((4,)))

    from jax._src import stages as jax_stages

    def _boom(self, *a, **k):
        raise AssertionError("enumeration must never compile")

    monkeypatch.setattr(jax_stages.Lowered, "compile", _boom)
    calls = {"n": 0}
    real_get = jax.device_get

    def counting(x):
        calls["n"] += 1
        return real_get(x)

    monkeypatch.setattr(jax, "device_get", counting)
    with jax.transfer_guard_device_to_host("disallow"):
        infos = led.programs()
        info = infos["mm"]
        assert info.dispatches == 1 and info.compiles == 1
        (var,) = info.variants
        assert var.signature and var.captured
        assert var.abstract_args is not None
        low = var.lower()  # trace only
        assert low is not None
    assert calls["n"] == 0
    assert led.record("mm").compiles == 1


# --- AOT capture (ISSUE 17): pedigree, prewarm routing, manifest --------------


def test_pedigree_captured_per_leaf_at_compile():
    """Each compile records the CONCRETE call's per-leaf dispatch-key
    pedigree (np vs jax vs static) in flatten order — the manifest codec
    zips against it so an AOT replay lands in the same dispatch entry."""
    led = ProgramLedger()
    f = led.wrap("mix", jax.jit(lambda x, y: x + y))
    f(np.ones((4,), np.float32), jnp.ones((4,)))
    (var,) = led.programs()["mix"].variants
    assert var.pedigree == [{"kind": "np"}, {"kind": "jax"}]


def test_prewarming_scope_routes_dispatch_accounting():
    """Inside prewarming(): compiles count (the replay EATS them — the
    decode_compilations contract), dispatches route to
    prewarm_dispatches so runtime traffic accounting stays clean (and
    GV05 coverage cannot be faked by a replay)."""
    led = ProgramLedger()
    f = led.wrap("pw", jax.jit(lambda x: x * 2))
    with led.prewarming():
        f(jnp.zeros(3))
    info = led.programs()["pw"]
    assert info.dispatches == 0 and info.prewarm_dispatches == 1
    assert info.compiles == 1
    f(jnp.zeros(3))
    info = led.programs()["pw"]
    assert info.dispatches == 1 and info.prewarm_dispatches == 1
    assert info.compiles == 1  # the real dispatch was a pure cache hit


def test_ledger_manifest_entries_replay():
    """ledger.manifest() emits a portable entry per captured variant;
    materialize_call rebuilds dummies with the recorded shapes."""
    from neuronx_distributed_tpu.inference.aot import materialize_call

    led = ProgramLedger()
    f = led.wrap("m", jax.jit(lambda x: x + 1))
    f(jnp.zeros((2, 2)))
    m = led.manifest()
    (entry,) = m.entries("m")
    assert entry["portable"] and entry["signature"]
    args, kwargs = materialize_call(entry["call"])
    assert not kwargs and args[0].shape == (2, 2)
    assert str(args[0].dtype) == "float32"
