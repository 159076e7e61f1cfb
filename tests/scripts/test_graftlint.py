"""graftlint: the repo-native static-analysis suite (scripts/graftlint/).

Covers: at least one true positive AND one clean negative per rule
GL01-GL05, pragma suppression (incl. the mandatory-reason contract),
the baseline ratchet (add / fix-shrinks / stale-fails), the repo-wide
tier-1 run (zero non-baselined violations — fast, pure AST), and the
acceptance re-injection checks: the PR 2 donated-leaf ``device_get`` bug
or a raw ``jax.experimental.shard_map`` import in ``serving/`` must make
the lint fail."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from neuronx_distributed_tpu.scripts.graftlint import baseline as baseline_mod
from neuronx_distributed_tpu.scripts.graftlint import runner

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
PKG = os.path.join(REPO_ROOT, "neuronx_distributed_tpu")


def lint(tmp_path, code, name="snippet.py"):
    p = tmp_path / name
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(code))
    return runner.scan([str(p)], root=str(tmp_path)).violations


def rules_of(violations):
    return sorted({v.rule for v in violations})


# --- GL01 donation-aliasing ---------------------------------------------------

GL01_POSITIVE = """\
    import jax
    import jax.numpy as jnp

    class Engine:
        def __init__(self):
            self._decode = jax.jit(lambda p, c, s: (c, s), donate_argnums=(1, 2))

        def step(self, params):
            cache, self._state = self._decode(params, self._cache, self._state)
            jax.device_get(self._state["keys"])  # the PR 2 bug, verbatim
"""


def test_gl01_donated_leaf_device_get(tmp_path):
    v = lint(tmp_path, GL01_POSITIVE)
    assert "GL01" in rules_of(v)
    assert any("_state" in x.message for x in v if x.rule == "GL01")


def test_gl01_cross_method_read_of_donated_attr(tmp_path):
    # PR 2's actual shape: the device_get lived in a SIBLING method
    # (`_pull_key`), not next to the dispatch
    v = lint(tmp_path, """\
        import jax

        class Engine:
            def __init__(self):
                self._decode = jax.jit(lambda p, s: s, donate_argnums=(1,))

            def step(self, params):
                self._state = self._decode(params, self._state)

            def pull_key(self, slot):
                return jax.device_get(self._state["keys"])[slot]
    """)
    assert "GL01" in rules_of(v)


def test_gl01_decorated_donated_param(tmp_path):
    v = lint(tmp_path, """\
        import jax
        from functools import partial

        @partial(jax.jit, donate_argnums=(0,))
        def update(state, x):
            bad = float(state["loss"])
            return state, bad
    """)
    assert "GL01" in rules_of(v)


def test_gl01_negative_copy_output_pattern(tmp_path):
    # the CORRECT pattern: read the chunk's copied output, not the donated
    # tree; rebinding between two dispatches is also fine
    v = lint(tmp_path, """\
        import jax

        class Engine:
            def __init__(self):
                self._decode = jax.jit(lambda p, s: (s, s["k"]), donate_argnums=(1,))

            def step(self, params):
                self._state, snap = self._decode(params, self._state)
                self._state, snap = self._decode(params, self._state)
                return jax.device_get(snap)
    """)
    assert [x for x in v if x.rule == "GL01"] == []


def test_gl01_second_dispatch_without_rebinding(tmp_path):
    v = lint(tmp_path, """\
        import jax

        def run(params, state):
            step = jax.jit(lambda p, s: s, donate_argnums=(1,))
            a = step(params, state)
            b = step(params, state)  # state was consumed by the first call
            return a, b
    """)
    assert any(
        "second donating dispatch" in x.message for x in v if x.rule == "GL01"
    )


def test_gl01_branch_exclusive_dispatches_not_flagged(tmp_path):
    # if/else (and try-body/except) arms are mutually exclusive — only one
    # dispatch runs, no buffer is consumed twice (review round 1)
    v = lint(tmp_path, """\
        import jax

        def run(params, state, fast):
            step = jax.jit(lambda p, s: s, donate_argnums=(1,))
            if fast:
                out = step(params, state)
            else:
                out = step(params, state)
            return out
    """)
    assert [x for x in v if x.rule == "GL01"] == []


# --- GL02 host-sync-in-hot-path ----------------------------------------------

GL02_POSITIVE = """\
    # graftlint: hot-path
    import jax
    import jax.numpy as jnp

    def hot_loop(xs):
        total = jnp.sum(xs)
        n = int(total)              # implicit sync
        if total > 0:               # branch on device value
            n += 1
        host = jax.device_get(total)  # undocumented explicit sync
        return n, host
"""


def test_gl02_hot_module_syncs(tmp_path):
    v = [x for x in lint(tmp_path, GL02_POSITIVE) if x.rule == "GL02"]
    msgs = " | ".join(x.message for x in v)
    assert len(v) == 3
    assert "int()" in msgs and "`if`" in msgs and "device_get" in msgs


def test_gl02_quiet_outside_hot_modules(tmp_path):
    # same code without the hot-path marker (and not one of the four named
    # hot modules): GL02 does not apply
    code = GL02_POSITIVE.replace("# graftlint: hot-path\n", "")
    assert [x for x in lint(tmp_path, code) if x.rule == "GL02"] == []


def test_gl02_host_values_not_flagged(tmp_path):
    # laundering through device_get makes later coercions free — the taint
    # layer must not flag host math (the readback-unpack pattern in
    # engine._decode)
    v = lint(tmp_path, """\
        # graftlint: hot-path
        import jax
        import jax.numpy as jnp
        import numpy as np

        def chunk(step, state):
            toks, counts = step(state)
            toks, counts = jax.device_get((toks, counts))  # graftlint: ok[GL02] the one per-chunk sync
            total = int(counts.sum())
            flat = np.asarray(toks)
            if total > 0:
                return flat
            return None
    """)
    assert [x for x in v if x.rule == "GL02"] == []


def test_gl02_named_hot_module_path(tmp_path):
    # the four contract modules are hot by PATH, no marker needed
    v = lint(
        tmp_path,
        """\
        import jax.numpy as jnp

        def f(x):
            return float(jnp.sum(x))
        """,
        name="serving/engine.py",
    )
    assert "GL02" in rules_of(v)


def test_gl02_metadata_reads_not_flagged(tmp_path):
    # len()/.shape/.ndim/.dtype on a jax.Array are host-side metadata, not
    # syncs (review round 1)
    v = lint(tmp_path, """\
        # graftlint: hot-path
        import jax.numpy as jnp

        def f(xs):
            y = jnp.cumsum(xs)
            n = len(y)
            m = int(y.shape[0])
            k = int(y.ndim)
            return n + m + k
    """)
    assert [x for x in v if x.rule == "GL02"] == []


def test_gl02_observability_emit_paths_are_hot(tmp_path):
    """ISSUE 8 satellite: the observability emit paths (metric record /
    trace emit functions called from engine/trainer inner loops) are on
    the hot-path list BY PATH — an implicit sync smuggled into future
    instrumentation trips GL02 with no marker needed."""
    code = """\
        import jax.numpy as jnp

        def observe(h, x):
            h.observe(float(jnp.sum(x)))
        """
    for name in (
        "observability/registry.py",
        "observability/tracing.py",
        "observability/flight_recorder.py",
        "serving/metrics.py",
        "utils/timeline.py",
    ):
        assert "GL02" in rules_of(lint(tmp_path, code, name=name)), name
    # ...and the shipped emit modules themselves scan clean
    targets = [
        os.path.join(PKG, "observability", "registry.py"),
        os.path.join(PKG, "observability", "tracing.py"),
        os.path.join(PKG, "observability", "flight_recorder.py"),
        os.path.join(PKG, "serving", "metrics.py"),
        os.path.join(PKG, "utils", "timeline.py"),
    ]
    assert all(os.path.exists(t) for t in targets)
    report = runner.scan(targets, root=REPO_ROOT)
    assert report.violations == []


def test_gl02_slo_and_traffic_modules_are_hot(tmp_path):
    """ISSUE 11 satellite: the SLO tracker's record paths run inside the
    engine's chunk-boundary bookkeeping and the traffic replay loop wraps
    engine.step() — both are hot BY PATH, so an implicit sync smuggled
    into either trips GL02 with no marker needed."""
    fixture = """\
        import jax.numpy as jnp

        def record(tracker, x):
            tracker.record_finish("t", float(jnp.sum(x)), None, 1, 0.0)
        """
    for name in ("observability/slo.py", "serving/traffic.py"):
        assert "GL02" in rules_of(lint(tmp_path, fixture, name=name)), name
    # an explicit undocumented device_get in the replay loop trips too
    v = lint(tmp_path, """\
        import jax

        def replay_step(engine, state):
            engine.step()
            return jax.device_get(state)
        """, name="serving/traffic.py")
    assert any("device_get" in x.message for x in v if x.rule == "GL02")
    # ...and the shipped modules scan clean
    targets = [
        os.path.join(PKG, "observability", "slo.py"),
        os.path.join(PKG, "serving", "traffic.py"),
    ]
    assert all(os.path.exists(t) for t in targets)
    report = runner.scan(targets, root=REPO_ROOT)
    assert report.violations == []


def test_gl02_programs_and_hbm_modules_are_hot(tmp_path):
    """ISSUE 12 satellite: the program ledger's dispatch proxy runs INSIDE
    every hot jit call and the HBM ledger's resident reads sit next to
    device trees — both are hot BY PATH, so an implicit sync smuggled into
    either trips GL02 with no marker needed."""
    fixture = """\
        import jax.numpy as jnp

        def record_dispatch(rec, out):
            rec.flops_seen += float(jnp.sum(out))
        """
    for name in ("observability/programs.py", "observability/hbm.py"):
        assert "GL02" in rules_of(lint(tmp_path, fixture, name=name)), name
    # an undocumented explicit device_get in the ledger trips too (the
    # whole point: accounting must never sync the dispatches it meters)
    v = lint(tmp_path, """\
        import jax

        def resident_bytes(tree):
            return sum(a.nbytes for a in jax.device_get(tree))
        """, name="observability/hbm.py")
    assert any("device_get" in x.message for x in v if x.rule == "GL02")
    # ...and the shipped modules scan clean
    targets = [
        os.path.join(PKG, "observability", "programs.py"),
        os.path.join(PKG, "observability", "hbm.py"),
    ]
    assert all(os.path.exists(t) for t in targets)
    report = runner.scan(targets, root=REPO_ROOT)
    assert report.violations == []


def test_gl02_router_disagg_sharding_modules_are_hot(tmp_path):
    """ISSUE 14 satellite: the replica router wraps every submission, the
    disaggregation server's handoff loop wraps every decode chunk, and the
    serving partitioner places live device trees — all three are hot BY
    PATH, so an implicit coercion smuggled into any of them trips GL02
    with no marker needed."""
    fixture = """\
        import jax.numpy as jnp

        def load_score(engine, pressure):
            return float(jnp.sum(pressure)) + engine.queued
        """
    for name in (
        "serving/router.py", "serving/disagg.py", "parallel/sharding.py"
    ):
        assert "GL02" in rules_of(lint(tmp_path, fixture, name=name)), name
    # an undocumented explicit device_get in the handoff loop trips too
    # (a handoff is a METADATA operation — reading staged KV back to host
    # would sync the very chunk boundary disaggregation protects)
    v = lint(tmp_path, """\
        import jax

        def handoff(engine, staged, logits):
            return engine.admit_staged(staged, jax.device_get(logits))
        """, name="serving/disagg.py")
    assert any("device_get" in x.message for x in v if x.rule == "GL02")
    # ...and the shipped modules scan clean
    targets = [
        os.path.join(PKG, "serving", "router.py"),
        os.path.join(PKG, "serving", "disagg.py"),
        os.path.join(PKG, "parallel", "sharding.py"),
    ]
    assert all(os.path.exists(t) for t in targets)
    report = runner.scan(targets, root=REPO_ROOT)
    assert report.violations == []


def test_gl02_sched_modules_are_hot(tmp_path):
    """ISSUE 16 satellite: every scheduling-policy module runs inside the
    admission/decode loop (the policy reorders the queue each round,
    fairness charges each emitted token, feedback reads pressure per
    step) — all four are hot BY PATH, so a device value leaking into any
    policy decision trips GL02 with no marker needed."""
    fixture = """\
        import jax.numpy as jnp

        def order_key(req, pressure):
            return float(jnp.max(pressure)) - req.rid
        """
    for name in (
        "serving/sched/policy.py",
        "serving/sched/priority.py",
        "serving/sched/fairness.py",
        "serving/sched/feedback.py",
    ):
        assert "GL02" in rules_of(lint(tmp_path, fixture, name=name)), name
    # an explicit device_get inside a victim-cost estimate trips too —
    # preemption choice is HOST bookkeeping (block tables, match_len);
    # reading device state to price a victim would sync every round
    v = lint(tmp_path, """\
        import jax

        def victim_cost(engine, req):
            return len(jax.device_get(engine.cache.pages(req.slot)))
        """, name="serving/sched/feedback.py")
    assert any("device_get" in x.message for x in v if x.rule == "GL02")
    # ...and the shipped modules scan clean
    targets = [
        os.path.join(PKG, "serving", "sched", m)
        for m in ("policy.py", "priority.py", "fairness.py", "feedback.py")
    ]
    assert all(os.path.exists(t) for t in targets)
    report = runner.scan(targets, root=REPO_ROOT)
    assert report.violations == []


def test_gl02_aot_module_is_hot_by_path(tmp_path):
    """ISSUE 17 satellite: the AOT prewarm module is on the GL02 hot-path
    list BY PATH — its replay dispatches run through the live ledger
    proxies and its AOTProgram shim wraps every dispatch of a deserialized
    program for the life of the engine, so an implicit coercion smuggled
    into a future edit trips with no marker needed — and the shipped
    module scans clean."""
    fixture = """\
        import jax.numpy as jnp

        def replay_ok(report, out):
            return float(jnp.sum(out)) if report else 0.0
        """
    assert "GL02" in rules_of(lint(tmp_path, fixture, name="inference/aot.py"))
    # an undocumented explicit device_get in the shim's dispatch path
    # trips too — the shim must forward device values untouched
    v = lint(tmp_path, """\
        import jax

        def dispatch(shim, args):
            return shim.compiled(*jax.device_get(args))
        """, name="inference/aot.py")
    assert any("device_get" in x.message for x in v if x.rule == "GL02")
    shipped = os.path.join(PKG, "inference", "aot.py")
    assert os.path.exists(shipped)
    report = runner.scan([shipped], root=REPO_ROOT)
    assert report.violations == []


def test_gl02_tiering_module_is_hot_by_path(tmp_path):
    """ISSUE 19 satellite: the host-RAM page tier module is on the GL02
    hot-path list BY PATH — it sits on the engine's admission/reclaim
    path but is PURE host numpy (the only device->host transfer in the
    whole tier is the pragma'd batched pull in ``paging.spill_pages``),
    so any jax coercion or device_get smuggled into a future edit trips
    with no marker needed — and the shipped module scans clean."""
    fixture = """\
        import jax.numpy as jnp

        def fingerprint(page, blocks):
            return float(jnp.sum(blocks[0])) if page else 0.0
        """
    assert "GL02" in rules_of(
        lint(tmp_path, fixture, name="serving/tiering.py")
    )
    # an undocumented explicit device_get trips too — the store speaks
    # numpy blocks the POOL already pulled; a second pull is a new sync
    v = lint(tmp_path, """\
        import jax

        def put(store, pids, items):
            return store._put(pids, jax.device_get(items))
        """, name="serving/tiering.py")
    assert any("device_get" in x.message for x in v if x.rule == "GL02")
    shipped = os.path.join(PKG, "serving", "tiering.py")
    assert os.path.exists(shipped)
    report = runner.scan([shipped], root=REPO_ROOT)
    assert report.violations == []


def test_gl02_transport_module_is_hot_by_path(tmp_path):
    """ISSUE 18 satellite: the elastic-fabric transport seam is on the
    GL02 hot-path list BY PATH — every router->replica and prefill->decode
    interaction (submit, adopt, probe, handoff, restore) passes through
    ``call()``/``_deliver()``, so an implicit coercion smuggled into a
    future edit (say of a request's device key riding an envelope) trips
    with no marker needed — and the shipped module scans clean."""
    fixture = """\
        import jax.numpy as jnp

        def deliver(env, payload):
            return float(jnp.sum(payload)) if env.rid >= 0 else 0.0
        """
    assert "GL02" in rules_of(
        lint(tmp_path, fixture, name="serving/transport.py")
    )
    # an undocumented explicit device_get in the delivery path trips too —
    # the seam must forward payloads untouched (it carries host callables,
    # never device values)
    v = lint(tmp_path, """\
        import jax

        def deliver(env, payload):
            return jax.device_get(payload)
        """, name="serving/transport.py")
    assert any("device_get" in x.message for x in v if x.rule == "GL02")
    shipped = os.path.join(PKG, "serving", "transport.py")
    assert os.path.exists(shipped)
    report = runner.scan([shipped], root=REPO_ROOT)
    assert report.violations == []


# --- GL03 recompile-hazard ----------------------------------------------------


def test_gl03_module_level_jit(tmp_path):
    v = lint(tmp_path, """\
        import jax

        _shared = jax.jit(lambda x: x + 1)
    """)
    assert any("module-level" in x.message for x in v if x.rule == "GL03")


def test_gl03_jit_on_method(tmp_path):
    v = lint(tmp_path, """\
        import jax

        class M:
            @jax.jit
            def forward(self, x):
                return x * self.scale
    """)
    assert any("method" in x.message for x in v if x.rule == "GL03")


def test_gl03_closure_capture_reassigned(tmp_path):
    v = lint(tmp_path, """\
        import jax

        def build(scale):
            @jax.jit
            def f(x):
                return x * scale
            scale = scale + 1  # f's trace keeps the OLD value
            return f
    """)
    assert any("captures 'scale'" in x.message for x in v if x.rule == "GL03")


def test_gl03_uncommitted_step_scalar(tmp_path):
    v = lint(tmp_path, """\
        import jax.numpy as jnp

        def make_state(cls, params):
            return cls(step=jnp.zeros((), jnp.int32), params=params)
    """)
    assert any("step" in x.message for x in v if x.rule == "GL03")


def test_gl03_negative_committed_and_local(tmp_path):
    # committed_step0 pattern + function-local jit + stable closure capture:
    # all clean
    v = lint(tmp_path, """\
        import jax
        import jax.numpy as jnp

        def committed_step0():
            return jax.device_put(jnp.zeros((), jnp.int32))

        def make_state(cls, params):
            return cls(step=committed_step0(), params=params)

        def build(model):
            clone = model.clone()

            @jax.jit
            def f(params, x):
                return clone.apply(params, x)

            return f
    """)
    assert [x for x in v if x.rule == "GL03"] == []


def test_gl03_sibling_function_locals_not_flagged(tmp_path):
    # a helper closure's LOCAL reusing the captured name is a different
    # scope, not a rebinding of what the jitted closure traced (review
    # round 1)
    v = lint(tmp_path, """\
        import jax

        def build(scale):
            @jax.jit
            def f(x):
                return x * scale

            def helper():
                scale = 2
                return scale

            return f, helper
    """)
    assert [x for x in v if x.rule == "GL03"] == []


# --- GL04 compat-layer bypass -------------------------------------------------


def test_gl04_raw_shard_map_import(tmp_path):
    v = lint(tmp_path, """\
        from jax.experimental.shard_map import shard_map

        def f(fn, mesh, specs):
            return shard_map(fn, mesh=mesh, in_specs=specs, out_specs=specs)
    """)
    assert "GL04" in rules_of(v)


def test_gl04_raw_axis_index_is_plain_jax(tmp_path):
    # lax.axis_index needed a wrapper only on jax < 0.5; with that support
    # gone the rule polices the shard_map seam and nothing else
    v = lint(tmp_path, """\
        from jax import lax

        def ring_step(x, axis_name):
            rank = lax.axis_index(axis_name)
            return x + rank
    """)
    assert "GL04" not in rules_of(v)


def test_gl04_get_abstract_mesh(tmp_path):
    v = lint(tmp_path, """\
        import jax

        def ctx():
            return jax.sharding.get_abstract_mesh()
    """)
    assert "GL04" in rules_of(v)


def test_gl04_mesh_module_exempt_and_compat_clean(tmp_path):
    mesh_code = """\
        import jax
        from jax.experimental.shard_map import shard_map

        def compat(fn, **kw):
            return shard_map(fn, **kw)
    """
    assert lint(tmp_path, mesh_code, name="parallel/mesh.py") == []
    v = lint(tmp_path, """\
        from neuronx_distributed_tpu.parallel import mesh as mesh_lib

        def region(fn, mesh, specs):
            return mesh_lib.compat_shard_map(fn, mesh, specs, specs)
    """)
    assert [x for x in v if x.rule == "GL04"] == []


# --- GL05 nondeterminism ------------------------------------------------------


def test_gl05_global_rng_and_wall_clock(tmp_path):
    v = lint(tmp_path, """\
        import random
        import time

        import jax
        import numpy as np

        def pick(items):
            np.random.shuffle(items)          # process-global numpy RNG
            noise = random.random()           # stdlib global RNG
            rng = np.random.default_rng()     # entropy-seeded
            key = jax.random.PRNGKey(int(time.time()))  # wall clock
            return items, noise, rng, key
    """)
    gl05 = [x for x in v if x.rule == "GL05"]
    assert len(gl05) == 4
    assert any("wall clock" in x.message for x in gl05)


def test_gl05_seeded_rng_clean(tmp_path):
    v = lint(tmp_path, """\
        import numpy as np

        def epoch_order(seed, epoch, n):
            rng = np.random.default_rng(np.random.SeedSequence([seed, epoch]))
            return rng.permutation(n)
    """)
    assert [x for x in v if x.rule == "GL05"] == []


# --- pragmas ------------------------------------------------------------------


def test_pragma_suppresses_with_reason(tmp_path):
    v = lint(tmp_path, """\
        import jax

        def f():
            return jax.sharding.get_abstract_mesh()  # graftlint: ok[GL04] fixture: seam verified by hand
    """)
    assert v == []


def test_pragma_own_line_covers_multiline_statement(tmp_path):
    v = lint(tmp_path, """\
        # graftlint: hot-path
        import jax

        def readback(step, state):
            # graftlint: ok[GL02] the one documented per-chunk sync
            # (continuation of the justification)
            toks = jax.device_get(
                step(state)
            )
            return toks
    """)
    assert [x for x in v if x.rule == "GL02"] == []


def test_pragma_missing_reason_is_gl00_and_does_not_suppress(tmp_path):
    v = lint(tmp_path, """\
        import jax

        def f():
            return jax.sharding.get_abstract_mesh()  # graftlint: ok[GL04]
    """)
    assert "GL00" in rules_of(v)
    assert "GL04" in rules_of(v)  # the naked pragma suppresses nothing


def test_pragma_wrong_rule_does_not_suppress(tmp_path):
    v = lint(tmp_path, """\
        import jax

        def f():
            return jax.sharding.get_abstract_mesh()  # graftlint: ok[GL05] wrong rule id
    """)
    assert "GL04" in rules_of(v)


# --- baseline ratchet ---------------------------------------------------------


def _write(tmp_path, code):
    p = tmp_path / "mod.py"
    p.write_text(code)
    return p


BAD_TWO = textwrap.dedent("""\
    import jax

    def f(x, a):
        return x + jax.sharding.get_abstract_mesh()

    def g(x, a):
        return x - jax.sharding.get_abstract_mesh()
""")


def test_baseline_ratchet(tmp_path):
    f = _write(tmp_path, BAD_TWO)
    bl = str(tmp_path / "bl.json")

    # 1. no baseline yet: everything is new, run fails
    rep = runner.run([str(f)], root=str(tmp_path), baseline_path=bl)
    assert rep.failed and len(rep.diff.new) == 2

    # 2. grandfather the debt: clean run, nothing new
    baseline_mod.save(bl, rep.violations)
    rep = runner.run([str(f)], root=str(tmp_path), baseline_path=bl)
    assert not rep.failed
    assert len(rep.diff.grandfathered) == 2 and rep.diff.new == []

    # 3. a NEW violation fails even though the old two are baselined
    _write(tmp_path, BAD_TWO + "\n\ndef h(x, a):\n    return jax.sharding.get_abstract_mesh()\n")
    rep = runner.run([str(f)], root=str(tmp_path), baseline_path=bl)
    assert rep.failed and len(rep.diff.new) == 1
    assert len(rep.diff.grandfathered) == 2

    # 4. fixing a violation leaves a STALE entry — the run fails until the
    #    baseline is regenerated (the ratchet can only shrink explicitly)
    _write(tmp_path, BAD_TWO.replace("x - jax.sharding.get_abstract_mesh()", "x - 1"))
    rep = runner.run([str(f)], root=str(tmp_path), baseline_path=bl)
    assert rep.failed
    assert len(rep.diff.stale) == 1 and rep.diff.new == []

    # 5. regenerating shrinks the debt and goes green
    baseline_mod.save(bl, rep.violations)
    rep = runner.run([str(f)], root=str(tmp_path), baseline_path=bl)
    assert not rep.failed and len(rep.diff.grandfathered) == 1
    assert len(baseline_mod.load(bl)) == 1


def test_baseline_fingerprints_survive_line_moves(tmp_path):
    f = _write(tmp_path, BAD_TWO)
    bl = str(tmp_path / "bl.json")
    rep = runner.run([str(f)], root=str(tmp_path), baseline_path=bl)
    baseline_mod.save(bl, rep.violations)
    # unrelated edits above the findings must not churn the baseline
    _write(tmp_path, "import os\n\nPAD = os.sep\n\n" + BAD_TWO)
    rep = runner.run([str(f)], root=str(tmp_path), baseline_path=bl)
    assert not rep.failed and len(rep.diff.grandfathered) == 2


# --- repo-wide run (the tier-1 gate) ------------------------------------------


def test_repo_wide_zero_non_baselined_violations():
    """`python -m ...graftlint neuronx_distributed_tpu/` must exit 0: every
    violation fixed, pragma'd with a reason, or explicitly baselined — and
    the checked-in baseline must not be stale."""
    rep = runner.run([PKG], root=REPO_ROOT)
    assert rep.files_scanned > 80
    new = "\n".join(v.format() for v in rep.diff.new)
    assert rep.diff.new == [], f"new graftlint violations:\n{new}"
    assert rep.diff.stale == [], (
        "stale baseline entries — shrink the debt with --write-baseline: "
        f"{json.dumps(rep.diff.stale, indent=2)}"
    )


def _engine_copy_with(tmp_path, needle, insertion):
    src = open(os.path.join(PKG, "serving", "engine.py")).read()
    assert needle in src
    i = src.index(needle)
    line_start = src.rindex("\n", 0, i) + 1
    indent = " " * (i - line_start)
    patched = src.replace(needle, needle + "\n" + indent + insertion, 1)
    out = tmp_path / "serving" / "engine.py"
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(patched)
    return out


def test_reintroducing_pr2_donated_leaf_bug_fails(tmp_path):
    """Acceptance: the PR 2 bug — device_get on the donated slot state —
    re-inserted into the real engine source must trip GL01."""
    out = _engine_copy_with(
        tmp_path,
        "cache_in = self.cache.take()",
        'jax.device_get(self._state["keys"])  # reintroduced PR 2 bug',
    )
    rep = runner.scan([str(out)], root=str(tmp_path))
    assert "GL01" in rules_of(rep.violations)


def test_raw_shard_map_import_in_serving_fails(tmp_path):
    """Acceptance: a raw jax.experimental.shard_map import appearing in
    serving/ must trip GL04."""
    src = open(os.path.join(PKG, "serving", "engine.py")).read()
    patched = src.replace(
        "import jax\n",
        "import jax\nfrom jax.experimental.shard_map import shard_map\n",
        1,
    )
    out = tmp_path / "serving" / "engine.py"
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(patched)
    rep = runner.scan([str(out)], root=str(tmp_path))
    assert "GL04" in rules_of(rep.violations)


def test_spec_decode_module_is_hot_by_path(tmp_path):
    """ISSUE 9 satellite: the speculative chunk builder module is on the
    GL02 hot-path list BY PATH — an implicit sync smuggled into a future
    draft/verify edit trips with no marker needed — and the shipped module
    scans clean."""
    code = """\
        import jax.numpy as jnp

        def round_fn(kv_valid):
            cursor = jnp.sum(kv_valid)
            return int(cursor)  # host read of a device cursor
        """
    assert "GL02" in rules_of(
        lint(tmp_path, code, name="inference/spec_decode.py")
    )
    shipped = os.path.join(PKG, "inference", "spec_decode.py")
    out = tmp_path / "inference" / "spec_decode.py"
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(open(shipped).read())
    rep = runner.scan([str(out)], root=str(tmp_path))
    assert rep.violations == []


def test_quantized_serving_modules_are_hot_by_path(tmp_path):
    """ISSUE 13 satellite: the quantized-matmul layer module and the
    quantized collective wrapper are on the GL02 hot-path list BY PATH —
    an implicit sync smuggled into either (they trace inside every
    quantize= engine's jitted matmuls / shard_map'd TP steps) trips with
    no marker needed — and both shipped modules scan clean."""
    code = """\
        import jax.numpy as jnp

        def quantized_matmul(x, k, s):
            amax = jnp.max(jnp.abs(s))
            return float(amax)  # host read of a device scale
        """
    for name in (
        "quantization/layers.py",
        "parallel/quantized_collectives.py",
    ):
        assert "GL02" in rules_of(lint(tmp_path, code, name=name)), name
    for rel in (
        os.path.join("quantization", "layers.py"),
        os.path.join("parallel", "quantized_collectives.py"),
    ):
        out = tmp_path / rel
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(open(os.path.join(PKG, rel)).read())
        rep = runner.scan([str(out)], root=str(tmp_path))
        assert rep.violations == [], (rel, rep.violations)


def test_draft_cache_cursor_host_read_in_chunk_loop_fails(tmp_path):
    """Acceptance re-injection (ISSUE 9): a host read of the draft cache
    inside the speculative chunk loop — the exact shape of the PR 2 bug,
    draft edition — must trip BOTH GL01 (the tree is about to be donated
    into the speculative chunk) and GL02 (an undocumented explicit sync in
    the engine)."""
    out = _engine_copy_with(
        tmp_path,
        "draft_in = self.draft_cache.take()",
        "jax.device_get(draft_in)  # reintroduced: draft cursor host read",
    )
    rep = runner.scan([str(out)], root=str(tmp_path))
    rules = rules_of(rep.violations)
    assert "GL01" in rules and "GL02" in rules


def test_real_engine_scan_is_clean_in_isolation(tmp_path):
    """The shipped engine (pragmas and all) carries zero findings even
    without the baseline — the debt really was driven to zero."""
    out = tmp_path / "serving" / "engine.py"
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(open(os.path.join(PKG, "serving", "engine.py")).read())
    rep = runner.scan([str(out)], root=str(tmp_path))
    assert rep.violations == []
    assert len(rep.suppressed) >= 4  # the documented intentional syncs


# --- CLI ----------------------------------------------------------------------


def _cli(args, capsys):
    """Run the CLI in-process (the subprocess form pays a full jax import
    per call; one real `python -m` invocation is kept below)."""
    from neuronx_distributed_tpu.scripts.graftlint.cli import main

    rc = main(args)
    out = capsys.readouterr()
    return rc, out.out, out.err


def test_cli_report_format_and_exit_codes(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("import jax\n\ndef f(a):\n    return jax.sharding.get_abstract_mesh()\n")
    rc, out, _ = _cli([str(bad), "--no-baseline"], capsys)
    assert rc == 1
    # clickable path:line:col convention
    assert f"{os.path.relpath(bad, tmp_path)}:4:11: GL04" in out
    ok = tmp_path / "ok.py"
    ok.write_text("X = 1\n")
    rc, out, _ = _cli([str(ok), "--no-baseline"], capsys)
    assert rc == 0
    assert "0 violation(s)" in out
    rc, out, _ = _cli(["--explain", "GL02"], capsys)
    assert rc == 0 and "host-sync-in-hot-path" in out
    rc, _, err = _cli(["--explain", "GL99"], capsys)
    assert rc == 2 and "unknown rule" in err
    rc, _, err = _cli([str(tmp_path / "missing.py"), "--no-baseline"], capsys)
    assert rc == 2 and "no such path" in err
    rc, _, err = _cli([str(ok), "--select", "GL77"], capsys)
    assert rc == 2


def test_cli_write_baseline_roundtrip(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("import jax\n\ndef f(a):\n    return jax.sharding.get_abstract_mesh()\n")
    bl = tmp_path / "bl.json"
    rc, _, _ = _cli([str(bad), "--baseline", str(bl), "--write-baseline"], capsys)
    assert rc == 0 and bl.exists()
    rc, out, _ = _cli([str(bad), "--baseline", str(bl)], capsys)
    assert rc == 0
    assert "1 baselined" in out


def test_write_baseline_partial_scope_preserves_out_of_scope_debt(tmp_path):
    """A subset-path or --select --write-baseline must not erase
    grandfathered entries it never re-checked (review round 1)."""
    a_dir = tmp_path / "a"
    b_dir = tmp_path / "b"
    a_dir.mkdir()
    b_dir.mkdir()
    bad = "import jax\n\ndef f(x):\n    return jax.sharding.get_abstract_mesh()\n"
    (a_dir / "mod_a.py").write_text(bad)
    (b_dir / "mod_b.py").write_text(bad)
    bl = str(tmp_path / "bl.json")

    # grandfather BOTH files' debt from a full-scope run
    rep = runner.run([str(tmp_path)], root=str(tmp_path), baseline_path=bl)
    baseline_mod.save(bl, rep.violations)
    assert len(baseline_mod.load(bl)) == 2

    # fix a/ and regenerate from a PARTIAL run over a/ only: a's entry is
    # retired, b's untouched entry survives
    (a_dir / "mod_a.py").write_text("X = 1\n")
    rep = runner.run([str(a_dir)], root=str(tmp_path), baseline_path=bl)
    baseline_mod.save_merged(
        bl, rep.violations, rep.scanned_relpaths, root=str(tmp_path)
    )
    remaining = baseline_mod.load(bl)
    assert len(remaining) == 1
    assert all(e["path"].startswith("b/") for e in remaining.values())

    # the full run is green against the merged baseline
    rep = runner.run([str(tmp_path)], root=str(tmp_path), baseline_path=bl)
    assert not rep.failed and len(rep.diff.grandfathered) == 1

    # a deleted file's debt is dropped on the next merged write
    (b_dir / "mod_b.py").unlink()
    rep = runner.run([str(a_dir)], root=str(tmp_path), baseline_path=bl)
    baseline_mod.save_merged(
        bl, rep.violations, rep.scanned_relpaths, root=str(tmp_path)
    )
    assert baseline_mod.load(bl) == {}


def test_python_dash_m_entry_point(tmp_path):
    """The documented invocation — `python -m
    neuronx_distributed_tpu.scripts.graftlint` — works end to end."""
    bad = tmp_path / "bad.py"
    bad.write_text("import jax\n\ndef f(a):\n    return jax.sharding.get_abstract_mesh()\n")
    r = subprocess.run(
        [sys.executable, "-m", "neuronx_distributed_tpu.scripts.graftlint",
         str(bad), "--no-baseline"],
        capture_output=True, text=True, cwd=REPO_ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 1
    assert "GL04" in r.stdout


# --- GL06 sharding-spec drift (ISSUE 15) --------------------------------------


def test_gl06_trailing_none_spec_at_commit_site(tmp_path):
    v = lint(tmp_path, """\
        import jax
        from jax.sharding import PartitionSpec as P
        from neuronx_distributed_tpu.parallel.sharding import constrain

        def f(x):
            x = constrain(x, P("tp", None))
            return jax.lax.with_sharding_constraint(x, P(None, "tp", None))
    """)
    assert rules_of(v) == ["GL06"]
    assert len([x for x in v if x.rule == "GL06"]) == 2


def test_gl06_reinjection_trailing_none_in_sharding_py(tmp_path):
    # the acceptance re-injection: a trailing-None spec in
    # parallel/sharding.py itself (the trim owner) must trip
    v = lint(tmp_path, """\
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        def place(mesh, x):
            return NamedSharding(mesh, P(None, None, "tp", None))
    """, name="parallel/sharding.py")
    assert "GL06" in rules_of(v)


def test_gl06_negative_trimmed_and_structural_specs(tmp_path):
    # trimmed commit specs and rank-complete shard_map STRUCTURE specs
    # (in_specs/out_specs) are both fine
    v = lint(tmp_path, """\
        import jax
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P
        from neuronx_distributed_tpu.parallel.sharding import constrain

        def f(x, mesh):
            x = constrain(x, P(None, "tp"))
            return shard_map(
                lambda v: v, mesh=mesh,
                in_specs=P("tp", None), out_specs=P("tp", None),
            )(x)
    """)
    assert "GL06" not in rules_of(v)


def test_gl06_raw_named_sharding_in_serving(tmp_path):
    v = lint(tmp_path, """\
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        def place(mesh, x):
            return jax.device_put(x, NamedSharding(mesh, P("tp")))
    """, name="serving/engine_helper.py")
    assert "GL06" in rules_of(v)
    # the SAME code in the placement layer is the blessed path
    v2 = lint(tmp_path, """\
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        def place(mesh, x):
            return jax.device_put(x, NamedSharding(mesh, P("tp")))
    """, name="parallel/sharding.py")
    assert "GL06" not in rules_of(v2)


# --- GL07 trace-scope leakage (ISSUE 15) --------------------------------------


def test_gl07_manual_enter_leaks(tmp_path):
    v = lint(tmp_path, """\
        from neuronx_distributed_tpu.parallel.quantized_collectives import (
            tp_comms,
        )

        def install(cfg):
            tp_comms(cfg).__enter__()  # never exited
    """)
    assert rules_of(v) == ["GL07"]


def test_gl07_jit_built_inside_scope(tmp_path):
    v = lint(tmp_path, """\
        import jax
        from neuronx_distributed_tpu.parallel.quantized_collectives import (
            tp_comms,
        )

        def build(cfg, step):
            with tp_comms(cfg):
                fn = jax.jit(step)  # traces lazily, AFTER the scope closed
            return fn
    """)
    assert rules_of(v) == ["GL07"]


def test_gl07_reentrant_scope(tmp_path):
    v = lint(tmp_path, """\
        from neuronx_distributed_tpu.modules.attention import (
            fused_paged_attention_scope,
        )

        def f(frame, inner):
            with fused_paged_attention_scope(*frame):
                with fused_paged_attention_scope(*inner):
                    pass
    """)
    assert rules_of(v) == ["GL07"]


def test_gl07_negative_scoped_call_and_in_trace_use(tmp_path):
    # wrapping the CALL (the engine _TraceScope pattern) and entering the
    # scope inside traced code (the generate.py chunk builder) are the two
    # legal shapes
    v = lint(tmp_path, """\
        import jax
        from neuronx_distributed_tpu.parallel.quantized_collectives import (
            tp_comms,
        )

        def scoped(fn, cfg):
            def call(*args):
                with tp_comms(cfg):
                    return fn(*args)
            return call

        def chunk_fn(params, state, cfg):
            with tp_comms(cfg):
                out = params["w"] @ state
            return out
    """)
    assert "GL07" not in rules_of(v)


# --- GL08 hold/refcount pairing (ISSUE 15) ------------------------------------


def test_gl08_acquire_without_release_in_handler(tmp_path):
    v = lint(tmp_path, """\
        class Server:
            def handoff(self, req):
                try:
                    staged = self.cache.stage_context(req.row, req.p, req.padded)
                    self.engine.admit_staged(staged)
                except Exception:
                    self.queue.append(req)  # staged holds orphaned: the leak
    """)
    assert rules_of(v) == ["GL08"]


def test_gl08_reinjection_in_paging_py(tmp_path):
    # the acceptance re-injection: an acquire-without-release handler in
    # serving/paging.py trips by construction
    v = lint(tmp_path, """\
        class PagedCacheManager:
            def admit_with_pin(self, ids):
                try:
                    self.pin_pages(ids)
                    return self._bind(ids)
                except Exception:
                    raise RuntimeError("admit failed")
    """, name="serving/paging.py")
    assert "GL08" in rules_of(v)


def test_gl08_negative_release_delegation_and_finally(tmp_path):
    v = lint(tmp_path, """\
        class Server:
            def handoff(self, req):
                try:
                    staged = self.cache.stage_context(req.row, req.p, req.padded)
                    self.engine.admit_staged(staged)
                except Exception:
                    self.cache.release_staged(staged)
                    self.queue.append(req)

            def handoff2(self, req):
                staged = None
                try:
                    staged = self.cache.stage_context(req.row, req.p, req.padded)
                    self.engine.admit_staged(staged)
                finally:
                    if staged is not None:
                        self.cache.release_staged(staged)

            def handoff3(self, req):
                try:
                    slot = self.cache.acquire()
                    self._admit(slot, req)
                except Exception:
                    self._recover_admission(req)  # delegated cleanup
    """)
    assert "GL08" not in rules_of(v)


# --- GL09 labeled-metrics hygiene (ISSUE 15) ----------------------------------


def test_gl09_interpolated_label_value(tmp_path):
    v = lint(tmp_path, """\
        def record(fam, tenant, shard):
            fam.labels(f"{tenant}-{shard}").inc()
            fam.labels("t-%s" % tenant).observe(1.0)
            fam.labels("{}".format(tenant)).inc()
    """)
    assert rules_of(v) == ["GL09"]
    assert len(v) == 3


def test_gl09_chained_concatenation(tmp_path):
    # `a + "-" + b` parses left-heavy: the str constant sits one BinOp
    # deep, exactly the "a-b"+"c" vs "a"+"b-c" collision vector — the walk
    # must find it at any chain depth
    v = lint(tmp_path, """\
        def record(fam, tenant, shard):
            fam.labels(tenant + "-" + shard).inc()
    """)
    assert rules_of(v) == ["GL09"]


def test_gl09_dynamic_label_names(tmp_path):
    v = lint(tmp_path, """\
        def build(view, names):
            return view.family("counter", "reqs", labels=tuple(names))
    """)
    assert rules_of(v) == ["GL09"]


def test_gl09_negative_raw_values_and_literal_names(tmp_path):
    v = lint(tmp_path, """\
        def build(view, tenant, engine):
            fam = view.family("counter", "reqs", labels=("tenant", "engine"))
            fam.labels(tenant, engine).inc()
            solo = view.family("gauge", "depth", labels="engine")
            solo.labels(engine).set(3)
    """)
    assert "GL09" not in rules_of(v)


# --- GL02 walrus + f-string census gaps (ISSUE 15) ----------------------------


def test_gl02_walrus_binding_carries_device_taint(tmp_path):
    v = lint(tmp_path, """\
        # graftlint: hot-path
        import jax.numpy as jnp

        def f(vals):
            y = (x := jnp.asarray(vals)) + 1
            return float(x)
    """)
    assert "GL02" in rules_of(v)
    assert any("float" in x.message for x in v if x.rule == "GL02")


def test_gl02_fstring_of_device_value(tmp_path):
    v = lint(tmp_path, """\
        # graftlint: hot-path
        import jax.numpy as jnp

        def log_max(x):
            m = jnp.max(x)
            return f"max={m}"
    """)
    assert "GL02" in rules_of(v)
    assert any("f-string" in x.message for x in v if x.rule == "GL02")


def test_gl02_fstring_of_host_metadata_clean(tmp_path):
    v = lint(tmp_path, """\
        # graftlint: hot-path
        import numpy as np

        def log_shape(x, raw):
            host = np.asarray(raw)  # unknown provenance: stays quiet
            w = (n := len(x))
            return f"shape={x.shape} n={n} host={host} w={w}"
    """)
    assert "GL02" not in rules_of(v)


# --- GL02 integrity sentinel modules (ISSUE 20) -------------------------------


def test_gl02_integrity_modules_are_hot_by_path(tmp_path):
    """ISSUE 20 satellite: the integrity sentinel's sync-free modules are
    on the GL02 hot-path list BY PATH — the fingerprint reductions trace
    inside jitted programs on the trainer/engine hot paths, and the
    sentinel's hooks plus the voting arithmetic run inside the training
    loop every check step (the ONE readback rides the anomaly guard's
    deferred device_get in trainer/loop.py) — so an implicit coercion or
    undocumented device_get smuggled into a future edit trips with no
    marker needed, and the shipped modules scan clean."""
    fixture = """\
        import jax.numpy as jnp

        def leaf_fp(leaf, report):
            return float(jnp.sum(leaf)) if report else 0.0
        """
    for name in (
        "utils/fingerprint.py",
        "integrity/sentinel.py",
        "integrity/voting.py",
    ):
        assert "GL02" in rules_of(lint(tmp_path, fixture, name=name)), name
    # an undocumented explicit device_get trips too — the sentinel's
    # fingerprint scalars must ride the loop's existing deferred readback,
    # never force their own
    v = lint(tmp_path, """\
        import jax

        def post_dispatch(self, state):
            return jax.device_get(self._fp(state))
        """, name="integrity/sentinel.py")
    assert any("device_get" in x.message for x in v if x.rule == "GL02")
    for rel in (
        ("utils", "fingerprint.py"),
        ("integrity", "sentinel.py"),
        ("integrity", "voting.py"),
    ):
        shipped = os.path.join(PKG, *rel)
        assert os.path.exists(shipped)
        report = runner.scan([shipped], root=REPO_ROOT)
        assert report.violations == [], rel


def test_gl02_integrity_chaos_module_is_not_hot(tmp_path):
    """integrity/chaos.py is deliberately NOT hot-listed: its host
    round-trips ARE the injected fault (pull, flip one bit, re-place),
    consulted only by chaos schedules outside the measured hot paths —
    the same coercions that trip in the sentinel stay quiet here."""
    fixture = """\
        import jax
        import numpy as np

        def flip(leaf):
            return np.asarray(jax.device_get(leaf))
        """
    assert "GL02" not in rules_of(
        lint(tmp_path, fixture, name="integrity/chaos.py")
    )
