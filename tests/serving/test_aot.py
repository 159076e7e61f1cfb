"""AOT serving (ISSUE 17): manifest codec, serialized executables, and
ledger-driven prewarm.

The acceptance pins live here:

* **Cross-process round-trip** — THE subprocess test of this file (one per
  suite policy, like graftlint's CLI smoke): a child process builds the
  tiny paged engine, serves a wave, and writes the full AOT bundle via
  ``save_aot``; the parent restores a FRESH engine from it with
  ``prewarm(cache_dir=...)`` and serves the same traffic with ZERO new
  compiles, pinned by ``_cache_size`` deltas across every manifest
  program and ``decode_compilations == 0`` (the decode chunk
  deserialized — XLA never ran). This is also the regression fence for
  the cache-loaded-executable bug: an XLA:CPU executable loaded from the
  persistent disk cache serializes WITHOUT its object code and
  deserializes cross-process to ``Symbols not found`` — ``save_aot``
  must compile afresh what it serializes (aot.compile_serializable).
* **Fallback ladder** — a corrupt artifact degrades deserialize → replay
  with a ``SkewError`` recorded on the flight recorder, never a crash;
  header skew (foreign jax version) raises :class:`SkewError` from
  ``load_executable`` directly.
* **Per-instance capture** (the ProgramLedger.wrap regression): TWO
  engines in one process each capture their own replayable decode-chunk
  signature — clone N's manifest must not alias clone 1's proxies.
"""

import os
import pickle
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from neuronx_distributed_tpu.inference import GenerationConfig, aot
from neuronx_distributed_tpu.models.llama import LlamaForCausalLM, tiny_llama
from neuronx_distributed_tpu.parallel import mesh as mesh_lib
from neuronx_distributed_tpu.serving import RequestState, ServingEngine

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(autouse=True)
def _restore_persistent_cache():
    """prewarm/save_aot rewire the PROCESS-WIDE persistent compile cache
    to their bundle dir; put the suite's cache back after each test so
    the rest of tier-1 keeps its disk hits."""
    prev = aot.persistent_cache_dir()
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    if prev and aot.persistent_cache_dir() != prev:
        aot.enable_persistent_cache(prev, host_scoped=False)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = tiny_llama()
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    ids = jax.random.randint(jax.random.PRNGKey(0), (1, 8), 1, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(1), ids)
    return cfg, model, params


def _fresh_engine(model, params):
    mesh_lib.destroy_model_parallel()
    return ServingEngine(
        model, params, num_slots=2, decode_chunk_size=4,
        prefix_cache=None, kv_page_size=8,
    )


def _drive(engine, cfg, n_req=2, new_tokens=2):
    """The EXACT wave the bundle child serves (same prompt shapes, same
    keys) so a prewarmed parent replays into the same dispatch entries."""
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


# Child: same tiny engine + wave as _fresh_engine/_drive, then save_aot.
# Deterministic init (fixed PRNG keys) means the parent's params are
# bit-identical, so the deserialized executables serve the parent's tree.
_CHILD = """
import os, sys
flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from neuronx_distributed_tpu.inference import GenerationConfig, aot
from neuronx_distributed_tpu.models.llama import LlamaForCausalLM, tiny_llama
from neuronx_distributed_tpu.serving import ServingEngine

out, repo = sys.argv[1], sys.argv[2]
aot.enable_persistent_cache(os.path.join(repo, ".jax_cache"),
                            min_compile_time_secs=0.0)
cfg = tiny_llama()
model = LlamaForCausalLM(cfg, attention_impl="xla")
ids = jax.random.randint(jax.random.PRNGKey(0), (1, 8), 1, cfg.vocab_size)
params = model.init(jax.random.PRNGKey(1), ids)
engine = ServingEngine(model, params, num_slots=2, decode_chunk_size=4,
                       prefix_cache=None, kv_page_size=8)
rng = np.random.RandomState(3)
gcfg = GenerationConfig(max_new_tokens=2, temperature=0.0)
reqs = [engine.submit(rng.randint(1, cfg.vocab_size, size=6).astype(np.int32),
                      gcfg, key=jax.random.PRNGKey(i)) for i in range(2)]
engine.run()
tokens = [[int(t) for t in r.tokens] for r in reqs]
rep = engine.save_aot(out)
assert rep["saved"], rep
import json
print("BUNDLE " + json.dumps({"saved": len(rep["saved"]), "tokens": tokens}))
"""


@pytest.fixture(scope="module")
def aot_bundle(tmp_path_factory):
    """The child-written bundle, shared by the round-trip and skew tests
    (ONE subprocess for the whole module — each child is a full jax
    import plus a compile wave)."""
    d = str(tmp_path_factory.mktemp("aot_bundle"))
    r = subprocess.run(
        [sys.executable, "-c", _CHILD, d, _REPO],
        capture_output=True, text=True, timeout=420, cwd=_REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 0, f"bundle child failed:\n{r.stdout}\n{r.stderr}"
    assert os.path.exists(os.path.join(d, aot.MANIFEST_NAME))
    import json

    line = [ln for ln in r.stdout.splitlines() if ln.startswith("BUNDLE ")][-1]
    return d, json.loads(line[len("BUNDLE "):])


def _program_cache_sizes(engine, names):
    sizes = {}
    for name in names:
        fn = engine._aot_resolve(name)
        if fn is not None:
            sizes[name] = int(fn._cache_size())
    return sizes


def test_cross_process_prewarm_serves_with_zero_compiles(aot_bundle, tiny_model):
    bundle, child = aot_bundle
    cfg, model, params = tiny_model
    engine = _fresh_engine(model, params)
    rep = engine.prewarm(cache_dir=bundle)
    assert rep["skew"] == [], f"cross-process deserialize skewed: {rep['skew']}"
    assert "decode_chunk" in rep["deserialized"], rep
    # nothing silently dropped: every PORTABLE manifest entry restored one
    # way (registered-but-never-dispatched programs have no captured
    # variants and correctly no-op)
    manifest = aot.ProgramManifest.load(bundle)
    restored = set(rep["deserialized"]) | {
        k.split("@")[0] for k in rep["replayed"]
    }
    portable = {
        n for n in manifest.names()
        if any(e.get("portable") for e in manifest.entries(n))
    }
    assert portable <= restored, (portable - restored, rep)

    # first REAL traffic after prewarm: zero new compiles anywhere —
    # every dispatch lands in the entry the replay (or the deserialized
    # executable) already owns
    before = _program_cache_sizes(engine, manifest.names())
    reqs = _drive(engine, cfg)
    after = _program_cache_sizes(engine, manifest.names())
    assert after == before, (
        f"prewarmed engine compiled during traffic: {before} -> {after}"
    )
    assert engine.decode_compilations == 0  # deserialized: XLA never ran
    # and the streams are the child's streams (same params, same keys)
    assert [[int(t) for t in r.tokens] for r in reqs] == child["tokens"]


def test_corrupt_artifact_degrades_to_replay(aot_bundle, tiny_model, tmp_path):
    bundle, _ = aot_bundle
    cfg, model, params = tiny_model
    d = str(tmp_path / "bundle")
    shutil.copytree(bundle, d)
    sig = aot.ProgramManifest.load(d).entries("decode_chunk")[0]["signature"]
    with open(aot._artifact_path(d, "decode_chunk", sig), "wb") as f:
        f.write(b"not a pickle")
    engine = _fresh_engine(model, params)
    rep = engine.prewarm(cache_dir=d)
    assert "decode_chunk" in rep["skew"]
    assert "decode_chunk" in rep["replayed"]  # dropped ONE rung, not out
    assert "decode_chunk" not in rep["deserialized"]
    skew_events = [e for e in engine.flight.events() if e.get("kind") == "aot_skew"]
    assert any(e.get("program") == "decode_chunk" for e in skew_events)
    _drive(engine, cfg, n_req=1)
    assert engine.decode_compilations == 1  # replay ate the compile


def test_version_skew_raises_skew_error(aot_bundle, tmp_path):
    bundle, _ = aot_bundle
    d = str(tmp_path / "bundle")
    shutil.copytree(bundle, d)
    sig = aot.ProgramManifest.load(d).entries("decode_chunk")[0]["signature"]
    path = aot._artifact_path(d, "decode_chunk", sig)
    with open(path, "rb") as f:
        header, payload, in_tree, out_tree = pickle.loads(f.read())
    header["jax"] = "0.0.0-foreign"
    with open(path, "wb") as f:
        f.write(pickle.dumps((header, payload, in_tree, out_tree)))
    with pytest.raises(aot.SkewError, match="jax"):
        aot.load_executable(d, "decode_chunk", sig)
    # absent artifact is None (no artifact != untrustworthy artifact)
    assert aot.load_executable(d, "no_such_program", sig) is None


def test_two_engines_capture_independent_manifests(tiny_model):
    """per_instance regression (ISSUE 17 satellite): the ledger's wrap()
    must capture signatures per ENGINE — a second engine's manifest has
    its own portable decode-chunk entry, and replays into a third."""
    cfg, model, params = tiny_model
    e1 = _fresh_engine(model, params)
    cold = _drive(e1, cfg, n_req=1)
    e2 = _fresh_engine(model, params)
    _drive(e2, cfg, n_req=1)
    for eng in (e1, e2):
        entries = eng.manifest().entries("decode_chunk")
        assert entries and entries[0]["portable"], entries
    m2 = e2.manifest()
    e3 = _fresh_engine(model, params)
    rep = e3.prewarm(manifest=m2, mode="trace")
    assert "decode_chunk" in rep["replayed"]
    assert not rep["skipped"], rep["skipped"]
    before = _program_cache_sizes(e3, m2.names())
    warm = _drive(e3, cfg, n_req=1)
    assert _program_cache_sizes(e3, m2.names()) == before
    assert e3.decode_compilations == 1
    # a replay-prewarmed engine serves the cold engine's streams
    assert [r.tokens for r in warm] == [r.tokens for r in cold]


def test_persistent_cache_env_opt_out(monkeypatch, tmp_path):
    prev = aot.persistent_cache_dir()
    monkeypatch.setenv(aot.DISABLE_ENV, "0")
    assert aot.enable_persistent_cache(str(tmp_path / "c")) is None
    assert aot.persistent_cache_dir() == prev  # untouched, not cleared


def test_cache_placed_from_outside_is_left_alone(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the directory is not ours to move:
    no other ``jax_compilation_cache_dir`` is set — for ANY caller, they all
    route through this one function — and nothing is appended to or created
    under the placed one (no ``host-<digest>`` namespace)."""
    placed, asked = tmp_path / "placed", tmp_path / "asked"
    placed.mkdir()
    monkeypatch.setenv(aot.PLACED_ENV, str(placed))
    configured = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(aot, "_CACHE_DIR", aot.persistent_cache_dir())
    assert aot.enable_persistent_cache(str(asked)) == str(placed)
    assert aot.persistent_cache_dir() == str(placed)
    assert jax.config.jax_compilation_cache_dir == configured  # never touched
    assert not asked.exists() and list(placed.iterdir()) == []
    # the engine's and the trainer's AOT entry points ask for their own
    # bundle dir — same owner, same answer
    assert aot.enable_persistent_cache(
        str(tmp_path / "bundle" / aot.XLA_SUBDIR)
    ) == str(placed)
    assert not (tmp_path / "bundle").exists()


def test_default_cache_is_host_scoped_on_cpu_only(monkeypatch, tmp_path):
    """Unset, the cache goes to the path given: under a host-CPU
    fingerprint namespace on the CPU backend (a foreign XLA:CPU AOT entry
    can SIGILL), as given on an accelerator (the fingerprint differs on
    every machine, so a namespaced TPU cache would never hit)."""
    monkeypatch.delenv(aot.PLACED_ENV, raising=False)
    on_cpu = aot.enable_persistent_cache(str(tmp_path / "a"))
    assert os.path.dirname(on_cpu) == str(tmp_path / "a")
    assert os.path.basename(on_cpu).startswith("host-")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert aot.enable_persistent_cache(str(tmp_path / "b")) == str(tmp_path / "b")


def test_encode_materialize_roundtrip_pedigrees():
    """The manifest codec reproduces each leaf's DISPATCH pedigree: numpy
    stays numpy, jax stays jax, weak-typed scalars stay weak, static
    Python leaves replay their exact value."""
    import jax.numpy as jnp

    args = (
        jnp.ones((2, 3), jnp.float32),
        np.arange(4, dtype=np.int32),
        jnp.asarray(5),  # weak-typed scalar
        7,
    )
    kwargs = {"flag": True}
    leaves, _ = jax.tree_util.tree_flatten((args, dict(kwargs)))
    peds = []
    for leaf in leaves:
        if isinstance(leaf, np.ndarray):
            peds.append({"kind": "np"})
        elif hasattr(leaf, "shape"):
            peds.append({"kind": "jax", "weak": bool(getattr(leaf, "weak_type", False))})
        else:
            peds.append({})
    node = aot.encode_call(args, kwargs, peds)
    out_args, out_kwargs = aot.materialize_call(node)
    assert len(out_args) == 4 and out_kwargs == {"flag": True}
    assert isinstance(out_args[1], np.ndarray)
    assert out_args[1].dtype == np.int32 and out_args[1].shape == (4,)
    assert not isinstance(out_args[0], np.ndarray)
    assert out_args[0].shape == (2, 3) and out_args[0].dtype == jnp.float32
    assert out_args[2].weak_type and out_args[2].shape == ()
    assert out_args[3] == 7  # static value replays EXACTLY, not zeroed


def test_encode_call_rejects_opaque_leaves():
    with pytest.raises(aot.UnportableError, match="opaque"):
        aot.encode_call((object(),), {})


def test_manifest_save_load_roundtrip(tmp_path):
    m = aot.ProgramManifest(
        {"p": [{"signature": "s", "call": None, "portable": False, "note": ""}]},
        {"format": 1},
    )
    path = m.save(str(tmp_path))
    assert os.path.basename(path) == aot.MANIFEST_NAME
    m2 = aot.ProgramManifest.load(str(tmp_path))
    assert m2.names() == ["p"] and m2.entries("p")[0]["signature"] == "s"
