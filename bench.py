"""Benchmark: Llama training-step throughput on the local chip, plus CPU proxies.

``python bench.py`` runs ONE device measurement — a 2-layer Llama-2-7B-width
train step (``--child``) — and then a series of CPU proxies. It prints ONE
JSON line: {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
"extras": {...}} and exits non-zero if the device measurement, or any
proxy, failed. There is no fallback: without a TPU the train child fails and
so does the run; nothing is ever reported under the device metric's name
from another platform.

The reference publishes no numbers (BASELINE.md), so ``vs_baseline`` is
computed against a hardware-grounded target: 40% MFU at the chip's peak bf16
FLOPs (the one table, ``observability/programs.py`` ``device_peaks``; a
device that is not in it is an error) — i.e. vs_baseline = achieved_MFU /
0.40. >1.0 beats the target.

FLOP accounting: the headline MFU is the *corrected* one —

    flops = 6 · (N − N_embed_table) · tokens   (input embedding is a lookup,
                                                not a matmul; lm_head counts)
          + 6 · L · B · S² · H                 (causal QKᵀ+AV fwd+bwd: the
                                                flash kernel computes only the
                                                lower triangle, so half of the
                                                full 12·L·B·S²·H)

both the raw 6·N number and every component are in ``extras`` so the MFU can
be recomputed from the artifact alone.

One process per chip: the parent is stdlib-only and never touches JAX, the
children run strictly one after another, the train child is the only one
that may take the chip, and every CPU proxy forces the ``cpu`` platform
itself before JAX initializes and names the platform in what it prints.
``extras.cpu_proxies`` says nothing about the chip: bit-identity, compile
counts and wire-byte arithmetic carry over, CPU walls do not.

This is not yet the round's benchmark (no BENCHMARK.json cells, no traces);
it is kept from misreporting until that lands.
"""

import json
import os
import subprocess
import sys
import time

# Cold-start clock zero: captured at bench-module import, BEFORE jax import
# (the --coldstart-leg children measure process-start → first-token, and the
# jax import itself is part of the bill a served process pays).
_PROC_T0 = time.perf_counter()

FULL_TIMEOUT_S = 600
PROXY_TIMEOUT_S = 420
SERVING_TIMEOUT_S = 420
FAULTS_TIMEOUT_S = 300
PREFIX_TIMEOUT_S = 420
TRAIN_FAULTS_TIMEOUT_S = 420
INTEGRITY_TIMEOUT_S = 420
OBSERVE_TIMEOUT_S = 300
SPEC_TIMEOUT_S = 540
PAGED_TIMEOUT_S = 540
QUANT_TIMEOUT_S = 540
TRAFFIC_TIMEOUT_S = 540
SCHED_TIMEOUT_S = 540
EFFICIENCY_TIMEOUT_S = 540
MULTICHIP_TIMEOUT_S = 540
GRAFTVERIFY_TIMEOUT_S = 420
COLDSTART_TIMEOUT_S = 600
COLDSTART_LEG_TIMEOUT_S = 150
FABRIC_TIMEOUT_S = 540

METRIC = "llama2_7b_width_train_tokens_per_sec_per_chip"


def _emit(payload: dict) -> None:
    print(json.dumps(payload), flush=True)


# --------------------------------------------------------------------------
# children
# --------------------------------------------------------------------------


def _child_setup_jax(cpu_devices=None):
    """Import jax for a child. ``cpu_devices`` (an int) makes this child a
    CPU proxy: it forces the ``cpu`` platform with that many virtual devices
    BEFORE the backend initializes, so it can never take the chip. None
    leaves the platform to JAX (the device measurement)."""
    if cpu_devices is not None:
        from neuronx_distributed_tpu.utils.platform import force_cpu_devices

        force_cpu_devices(cpu_devices)
    import jax

    # Persistent compilation cache: a rerun in the same checkout skips the
    # first compiles. One owner for the knob: aot.enable_persistent_cache
    # leaves a directory placed from outside alone and honors
    # NXD_TPU_PERSISTENT_CACHE=0.
    from neuronx_distributed_tpu.inference import aot

    aot.enable_persistent_cache(
        os.path.join(os.path.dirname(os.path.abspath(__file__)), ".jax_cache"),
        min_compile_time_secs=1.0,
    )
    return jax


def child_train() -> None:
    """The device measurement (``--child``). Needs a TPU; any failure
    propagates — a traceback and a non-zero exit, never a value-0 line."""
    jax = _child_setup_jax()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"bench --child measures the chip; found platform "
            f"{devs[0].platform!r}"
        )
    _measure(devs)


def _measure(devs) -> None:
    import jax
    import jax.numpy as jnp

    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib
    from neuronx_distributed_tpu.trainer import (
        OptimizerConfig,
        build_train_step,
        create_train_state,
        make_optimizer,
        shard_batch,
    )

    from neuronx_distributed_tpu.observability.programs import (
        UNAVAILABLE,
        device_peaks,
    )

    peaks = device_peaks(devs[0])
    if peaks["flops"] == UNAVAILABLE:
        raise RuntimeError(
            f"no peak FLOP/s for device kind {peaks['kind']!r} "
            f"({peaks['source']}) — an MFU against a guessed ceiling is "
            "worse than none; add the chip to observability/programs.py"
        )
    peak = peaks["flops"]
    mesh_lib.destroy_model_parallel()
    mesh_lib.initialize_model_parallel(tensor_model_parallel_size=1)

    # Llama-2-7B layer geometry, depth scaled to single-chip HBM (the
    # reference integration-test trick: full width, few layers).
    num_layers, batch, seq = 2, 4, 2048
    cfg = LlamaConfig(
        vocab_size=32000,
        hidden_size=4096,
        intermediate_size=11008,
        num_layers=num_layers,
        num_heads=32,
        num_kv_heads=32,
        max_seq_len=2048,
        dtype=jnp.bfloat16,
        param_dtype=jnp.float32,
        remat=False,
        scan_layers=False,
    )

    # the Pallas flash kernel, compiled by Mosaic
    attention_impl = "flash"
    model = LlamaForCausalLM(cfg, attention_impl=attention_impl)
    optimizer = make_optimizer(OptimizerConfig(zero1=False))
    key = jax.random.PRNGKey(0)
    ids = jax.random.randint(key, (batch, seq), 0, cfg.vocab_size)

    state, p_sh, s_sh = create_train_state(model, optimizer, key, ids, zero1=False)
    step = build_train_step(model, optimizer, p_sh, s_sh)
    data = shard_batch({"input_ids": ids, "labels": jnp.roll(ids, -1, axis=1)})

    n_params = sum(p.size for p in jax.tree.leaves(state.params))
    # input embedding table does a lookup, not a matmul — exclude from the
    # 6·N count (the lm_head, a real matmul, stays)
    embed_params = cfg.vocab_size * cfg.hidden_size

    # warmup (compile). The float() readback waits for the step; the
    # two-point slope cancels its fixed cost.
    for _ in range(2):
        state, metrics = step(state, data)
    _ = float(metrics["loss"])

    def timed(iters):
        nonlocal state
        t0 = time.perf_counter()
        m = None
        for _ in range(iters):
            state, m = step(state, data)
        _ = float(m["loss"])  # force full pipeline completion
        return time.perf_counter() - t0

    n1, n2 = 3, 13
    t1 = timed(n1)
    t2 = timed(n2)
    dt = (t2 - t1) / (n2 - n1)
    if dt <= 0:  # fall back if noise dominates
        dt = t2 / n2

    tokens = batch * seq
    tokens_per_sec = tokens / dt
    # compiler-truth FLOPs (ISSUE 12): cost_analysis of the very train
    # step that ran, alongside the hand 6·N accounting — a re-lower is a
    # trace (no compile), so this costs milliseconds. flops_source records
    # which number backs the headline MFU comparison.
    flops_compiler = None
    ca = step.lower(state, data).cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if isinstance(ca, dict) and "flops" in ca:
        flops_compiler = float(ca["flops"])
    flops_raw = 6.0 * n_params * tokens
    flops_matmul = 6.0 * (n_params - embed_params) * tokens
    # causal attention (QK^T + AV), fwd+bwd = 3× fwd; the flash kernel only
    # computes the lower triangle, so the honest hardware count is half of
    # the full 12·L·B·S²·H
    flops_attn = 6.0 * cfg.num_layers * batch * seq * seq * cfg.hidden_size
    mfu_raw = (flops_raw / dt) / peak
    mfu = ((flops_matmul + flops_attn) / dt) / peak
    target_mfu = 0.40
    payload = {
        "metric": METRIC,
        "value": round(tokens_per_sec, 2),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / target_mfu, 4),
        "extras": {
            "mfu": round(mfu, 4),
            "mfu_raw_6n": round(mfu_raw, 4),
            "flops_matmul_per_step": flops_matmul,
            "flops_attn_per_step": flops_attn,
            # compiler-reported step FLOPs vs the 6·N heuristic (ISSUE 12)
            "flops_compiler_per_step": flops_compiler,
            "flops_source": (
                "cost_analysis+6n" if flops_compiler is not None
                else "6n_heuristic"
            ),
            "mfu_compiler": (
                round((flops_compiler / dt) / peak, 4)
                if flops_compiler is not None else None
            ),
            "embed_params_excluded": int(embed_params),
            "peak_flops": peak,
            "peak_source": peaks["source"],
            "device_kind": peaks["kind"],
            "n_params": int(n_params),
            "step_time_s": round(dt, 4),
            "batch": batch,
            "seq": seq,
            "layers": cfg.num_layers,
            "platform": devs[0].platform,
            "attention_impl": attention_impl,
        },
    }
    # emit the headline BEFORE the side measurements: the parent takes the
    # LAST parseable line, so the augmented line wins when it lands. A side
    # measurement that fails fails the child (non-zero exit) — it is not
    # recorded as a value and carried past.
    _emit(payload)

    # GQA evidence: same width at 8 kv-heads exercises the kernels' native
    # grouped-head path (no KV replication in HBM).
    payload["extras"]["gqa"] = _measure_gqa(cfg, batch, seq, attention_impl)
    _emit(payload)
    # flash block-size sweep: raw kernel fwd+bwd time at block 256/512/1024
    payload["extras"]["flash_block_sweep"] = _flash_block_sweep(batch, seq)
    _emit(payload)
    # flash-decode vs einsum at 8k context
    payload["extras"]["flash_decode_8k"] = _measure_flash_decode(devs)
    _emit(payload)
    # quantized serving: dequant vs native int8 MXU
    payload["extras"]["int8_serving"] = _measure_int8_serving(devs)
    _emit(payload)


def _measure_flash_decode(devs):
    """Decode attention at 8k context: einsum path vs the Pallas flash-decode
    kernel (kernels/flash_decode.py), p50 over 20 steps. Llama-3-8B head
    geometry (32 q / 8 kv heads, d=128)."""
    import jax
    import jax.numpy as jnp

    from neuronx_distributed_tpu.kernels.flash_decode import (
        flash_decode_attention,
    )
    from neuronx_distributed_tpu.modules.attention import decode_attention

    b, L, h, hkv, d = 1, 8192, 32, 8, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, 1, h, d), jnp.bfloat16)
    kc = jax.random.normal(ks[1], (b, L, hkv, d), jnp.bfloat16)
    vc = jax.random.normal(ks[2], (b, L, hkv, d), jnp.bfloat16)
    pos = jnp.asarray([L - 1], jnp.int32)

    # einsum golden path (what decode_attention does below the threshold)
    from neuronx_distributed_tpu.kernels.ring_attention import _block_attn

    def einsum_decode(q, kc, vc):
        qt = jnp.swapaxes(q, 1, 2).reshape(b, hkv, h // hkv, 1, d)
        num, _, l = _block_attn(
            qt, jnp.swapaxes(kc, 1, 2), jnp.swapaxes(vc, 1, 2),
            pos, jnp.arange(L), causal=True,
        )
        return num / jnp.maximum(l, 1e-20)[..., None]

    out = {}
    for name, fn in (
        ("einsum", jax.jit(einsum_decode)),
        ("flash", jax.jit(lambda q, kc, vc: flash_decode_attention(q, kc, vc, pos))),
    ):
        r = fn(q, kc, vc)  # compile
        _ = float(jnp.sum(r.astype(jnp.float32)))
        times = []
        for _i in range(20):
            t0 = time.perf_counter()
            r = fn(q, kc, vc)
            _ = float(jnp.sum(r.astype(jnp.float32)))
            times.append(time.perf_counter() - t0)
        times.sort()
        out[name + "_p50_ms"] = round(times[len(times) // 2] * 1e3, 3)
    out["speedup"] = round(
        out["einsum_p50_ms"] / max(out["flash_p50_ms"], 1e-9), 3
    )
    out["shape"] = f"b={b} L={L} h={h} hkv={hkv} d={d} s=1"
    return out


def _measure_int8_serving(devs):
    """Quantized-serving decode step time: dequant-then-matmul vs the native
    int8 MXU path (VERDICT r4 next #6 'Done = serving step-time comparison
    recorded'). 1-layer full-width Llama, greedy decode steps."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from neuronx_distributed_tpu.quantization.config import QuantizationConfig
    from neuronx_distributed_tpu.quantization.utils import quantize_param_tree
    from flax.core import meta

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=11008,
        num_layers=1, num_heads=32, num_kv_heads=32, max_seq_len=2048,
        dtype=jnp.bfloat16, param_dtype=jnp.float32, remat=False,
        scan_layers=False,
    )
    fmodel = LlamaForCausalLM(cfg, attention_impl="flash")
    ids = jax.random.randint(jax.random.PRNGKey(0), (1, 1024), 0, cfg.vocab_size)
    fparams = meta.unbox(jax.jit(fmodel.init)(jax.random.PRNGKey(1), ids))
    qcfg = QuantizationConfig()
    qparams = quantize_param_tree(fparams, qcfg)
    out = {}
    for name, q in (
        ("dequant", qcfg),
        ("int8_mxu", dataclasses.replace(qcfg, use_int8_matmul=True)),
    ):
        model = LlamaForCausalLM(
            dataclasses.replace(cfg, quantization=q), attention_impl="flash"
        )
        prefill = model.clone(mode="prefill")
        decode = model.clone(mode="decode")

        @jax.jit
        def step(params, cache, tok):
            o, v = decode.apply(
                {**params, "cache": cache}, tok, mutable=["cache"]
            )
            return o[:, -1].argmax(-1).astype(jnp.int32)[:, None], v["cache"]

        _, v = jax.jit(lambda p, i: prefill.apply(p, i, mutable=["cache"]))(
            qparams, ids
        )
        cache = v["cache"]
        tok = jnp.zeros((1, 1), jnp.int32)
        tok, cache = step(qparams, cache, tok)  # compile
        _ = int(tok[0, 0])
        t0 = time.perf_counter()
        for _i in range(30):
            tok, cache = step(qparams, cache, tok)
        _ = int(tok[0, 0])
        out[name + "_decode_ms"] = round((time.perf_counter() - t0) / 30 * 1e3, 3)
    out["int8_speedup"] = round(
        out["dequant_decode_ms"] / max(out["int8_mxu_decode_ms"], 1e-9), 3
    )
    return out


def _measure_serving_chunk(devs):
    """Serving decode-throughput: the continuous-batching engine's fused
    multi-token decode chunks (donated cache, device-resident slot state,
    one host sync per chunk) vs the per-token chunk=1 loop on the SAME
    request workload. decode_tok_s reads the engine's dispatch+readback
    hot-path counters (prefill/compile excluded); e2e_tok_s is whole-run
    wall including prefills."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_tpu.inference import GenerationConfig
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from neuronx_distributed_tpu.serving import ServingEngine

    cfg = LlamaConfig(
        vocab_size=2048, hidden_size=256, intermediate_size=704,
        num_layers=2, num_heads=8, num_kv_heads=4, max_seq_len=512,
        dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
        scan_layers=False,
    )
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    rng = np.random.RandomState(0)
    init_ids = rng.randint(1, cfg.vocab_size, size=(1, 8)).astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), init_ids)
    prompts = [
        rng.randint(1, cfg.vocab_size, size=int(rng.randint(6, 18))).astype(np.int32)
        for _ in range(8)
    ]
    gcfg = GenerationConfig(max_new_tokens=64, temperature=0.8, top_k=20)
    out = {}
    for chunk in (1, 8):
        # paged KV is the serving children's default layout now (ISSUE 13
        # fold-in) — the row engine keeps its own head-to-head in
        # --child-paged
        engine = ServingEngine(
            model, params, num_slots=4, decode_chunk_size=chunk,
            kv_page_size=16,
        )
        # warmup wave: compiles the prefill buckets + the one decode program
        for i, p in enumerate(prompts[:4]):
            engine.submit(
                p,
                GenerationConfig(max_new_tokens=10, temperature=0.8, top_k=20),
                key=jax.random.PRNGKey(i),
            )
        engine.run()
        m = engine.metrics
        base_tok = m.decode_tokens
        base_wall = m.decode_dispatch_s + m.decode_readback_s
        base_chunks = m.chunks
        t0 = time.perf_counter()
        for i, p in enumerate(prompts):
            engine.submit(p, gcfg, key=jax.random.PRNGKey(100 + i))
        engine.run()
        wall = time.perf_counter() - t0
        dtok = m.decode_tokens - base_tok
        dwall = (m.decode_dispatch_s + m.decode_readback_s) - base_wall
        out[f"chunk{chunk}"] = {
            "decode_tok_s": round(dtok / dwall, 2) if dwall > 0 else 0.0,
            "e2e_tok_s": round(dtok / wall, 2) if wall > 0 else 0.0,
            "decode_tokens": int(dtok),
            "host_syncs": int(m.chunks - base_chunks),
            "decode_compilations": engine.decode_compilations,
        }
    out["decode_speedup_chunk8"] = round(
        out["chunk8"]["decode_tok_s"]
        / max(out["chunk1"]["decode_tok_s"], 1e-9),
        3,
    )
    return out


def _divergence_lost(clean, other):
    """Clean-run entries NOT reproduced by ``other``: everything past the
    first divergence point (every recovery contract here requires 0)."""
    agree = 0
    for a, b in zip(clean, other):
        if a != b:
            break
        agree += 1
    return len(clean) - agree


def _measure_serving_faults(devs):
    """Fault-tolerance recovery overhead (``--child-faults``): the SAME
    request workload through the continuous-batching engine clean vs with
    one injected mid-run dispatch failure (bounded-retry recovery requeues
    the in-flight requests and resumes). Reports the recovery's wall-clock
    overhead and proves zero token loss: every stream in the faulted run is
    bit-identical to the clean run's."""
    import time as _t

    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_tpu.inference import GenerationConfig
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from neuronx_distributed_tpu.serving import FaultInjector, ServingEngine

    cfg = LlamaConfig(
        vocab_size=2048, hidden_size=256, intermediate_size=704,
        num_layers=2, num_heads=8, num_kv_heads=4, max_seq_len=512,
        dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
        scan_layers=False,
    )
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    rng = np.random.RandomState(0)
    init_ids = rng.randint(1, cfg.vocab_size, size=(1, 8)).astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), init_ids)
    prompts = [
        rng.randint(1, cfg.vocab_size, size=int(rng.randint(6, 18))).astype(np.int32)
        for _ in range(6)
    ]
    gcfg = GenerationConfig(max_new_tokens=48, temperature=0.8, top_k=20)

    def run(injector):
        engine = ServingEngine(
            model, params, num_slots=4, decode_chunk_size=4,
            fault_injector=injector, kv_page_size=16,
        )
        # warmup wave compiles prefill buckets + the decode program so the
        # fault run's overhead measures RECOVERY, not compilation
        for i, p in enumerate(prompts[:4]):
            engine.submit(
                p,
                GenerationConfig(max_new_tokens=8, temperature=0.8, top_k=20),
                key=jax.random.PRNGKey(i),
            )
        engine.run()
        t0 = _t.perf_counter()
        reqs = [
            engine.submit(p, gcfg, key=jax.random.PRNGKey(100 + i))
            for i, p in enumerate(prompts)
        ]
        engine.run()
        wall = _t.perf_counter() - t0
        return engine, reqs, wall

    _, clean_reqs, clean_wall = run(None)
    inj = FaultInjector().fail_dispatch(at=6, times=1)  # mid-run, post-warmup
    engine, fault_reqs, fault_wall = run(inj)

    clean_streams = [r.tokens for r in clean_reqs]
    fault_streams = [r.tokens for r in fault_reqs]

    tokens_lost = sum(
        _divergence_lost(c, f) for c, f in zip(clean_streams, fault_streams)
    )
    return {
        "injected_dispatch_failures": inj.counters["dispatch_failures"],
        "dispatch_retries": engine.metrics.dispatch_retries,
        "recoveries": engine.metrics.recoveries,
        "health_after": engine.metrics.snapshot()["health"],
        "streams_bit_identical": clean_streams == fault_streams,
        "tokens_lost": int(tokens_lost),
        "clean_wall_s": round(clean_wall, 4),
        "fault_wall_s": round(fault_wall, 4),
        "recovery_overhead_s": round(fault_wall - clean_wall, 4),
        "recovery_overhead_pct": round(
            100.0 * (fault_wall - clean_wall) / clean_wall, 2
        ) if clean_wall > 0 else 0.0,
    }


def _measure_train_faults(devs):
    """Training fault-tolerance (``--child-train-faults``): the SAME short
    training run on the CPU backend clean vs fault-injected (one NaN loss
    skipped on device + one recovered dispatch failure), recording the
    recovery's wall overhead and the anomaly-skip count — then a
    kill-and-resume split of the same run proving the resumed loss stream
    is bit-identical to the uninterrupted one (tokens_lost must be 0: the
    exact-resume contract, not an approximation)."""
    import tempfile
    import time as _t

    import jax

    from neuronx_distributed_tpu.models.llama import LlamaForCausalLM, tiny_llama
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib
    from neuronx_distributed_tpu.trainer import OptimizerConfig
    from neuronx_distributed_tpu.trainer.data import SyntheticTokens
    from neuronx_distributed_tpu.trainer.faults import FaultInjector
    from neuronx_distributed_tpu.trainer.loop import CheckpointCallback, Trainer
    from neuronx_distributed_tpu.utils.retry import RetryPolicy

    if not mesh_lib.model_parallel_is_initialized():
        mesh_lib.initialize_model_parallel()
    cfg = tiny_llama(num_layers=2, max_seq_len=32)
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    STEPS, BS, SEQ = 8, 4, 16

    class Rec:
        def __init__(self):
            self.losses = []

        def on_train_start(self, t):
            pass

        def on_step_end(self, t, m):
            self.losses.append(float(m["loss"]))

        def on_train_end(self, t):
            pass

    def run(injector=None, steps=STEPS, resume_from=None, callbacks=()):
        rec = Rec()
        tr = Trainer(
            model=model, optimizer_config=OptimizerConfig(zero1=False),
            callbacks=[rec, *callbacks], fault_injector=injector,
            dispatch_retry=RetryPolicy(max_attempts=3, first_wait=0.01,
                                       min_wait=0.0),
        )
        t0 = _t.perf_counter()
        tr.fit(
            SyntheticTokens(cfg.vocab_size, BS, SEQ, seed=11),
            jax.random.PRNGKey(0), max_steps=steps, resume_from=resume_from,
        )
        return tr, rec.losses, _t.perf_counter() - t0

    run(steps=2)  # compile outside the timed windows
    _, clean_losses, clean_wall = run()

    # dispatch attempts are counted per fit(): 8 steps = attempts 0..7, so
    # attempt 5 is a mid-run failure (its retry lands the same run)
    inj = FaultInjector().nan_loss(at=3).fail_dispatch(at=5, times=1)
    tr_f, fault_losses, fault_wall = run(injector=inj)

    # kill-and-resume split: 4 steps + checkpoint, fresh trainer to 8
    with tempfile.TemporaryDirectory() as d:
        _, head, _ = run(steps=4, callbacks=[CheckpointCallback(d, every=4, async_save=False)])
        tr_r, tail, _ = run(steps=STEPS, resume_from=d)
    resumed = head + tail

    return {
        "steps": STEPS,
        "injected": dict(inj.counters),
        "anomaly_skips": int(tr_f.anomaly_skips),
        "dispatch_retries": int(tr_f.dispatch_retries),
        "health_after_faults": tr_f.health().value,
        "clean_wall_s": round(clean_wall, 4),
        "fault_wall_s": round(fault_wall, 4),
        "recovery_overhead_s": round(fault_wall - clean_wall, 4),
        "recovery_overhead_pct": round(
            100.0 * (fault_wall - clean_wall) / clean_wall, 2
        ) if clean_wall > 0 else 0.0,
        "resume_bit_identical": resumed == clean_losses,
        "resumed_tokens_lost": int(_divergence_lost(clean_losses, resumed)),
        "resumed_steps_run": int(tr_r.steps_run),
    }


def _measure_integrity(devs):
    """SDC sentinel overhead + detection (``--child-integrity``): the SAME
    short training run with the sentinel OFF vs ON (vote mode over the
    CPU proxy's dp replicas, ``check_every=16``), comparing trimmed mean
    step wall — the ≤2% budget — and proving determinism (the loss
    streams must be bit-identical: fingerprinting is observation, never
    perturbation). Then an injected single-bit params flip mid-window
    measures detection latency in steps and the rollback count."""
    import time as _t

    import jax

    from neuronx_distributed_tpu.integrity import SentinelConfig
    from neuronx_distributed_tpu.models.llama import LlamaForCausalLM, tiny_llama
    from neuronx_distributed_tpu.observability.flight_recorder import (
        FlightRecorder,
    )
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib
    from neuronx_distributed_tpu.trainer import OptimizerConfig
    from neuronx_distributed_tpu.trainer.data import SyntheticTokens
    from neuronx_distributed_tpu.trainer.faults import FaultInjector
    from neuronx_distributed_tpu.trainer.loop import Trainer

    if not mesh_lib.model_parallel_is_initialized():
        mesh_lib.initialize_model_parallel()
    cfg = tiny_llama(num_layers=2, max_seq_len=32)
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    STEPS, BS, SEQ, CHECK = 32, 8, 16, 16
    FLIP_AT = 20  # mid window: the idx-31 check is the first to see it

    class Rec:
        def __init__(self):
            self.losses, self.times = [], []

        def on_train_start(self, t):
            pass

        def on_step_end(self, t, m):
            self.losses.append(float(m["loss"]))
            self.times.append(_t.perf_counter())

        def on_train_end(self, t):
            pass

    def run(integrity=None, injector=None, flight=None, steps=STEPS):
        rec = Rec()
        tr = Trainer(
            model=model, optimizer_config=OptimizerConfig(zero1=False),
            callbacks=[rec], fault_injector=injector, integrity=integrity,
            flight_recorder=flight,
        )
        t0 = _t.perf_counter()
        tr.fit(
            SyntheticTokens(cfg.vocab_size, BS, SEQ, seed=11),
            jax.random.PRNGKey(0), max_steps=steps,
        )
        rec.times.insert(0, t0)
        return tr, rec

    def step_ms(rec):
        # trimmed mean: drop the two slowest steps (first-step train
        # compile / first-check fingerprint compile), average the rest —
        # the steady-state per-step wall the 2% budget is about
        deltas = sorted(
            b - a for a, b in zip(rec.times, rec.times[1:])
        )[:-2]
        return 1000.0 * sum(deltas) / max(len(deltas), 1)

    run(steps=2)  # compile the train step outside every timed window
    tr_off, rec_off = run()
    tr_on, rec_on = run(integrity=SentinelConfig(check_every=CHECK))
    off_ms, on_ms = step_ms(rec_off), step_ms(rec_on)
    overhead_pct = (
        100.0 * (on_ms - off_ms) / off_ms if off_ms > 0 else 0.0
    )

    fl = FlightRecorder(subsystem="bench")
    inj = FaultInjector().flip_bits("params", at=FLIP_AT, device=1)
    tr_d, _ = run(
        integrity=SentinelConfig(check_every=CHECK), injector=inj,
        flight=fl,
    )
    detected = [e for e in fl.events() if e["kind"] == "sdc_detected"]
    det_step = int(detected[0]["step"]) if detected else None

    return {
        "steps": STEPS,
        "check_every": CHECK,
        "mode": tr_on._sentinel.mode,
        "dp_replicas": len(devs),
        "step_ms_off": round(off_ms, 4),
        "step_ms_on": round(on_ms, 4),
        "overhead_pct": round(overhead_pct, 2),
        "within_budget": overhead_pct <= 2.0,
        "checks_run": int(tr_on._sentinel.counters["integrity_checks"]),
        "false_positives": int(tr_on._sentinel.counters["sdc_detected"]),
        "deterministic": rec_on.losses == rec_off.losses,
        "injected_flip_step": FLIP_AT,
        "detected_step": det_step,
        "detection_latency_steps": (
            det_step - FLIP_AT if det_step is not None else None
        ),
        "rollbacks": int(tr_d._sentinel.counters["sdc_rollbacks"]),
        "quarantined_devices": list(tr_d._sentinel.quarantined_devices),
        "final_step": int(tr_d.step),
    }


def _measure_serving_prefix(devs):
    """Prefix-cache payoff (``--child-prefix``): the SAME shared-system-
    prompt workload through the continuous-batching engine with the prefix
    cache OFF vs ON (fixed seeds/keys, identical submission order). After a
    warmup wave compiles every program on both sides (the cached engine's
    store is then cleared so the measured run starts cold), the comparison
    isolates the admission-path saving: total prefill wall, TTFT, hit
    rate — and proves the streams are bit-identical (tokens_lost must be
    0, the prefix cache is an optimization, not an approximation)."""
    import time as _t

    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_tpu.inference import GenerationConfig
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from neuronx_distributed_tpu.serving import PrefixCache, ServingEngine

    cfg = LlamaConfig(
        vocab_size=2048, hidden_size=256, intermediate_size=704,
        num_layers=2, num_heads=8, num_kv_heads=4, max_seq_len=512,
        dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
        scan_layers=False,
    )
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    rng = np.random.RandomState(0)
    init_ids = rng.randint(1, cfg.vocab_size, size=(1, 8)).astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), init_ids)
    # a 224-token shared system prompt + short unique tails: the realistic
    # shape where prefill dominates TTFT and almost all of it is shared
    # (full prefill pads to the 256 bucket; a hit prefills an 8-token-max
    # suffix chunk — a ~30x token-count reduction on the admission path)

    class _Blocking:
        """Wrap a jitted prefill program so the engine's
        ``record_prefill_wall`` measures COMPLETED compute: dispatch is
        async (it returns in ~1 ms whatever the program costs), so without
        the barrier the per-path walls are scheduler noise, not prefill
        cost. The serving engine rightly never blocks here in production —
        this is a bench-only measurement shim, identical for both
        engines."""

        def __init__(self, fn):
            self._fn = fn

        def __call__(self, *a):
            out = self._fn(*a)
            jax.block_until_ready(out)
            return out

        def _cache_size(self):
            return self._fn._cache_size()

    n_requests = 12
    system = rng.randint(1, cfg.vocab_size, size=224).astype(np.int32)
    warm_system = rng.randint(1, cfg.vocab_size, size=224).astype(np.int32)
    tails = [
        rng.randint(1, cfg.vocab_size, size=int(rng.randint(4, 9))).astype(np.int32)
        for _ in range(n_requests)
    ]
    # warmup tails chosen so BOTH suffix chunk buckets the measured tails
    # can hit (4 and 8) compile during warmup: the longest-prefill-first
    # round seeds on the len-8 tail, then hits with suffixes of 6, 4, 4
    warm_tails = [
        rng.randint(1, cfg.vocab_size, size=n).astype(np.int32)
        for n in (8, 4, 6, 4)
    ]
    gcfg = GenerationConfig(max_new_tokens=24, temperature=0.8, top_k=20)

    def run(prefix_cache):
        engine = ServingEngine(
            model, params, num_slots=4, decode_chunk_size=4,
            prefix_cache=prefix_cache, kv_page_size=16,
        )
        orig_prefill_fn = engine._prefill_fn
        engine._prefill_fn = lambda padded: _Blocking(orig_prefill_fn(padded))
        engine._suffix_fn = _Blocking(engine._suffix_fn)
        # warmup wave: same shapes, DIFFERENT system prompt — compiles the
        # full-prefill buckets, the decode program, and (cached side) the
        # suffix/extract/seed/fingerprint programs, without pre-seeding the
        # measured workload's prefix
        for i, tail in enumerate(warm_tails):
            engine.submit(
                np.concatenate([warm_system, tail]),
                GenerationConfig(max_new_tokens=4, temperature=0.8, top_k=20),
                key=jax.random.PRNGKey(i),
            )
        engine.run()
        if engine.prefix is not None:
            engine.prefix.clear()  # measured run starts with a cold store
        m = engine.metrics
        base = m.snapshot()
        t0 = _t.perf_counter()
        reqs = [
            engine.submit(
                np.concatenate([system, tail]), gcfg,
                key=jax.random.PRNGKey(100 + i),
            )
            for i, tail in enumerate(tails)
        ]
        engine.run()
        wall = _t.perf_counter() - t0
        snap = m.snapshot()
        delta = {
            k: snap[k] - base[k]
            for k in (
                "prefill_wall_s", "prefix_hits", "prefix_misses",
                "prefix_tokens_reused",
            )
        }
        ttfts = [
            m.request_snapshot(r.rid)["ttft"] for r in reqs
        ]
        return engine, reqs, wall, delta, sum(ttfts) / len(ttfts)

    _, clean_reqs, clean_wall, clean_d, clean_ttft = run(None)
    engine, cache_reqs, cache_wall, cache_d, cache_ttft = run(
        PrefixCache(max_entries=32, min_match=16)
    )

    clean_streams = [r.tokens for r in clean_reqs]
    cache_streams = [r.tokens for r in cache_reqs]

    tokens_lost = sum(
        _divergence_lost(c, f) for c, f in zip(clean_streams, cache_streams)
    )
    hits = cache_d["prefix_hits"]
    total = hits + cache_d["prefix_misses"]
    return {
        "requests": n_requests,
        "shared_prefix_tokens": int(system.size),
        "prefix_hits": int(hits),
        "prefix_hit_rate": round(hits / total, 4) if total else 0.0,
        "prefix_tokens_reused": int(cache_d["prefix_tokens_reused"]),
        "streams_bit_identical": clean_streams == cache_streams,
        "tokens_lost": int(tokens_lost),
        "clean_prefill_wall_s": round(clean_d["prefill_wall_s"], 4),
        "cached_prefill_wall_s": round(cache_d["prefill_wall_s"], 4),
        "prefill_wall_saved_s": round(
            clean_d["prefill_wall_s"] - cache_d["prefill_wall_s"], 4
        ),
        "prefill_speedup": round(
            clean_d["prefill_wall_s"] / max(cache_d["prefill_wall_s"], 1e-9), 3
        ),
        "clean_mean_ttft_s": round(clean_ttft, 4),
        "cached_mean_ttft_s": round(cache_ttft, 4),
        "ttft_saved_s": round(clean_ttft - cache_ttft, 4),
        "clean_wall_s": round(clean_wall, 4),
        "cached_wall_s": round(cache_wall, 4),
        "prefill_compilations": engine.prefill_compilations,
        "prefix_compilations": engine.prefix_compilations,
    }


def _measure_serving_paged(devs):
    """Paged-KV payoff (``--child-paged``): the SAME mixed-length workload
    (short shared-prefix chat + long-doc requests) through the engine with
    the row-per-slot manager vs the paged manager, BOTH at the same fixed
    KV HBM budget (cache columns per layer). The row manager can hold
    ``budget // max_seq_len`` slots at that budget whatever the traffic
    looks like; the paged manager packs by ACTUAL footprint (block tables
    + free-page admission), so mixed-length traffic sustains more
    concurrent slots and higher aggregate decode throughput. Also reports
    page utilization and proves the CoW prefix-sharing contract: hits map
    pool pages (``prefix_pages_shared``) and the allocator's ``copy_bytes``
    stays 0 — zero-copy by accounting, not timing. Streams must be
    bit-identical across managers (tokens_lost = 0)."""
    import time as _t

    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_tpu.inference import GenerationConfig
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from neuronx_distributed_tpu.serving import PrefixCache, ServingEngine

    cfg = LlamaConfig(
        vocab_size=2048, hidden_size=256, intermediate_size=704,
        num_layers=2, num_heads=8, num_kv_heads=4, max_seq_len=512,
        dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
        scan_layers=False,
    )
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    rng = np.random.RandomState(0)
    init_ids = rng.randint(1, cfg.vocab_size, size=(1, 8)).astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), init_ids)

    KV_BUDGET_COLS = 2048  # per-layer cache columns both managers may hold
    PAGE = 16
    # mixed-length traffic: 12 chat turns sharing a 32-token system prompt
    # (2 whole pages -> CoW-shareable) + 3 long documents. The row manager
    # at this budget holds 2048 // 512 = 4 slots, period; the paged
    # manager packs by footprint.
    system = rng.randint(1, cfg.vocab_size, size=32).astype(np.int32)
    chats = [
        np.concatenate([
            system,
            rng.randint(1, cfg.vocab_size,
                        size=int(rng.randint(4, 17))).astype(np.int32),
        ])
        for _ in range(12)
    ]
    docs = [
        rng.randint(1, cfg.vocab_size,
                    size=int(rng.randint(180, 300))).astype(np.int32)
        for _ in range(3)
    ]
    workload = []
    for i, p in enumerate(chats):
        workload.append((p, GenerationConfig(max_new_tokens=32,
                                             temperature=0.8, top_k=20)))
        if i % 4 == 3:
            workload.append((docs[i // 4],
                             GenerationConfig(max_new_tokens=32,
                                              temperature=0.8, top_k=20)))

    def run(paged: bool):
        if paged:
            engine = ServingEngine(
                model, params, num_slots=16, decode_chunk_size=8,
                kv_page_size=PAGE, kv_num_pages=KV_BUDGET_COLS // PAGE + 1,
                prefix_cache=PrefixCache(min_match=PAGE),
            )
        else:
            engine = ServingEngine(
                model, params, num_slots=KV_BUDGET_COLS // cfg.max_seq_len,
                decode_chunk_size=8, prefix_cache=PrefixCache(min_match=PAGE),
            )
        # warmup wave: compiles the decode program + the prefill buckets the
        # measured run uses (store cleared after, so the run starts cold)
        warm = [rng.randint(1, cfg.vocab_size, size=n).astype(np.int32)
                for n in (40, 44, 48, 200, 260)]
        for i, p in enumerate(warm):
            engine.submit(
                p, GenerationConfig(max_new_tokens=8, temperature=0.8,
                                    top_k=20),
                key=jax.random.PRNGKey(900 + i),
            )
        engine.run()
        if engine.prefix is not None:
            engine.prefix.clear()
        m = engine.metrics
        base = {
            "tok": m.decode_tokens,
            "wall": m.decode_dispatch_s + m.decode_readback_s,
            "occ": m.occupied_slot_steps, "steps": m.steps,
        }
        reqs = [
            engine.submit(p, g, key=jax.random.PRNGKey(100 + i))
            for i, (p, g) in enumerate(workload)
        ]
        peak_active = 0
        peak_pages = 0
        t0 = _t.perf_counter()
        while engine.has_work:
            engine.step()
            peak_active = max(peak_active, int(engine._active.sum()))
            if paged:
                peak_pages = max(peak_pages, engine.cache.pages_mapped)
        wall = _t.perf_counter() - t0
        snap = m.snapshot()
        dtok = m.decode_tokens - base["tok"]
        dwall = (m.decode_dispatch_s + m.decode_readback_s) - base["wall"]
        dsteps = m.steps - base["steps"]
        docc = m.occupied_slot_steps - base["occ"]
        stats = {
            "num_slots": engine.num_slots,
            "mean_concurrent_slots": round(docc / dsteps, 3) if dsteps else 0.0,
            "peak_concurrent_slots": peak_active,
            "decode_tok_s": round(dtok / dwall, 2) if dwall > 0 else 0.0,
            "e2e_tok_s": round(dtok / wall, 2) if wall > 0 else 0.0,
            "decode_tokens": int(dtok),
            "preemptions": int(snap["preemptions"]),
            "prefix_hits": int(snap["prefix_hits"]),
            "prefix_hit_rate": round(snap["prefix_hit_rate"], 4),
            "decode_compilations": engine.decode_compilations,
        }
        if paged:
            cap = engine.cache.alloc.capacity
            engine.cache.check()  # leak invariant on the way out
            stats.update(
                page_size=PAGE,
                kv_pages=cap,
                peak_pages_mapped=peak_pages,
                peak_page_utilization=round(peak_pages / cap, 4) if cap else 0.0,
                prefix_pages_shared=int(snap["prefix_pages_shared"]),
                copy_bytes_on_hit=int(engine.cache.alloc.copy_bytes),
            )
        return stats, [r.tokens for r in reqs]

    row_stats, row_toks = run(False)
    paged_stats, paged_toks = run(True)
    tokens_lost = sum(
        _divergence_lost(a, b) for a, b in zip(row_toks, paged_toks)
    )

    # --- tiered leg (ISSUE 19): hit rate + TTFT vs WORKING SET at a fixed
    # tiny device pool. Off: once the distinct-prefix working set outgrows
    # what the pool can pin, the reclaim valve EVICTS and every revisit is
    # a full prefill (the cliff). On: the valve spills to host RAM and
    # admission prefetches matched pages back, so the hit rate degrades
    # into a slope and revisit TTFT stays at suffix-prefill cost. Streams
    # must be bit-identical off vs on (deterministic greedy), copy_bytes
    # stays 0, and kv_prefetch_late==0 is the overlap proof: every
    # prefetch completed inside the admission it served, never stalling a
    # decode chunk. (CPU proxy: TTFT deltas are real prefill-work deltas —
    # suffix vs full — not accelerator transfer rates.)
    TIER_POOL = 9   # 8 usable pages; pins at most 2 idle prefix entries
    TIER_HOST = 32
    g_tier = GenerationConfig(max_new_tokens=16, temperature=0.0)

    def run_tiered(working_set: int, host_pages):
        prefixes = [
            np.random.RandomState(50 + j)
            .randint(1, cfg.vocab_size, size=2 * PAGE)
            .astype(np.int32)
            for j in range(working_set)
        ]
        engine = ServingEngine(
            model, params, num_slots=2, decode_chunk_size=8,
            kv_page_size=PAGE, kv_num_pages=TIER_POOL,
            kv_host_pages=host_pages, admission="eager",
            prefix_cache=PrefixCache(min_match=PAGE),
        )
        # warmup: compile every program the measured rounds use — full +
        # suffix prefill buckets, the decode chunk, and (tiering on) the
        # spill pull / prefetch import — via a hit, a pool-overflow
        # spill, and a host-tier revisit. Cache cleared after; counters
        # baseline-subtracted so only the measured rounds report.
        wrng = np.random.RandomState(70)
        wpre = [
            wrng.randint(1, cfg.vocab_size, size=2 * PAGE).astype(np.int32)
            for _ in range(4)
        ]
        warm_wave = [wpre[0], wpre[0], wpre[1], wpre[2], wpre[3], wpre[0]]
        for i, pre in enumerate(warm_wave):
            engine.submit(
                np.concatenate([
                    pre,
                    wrng.randint(1, cfg.vocab_size, size=8).astype(np.int32),
                ]),
                g_tier, key=jax.random.PRNGKey(700 + i),
            )
            engine.run()
        engine.prefix.clear()
        base = engine.metrics.snapshot()
        srng = np.random.RandomState(60)
        toks = []
        revisit_walls = []
        for rnd in range(2):
            for j in range(working_set):
                suffix = srng.randint(
                    1, cfg.vocab_size, size=8
                ).astype(np.int32)
                t0 = _t.perf_counter()
                req = engine.submit(
                    np.concatenate([prefixes[j], suffix]), g_tier,
                    key=jax.random.PRNGKey(500 + rnd * working_set + j),
                )
                engine.run()
                if rnd == 1:
                    # round 2 replays every prefix: submit->done wall is
                    # the TTFT proxy (decode is 16 tokens flat across
                    # legs, so the off/on delta is PREFILL work — full
                    # re-prefill on the cliff, suffix-only on a hit)
                    revisit_walls.append(_t.perf_counter() - t0)
                toks.append(req.tokens)
        snap = engine.metrics.snapshot()
        engine.cache.check()
        if engine.tier is not None:
            engine.tier.check()
        revisits = working_set  # round 2 replays every prefix once
        hits = snap["prefix_hits"] - base["prefix_hits"]
        tier_counts = {
            k: v - base["prefix_hit_tier"].get(k, 0)
            for k, v in snap["prefix_hit_tier"].items()
            if v - base["prefix_hit_tier"].get(k, 0)
        }
        return {
            "prefix_hits": int(hits),
            "hit_rate": round(hits / revisits, 4),
            "hit_tier": tier_counts,
            "revisit_wall_mean_s": round(
                sum(revisit_walls) / len(revisit_walls), 5
            ),
            "prefill_full_wall_s": round(
                snap["prefill_full_wall_s"] - base["prefill_full_wall_s"],
                5,
            ),
            "prefill_suffix_wall_s": round(
                snap["prefill_suffix_wall_s"]
                - base["prefill_suffix_wall_s"], 5,
            ),
            "pages_spilled": int(
                snap["kv_pages_spilled"] - base["kv_pages_spilled"]
            ),
            "pages_prefetched": int(
                snap["kv_pages_prefetched"] - base["kv_pages_prefetched"]
            ),
            "prefetch_late": int(
                snap["kv_prefetch_late"] - base["kv_prefetch_late"]
            ),
            "copy_bytes": int(engine.cache.alloc.copy_bytes),
        }, toks

    tiered_curve = []
    tiered_identical = True
    for ws in (2, 4, 6):
        off_s, off_t = run_tiered(ws, None)
        on_s, on_t = run_tiered(ws, TIER_HOST)
        tiered_identical = tiered_identical and off_t == on_t
        tiered_curve.append({
            "working_set_prefixes": ws,
            "working_set_pages": 2 * ws,
            "off": off_s,
            "on": on_s,
        })

    return {
        "kv_budget_cols": KV_BUDGET_COLS,
        "workload": {
            "chat_requests": len(chats), "doc_requests": len(docs),
            "shared_prefix_tokens": int(system.size),
        },
        "row": row_stats,
        "paged": paged_stats,
        "concurrent_slots_ratio": round(
            paged_stats["mean_concurrent_slots"]
            / max(row_stats["mean_concurrent_slots"], 1e-9), 3
        ),
        "e2e_tok_s_ratio": round(
            paged_stats["e2e_tok_s"] / max(row_stats["e2e_tok_s"], 1e-9), 3
        ),
        "streams_bit_identical": row_toks == paged_toks,
        "tokens_lost": int(tokens_lost),
        "zero_copy_prefix": paged_stats.get("copy_bytes_on_hit", -1) == 0,
        "tiered": {
            "device_pool_pages": TIER_POOL - 1,
            "host_pool_pages": TIER_HOST,
            "page_size": PAGE,
            "curve": tiered_curve,
            "deterministic": bool(tiered_identical),
            "zero_copy": all(
                pt["off"]["copy_bytes"] == 0 and pt["on"]["copy_bytes"] == 0
                for pt in tiered_curve
            ),
        },
    }


def _measure_serving_quant(devs):
    """Quantized serving (``--child-quant``, ISSUE 13): the SAME workload
    through three engines — fp32, int8 weights (dequantize-on-load), and
    int8 weights + int8 KV pages — all on the paged layout. Reports decode
    tok/s per variant, the HBMLedger's resident deltas (params + page
    pool), the ``plan()``-reported page capacity at a FIXED byte budget
    (the half-size-pages → 2x-pages claim as ledger arithmetic), and the
    MEASURED logit divergence of the quantized decode vs the fp32 stream
    (max/mean KL + top-1 agreement over teacher-forced decode steps) —
    the acceptance contract's both axes in one artifact."""
    import time as _t

    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_tpu.inference import GenerationConfig
    from neuronx_distributed_tpu.inference.generate import serving_clones
    from neuronx_distributed_tpu.inference.utils import unwrap_logits
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from neuronx_distributed_tpu.quantization import (
        QuantConfig,
        quantize_param_tree,
    )
    from neuronx_distributed_tpu.serving import ServingEngine

    cfg = LlamaConfig(
        vocab_size=2048, hidden_size=256, intermediate_size=704,
        num_layers=2, num_heads=8, num_kv_heads=4, max_seq_len=512,
        dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
        scan_layers=False,
    )
    PAGE = 16
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    rng = np.random.RandomState(0)
    init_ids = rng.randint(1, cfg.vocab_size, size=(1, 8)).astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), init_ids)
    prompts = [
        rng.randint(1, cfg.vocab_size,
                    size=int(rng.randint(6, 18))).astype(np.int32)
        for _ in range(8)
    ]
    gcfg = GenerationConfig(max_new_tokens=48, temperature=0.0)  # greedy

    def run(quantize):
        engine = ServingEngine(
            model, params, num_slots=4, decode_chunk_size=8,
            kv_page_size=PAGE, prefix_cache=None, quantize=quantize,
        )
        # warmup wave compiles the prefill buckets + the decode program
        for i, p in enumerate(prompts[:4]):
            engine.submit(
                p, GenerationConfig(max_new_tokens=8, temperature=0.0),
                key=jax.random.PRNGKey(i),
            )
        engine.run()
        m = engine.metrics
        base_tok = m.decode_tokens
        base_wall = m.decode_dispatch_s + m.decode_readback_s
        t0 = _t.perf_counter()
        reqs = [
            engine.submit(p, gcfg, key=jax.random.PRNGKey(100 + i))
            for i, p in enumerate(prompts)
        ]
        engine.run()
        wall = _t.perf_counter() - t0
        dtok = m.decode_tokens - base_tok
        dwall = (m.decode_dispatch_s + m.decode_readback_s) - base_wall
        hbm = engine.hbm.snapshot()["residents"]
        engine.cache.check()
        stats = {
            "decode_tok_s": round(dtok / dwall, 2) if dwall > 0 else 0.0,
            "e2e_tok_s": round(dtok / wall, 2) if wall > 0 else 0.0,
            "decode_tokens": int(dtok),
            "decode_compilations": engine.decode_compilations,
            "params_bytes": int(hbm["params"]["bytes"]),
            "kv_pool_bytes": int(hbm["kv_pages"]["bytes"]),
            "page_bytes": int(engine.cache.page_nbytes),
        }
        return stats, [r.tokens for r in reqs], engine

    out, engines = {}, {}
    out["fp32"], fp_toks, engines["fp32"] = run(None)
    out["int8_weights"], w_toks, engines["int8_weights"] = run(
        QuantConfig(weights="int8")
    )
    out["int8_weights_int8_kv"], wk_toks, engines["int8_weights_int8_kv"] = (
        run(QuantConfig(weights="int8", kv="int8"))
    )
    # fixed-budget page capacity, REPORTED BY plan() itself (the HBM
    # ledger's capacity answer): the same byte budget for every variant
    # (2x the fp32 engine's residents, the demo's no-device-limit
    # yardstick) — half/quarter-size quantized pages fit proportionally
    # more of the remaining headroom
    budget = 2 * engines["fp32"].hbm.resident_bytes_total()
    for name, engine in engines.items():
        fit = engine.hbm.plan(budget_bytes=budget)["fits"]["kv_pages"]
        out[name]["plan_pages_at_budget"] = int(fit["additional"])
    engines.clear()

    # measured logit divergence: teacher-force the fp32 greedy continuation
    # through BOTH decode stacks and compare per-step next-token logits
    import dataclasses

    qcfg = QuantConfig(weights="int8", kv=None).weight_qconfig()
    qmodel = LlamaForCausalLM(
        dataclasses.replace(cfg, quantization=qcfg), attention_impl="xla"
    )
    qparams = quantize_param_tree(params, qcfg)
    prompt0 = jnp.asarray(prompts[0])
    cont = jnp.asarray(np.asarray(fp_toks[0], np.int32))

    def teacher_forced_logits(m_, p_):
        prefill, decode = serving_clones(m_)

        @jax.jit
        def steps(p, prompt_ids, cont_ids):
            out_, v = prefill.apply(p, prompt_ids[None], mutable=["cache"])
            first = unwrap_logits(out_)[0, -1]

            def step(cache, tok):
                o, vv = decode.apply(
                    {**p, "cache": cache}, tok[None, None],
                    mutable=["cache"],
                )
                return vv["cache"], unwrap_logits(o)[0, -1]

            _, rest = jax.lax.scan(step, v["cache"], cont_ids)
            return jnp.concatenate([first[None], rest], 0)

        return np.asarray(steps(dict(p_), prompt0, cont[:-1]))

    ref_logits = teacher_forced_logits(model, params)
    q_logits = teacher_forced_logits(qmodel, qparams)
    pr = jax.nn.softmax(jnp.asarray(ref_logits), -1)
    lq = jax.nn.log_softmax(jnp.asarray(q_logits), -1)
    lr = jax.nn.log_softmax(jnp.asarray(ref_logits), -1)
    kl = np.asarray(jnp.sum(pr * (lr - lq), -1))
    top1 = np.asarray(ref_logits).argmax(-1) == np.asarray(q_logits).argmax(-1)
    tokens_identical_w = fp_toks == w_toks
    tokens_identical_wk = fp_toks == wk_toks

    def prefix_agree(a_list, b_list):
        fracs = []
        for a, b in zip(a_list, b_list):
            n = min(len(a), len(b))
            i = 0
            while i < n and a[i] == b[i]:
                i += 1
            fracs.append(i / max(n, 1))
        return round(float(np.mean(fracs)), 4)
    return {
        **out,
        "decode_tok_s_ratio_int8": round(
            out["int8_weights"]["decode_tok_s"]
            / max(out["fp32"]["decode_tok_s"], 1e-9), 3
        ),
        "decode_tok_s_ratio_int8_kv": round(
            out["int8_weights_int8_kv"]["decode_tok_s"]
            / max(out["fp32"]["decode_tok_s"], 1e-9), 3
        ),
        "plan_pages_ratio_int8_kv": round(
            out["int8_weights_int8_kv"]["plan_pages_at_budget"]
            / max(out["fp32"]["plan_pages_at_budget"], 1), 3
        ),
        "params_bytes_ratio": round(
            out["fp32"]["params_bytes"]
            / max(out["int8_weights"]["params_bytes"], 1), 3
        ),
        "logit_divergence": {
            "steps": int(kl.shape[0]),
            "max_kl": round(float(kl.max()), 6),
            "mean_kl": round(float(kl.mean()), 6),
            "top1_agreement": round(float(top1.mean()), 4),
        },
        "greedy_tokens_identical_int8": bool(tokens_identical_w),
        "greedy_tokens_identical_int8_kv": bool(tokens_identical_wk),
        "greedy_prefix_agreement_int8": prefix_agree(fp_toks, w_toks),
        "greedy_prefix_agreement_int8_kv": prefix_agree(fp_toks, wk_toks),
    }


def _flash_block_sweep(batch, seq):
    import jax
    import jax.numpy as jnp

    from neuronx_distributed_tpu.kernels.flash_attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    h, d = 32, 128
    q = jax.random.normal(ks[0], (batch, seq, h, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (batch, seq, h, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (batch, seq, h, d), jnp.bfloat16)
    out = {}
    for blk in (256, 512, 1024):
        if seq % blk != 0:
            out[f"block_{blk}"] = f"skipped: seq {seq} not divisible"
            continue
        # grad wrt ALL inputs so neither backward kernel (dq, dk/dv) is
        # dead-code-eliminated — the sweep must time the full fwd+bwd
        fn = jax.jit(jax.grad(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, block_q=blk, block_k=blk
            ).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        ))
        g = fn(q, k, v)  # compile
        _ = float(jnp.sum(g[0]))
        t0 = time.perf_counter()
        for _i in range(5):
            g = fn(q, k, v)
        _ = float(jnp.sum(g[0]))
        out[f"block_{blk}"] = round((time.perf_counter() - t0) / 5, 4)
    return out


def _measure_gqa(base_cfg, batch, seq, attention_impl):
    """Steps/s of the same width at num_kv_heads=8 (Llama-2-70B-style GQA
    4:1) through the GQA-native flash kernel."""
    import dataclasses
    import jax
    import jax.numpy as jnp

    from neuronx_distributed_tpu.models.llama import LlamaForCausalLM
    from neuronx_distributed_tpu.trainer import (
        OptimizerConfig,
        build_train_step,
        create_train_state,
        make_optimizer,
        shard_batch,
    )

    cfg = dataclasses.replace(base_cfg, num_kv_heads=8)
    model = LlamaForCausalLM(cfg, attention_impl=attention_impl)
    optimizer = make_optimizer(OptimizerConfig(zero1=False))
    key = jax.random.PRNGKey(0)
    ids = jax.random.randint(key, (batch, seq), 0, cfg.vocab_size)
    state, p_sh, s_sh = create_train_state(model, optimizer, key, ids, zero1=False)
    step = build_train_step(model, optimizer, p_sh, s_sh)
    data = shard_batch({"input_ids": ids, "labels": jnp.roll(ids, -1, axis=1)})
    for _ in range(2):
        state, metrics = step(state, data)
    _ = float(metrics["loss"])
    t0 = time.perf_counter()
    m = None
    for _ in range(8):
        state, m = step(state, data)
    _ = float(m["loss"])
    dt = (time.perf_counter() - t0) / 8
    return {
        "num_kv_heads": 8,
        "step_time_s": round(dt, 4),
        "tokens_per_sec": round(batch * seq / dt, 2),
    }


def _measure_serving_spec(devs):
    """Speculative serving (``--child-spec``): engine decode tokens/s,
    spec-OFF vs spec-ON, at a CONTROLLED synthetic acceptance rate on the
    CPU proxy.

    The acceptance knob is an early-exit draft: the target is a 6-layer
    model whose layers 1..5 have their residual contributions (``o_proj``/
    ``down_proj`` kernels) scaled by ``eps``, and the draft is the SAME
    weights truncated to layer 0. At ``eps=0`` the two functions are
    identical (acceptance exactly 1.0); growing ``eps`` degrades agreement
    smoothly — a deterministic acceptance dial with a genuinely ~6x
    cheaper draft, which is the regime speculation is for. The sweep shows
    BOTH sides of the trade: high acceptance wins >=1.5x, low acceptance
    (eps=0.3, ~0.2 accept) is a measured LOSS — speculation is not free.

    Every leg proves streams bit-identical to its spec-off twin
    (speculation is a transport, not an approximation), and the chaos leg
    injects a draft-dispatch failure mid-run: tokens_lost must be 0
    through the non-speculative fallback + draft-cache resync."""
    import time as _t

    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_tpu.inference import GenerationConfig
    from neuronx_distributed_tpu.models.llama import (
        LlamaConfig,
        LlamaForCausalLM,
        early_exit_draft_params,
    )
    from neuronx_distributed_tpu.serving import FaultInjector, ServingEngine

    n_layers = 6
    cfg = LlamaConfig(
        vocab_size=2048, hidden_size=256, intermediate_size=704,
        num_layers=n_layers, num_heads=8, num_kv_heads=4, max_seq_len=512,
        dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
        scan_layers=False,
    )
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    draft_cfg = LlamaConfig(**{**cfg.__dict__, "num_layers": 1})
    draft = LlamaForCausalLM(draft_cfg, attention_impl="xla")
    rng = np.random.RandomState(0)
    init_ids = rng.randint(1, cfg.vocab_size, size=(1, 8)).astype(np.int32)
    base_params = jax.jit(model.init)(jax.random.PRNGKey(1), init_ids)

    def make_params(eps: float):
        """Target params with eps-scaled late layers + the layer-0
        early-exit draft subset (shared embed/norm/head)."""
        return early_exit_draft_params(base_params, n_layers, 1, eps)

    prompts = [
        rng.randint(1, cfg.vocab_size, size=int(rng.randint(6, 18))).astype(np.int32)
        for _ in range(8)
    ]
    gcfg = GenerationConfig(max_new_tokens=64, temperature=0.0)

    def run(t_params, d_params=None, gamma=4, injector=None):
        kw = {}
        if d_params is not None:
            kw = dict(
                draft_model=draft, draft_params=d_params, gamma=gamma,
                fault_injector=injector, sleep_fn=lambda s: None,
            )
        engine = ServingEngine(
            model, t_params, num_slots=4, decode_chunk_size=4,
            prefix_cache=None, kv_page_size=16, **kw,
        )
        # warmup wave compiles prefill buckets + the decode program
        for i, p in enumerate(prompts[:4]):
            engine.submit(
                p, GenerationConfig(max_new_tokens=10, temperature=0.0),
                key=jax.random.PRNGKey(i),
            )
        engine.run()
        m = engine.metrics
        base_tok = m.decode_tokens
        base_wall = m.decode_dispatch_s + m.decode_readback_s
        t0 = _t.perf_counter()
        reqs = [
            engine.submit(p, gcfg, key=jax.random.PRNGKey(100 + i))
            for i, p in enumerate(prompts)
        ]
        engine.run()
        wall = _t.perf_counter() - t0
        dtok = m.decode_tokens - base_tok
        dwall = (m.decode_dispatch_s + m.decode_readback_s) - base_wall
        return {
            "streams": [r.tokens for r in reqs],
            "decode_tok_s": dtok / dwall if dwall > 0 else 0.0,
            "e2e_tok_s": dtok / wall if wall > 0 else 0.0,
            "snap": m.snapshot(),
            "decode_compilations": engine.decode_compilations,
        }

    sweep = []
    headline = None
    for eps in (0.0, 0.02, 0.1, 0.3):
        t_params, d_params = make_params(eps)
        off = run(t_params)
        on = run(t_params, d_params, gamma=4)
        lost = sum(
            _divergence_lost(c, s)
            for c, s in zip(off["streams"], on["streams"])
        )
        row = {
            "eps": eps,
            "accept_rate": round(on["snap"]["spec_accept_rate"], 4),
            "accept_len_p50": on["snap"]["spec_accept_len_p50"],
            "draft_tokens_wasted": on["snap"]["draft_tokens_wasted"],
            "off_decode_tok_s": round(off["decode_tok_s"], 2),
            "on_decode_tok_s": round(on["decode_tok_s"], 2),
            "decode_speedup": round(
                on["decode_tok_s"] / max(off["decode_tok_s"], 1e-9), 3
            ),
            "e2e_speedup": round(
                on["e2e_tok_s"] / max(off["e2e_tok_s"], 1e-9), 3
            ),
            "streams_bit_identical": off["streams"] == on["streams"],
            "tokens_lost": int(lost),
        }
        sweep.append(row)
        if eps == 0.02:
            headline = dict(row)
            headline["decode_compilations"] = on["decode_compilations"]
            # chaos leg at the headline operating point: a draft-dispatch
            # failure mid-run must cost zero tokens through the fallback
            inj = FaultInjector().fail_draft_dispatch(at=3, times=1)
            chaos = run(t_params, d_params, gamma=4, injector=inj)
            headline["chaos_draft_dispatch"] = {
                "fired": inj.counters["draft_dispatch_failures"],
                "spec_fallbacks": chaos["snap"]["spec_fallbacks"],
                "tokens_lost": int(sum(
                    _divergence_lost(c, s)
                    for c, s in zip(off["streams"], chaos["streams"])
                )),
                "streams_bit_identical": chaos["streams"] == off["streams"],
            }
    return {
        "gamma": 4,
        "requests": len(prompts),
        "max_new_tokens": 64,
        "target_layers": n_layers,
        "draft_layers": 1,
        **{f"headline_{k}": v for k, v in headline.items()},
        "accept_sweep": sweep,
        "speedup_ok": bool(
            headline["decode_speedup"] >= 1.5
            and headline["accept_rate"] >= 0.7
            and headline["streams_bit_identical"]
            and headline["chaos_draft_dispatch"]["tokens_lost"] == 0
        ),
    }


def _measure_observability(devs):
    """Instrumentation overhead (``--child-observe``): the SAME request
    workload through the continuous-batching engine BARE vs fully
    instrumented (timeline + request-flow tracer + flight recorder +
    registry TTFT/TPOT histograms). The decode wall reads the engine's
    dispatch+readback hot-path counters, min over interleaved waves so
    compile time and scheduler drift cancel; the overhead budget the
    tier-1 test pins is ≤2%. Also replays a deterministic latency stream
    through the log-bucketed histogram vs an exact sorted list, reporting
    the percentile error the fixed-memory representation costs."""
    import math
    import random
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_tpu.inference import GenerationConfig
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from neuronx_distributed_tpu.observability import MetricsRegistry
    from neuronx_distributed_tpu.serving import ServingEngine
    from neuronx_distributed_tpu.utils.timeline import Timeline

    cfg = LlamaConfig(
        vocab_size=2048, hidden_size=256, intermediate_size=704,
        num_layers=2, num_heads=8, num_kv_heads=4, max_seq_len=512,
        dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
        scan_layers=False,
    )
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    rng = np.random.RandomState(0)
    init_ids = rng.randint(1, cfg.vocab_size, size=(1, 8)).astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), init_ids)
    tmp = tempfile.mkdtemp(prefix="observe_bench_")
    bare = ServingEngine(
        model, params, num_slots=4, decode_chunk_size=8,
        timeline=None, flight_recorder=None, prefix_cache=None,
        kv_page_size=16,
    )
    inst = ServingEngine(
        model, params, num_slots=4, decode_chunk_size=8,
        timeline=Timeline(os.path.join(tmp, "trace.json")),
        registry=MetricsRegistry(), flight_dir=tmp, prefix_cache=None,
        kv_page_size=16,
    )
    gcfg = GenerationConfig(max_new_tokens=64, temperature=0.8, top_k=20)

    def wave(engine):
        wrng = np.random.RandomState(7)  # same prompts every wave/engine
        m = engine.metrics
        wall0 = m.decode_dispatch_s + m.decode_readback_s
        tok0 = m.decode_tokens
        for i, plen in enumerate(wrng.randint(6, 18, size=8)):
            engine.submit(
                wrng.randint(1, cfg.vocab_size, size=int(plen)).astype(np.int32),
                gcfg, key=jax.random.PRNGKey(100 + i),
            )
        engine.run()
        return (
            (m.decode_dispatch_s + m.decode_readback_s) - wall0,
            m.decode_tokens - tok0,
        )

    wave(bare)  # warmup: compiles prefill buckets + the decode program
    wave(inst)
    # paired rounds, order alternating: this shared box's wall-clock noise
    # (neighbor load, thermal) drifts 3-10% on second scales — far above
    # the sub-1% effect under measurement — but a bare/instrumented pair
    # run back-to-back shares the same drift, so the PER-ROUND ratio is
    # clean; the median over rounds then drops the fast-jitter outliers
    # the ordering alternation hasn't already cancelled
    ratios = []
    walls = {"bare": [], "inst": []}
    toks = {"bare": [], "inst": []}
    for rnd in range(8):
        order = (("bare", bare), ("inst", inst))
        if rnd % 2:
            order = order[::-1]
        got = {}
        for name, engine in order:
            w, t = wave(engine)
            got[name] = w
            walls[name].append(w)
            toks[name].append(t)
        if got["bare"] > 0:
            ratios.append(got["inst"] / got["bare"])
    ratios.sort()
    med_ratio = ratios[len(ratios) // 2]
    w_bare, w_inst = sum(walls["bare"]), sum(walls["inst"])
    tok = sum(toks["bare"])
    bare_tok_s = tok / w_bare if w_bare > 0 else 0.0
    inst_tok_s = tok / w_inst if w_inst > 0 else 0.0
    overhead_pct = (med_ratio - 1.0) * 100.0

    # histogram-vs-sorted-list percentile error on a replayed stream
    reg = MetricsRegistry()
    h = reg.histogram("replay_latency_s")
    r = random.Random(0)
    stream = [r.lognormvariate(-4, 1.2) for _ in range(20_000)]
    for v in stream:
        h.observe(v)
    stream.sort()
    pct_err = {}
    for q in (0.50, 0.95, 0.99):
        true = stream[max(0, math.ceil(q * len(stream)) - 1)]
        est = h.percentile(q)
        pct_err[f"p{int(q * 100)}_rel_err"] = round(est / true - 1.0, 5)
    return {
        "decode_wall_bare_s": round(w_bare, 4),
        "decode_wall_instrumented_s": round(w_inst, 4),
        "decode_tok_s_bare": round(bare_tok_s, 2),
        "decode_tok_s_instrumented": round(inst_tok_s, 2),
        "overhead_pct": round(overhead_pct, 3),
        "round_ratios": [round(r, 4) for r in ratios],
        "within_budget": bool(overhead_pct <= 2.0),
        "tokens_measured": int(tok),
        "trace_events": len(inst.timeline._events),
        "flight_events_recorded": inst.flight._seq,
        "histogram": {
            "samples": len(stream),
            "buckets_touched": len(h._buckets),
            "max_rel_err_bound": round(h.relative_error, 4),
            **pct_err,
        },
    }


def _measure_traffic(devs):
    """SLO observability under realistic load (``--child-traffic``): the
    SAME two-tenant workload (interactive chat under a tight SLO, batch
    long-doc under a loose one) replayed through the engine under Poisson
    AND bursty/diurnal arrivals on a virtual clock. Reports per-tenant
    p50/p99 TTFT, TPOT, goodput, and SLO attainment — and proves the
    whole pipeline is DETERMINISTIC by running every scenario twice from
    the same seed and comparing the reports byte-for-byte (the property
    that makes the harness a judge for scheduler/cache changes: a perf
    diff is a real diff, not replay noise)."""
    import dataclasses
    import hashlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from neuronx_distributed_tpu.observability import SLOSpec
    from neuronx_distributed_tpu.serving import (
        ServingEngine,
        TenantProfile,
        VirtualClock,
        generate_tape,
        replay,
        tape_bytes,
    )

    cfg = LlamaConfig(
        vocab_size=2048, hidden_size=256, intermediate_size=704,
        num_layers=2, num_heads=8, num_kv_heads=4, max_seq_len=512,
        dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
        scan_layers=False,
    )
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    rng = np.random.RandomState(0)
    init_ids = rng.randint(1, cfg.vocab_size, size=(1, 8)).astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), init_ids)

    # virtual-time budget: step_dt=0.05 makes 3 slots × chunk 4 ≈ 12 req/s
    # of service capacity, so the bursty peak (4 rps × 4) actually queues —
    # attainment must be measured where the SLO can fail, or it measures
    # nothing
    STEP_DT = 0.05
    slo = {
        "chat": SLOSpec(ttft_p99_s=0.15, tpot_p99_s=0.02),
        "docs": SLOSpec(ttft_p99_s=1.00, tpot_p99_s=0.05),
    }

    def tenants(arrival):
        return [
            TenantProfile(
                "chat", rate_rps=4.0, arrival=arrival, workload="chat",
                priority="interactive", burst_factor=4.0,
                burst_period_s=4.0, burst_duty=0.25, deadline_s=2.0,
            ),
            TenantProfile(
                "docs", rate_rps=1.0, arrival=arrival, workload="longdoc",
                priority="batch",
            ),
        ]

    def run_once(tape):
        clock = VirtualClock()
        engine = ServingEngine(
            model, params, num_slots=3, decode_chunk_size=4,
            admission="eager", prefix_cache=None, slo=slo,
            timeline=None, flight_recorder=None, kv_page_size=16,
            time_fn=clock, sleep_fn=lambda s: None,
        )
        report = replay(engine, tape, clock, step_dt=STEP_DT)
        report["decode_compilations"] = engine.decode_compilations
        return report

    out = {"step_dt_s": STEP_DT, "slo_specs": {
        t: dataclasses.asdict(s) for t, s in sorted(slo.items())
    }}
    deterministic = True
    for arrival in ("poisson", "bursty"):
        tape = generate_tape(
            tenants(arrival), duration_s=6.0, seed=7,
            vocab_size=cfg.vocab_size,
        )
        tape_again = generate_tape(
            tenants(arrival), duration_s=6.0, seed=7,
            vocab_size=cfg.vocab_size,
        )
        raw = tape_bytes(tape)
        tape_identical = raw == tape_bytes(tape_again)
        first = run_once(tape)
        second = run_once(tape)
        same = json.dumps(first, sort_keys=True) == json.dumps(
            second, sort_keys=True
        )
        deterministic = deterministic and same and tape_identical
        out[arrival] = {
            **first,
            "tape_arrivals": len(tape),
            "tape_sha256": hashlib.sha256(raw).hexdigest()[:16],
            "tape_identical_across_gens": tape_identical,
            "report_identical_across_runs": same,
        }
    out["deterministic"] = deterministic
    return out


def _measure_sched(devs) -> dict:
    """Scheduler A/B (``--child-sched``, ISSUE 16): the SAME PR-10 bursty
    two-tenant tape (seed 7 — interactive chat bursts against a batch
    long-doc grind) replayed through a FIFO engine and an SLO-policy
    engine, everything else identical. Reports per-tenant attainment and
    goodput under both policies plus the deltas — the judge for the
    tentpole's claim: the interactive tenant's attainment/goodput must
    move UP under contention without collapsing the batch tenant. Two
    slots (not three): the A/B needs a regime where slots are scarce
    during the burst, or FIFO already attains and the policies are
    indistinguishable. Determinism is part of the contract: the tape is
    sha-pinned and every leg runs twice from the same seed with
    byte-identical reports."""
    import dataclasses
    import hashlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from neuronx_distributed_tpu.observability import SLOSpec
    from neuronx_distributed_tpu.serving import (
        ServingEngine,
        TenantProfile,
        VirtualClock,
        generate_tape,
        replay,
        tape_bytes,
    )

    cfg = LlamaConfig(
        vocab_size=2048, hidden_size=256, intermediate_size=704,
        num_layers=2, num_heads=8, num_kv_heads=4, max_seq_len=512,
        dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
        scan_layers=False,
    )
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    rng = np.random.RandomState(0)
    init_ids = rng.randint(1, cfg.vocab_size, size=(1, 8)).astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), init_ids)

    STEP_DT = 0.05
    slo = {
        "chat": SLOSpec(ttft_p99_s=0.15, tpot_p99_s=0.02),
        "docs": SLOSpec(ttft_p99_s=1.00, tpot_p99_s=0.05),
    }
    tenants = [
        TenantProfile(
            "chat", rate_rps=4.0, arrival="bursty", workload="chat",
            priority="interactive", burst_factor=4.0,
            burst_period_s=4.0, burst_duty=0.25, deadline_s=2.0,
        ),
        TenantProfile(
            "docs", rate_rps=1.0, arrival="bursty", workload="longdoc",
            priority="batch",
        ),
    ]
    tape = generate_tape(tenants, duration_s=6.0, seed=7,
                         vocab_size=cfg.vocab_size)
    raw = tape_bytes(tape)

    def run_once(scheduling):
        clock = VirtualClock()
        engine = ServingEngine(
            model, params, num_slots=2, decode_chunk_size=4,
            admission="eager", scheduling=scheduling, prefix_cache=None,
            slo=slo, timeline=None, flight_recorder=None, kv_page_size=16,
            time_fn=clock, sleep_fn=lambda s: None,
        )
        report = replay(engine, tape, clock, step_dt=STEP_DT)
        report["decode_compilations"] = engine.decode_compilations
        report["policy"] = engine.policy.snapshot()
        return report

    out = {
        "step_dt_s": STEP_DT,
        "num_slots": 2,
        "tape_arrivals": len(tape),
        "tape_sha256": hashlib.sha256(raw).hexdigest()[:16],
        "slo_specs": {
            t: dataclasses.asdict(s) for t, s in sorted(slo.items())
        },
    }
    deterministic = True
    reports = {}
    for scheduling in ("fifo", "slo"):
        first = run_once(scheduling)
        second = run_once(scheduling)
        same = json.dumps(first, sort_keys=True) == json.dumps(
            second, sort_keys=True
        )
        deterministic = deterministic and same
        deterministic = deterministic and first["decode_compilations"] == 1
        reports[scheduling] = first
        out[scheduling] = {
            **first,
            "report_identical_across_runs": same,
        }
    out["delta"] = {
        t: {
            "attainment": (
                reports["slo"]["tenants"][t]["attainment"]
                - reports["fifo"]["tenants"][t]["attainment"]
            ),
            "goodput_tok_s": (
                reports["slo"]["tenants"][t]["goodput_tok_s"]
                - reports["fifo"]["tenants"][t]["goodput_tok_s"]
            ),
        }
        for t in sorted(reports["fifo"]["tenants"])
    }
    out["deterministic"] = deterministic
    return out


def _measure_serving_multichip(devs) -> dict:
    """Multi-chip serving (``--child-multichip``, ISSUE 14), three legs on
    the CPU mesh proxy (structure/identity numbers, not chip speed):

    * **tp scaling** — the same mixed greedy/sampled workload through the
      mesh-free engine and tp ∈ {1, 2, 4} TP-sharded engines: streams must
      be BIT-identical everywhere (and across two runs of each),
      ``decode_compilations == 1``, plus the tp=2 EQuARX-comms leg and the
      analytical per-decode-step all-reduce wire bytes with/without
      quantized collectives (the EQuARX arithmetic at serving shapes).
    * **coupled vs disaggregated** — the ISSUE 11 BURSTY tape replayed on
      the WALL clock through a coupled paged engine and through the
      prefill/decode-disaggregated server over an identical engine: TPOT
      p99 under bursts is the decode-isolation headline (a coupled engine
      admits whole prefill rounds between chunks; the disagg server bounds
      prefill to one per loop iteration and hands off by page table,
      ``copy_bytes == 0``).
    * **determinism** — tape byte-identity across generations and stream
      identity across runs (wall-clock latencies are measurements, never
      part of the pin)."""
    import hashlib
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_tpu.inference import GenerationConfig
    from neuronx_distributed_tpu.models.llama import (
        LlamaConfig,
        LlamaForCausalLM,
    )
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib
    from neuronx_distributed_tpu.parallel.quantized_collectives import (
        QuantizedAllReduceConfig,
        comm_bytes,
    )
    from neuronx_distributed_tpu.serving import (
        DisaggregatedServer,
        ServingEngine,
        TenantProfile,
        generate_tape,
        tape_bytes,
    )

    cfg = LlamaConfig(
        vocab_size=512, hidden_size=128, intermediate_size=352,
        num_layers=2, num_heads=8, num_kv_heads=4, max_seq_len=256,
        dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
        scan_layers=False,
    )
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    rng = np.random.RandomState(0)
    init_ids = rng.randint(1, cfg.vocab_size, size=(1, 8)).astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), init_ids)
    SLOTS = 3

    prompts = [
        rng.randint(1, cfg.vocab_size, size=int(n)).astype(np.int32)
        for n in rng.randint(6, 24, size=6)
    ]
    gcfgs = [
        GenerationConfig(max_new_tokens=16, temperature=0.0)
        if i % 2 == 0
        else GenerationConfig(max_new_tokens=16, temperature=0.8, top_k=13)
        for i in range(6)
    ]
    keys = [jax.random.PRNGKey(300 + i) for i in range(6)]

    def run_tp(tp, tp_comms=None):
        mesh_lib.destroy_model_parallel()
        engine = ServingEngine(
            model, params, num_slots=SLOTS, decode_chunk_size=4,
            prefix_cache=None, kv_page_size=16,
            tp=tp, tp_comms=tp_comms,
        )
        reqs = [
            engine.submit(p, c, key=k)
            for p, c, k in zip(prompts, gcfgs, keys)
        ]
        t0 = time.monotonic()
        engine.run()
        wall = time.monotonic() - t0
        snap = engine.metrics.snapshot()
        streams = [r.tokens for r in reqs]
        return streams, {
            "decode_compilations": engine.decode_compilations,
            "decode_tok_s": round(snap["decode_tokens"] / max(wall, 1e-9), 1),
            "wall_s": round(wall, 3),
        }

    base_streams, base_stats = run_tp(None)
    deterministic = True
    tp_rows = {"mesh_free": base_stats}
    for tp in (1, 2, 4):
        s1, stats = run_tp(tp)
        s2, _ = run_tp(tp)
        bit = s1 == base_streams
        same = s1 == s2
        deterministic = deterministic and bit and same
        tp_rows[f"tp{tp}"] = {
            **stats,
            "bit_identical_to_mesh_free": bit,
            "identical_across_runs": same,
        }
    sq, stats_q = run_tp(2, tp_comms=QuantizedAllReduceConfig(enabled=True))
    agree = sum(
        1 for a, b in zip(sq, base_streams)
        if a[: min(len(a), len(b))] == b[: min(len(a), len(b))]
    ) / len(base_streams)
    tp_rows["tp2_quantized_comms"] = {
        **stats_q, "stream_agreement_vs_exact": round(agree, 3),
    }
    mesh_lib.destroy_model_parallel()

    # analytical wire bytes of ONE decode step's row-parallel all-reduces
    # (attention o_proj + MLP down_proj per layer, hidden-sized activations
    # across the active slots), with and without the EQuARX int8 ring
    reduces = 2 * cfg.num_layers
    wire = {}
    for tp in (2, 4, 8):
        per = comm_bytes(cfg.hidden_size * SLOTS, tp)
        wire[f"tp{tp}"] = {
            "fp_bytes_per_step": per["fp_bytes"] * reduces,
            "quantized_bytes_per_step": per["quantized_bytes"] * reduces,
            "ratio": per["ratio"],
        }

    # --- coupled vs disaggregated under the ISSUE 11 bursty tape ---------
    tenants = [
        TenantProfile(
            "chat", rate_rps=4.0, arrival="bursty", workload="chat",
            priority="interactive", burst_factor=4.0, burst_period_s=2.0,
            burst_duty=0.25,
        ),
        TenantProfile(
            "docs", rate_rps=1.0, arrival="bursty", workload="longdoc",
            priority="batch", burst_factor=3.0, burst_period_s=3.0,
            burst_duty=0.3,
        ),
    ]
    tape = generate_tape(
        tenants, duration_s=4.0, seed=7, vocab_size=cfg.vocab_size
    )
    raw = tape_bytes(tape)
    tape_identical = raw == tape_bytes(
        generate_tape(
            tenants, duration_s=4.0, seed=7, vocab_size=cfg.vocab_size
        )
    )
    deterministic = deterministic and tape_identical

    def wall_replay(make):
        target, engine = make()
        t0 = time.monotonic()
        i = 0
        while i < len(tape) or target.has_work:
            now = time.monotonic() - t0
            while i < len(tape) and tape[i].t <= now:
                a = tape[i]
                i += 1
                try:
                    target.submit(
                        np.asarray(a.prompt, np.int32),
                        GenerationConfig(
                            max_new_tokens=a.max_new_tokens,
                            temperature=a.temperature,
                        ),
                        key=jax.random.PRNGKey(a.key_seed),
                        tenant=a.tenant,
                    )
                except Exception:
                    pass  # backpressure under the burst is signal, not error
            if target.has_work:
                target.step()
            elif i < len(tape):
                time.sleep(0.001)
        snap = engine.metrics.snapshot()
        return {
            "arrivals": len(tape),
            "completed": snap["completed"],
            "ttft_p50_ms": round(snap["ttft_p50_s"] * 1e3, 2),
            "ttft_p99_ms": round(snap["ttft_p99_s"] * 1e3, 2),
            "tpot_p50_ms": round(snap["tpot_p50_s"] * 1e3, 3),
            "tpot_p99_ms": round(snap["tpot_p99_s"] * 1e3, 3),
            "preemptions": snap["preemptions"],
        }

    def coupled():
        e = ServingEngine(
            model, params, num_slots=SLOTS, decode_chunk_size=4,
            prefix_cache=None, kv_page_size=16,
        )
        return e, e

    def disagg():
        e = ServingEngine(
            model, params, num_slots=SLOTS, decode_chunk_size=4,
            prefix_cache=None, kv_page_size=16,
        )
        return DisaggregatedServer(e, n_workers=1), e

    coupled_row = wall_replay(coupled)
    srv_holder = {}

    def disagg_capture():
        s, e = disagg()
        srv_holder["s"], srv_holder["e"] = s, e
        return s, e

    disagg_row = wall_replay(disagg_capture)
    disagg_row["handoffs"] = srv_holder["s"].stats["handoffs"]
    disagg_row["coupled_fallbacks"] = (
        srv_holder["s"].stats["coupled_fallbacks"]
    )
    disagg_row["copy_bytes"] = srv_holder["e"].cache.alloc.copy_bytes
    improvement = (
        coupled_row["tpot_p99_ms"] / disagg_row["tpot_p99_ms"]
        if disagg_row["tpot_p99_ms"] > 0 else None
    )
    return {
        "tp_scaling": tp_rows,
        "allreduce_wire_bytes_per_decode_step": wire,
        "bursty_tape": {
            "arrivals": len(tape),
            "sha256": hashlib.sha256(raw).hexdigest()[:16],
            "identical_across_gens": tape_identical,
        },
        "coupled": coupled_row,
        "disaggregated": disagg_row,
        "coupled_over_disagg_tpot_p99": (
            round(improvement, 3) if improvement else None
        ),
        "deterministic": deterministic,
    }


def _measure_graftverify(devs):
    """IR-level verification census (``--child-graftverify``, ISSUE 15):
    drive a small paged engine plus a tp=2 exact/quantized pair on the CPU
    mesh proxy, run graftverify over their ledgers, and report the
    donation/transfer/collective tables plus the STATIC EQuARX wire-byte
    ratio."""
    import jax
    import numpy as np

    from neuronx_distributed_tpu.inference import GenerationConfig
    from neuronx_distributed_tpu.models.llama import (
        LlamaForCausalLM,
        tiny_llama,
    )
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib
    from neuronx_distributed_tpu.parallel.quantized_collectives import (
        QuantizedAllReduceConfig,
    )
    from neuronx_distributed_tpu.scripts.graftlint import baseline as bl
    from neuronx_distributed_tpu.scripts.graftverify import (
        runner as gv_runner,
    )
    from neuronx_distributed_tpu.scripts.graftverify.core import (
        DEFAULT_BASELINE_NAME,
    )
    from neuronx_distributed_tpu.serving import ServingEngine

    # hidden 256 / 4 slots: the row-parallel reduction is 1024 elements —
    # divisible by tp*block_size, so the quantized ring pads nothing and
    # the static ratio is the pure EQuARX 4/(1+4/256)
    cfg = tiny_llama(num_layers=2, hidden_size=256,
                     intermediate_size=768, vocab_size=128)
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    rng = np.random.RandomState(0)
    ids = rng.randint(1, cfg.vocab_size, size=(1, 8)).astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), ids)
    gcfg = GenerationConfig(max_new_tokens=2, temperature=0.0)

    def drive(engine):
        r = np.random.RandomState(3)
        for i in range(2):
            engine.submit(
                r.randint(1, cfg.vocab_size, size=6).astype(np.int32),
                gcfg, key=jax.random.PRNGKey(i),
            )
        engine.run()
        return engine

    def build(tp, quantized, paged):
        mesh_lib.destroy_model_parallel()
        kw = {}
        if tp > 1:
            kw = dict(
                tp=tp,
                tp_comms=QuantizedAllReduceConfig(enabled=quantized),
            )
        return drive(ServingEngine(
            model, params, num_slots=4, decode_chunk_size=2,
            prefix_cache=None, kv_page_size=8 if paged else None, **kw,
        ))

    root = os.path.dirname(os.path.abspath(__file__))
    baseline_path = os.path.join(root, DEFAULT_BASELINE_NAME)
    plain = build(tp=1, quantized=False, paged=True)
    report = gv_runner.verify(
        {"serving": plain.programs}, baseline_path=baseline_path
    )
    exact = build(tp=2, quantized=False, paged=True)
    rep_exact = gv_runner.verify(
        {"serving": exact.programs}, use_baseline=False
    )
    quant = build(tp=2, quantized=True, paged=False)
    rep_quant = gv_runner.verify(
        {"serving": quant.programs}, use_baseline=False
    )
    te = rep_exact.audit("decode_chunk").collective_table
    tq = rep_quant.audit("decode_chunk").collective_table
    residual = tq["by_kind"].get("all_reduce", {"wire_bytes": 0})[
        "wire_bytes"
    ]
    ring_quant = sum(
        tq["by_kind"].get(k, {"wire_bytes": 0})["wire_bytes"]
        for k in ("collective_permute", "all_gather")
    )
    routed_exact = (
        te["by_kind"].get("all_reduce", {"wire_bytes": 0})["wire_bytes"]
        - residual
    )
    stats = report.stats()
    tp_stats = rep_exact.stats()
    mesh_lib.destroy_model_parallel()
    return {
        "programs_checked": stats["programs_checked"],
        "variants_checked": stats["variants_checked"],
        "donations_declared": stats["donations_declared"],
        "donations_aliased": stats["donations_aliased"],
        "donations_deferred": tp_stats["donations_deferred"],
        "donations_pruned": stats["donations_pruned"],
        "donations_dropped": (
            stats["donations_dropped"] + tp_stats["donations_dropped"]
        ),
        "transfer_ops": stats["transfer_ops"] + tp_stats["transfer_ops"],
        "collective_table_tp2_exact": te,
        "collective_table_tp2_quant": tq,
        "equarx_static_wire_ratio": (
            round(routed_exact / ring_quant, 3) if ring_quant else None
        ),
        "findings_by_rule": report.by_rule(),
        "baseline_size": len(bl.load(baseline_path)),
        "clean": not report.failed,
    }


def _coldstart_workload(jax):
    """Shared model/workload for every --coldstart-leg process. Bigger than
    the serving-chunk config (4 layers) so compile wall dominates the cold
    leg and the prewarm ratio measures something real; prompts and sampling
    keys are FIXED so streams must be bit-identical across regimes."""
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_tpu.inference import GenerationConfig
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from neuronx_distributed_tpu.serving import ServingEngine

    cfg = LlamaConfig(
        vocab_size=2048, hidden_size=256, intermediate_size=704,
        num_layers=4, num_heads=8, num_kv_heads=4, max_seq_len=512,
        dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
        scan_layers=False,
    )
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    rng = np.random.RandomState(7)
    init_ids = rng.randint(1, cfg.vocab_size, size=(1, 8)).astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), init_ids)
    prompts = [
        rng.randint(1, cfg.vocab_size, size=int(rng.randint(6, 14))).astype(np.int32)
        for _ in range(4)
    ]
    gcfg = GenerationConfig(max_new_tokens=10, temperature=0.7, top_k=8)
    engine = ServingEngine(
        model, params, num_slots=2, decode_chunk_size=4, kv_page_size=16,
    )
    return engine, prompts, gcfg


def coldstart_leg(leg: str, cache_dir: str) -> None:
    """One cold-start process (``--coldstart-leg LEG DIR``). ``setup`` warms
    an engine on the workload and writes the AOT cache (manifest + serialized
    executables + the persistent XLA disk cache). The measurement legs each
    start FRESH — ``cold`` with every cache disabled (the parent exports
    NXD_TPU_PERSISTENT_CACHE=0), ``trace`` with ledger-driven replay prewarm
    over the manifest (compiles land before the first request, disk-cache
    backed), ``deser`` restoring serialized executables (no XLA at all) —
    and report process-start → first-token wall plus the full streams."""
    jax = _child_setup_jax(cpu_devices=1)

    from neuronx_distributed_tpu.inference import aot

    if leg != "cold":
        # the shared XLA disk cache lives INSIDE the leg workdir, so the
        # cold leg (persistent cache disabled via env) cannot see it and
        # the repo-level .jax_cache never pollutes the comparison
        aot.enable_persistent_cache(os.path.join(cache_dir, aot.XLA_SUBDIR))

    engine, prompts, gcfg = _coldstart_workload(jax)

    if leg == "setup":
        t0 = time.perf_counter()
        for i, p in enumerate(prompts):
            engine.submit(p, gcfg, key=jax.random.PRNGKey(i))
        engine.run()
        report = engine.save_aot(cache_dir)
        _emit(
            {
                "metric": "coldstart_leg",
                "leg": leg,
                "saved": report["saved"],
                "skipped": sorted(report["skipped"]),
                "manifest_programs": sorted(engine.manifest().names()),
                "wall_s": round(time.perf_counter() - t0, 3),
            }
        )
        return

    prewarm = None
    if leg in ("trace", "deser"):
        rep = engine.prewarm(
            cache_dir=cache_dir, mode="trace" if leg == "trace" else "auto"
        )
        prewarm = {
            "deserialized": len(rep["deserialized"]),
            "compiled": len(rep["compiled"]),
            "replayed": len(rep["replayed"]),
            "skew": rep["skew"],
            "skipped": sorted(rep["skipped"]),
            "wall_s": rep["wall_s"],
        }

    req0 = engine.submit(prompts[0], gcfg, key=jax.random.PRNGKey(0))
    guard = 0
    while not req0.tokens and guard < 10_000:
        engine.step()
        guard += 1
    first_token_s = time.perf_counter() - _PROC_T0
    for i, p in enumerate(prompts[1:], start=1):
        engine.submit(p, gcfg, key=jax.random.PRNGKey(i))
    reqs = engine.run()
    payload = {
        "metric": "coldstart_leg",
        "leg": leg,
        "platform": jax.devices()[0].platform,
        "first_token_s": round(first_token_s, 3),
        "e2e_s": round(time.perf_counter() - _PROC_T0, 3),
        "decode_compilations": engine.decode_compilations,
        "streams": [
            [int(t) for t in reqs[rid].tokens] for rid in sorted(reqs)
        ],
        "prewarm": prewarm,
    }
    if leg == "trace":
        # GV05 coverage over the leg that actually served traffic: every
        # dispatched program must be named by the prewarmed manifest
        from neuronx_distributed_tpu.scripts.graftverify import runner as gv

        rep = gv.verify(
            {"serving": engine.programs}, use_baseline=False,
            select={"GV05"},
            manifest=os.path.join(cache_dir, aot.MANIFEST_NAME),
        )
        payload["gv05_findings"] = [v.snippet for v in rep.findings]
    _emit(payload)


def _run_coldstart_leg(leg: str, workdir: str, env_extra=None):
    """Spawn one --coldstart-leg process; returns (json_or_None, err)."""
    env = dict(os.environ)
    if env_extra:
        env.update(env_extra)
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--coldstart-leg", leg, workdir],
            capture_output=True, text=True, timeout=COLDSTART_LEG_TIMEOUT_S,
            env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
        )
    except subprocess.TimeoutExpired:
        return None, f"{leg} leg timed out after {COLDSTART_LEG_TIMEOUT_S}s"
    result = _parse_result(proc.stdout)
    if result is None:
        tail = (proc.stderr or proc.stdout or "").strip()[-400:]
        return None, f"{leg} leg rc={proc.returncode}, no JSON: {tail}"
    return result, None


def child_coldstart() -> None:
    """Cold-start child (``--child-coldstart``, ISSUE 17): process-start →
    first-token wall for a fresh serving process under three regimes — no
    cache at all (cold trace+compile), ledger-driven trace prewarm backed by
    the persistent XLA disk cache, serialized-executable deserialization —
    against one AOT cache written by a setup leg. Every regime is its OWN
    process (an in-process "cold start" is a contradiction); the clock
    starts at bench-module import, before the jax import. Streams must be
    bit-identical across regimes (``deterministic``). Merged into the BENCH
    artifact as ``extras.serving_coldstart``."""
    import shutil
    import tempfile

    workdir = tempfile.mkdtemp(prefix="nxd_coldstart_")
    out = {
        "metric": "serving_coldstart",
        "unit": "process-start → first-token s",
    }
    try:
        legs = {}
        setup, err = _run_coldstart_leg("setup", workdir)
        if setup is None:
            raise SystemExit(f"coldstart setup leg failed: {err}")
        setup.pop("metric", None)
        legs["setup"] = setup
        for leg, env_extra in (
            ("cold", {"NXD_TPU_PERSISTENT_CACHE": "0"}),
            ("trace", None),
            ("deser", None),
        ):
            r, err = _run_coldstart_leg(leg, workdir, env_extra)
            if r is None:
                raise SystemExit(f"coldstart {leg} leg failed: {err}")
            r.pop("metric", None)
            legs[leg] = r
        cold_s = legs["cold"]["first_token_s"]
        out["platform"] = legs["cold"]["platform"]
        out["cold_first_token_s"] = cold_s
        out["trace_first_token_s"] = legs["trace"]["first_token_s"]
        out["deser_first_token_s"] = legs["deser"]["first_token_s"]
        out["speedup_trace"] = round(
            cold_s / max(legs["trace"]["first_token_s"], 1e-9), 2
        )
        out["speedup_deser"] = round(
            cold_s / max(legs["deser"]["first_token_s"], 1e-9), 2
        )
        out["decode_compilations"] = {
            k: legs[k]["decode_compilations"]
            for k in ("cold", "trace", "deser")
        }
        out["deterministic"] = (
            legs["cold"]["streams"] == legs["trace"]["streams"]
            == legs["deser"]["streams"]
        )
        out["gv05_findings"] = legs["trace"].get("gv05_findings")
        for k in ("cold", "trace", "deser"):
            legs[k].pop("streams", None)
        out["legs"] = legs
        _emit(out)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def _measure_serving_fabric(devs) -> dict:
    """Elastic-fabric child (``--child-fabric``, ISSUE 18), two legs on the
    virtual clock (wall-independent except where a latency is explicitly a
    wall measurement):

    * **fabric replay** — the bursty multi-tenant tape through a 2-replica
      router whose every message rides the ChaosTransport (scattered
      dup/drop/delay faults) with the watchdog ON; mid-tape, replica 0 is
      killed and WARM-RESTARTED (``restart_replica``: fence → snapshot →
      fresh engine → restore, streaming callbacks reattached), later
      replica 1 is killed and its work RE-HOMED to the survivors, and
      finally a fresh replica JOINS live. Per-arrival streams must equal a
      fault-free FIFO single-engine oracle (``tokens_lost == 0``); the
      soft-TTFT attainment per tape quarter shows the dip while the
      fabric runs one replica short and the recovery after the join.
    * **warm vs cold restart** — a standalone engine killed mid-stream;
      restart-to-first-token of a snapshot/restore warm restart vs a cold
      engine's first token (wall numbers, compiles pre-warmed out of both
      paths), with the restored streams bit-identical to the
      uninterrupted run."""
    import hashlib
    import time

    import jax
    import numpy as np

    from neuronx_distributed_tpu.inference import GenerationConfig
    from neuronx_distributed_tpu.models.llama import (
        LlamaForCausalLM,
        tiny_llama,
    )
    from neuronx_distributed_tpu.observability import MetricsRegistry
    from neuronx_distributed_tpu.serving import (
        ChaosTransport,
        FaultInjector,
        ReplicaRouter,
        RequestState,
        ServingEngine,
        SloPolicy,
        TenantProfile,
        VirtualClock,
        WatchdogConfig,
        generate_tape,
        replay,
        tape_bytes,
    )

    cfg = tiny_llama(
        num_layers=2, hidden_size=32, intermediate_size=96, vocab_size=128
    )
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    ids = np.random.RandomState(0).randint(1, cfg.vocab_size, (1, 8))
    params = jax.jit(model.init)(
        jax.random.PRNGKey(1), ids.astype(np.int32)
    )

    tenants = [
        TenantProfile(
            "chat", rate_rps=2.5, arrival="bursty", workload="chat",
            priority="interactive", temperature=0.8, burst_factor=4.0,
            burst_period_s=2.0, burst_duty=0.3,
        ),
        TenantProfile(
            "docs", rate_rps=0.8, arrival="poisson", workload="longdoc",
            priority="batch",
        ),
    ]
    tape = generate_tape(
        tenants, duration_s=6.0, seed=18, vocab_size=cfg.vocab_size
    )
    raw = tape_bytes(tape)
    tape_identical = raw == tape_bytes(generate_tape(
        tenants, duration_s=6.0, seed=18, vocab_size=cfg.vocab_size
    ))

    # fault-free FIFO row-layout oracle: every fabric layer above it is
    # placement and recovery, never math
    oracle_clock = VirtualClock()
    oracle = ServingEngine(
        model, params, num_slots=4, decode_chunk_size=2,
        prefix_cache=None, time_fn=oracle_clock,
    )
    replay(oracle, tape, oracle_clock, step_dt=0.05)
    oracle_reqs = sorted(
        oracle.scheduler.requests.values(), key=lambda r: r.rid
    )
    refs = [list(r.tokens) for r in oracle_reqs]

    # --- leg 1: fabric replay with kill→restart, kill→re-home, join -----
    n = len(tape)
    k_restart = max(1, n // 4)
    k_rehome = max(k_restart + 1, n // 2)
    k_join = max(k_rehome + 1, (3 * n) // 4)

    clock = VirtualClock()
    inj = (
        FaultInjector()
        .dup_send(at=3, times=1)
        .drop_send(at=11, times=1)
        .delay_send(at=19, times=1, by=0.01)
        .dup_send(at=31, times=1)
        .drop_send(at=43, times=1)
    )
    transport = ChaosTransport(inj, time_fn=clock)
    registry = MetricsRegistry()
    router = ReplicaRouter.build(
        model, params, 2, registry=registry, num_slots=2,
        decode_chunk_size=2, prefix_cache=None, kv_page_size=8,
        scheduling=SloPolicy(), time_fn=clock, transport=transport,
        watchdog=WatchdogConfig(),
    )

    submit_t, first_tok_t = {}, {}

    def on_token(req, tok):
        if req.rid not in first_tok_t:
            first_tok_t[req.rid] = clock.now

    restart_wall_ms = None
    reqs = []
    i = 0
    steps = 0
    while i < len(tape) or router.has_work:
        while i < len(tape) and tape[i].t <= clock.now:
            a = tape[i]
            i += 1
            r = router.submit(
                np.asarray(a.prompt, np.int32),
                GenerationConfig(
                    max_new_tokens=a.max_new_tokens,
                    temperature=a.temperature, eos_token_id=None,
                ),
                key=jax.random.PRNGKey(a.key_seed),
                tenant=a.tenant, priority=a.priority, on_token=on_token,
            )
            submit_t[r.rid] = clock.now
            reqs.append(r)
            if len(reqs) == k_restart:
                # kill + WARM-RESTART: fence, snapshot, fresh engine,
                # restore, callbacks reattached — before any step re-homes
                router.replicas[0].fence("bench kill (restart)")
                t0 = time.perf_counter()
                router.restart_replica(0)
                restart_wall_ms = (time.perf_counter() - t0) * 1e3
            elif len(reqs) == k_rehome:
                # kill + RE-HOME: the next step() notices the halt and
                # moves the work to the survivors by halt/adopt
                router.replicas[1].fence("bench kill (rehome)")
            elif len(reqs) == k_join:
                router.add_replica()  # live join, no pause
        if not router.has_work:
            if i < len(tape):
                clock.advance_to(tape[i].t)
                continue
            break
        if steps >= 200_000:
            raise RuntimeError("fabric replay did not converge")
        router.step()
        steps += 1
        clock.advance(0.05)

    tokens_lost = 0
    for req, ref in zip(reqs, refs):
        final = router.requests[req.rid]
        if final.state is not RequestState.DONE or final.tokens != ref:
            tokens_lost += 1

    # soft-TTFT attainment per tape quarter (virtual seconds): the dip is
    # the one-replica stretch after the re-home kill, the recovery is the
    # join — a measurement, never a pin
    TTFT_TARGET_S = 1.0
    bounds = [0, k_restart, k_rehome, k_join, len(reqs)]
    names = ["full", "after_restart", "one_replica", "after_join"]
    windows = {}
    for w, name in enumerate(names):
        chunk = reqs[bounds[w]:bounds[w + 1]]
        ttfts = [
            first_tok_t[r.rid] - submit_t[r.rid]
            for r in chunk if r.rid in first_tok_t
        ]
        if not ttfts:
            windows[name] = {"arrivals": 0}
            continue
        ttfts.sort()
        windows[name] = {
            "arrivals": len(chunk),
            "attained_frac": round(
                sum(1 for t in ttfts if t <= TTFT_TARGET_S) / len(ttfts), 3
            ),
            "ttft_p95_s": round(ttfts[int(0.95 * (len(ttfts) - 1))], 3),
        }

    stats = router.stats
    fabric_row = {
        "arrivals": n,
        "kill_restart_at": k_restart,
        "kill_rehome_at": k_rehome,
        "join_at": k_join,
        "tokens_lost": tokens_lost,
        "rehomed_requests": stats["rehomed_requests"],
        "replicas_restarted": stats["replicas_restarted"],
        "replicas_joined": stats["replicas_joined"],
        "restart_wall_ms": round(restart_wall_ms, 2),
        "rehome_latency_p95_ms": round(
            router._h_rehome.percentile(0.95) * 1e3, 2
        ),
        "watchdog_probes": stats["probes"],
        "transport": {
            k: transport.stats[k]
            for k in ("messages", "retries", "dedup_hits")
        },
        "faults": {
            k: inj.counters[k]
            for k in ("dup_sends", "dropped_sends", "delayed_sends")
        },
        "ttft_target_s": TTFT_TARGET_S,
        "ttft_attainment_by_window": windows,
    }

    # --- leg 2: warm restart-to-first-token vs cold first token ---------
    def _mk(clock_):
        return ServingEngine(
            model, params, num_slots=2, decode_chunk_size=2,
            prefix_cache=None, time_fn=clock_,
        )

    rng = np.random.RandomState(7)
    prompts = [
        rng.randint(1, cfg.vocab_size, size=int(s)).astype(np.int32)
        for s in rng.randint(5, 12, size=3)
    ]
    gcfgs = [
        GenerationConfig(max_new_tokens=12, temperature=0.0),
        GenerationConfig(max_new_tokens=10, temperature=0.8, top_k=13),
        GenerationConfig(max_new_tokens=12, temperature=0.0),
    ]
    keys = [jax.random.PRNGKey(700 + j) for j in range(3)]

    def _submit_all(e):
        return [
            e.submit(p, c, key=k)
            for p, c, k in zip(prompts, gcfgs, keys)
        ]

    # uninterrupted golden (also pre-warms every compile out of the
    # warm/cold wall measurements below)
    g = _mk(VirtualClock())
    g_reqs = _submit_all(g)
    g.run()
    goldens = [list(r.tokens) for r in g_reqs]

    kill_clock = VirtualClock()
    a = _mk(kill_clock)
    a_reqs = _submit_all(a)
    for _ in range(2):
        a.step()
    a.fence("bench kill")
    snap = a.snapshot_serving_state()
    pre = {r.rid: len(r.tokens) for r in a_reqs}

    # warm: clock CONTINUES at the snapshot time (delta=0) so the restored
    # run is the uninterrupted run, bit for bit
    t0 = time.perf_counter()
    b = _mk(VirtualClock(start=kill_clock.now))
    b.restore_serving_state(snap)
    while not any(
        len(r.tokens) > pre[r.rid]
        for r in b.scheduler.requests.values()
    ):
        b.step()
    warm_ttft_ms = (time.perf_counter() - t0) * 1e3
    b.run()
    warm_bit = [
        list(b.scheduler.requests[r.rid].tokens) for r in a_reqs
    ] == goldens

    t0 = time.perf_counter()
    c = _mk(VirtualClock())
    c_reqs = _submit_all(c)
    while not any(r.tokens for r in c_reqs):
        c.step()
    cold_ttft_ms = (time.perf_counter() - t0) * 1e3
    c.run()

    restart_row = {
        "restored": len(a_reqs),
        "restart_to_first_token_ms": round(warm_ttft_ms, 2),
        "cold_first_token_ms": round(cold_ttft_ms, 2),
        "warm_over_cold": round(warm_ttft_ms / max(cold_ttft_ms, 1e-9), 3),
        "streams_bit_identical": warm_bit,
    }

    return {
        "tape": {
            "arrivals": n,
            "sha256": hashlib.sha256(raw).hexdigest()[:16],
            "identical_across_gens": tape_identical,
        },
        "fabric": fabric_row,
        "warm_restart": restart_row,
        "deterministic": (
            tape_identical and tokens_lost == 0 and warm_bit
        ),
    }


def _measure_efficiency(devs) -> dict:
    """Device-efficiency snapshot (``--child-efficiency``): a ledgered
    serving engine with ``memory_analysis=True`` (the AOT-compile opt-in —
    bench pays it so the artifact carries argument/output/temp bytes), the
    compiler-truth per-program table, the MFU proxy, and a two-run
    determinism check over the timing-free snapshot projection."""
    import jax
    import numpy as np

    from neuronx_distributed_tpu.inference import GenerationConfig
    from neuronx_distributed_tpu.models.llama import (
        LlamaForCausalLM,
        tiny_llama,
    )
    from neuronx_distributed_tpu.observability import (
        ProgramLedger,
        device_peaks,
    )
    from neuronx_distributed_tpu.serving import ServingEngine

    cfg = tiny_llama()
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    ids = jax.random.randint(jax.random.PRNGKey(0), (1, 8), 1, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(1), ids)
    gcfg = GenerationConfig(max_new_tokens=16, temperature=0.0)

    def run_once():
        ledger = ProgramLedger(
            prefix="serving", subsystem="serving", memory_analysis=True
        )
        engine = ServingEngine(
            model, params, num_slots=4, decode_chunk_size=8,
            program_ledger=ledger, kv_page_size=16,
        )
        for i in range(6):
            engine.submit(
                np.arange(1 + i, 9 + i, dtype=np.int32), gcfg,
                key=jax.random.PRNGKey(100 + i),
            )
        engine.run()
        return engine

    a = run_once()
    b = run_once()
    stable_a = json.dumps(
        a.programs.snapshot(include_timing=False), sort_keys=True
    )
    stable_b = json.dumps(
        b.programs.snapshot(include_timing=False), sort_keys=True
    )
    hbm_a = json.dumps(a.hbm.snapshot(), sort_keys=True)
    hbm_b = json.dumps(b.hbm.snapshot(), sort_keys=True)
    deterministic = stable_a == stable_b and hbm_a == hbm_b

    full = a.programs.snapshot()
    by = full["by_program"]
    # deterministic-schema per-program table: fixed keys per entry, names
    # sorted, timing excluded (walls live under the separate roofline block)
    table = {
        name: {
            "dispatches": e["dispatches"],
            "compiles": e["compiles"],
            "flops_per_dispatch": e["flops_per_dispatch"],
            "bytes_per_dispatch": e["bytes_per_dispatch"],
            "arithmetic_intensity": e["arithmetic_intensity"],
            "argument_bytes": e["memory"]["argument_bytes"],
            "output_bytes": e["memory"]["output_bytes"],
            "temp_bytes": e["memory"]["temp_bytes"],
        }
        for name, e in sorted(by.items())
    }
    dc = by["decode_chunk"]
    mfu = dc.get("mfu_p50")
    achieved = dc.get("achieved_flops_p50")
    hbm = a.hbm.snapshot()
    return {
        "deterministic": deterministic,
        "flops_source": "cost_analysis",
        "device_peaks": device_peaks(),
        "programs": table,
        "roofline": {
            "decode_chunk_wall_p50_s": dc.get("wall", {}).get("p50_s"),
            "achieved_flops_p50": (
                achieved if isinstance(achieved, float) else None
            ),
            # MFU proxy: a real fraction on known TPU kinds; null on this
            # container (unknown CPU peak — degradation is explicit)
            "mfu_proxy": mfu if isinstance(mfu, float) else None,
        },
        "hbm": hbm,
        "plan_2x_budget": a.hbm.plan(
            budget_bytes=hbm["resident_bytes_total"] * 2
        ),
    }


def child_parallel() -> None:
    """Parallelism proxy on an 8-device virtual CPU mesh: step time + XLA
    temp-allocation of the explicit-1F1B engine vs the GPipe scan engine at
    pp=2×tp=2×dp=2 with ZeRO-1 + SP. Emits one JSON line merged by the parent
    into ``extras.parallel_proxy``."""
    jax = _child_setup_jax(cpu_devices=8)
    import jax.numpy as jnp

    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib
    from neuronx_distributed_tpu.pipeline.llama import LlamaPipelineAdapter
    from neuronx_distributed_tpu.pipeline.model import (
        microbatch,
        shard_microbatched_batch,
    )
    from neuronx_distributed_tpu.trainer import OptimizerConfig, make_optimizer

    cfg = LlamaConfig(
        vocab_size=512,
        hidden_size=256,
        intermediate_size=704,
        num_layers=4,
        num_heads=8,
        num_kv_heads=4,
        max_seq_len=128,
        # fp32: the CPU backend's AllReducePromotion pass CHECK-crashes on
        # bf16 all-reduces ("Invalid binary instruction opcode copy"); the
        # proxy measures relative engine cost, dtype is immaterial
        dtype=jnp.float32,
        param_dtype=jnp.float32,
        remat=False,
        scan_layers=True,
        sequence_parallel=True,
    )
    M = 8
    mesh_lib.destroy_model_parallel()
    mesh_lib.initialize_model_parallel(
        tensor_model_parallel_size=2, pipeline_model_parallel_size=2
    )
    dp = mesh_lib.get_data_parallel_size()
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    key = jax.random.PRNGKey(0)
    ids = jax.random.randint(key, (M * dp, 64), 0, cfg.vocab_size)
    batch = shard_microbatched_batch(
        microbatch({"input_ids": ids, "labels": jnp.roll(ids, -1, 1)}, M)
    )

    import dataclasses as _dc

    cfg8 = _dc.replace(cfg, num_layers=8)
    model8 = LlamaForCausalLM(cfg8, attention_impl="xla")
    out = {}
    # engine shoot-out (VERDICT r4 next #7): gpipe vs sync-1F1B vs
    # interleaved at C=2 and C=4 (the C=4 row runs 8 layers so each of the
    # pp·C virtual stages holds one layer)
    for sched, chunks in (
        ("1f1b", 1), ("interleaved", 2), ("gpipe", 1), ("interleaved_c4", 4),
    ):
        row_cfg, row_model = (cfg8, model8) if chunks == 4 else (cfg, model)
        adapter = LlamaPipelineAdapter(
            config=row_cfg, num_microbatches=M, attention_impl="xla",
            schedule="interleaved" if sched.startswith("interleaved") else sched,
            num_chunks=chunks if chunks > 1 else 1,
        )
        state, step, _engine = adapter.build_state_and_step(
            row_model, make_optimizer(OptimizerConfig()), key, ids
        )
        # temp-allocation evidence via compiled memory analysis
        lowered = step.lower(state, batch)
        compiled = lowered.compile()
        try:
            temp_bytes = int(compiled.memory_analysis().temp_size_in_bytes)
        except Exception:
            temp_bytes = -1
        state, metrics = step(state, batch)
        _ = float(metrics["loss"])
        t0 = time.perf_counter()
        iters = 3
        for _ in range(iters):
            state, metrics = step(state, batch)
        _ = float(metrics["loss"])
        out[sched] = {
            "step_time_s": round((time.perf_counter() - t0) / iters, 4),
            "temp_alloc_bytes": temp_bytes,
            "loss": round(float(metrics["loss"]), 4),
        }
    # emit the schedule measurements FIRST (the parent takes the last
    # parseable line and salvages partial stdout on timeout), then augment
    # with the blockwise-EP comparison — it tears down and rebuilds the
    # global mesh and must never sink the already-measured schedules
    payload = {
        "metric": "parallel_proxy",
        "platform": jax.devices()[0].platform,
        "mesh": "cpu pp=2 tp=2 dp=2 sp=on zero1=on",
        "microbatches": M,
        "schedules": out,
        "note": "interleaved_c4 runs 8 layers (1 per virtual stage) — 2x the"
                " compute of the 4-layer rows; compare its step time per layer",
    }
    _emit(payload)
    payload["blockwise_ep"] = _blockwise_ep_comparison()
    _emit(payload)


def _blockwise_ep_comparison():
    """Timed comparison (VERDICT r3 next #10): the blockwise-EP local-offset
    GATHER alignment vs the legacy double-ROLL formulation, fwd+bwd at ep=2
    x tp=2 on the virtual mesh. Returns per-variant step times + the gather
    speedup; failures are reported, never fatal (this augments the proxy)."""
    import jax
    import jax.numpy as jnp

    from neuronx_distributed_tpu.modules.moe.expert_mlps import (
        _sharded_blockwise_mlp,
        _sharded_blockwise_mlp_manual,
        _sharded_blockwise_mlp_rolled,
    )
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib

    try:
        mesh_lib.destroy_model_parallel()
        mesh_lib.initialize_model_parallel(
            tensor_model_parallel_size=2, expert_model_parallel_size=2
        )
        mesh = mesh_lib.get_mesh()
        T, H, I, E, k = 4096, 512, 1024, 8, 2
        key = jax.random.PRNGKey(0)
        ks = jax.random.split(key, 5)
        x = jax.random.normal(ks[0], (T, H), jnp.float32)
        top_e = jax.random.randint(ks[1], (T, k), 0, E)
        top_w = jax.nn.softmax(jax.random.normal(ks[2], (T, k)), -1)
        gate = jax.random.normal(ks[3], (E, H, I)) * 0.02
        up = jax.random.normal(ks[4], (E, H, I)) * 0.02
        down = jax.random.normal(ks[0], (E, I, H)) * 0.02

        flat_e = top_e.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        token_idx = order // k
        sizes = jnp.bincount(flat_e, length=E).astype(jnp.int32)
        ws = top_w.reshape(-1)[order]

        gathered = _sharded_blockwise_mlp(
            mesh, mesh_lib.EP_AXIS, mesh_lib.TP_AXIS, E // 2, 2, True, "silu")
        rolled = _sharded_blockwise_mlp_rolled(
            mesh, mesh_lib.EP_AXIS, mesh_lib.TP_AXIS, E // 2, 2, True, "silu")
        # round-5 production path: fully-manual, routing in-region, combine
        # as an IN-REGION psum (no stacked (ep, tp, T, H) buffer at all)
        manual = _sharded_blockwise_mlp_manual(
            mesh, mesh_lib.EDP_AXIS, mesh_lib.EP_AXIS, mesh_lib.TP_AXIS,
            E, E // 2, 2, k, True, "silu")

        def loss_gather(g, u, d):
            return gathered(x, token_idx, ws, sizes, g, u, d).sum(
                axis=(0, 1)).sum()

        def loss_rolled(g, u, d):
            ys = rolled(x[token_idx], sizes, g, u, d).sum(axis=(0, 1))
            return (
                jnp.zeros((T, H)).at[token_idx].add(ys * ws[:, None]).sum()
            )

        def loss_manual(g, u, d):
            return manual(x, top_e, top_w, g, u, d).sum()

        results = {}
        vals = {}
        for name, fn in (
            ("gather", loss_gather), ("rolled", loss_rolled),
            ("manual_psum", loss_manual),
        ):
            step = jax.jit(jax.value_and_grad(fn, argnums=(0, 1, 2)))
            v, g = step(gate, up, down)  # compile + correctness sample
            jax.block_until_ready(g)
            vals[name] = float(v)
            t0 = time.perf_counter()
            iters = 3
            for _ in range(iters):
                v, g = step(gate, up, down)
            jax.block_until_ready(g)
            results[name + "_step_s"] = round(
                (time.perf_counter() - t0) / iters, 4
            )
        results["loss_match"] = (
            abs(vals["gather"] - vals["rolled"]) < 1e-2
            and abs(vals["gather"] - vals["manual_psum"]) < 1e-2
        )
        results["gather_speedup"] = round(
            results["rolled_step_s"] / max(results["gather_step_s"], 1e-9), 3
        )
        results["manual_psum_speedup_vs_stacked"] = round(
            results["gather_step_s"] / max(results["manual_psum_step_s"], 1e-9), 3
        )
        results["shape"] = f"T={T} H={H} I={I} E={E} k={k} ep=2 tp=2 fwd+bwd"
        return results
    except Exception as e:
        return {"error": f"{type(e).__name__}: {str(e)[:200]}"}
    finally:
        mesh_lib.destroy_model_parallel()


# --------------------------------------------------------------------------
# CPU proxies
# --------------------------------------------------------------------------

# flag -> (metric, unit, measurement, parent timeout, virtual CPU devices).
# Every one runs on the CPU backend, by its own hand (``_child_setup_jax``
# forces it before JAX initializes), whatever machine bench.py is on.
CPU_PROXIES = {
    "--child-serving": (
        "serving_chunk", "decode tokens/s",
        _measure_serving_chunk, SERVING_TIMEOUT_S, 1),
    "--child-faults": (
        "serving_faults", "recovery overhead",
        _measure_serving_faults, FAULTS_TIMEOUT_S, 1),
    "--child-prefix": (
        "serving_prefix", "prefill wall saved",
        _measure_serving_prefix, PREFIX_TIMEOUT_S, 1),
    "--child-train-faults": (
        "train_faults", "recovery overhead + exact resume",
        _measure_train_faults, TRAIN_FAULTS_TIMEOUT_S, 1),
    # vote mode needs dp replicas: 8 virtual CPU devices, like the other
    # mesh-driven proxies
    "--child-integrity": (
        "integrity", "sentinel overhead + detection latency",
        _measure_integrity, INTEGRITY_TIMEOUT_S, 8),
    "--child-observe": (
        "observability", "instrumentation overhead",
        _measure_observability, OBSERVE_TIMEOUT_S, 1),
    "--child-spec": (
        "serving_spec", "decode tokens/s (spec-on / spec-off)",
        _measure_serving_spec, SPEC_TIMEOUT_S, 1),
    "--child-paged": (
        "serving_paged", "concurrent slots @ fixed KV budget",
        _measure_serving_paged, PAGED_TIMEOUT_S, 1),
    "--child-quant": (
        "serving_quant", "decode tok/s + pages @ fixed budget",
        _measure_serving_quant, QUANT_TIMEOUT_S, 1),
    "--child-traffic": (
        "serving_traffic", "per-tenant SLO attainment/goodput (virtual clock)",
        _measure_traffic, TRAFFIC_TIMEOUT_S, 1),
    "--child-sched": (
        "serving_sched", "per-tenant attainment/goodput deltas, FIFO vs SLO",
        _measure_sched, SCHED_TIMEOUT_S, 1),
    "--child-efficiency": (
        "device_efficiency", "compiler-reported cost",
        _measure_efficiency, EFFICIENCY_TIMEOUT_S, 1),
    "--child-multichip": (
        "serving_multichip", "tp bit-identity + TPOT p99 (CPU mesh proxy)",
        _measure_serving_multichip, MULTICHIP_TIMEOUT_S, 8),
    "--child-graftverify": (
        "graftverify", "IR-verified donations / wire bytes (CPU proxy)",
        _measure_graftverify, GRAFTVERIFY_TIMEOUT_S, 8),
    "--child-fabric": (
        "serving_fabric", "tokens_lost + re-home/restart latency",
        _measure_serving_fabric, FABRIC_TIMEOUT_S, 1),
}
# proxies with their own process structure
CUSTOM_PROXIES = {
    "--child-parallel": ("parallel_proxy", child_parallel, PROXY_TIMEOUT_S),
    "--child-coldstart": ("serving_coldstart", child_coldstart,
                          COLDSTART_TIMEOUT_S),
}


def child_cpu_proxy(flag: str) -> None:
    """Run one table proxy on the CPU backend and print its JSON line,
    platform named. A failure propagates: traceback, non-zero exit."""
    metric, unit, measure, _, n_devices = CPU_PROXIES[flag]
    jax = _child_setup_jax(cpu_devices=n_devices)
    devs = jax.devices()
    _emit({
        "metric": metric,
        "unit": unit,
        "platform": devs[0].platform,
        **measure(devs),
    })


# --------------------------------------------------------------------------
# parent orchestration
# --------------------------------------------------------------------------


def _parse_result(stdout: str):
    """Last stdout line that parses as a JSON object with a 'metric' key."""
    for line in reversed(stdout.strip().splitlines()):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(obj, dict) and "metric" in obj:
            return obj
    return None


_RUNNING = []  # the child process in flight (the SIGTERM handler kills it)


def _run_child(flag: str, timeout_s: float):
    """Run one child to its end. Returns ``(json, None)`` only for a child
    that exited 0 with a result line; anything else — a non-zero exit, a
    timeout, no JSON — is ``(None, why)``."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), flag],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    _RUNNING.append(proc)
    try:
        stdout, stderr = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        return None, f"timed out after {int(timeout_s)}s"
    finally:
        _RUNNING.remove(proc)
    tail = (stderr or stdout or "").strip()[-400:]
    if proc.returncode != 0:
        return None, f"rc={proc.returncode}: {tail}"
    result = _parse_result(stdout)
    if result is None:
        return None, f"rc=0 but no JSON line: {tail}"
    return result, None


def main() -> int:
    import signal

    def _on_term(signum, frame):
        for proc in _RUNNING:
            proc.kill()  # don't orphan a child that holds the chip
        print(f"bench: killed by signal {signum} — no result", file=sys.stderr)
        sys.exit(128 + signum)

    signal.signal(signal.SIGTERM, _on_term)
    signal.signal(signal.SIGINT, _on_term)

    # 1. The device measurement. No probe, no smaller stand-in, no retry on
    #    another platform: it either measured the chip or the run failed.
    result, err = _run_child("--child", FULL_TIMEOUT_S)
    if result is None:
        print(f"bench: device measurement failed — {err}", file=sys.stderr)
        return 1

    # 2. The CPU proxies, strictly one at a time (wall-clock comparisons
    #    must not contend for cores, and nothing may run beside the child
    #    that holds the chip). They go under their own key and never into
    #    the device headline.
    proxies, errors = {}, {}
    jobs = [(flag, spec[0], spec[3]) for flag, spec in CPU_PROXIES.items()]
    jobs += [(flag, spec[0], spec[2]) for flag, spec in CUSTOM_PROXIES.items()]
    for flag, metric, timeout_s in jobs:
        proxy, err = _run_child(flag, timeout_s)
        if proxy is None:
            errors[metric] = err
            continue
        proxy.pop("metric", None)
        proxies[metric] = proxy
    extras = result.setdefault("extras", {})
    extras["cpu_proxies"] = proxies
    if errors:
        extras["cpu_proxy_errors"] = errors
    _emit(result)
    if errors:
        print(f"bench: CPU proxies failed: {sorted(errors)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    _flag = next((a for a in sys.argv[1:] if a.startswith("--")), None)
    if _flag is None:
        sys.exit(main())
    elif _flag == "--child":
        child_train()
    elif _flag == "--coldstart-leg":
        _i = sys.argv.index("--coldstart-leg")
        coldstart_leg(sys.argv[_i + 1], sys.argv[_i + 2])
    elif _flag in CPU_PROXIES:
        child_cpu_proxy(_flag)
    elif _flag in CUSTOM_PROXIES:
        CUSTOM_PROXIES[_flag][1]()
    else:
        sys.exit(f"bench: unknown flag {_flag}")
