#!/usr/bin/env python
"""Llama inference example — trace / generate / benchmark harness (reference:
``examples/inference/runner.py:475-765`` — ``trace``, ``serve``, and
``benchmark_sampling`` with p50/p99 latency reporting).

Modes:

  generate   — KV-cache autoregressive generation from a prompt
  benchmark  — repeat generation ``--iters`` times, report p50/p99 e2e
               latency, per-token decode latency, and tokens/s
  trace      — AOT-compile prefill buckets + decode step via ModelBuilder
               and (optionally) serialize the executables with --save-dir
  speculative— draft-model speculative decoding (tiny draft of the same
               family), reports mean accepted tokens/round
  medusa     — Medusa tree decoding with freshly-initialized heads
               (reference examples/inference/run_llama_medusa.py), reports
               mean accepted tokens/round
  check      — serving-path accuracy check: greedy KV-cache generation must
               EXACTLY equal the model's full-recompute greedy golden
               (reference check_accuracy; always greedy — sampling flags
               are ignored)

Examples (development host, virtual CPU devices):

  python examples/run_inference.py --model tiny --mode generate \
      --prompt-len 16 --max-new-tokens 32 --force-cpu-devices 8 --tp 2
  python examples/run_inference.py --model tiny --mode benchmark --iters 10
  python examples/run_inference.py --model tiny --mode trace \
      --buckets 64,128 --save-dir /tmp/traced

On TPU (BASELINE config 5 shape): --model 7b --tp 8 --prompt-len 1024.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time

_repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _repo_root not in sys.path:
    sys.path.insert(0, _repo_root)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--model", default="tiny", choices=["tiny", "7b", "llama3-8b"])
    p.add_argument("--mode", default="generate",
                   choices=["generate", "benchmark", "trace", "speculative",
                            "medusa", "check"])
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--prompt-len", type=int, default=16)
    p.add_argument("--max-new-tokens", type=int, default=32)
    p.add_argument("--temperature", type=float, default=None,
                   help="sampling temperature (generate default 1.0; "
                        "speculative default 0.0 = greedy; medusa is "
                        "always greedy and ignores sampling flags)")
    p.add_argument("--top-k", type=int, default=None)
    p.add_argument("--top-p", type=float, default=None)
    p.add_argument("--greedy", action="store_true", help="temperature-0 argmax")
    p.add_argument("--iters", type=int, default=10, help="benchmark iterations")
    p.add_argument("--warmup", type=int, default=2)
    p.add_argument("--gamma", type=int, default=4, help="speculative window")
    p.add_argument("--buckets", default="64,256",
                   help="comma-separated prompt buckets for trace mode")
    p.add_argument("--save-dir", default=None,
                   help="serialize traced executables here (trace mode)")
    p.add_argument("--attention", default="auto", choices=["auto", "flash", "xla"])
    p.add_argument("--quantize", default=None,
                   choices=["int8", "fp8", "int8-mxu"],
                   help="weight-only serving quantization: every linear "
                        "kernel stored int8/fp8e4m3 + per-channel scale "
                        "(generate/benchmark/check modes)")
    p.add_argument("--report-file", default=None,
                   help="benchmark mode: also write the report JSON here "
                        "(reference BENCHMARK_REPORT_FILENAME)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--force-cpu-devices", type=int, default=None)
    return p.parse_args(argv)


# Medusa tree used by both the KV-cache sizing (build_model) and the
# generation call — one source of truth so they cannot desync.
MEDUSA_TOP_K = 10


def _medusa_choices():
    from neuronx_distributed_tpu.inference.medusa import DEFAULT_CHOICES

    return DEFAULT_CHOICES


def build_model(args):
    import jax.numpy as jnp

    from neuronx_distributed_tpu.models import llama as llama_lib
    from neuronx_distributed_tpu.models.llama import LlamaForCausalLM

    preset = {
        "tiny": llama_lib.tiny_llama,
        "7b": llama_lib.llama2_7b,
        "llama3-8b": llama_lib.llama3_8b,
    }[args.model]
    # KV-cache slack beyond prompt+new: speculative looks ahead gamma draft
    # tokens; medusa enters the whole candidate tree (+ its depth of accepted
    # tokens) into the cache each round
    slack = args.gamma if args.mode == "speculative" else 0
    if args.mode == "medusa":
        from neuronx_distributed_tpu.utils.medusa import generate_medusa_buffers

        buffers = generate_medusa_buffers(_medusa_choices(), top_k=MEDUSA_TOP_K)
        n_nodes = buffers["attn_mask"].shape[0]
        depth = buffers["retrieve_indices"].shape[1] - 1
        slack = n_nodes + depth
    need = args.prompt_len + args.max_new_tokens + slack
    cfg = preset()
    if cfg.max_seq_len < need:
        cfg = dataclasses.replace(cfg, max_seq_len=need)
    if args.model == "tiny":
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    return LlamaForCausalLM(cfg, attention_impl=args.attention), cfg


def main(argv=None):
    args = parse_args(argv)
    if args.quantize and args.mode not in ("generate", "benchmark", "check"):
        # fail BEFORE any model init — silent float serving while the user
        # believes int8 is active would invalidate whatever they measure next
        raise SystemExit(
            f"--quantize is not supported in --mode {args.mode} "
            "(generate/benchmark/check only)"
        )
    if args.force_cpu_devices:
        from neuronx_distributed_tpu.utils.platform import force_cpu_devices

        force_cpu_devices(args.force_cpu_devices)

    import jax
    import jax.numpy as jnp

    from flax.core import meta

    # compile cache: where JAX_COMPILATION_CACHE_DIR says, else the fixed
    # <checkout>/.jax_cache (a path that moves never hits)
    from neuronx_distributed_tpu.inference import aot

    aot.enable_persistent_cache(
        os.path.join(_repo_root, ".jax_cache"), min_compile_time_secs=0.5
    )

    from neuronx_distributed_tpu.inference.generate import (
        GenerationConfig,
        generate,
    )
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib
    from neuronx_distributed_tpu.utils.logger import get_logger

    logger = get_logger("examples.run_inference")
    if mesh_lib.model_parallel_is_initialized():
        mesh_lib.destroy_model_parallel()
    mesh_lib.initialize_model_parallel(tensor_model_parallel_size=args.tp)

    model, cfg = build_model(args)
    key = jax.random.PRNGKey(args.seed)
    prompt = jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab_size, jnp.int32
    )
    logger.info("initializing %s (tp=%d, %d layers)", args.model, args.tp,
                cfg.num_layers)
    # medusa re-inits its own multi-head model below; skip the base init
    params = (None if args.mode == "medusa"
              else meta.unbox(jax.jit(model.init)(key, prompt)))

    if args.quantize:
        # weight-only serving quantization: quantize the float checkpoint
        # tree and serve it through the quantized model (HBM holds 1-byte
        # weights; XLA fuses the dequant scale into the matmul epilogue)
        from neuronx_distributed_tpu.quantization.config import (
            QuantizationConfig,
            QuantizedDtype,
        )
        from neuronx_distributed_tpu.quantization.utils import (
            quantize_param_tree,
        )

        qcfg = QuantizationConfig(
            quantized_dtype={"int8": QuantizedDtype.INT8,
                             "fp8": QuantizedDtype.FP8E4M3,
                             # native int8 MXU GEMMs + dynamic activation
                             # quant (adds ~1e-2 rel error over dequant —
                             # verify with --mode check)
                             "int8-mxu": QuantizedDtype.INT8}[args.quantize],
            use_int8_matmul=args.quantize == "int8-mxu",
        )
        params = quantize_param_tree(params, qcfg)
        cfg = dataclasses.replace(cfg, quantization=qcfg)
        from neuronx_distributed_tpu.models.llama import LlamaForCausalLM

        model = LlamaForCausalLM(cfg, attention_impl=args.attention)
        logger.info("serving %s weights (weight-only quantization)",
                    args.quantize)

    gen_temp = 1.0 if args.temperature is None else args.temperature
    gen_cfg = GenerationConfig(
        max_new_tokens=args.max_new_tokens,
        temperature=0.0 if args.greedy else gen_temp,
        top_k=args.top_k,
        top_p=args.top_p,
    )

    if args.mode == "check":
        # serving-path accuracy check (reference check_accuracy,
        # runner.py:348): greedy KV-cache generation must EXACTLY equal the
        # model's own full-recompute greedy continuation — one teacher-forced
        # apply over [prompt, generated] is that golden (each token must be
        # the argmax given its prefix). Works with --quantize: the quantized
        # serving path is checked against the quantized model's own golden.
        import numpy as np

        greedy = dataclasses.replace(gen_cfg, temperature=0.0)
        toks = generate(model, params, prompt, key, greedy)
        full = jnp.concatenate([prompt, toks], axis=1)
        logits = jax.jit(model.apply)(params, full)
        s0 = prompt.shape[1]
        preds = jnp.argmax(logits[:, s0 - 1 : -1], -1).astype(jnp.int32)
        match = bool(jnp.array_equal(toks, preds))
        agreement = float((np.asarray(toks) == np.asarray(preds)).mean())
        print(f"serving path vs full-recompute golden: "
              f"{'EXACT MATCH' if match else f'MISMATCH (agreement {agreement:.3f})'}")
        if not match:
            raise SystemExit(1)
        return {"match": match, "agreement": agreement}

    if args.mode == "generate":
        toks = generate(model, params, prompt, key, gen_cfg)
        toks = jax.device_get(toks)
        print(f"prompt ids[0]: {jax.device_get(prompt)[0].tolist()}")
        print(f"generated ids[0]: {toks[0].tolist()}")
        return {"tokens": toks}

    if args.mode == "benchmark":
        # reference benchmark_sampling (runner.py:521-765): e2e latency AND
        # per-submodule collectors (context-encoding / per-token-gen /
        # sampling), each reported p50/p90/p95/p99/p100/avg + throughput
        from neuronx_distributed_tpu.inference.benchmark import benchmark_generate

        sub = benchmark_generate(
            model, params, prompt, key, gen_cfg,
            iters=args.iters, warmup=args.warmup,
        )
        p50 = sub["e2e_model"]["latency_ms_p50"] / 1e3
        p99 = sub["e2e_model"]["latency_ms_p99"] / 1e3
        new_tokens = args.batch * args.max_new_tokens
        report = {
            "e2e_p50_s": round(p50, 4),
            "e2e_p99_s": round(p99, 4),
            "per_token_p50_ms": round(1e3 * p50 / args.max_new_tokens, 3),
            "tokens_per_s_p50": round(new_tokens / p50, 1),
            "iters": args.iters,
            "batch": args.batch,
            "prompt_len": args.prompt_len,
            "max_new_tokens": args.max_new_tokens,
            "submodules": sub,
        }
        import json as _json

        print(_json.dumps(report, indent=2))
        if args.report_file:
            with open(args.report_file, "w") as f:
                _json.dump(report, f, indent=2)
            print(f"benchmark report -> {args.report_file}")
        return report

    if args.mode == "trace":
        # reference ModelBuilder.trace path: prefill per bucket + decode step
        from neuronx_distributed_tpu.inference.model_builder import ModelBuilder

        buckets = sorted(int(b) for b in args.buckets.split(","))
        prefill = model.clone(mode="prefill")
        decode = model.clone(mode="decode")

        def prefill_fn(ids, params):
            logits, variables = prefill.apply(params, ids, mutable=["cache"])
            return logits[:, -1], variables["cache"]

        def decode_fn(tok, params, cache):
            logits, variables = decode.apply(
                {**params, "cache": cache}, tok, mutable=["cache"]
            )
            return logits[:, -1], variables["cache"]

        builder = ModelBuilder()
        bucket_args = []
        for b in buckets:
            ids = jnp.zeros((args.batch, b), jnp.int32)
            bucket_args.append((ids, params))
        builder.add("context_encode", prefill_fn, bucket_args, bucket_dim=1,
                    route_argnum=0)
        _, cache0 = jax.jit(prefill_fn)(
            jnp.zeros((args.batch, buckets[0]), jnp.int32), params
        )
        builder.add(
            "token_gen",
            decode_fn,
            [(jnp.zeros((args.batch, 1), jnp.int32), params, cache0)],
            bucket_dim=1,
            route_argnum=0,
        )
        t0 = time.perf_counter()
        nxd_model = builder.trace()
        print(f"traced {len(buckets)} prefill buckets + decode in "
              f"{time.perf_counter() - t0:.1f}s")
        logits, cache = nxd_model("context_encode", prompt, params)
        print(f"context_encode(prompt {prompt.shape}) -> logits {logits.shape}")
        if args.save_dir:
            builder.save(args.save_dir)
            print(f"serialized executables -> {args.save_dir}")
        return {"buckets": buckets}

    if args.mode == "speculative":
        from neuronx_distributed_tpu.inference.speculative import (
            speculative_generate,
        )
        from neuronx_distributed_tpu.models.llama import LlamaForCausalLM

        draft_cfg = dataclasses.replace(
            cfg,
            num_layers=max(1, cfg.num_layers // 4),
            scan_layers=False,
        )
        draft = LlamaForCausalLM(draft_cfg, attention_impl=args.attention)
        draft_params = meta.unbox(jax.jit(draft.init)(key, prompt))
        temp = 0.0 if (args.greedy or args.temperature is None) else args.temperature
        t0 = time.perf_counter()
        toks, accepted = speculative_generate(
            model, params, draft, draft_params, prompt,
            max_new_tokens=args.max_new_tokens, gamma=args.gamma,
            temperature=temp, key=key if temp > 0 else None,
        )
        dt = time.perf_counter() - t0
        print(f"speculative: {args.max_new_tokens} tokens in {dt:.2f}s, "
              f"mean accepted/round {float(accepted):.2f}")
        print(f"generated ids[0]: {jax.device_get(toks)[0].tolist()}")
        return {"accepted_per_round": float(accepted)}

    if args.mode == "medusa":
        from neuronx_distributed_tpu.inference.medusa import medusa_generate
        from neuronx_distributed_tpu.models.medusa import MedusaForCausalLM

        medusa = MedusaForCausalLM(cfg, attention_impl=args.attention)
        medusa_params = meta.unbox(jax.jit(medusa.init)(key, prompt))
        t0 = time.perf_counter()
        toks, accepted = medusa_generate(
            medusa, medusa_params, prompt, max_new_tokens=args.max_new_tokens,
            choices=_medusa_choices(), top_k=MEDUSA_TOP_K,
        )
        dt = time.perf_counter() - t0
        # per-row acceptance is draft quality; realized throughput (printed)
        # is bounded by the batch-min advance at batch > 1
        print(f"medusa: {args.max_new_tokens} tokens in {dt:.2f}s "
              f"({args.batch * args.max_new_tokens / dt:.1f} tokens/s), "
              f"mean accepted/round {float(accepted):.2f}")
        print(f"generated ids[0]: {jax.device_get(toks)[0].tolist()}")
        return {"accepted_per_round": float(accepted),
                "tokens": jax.device_get(toks)}

    raise ValueError(f"unknown mode {args.mode!r}")


if __name__ == "__main__":
    sys.exit(0 if main() is not None else 1)
