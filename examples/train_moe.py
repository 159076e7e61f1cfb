#!/usr/bin/env python
"""Mixtral MoE pretraining example (reference:
``examples/training/mixtral/`` — the MoE counterpart of run_llama_nxd.py:
args → mesh (tp×ep×dp) → synthetic data → Trainer loop → throughput).

Exercises the MoE-specific machinery end to end: TopK routing with aux +
z losses, the four expert-execution strategies (``--expert-strategy``),
expert parallelism (``--ep``), token shuffling for DP load balance
(``--token-shuffle``), and capacity-factor token dropping (``--capacity``).

Examples (development host, virtual CPU devices):

  # dropless blockwise experts, ep=2 x tp=2
  python examples/train_moe.py --model tiny --tp 2 --ep 2 --steps 4 \
      --force-cpu-devices 8

  # capacity-factor dropping + token shuffling
  python examples/train_moe.py --model tiny --capacity 1.25 \
      --token-shuffle --steps 4 --force-cpu-devices 8

On TPU (reference shape): --model 8x7b --tp 8 --ep 4 --sp.
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
    m = p.add_argument_group("model")
    m.add_argument("--model", default="tiny", choices=["tiny", "8x7b"])
    m.add_argument("--layers", type=int, default=None)
    m.add_argument("--seq-len", type=int, default=None)
    m.add_argument("--attention", default="auto",
                   choices=["auto", "flash", "xla"])
    m.add_argument("--experts", type=int, default=None,
                   help="override number of experts")
    m.add_argument("--top-k", type=int, default=None)

    moe = p.add_argument_group("moe")
    moe.add_argument("--expert-strategy", default="auto",
                     choices=["auto", "all_experts", "capacity", "blockwise",
                              "selective"])
    moe.add_argument("--capacity", type=float, default=None,
                     help="capacity factor (token dropping); None = dropless")
    moe.add_argument("--token-shuffle", action="store_true",
                     help="shuffle tokens across DP before routing")
    moe.add_argument("--aux-loss-coef", type=float, default=0.02)
    moe.add_argument("--z-loss-coef", type=float, default=0.0)

    par = p.add_argument_group("parallelism")
    par.add_argument("--tp", type=int, default=1)
    par.add_argument("--ep", type=int, default=1, help="expert parallel size")
    par.add_argument("--sp", action="store_true",
                     help="Megatron sequence parallel")
    par.add_argument("--pp", type=int, default=1,
                     help="pipeline parallel size (generic Mixtral adapter)")
    par.add_argument("--schedule", default="1f1b",
                     choices=["gpipe", "1f1b", "interleaved"])
    par.add_argument("--chunks", type=int, default=2,
                     help="virtual chunks per rank (interleaved)")
    par.add_argument("--microbatches", type=int, default=4)

    t = p.add_argument_group("training")
    t.add_argument("--batch-size", type=int, default=None,
                   help="global batch (default: one sequence per dp rank)")
    t.add_argument("--steps", type=int, default=10)
    t.add_argument("--lr", type=float, default=3e-4)
    t.add_argument("--no-zero1", action="store_true")
    t.add_argument("--max-grad-norm", type=float, default=1.0)
    t.add_argument("--seed", type=int, default=0)

    io = p.add_argument_group("io")
    io.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory (local or gs://)")
    io.add_argument("--ckpt-every", type=int, default=100)
    io.add_argument("--ckpt-keep", type=int, default=3)
    io.add_argument("--resume", action="store_true")
    io.add_argument("--tensorboard-dir", default=None)
    io.add_argument("--log-every", type=int, default=1)

    e = p.add_argument_group("environment")
    e.add_argument("--force-cpu-devices", type=int, default=None)
    return p.parse_args(argv)


def build_config(args):
    import jax.numpy as jnp

    from neuronx_distributed_tpu.models import mixtral as mixtral_lib

    preset = {
        "tiny": mixtral_lib.tiny_mixtral,
        "8x7b": mixtral_lib.mixtral_8x7b,
    }[args.model]
    over = {
        "sequence_parallel": args.sp,
        "expert_strategy": args.expert_strategy,
        "capacity_factor": args.capacity,
        "token_shuffle": args.token_shuffle,
        "router_aux_loss_coef": args.aux_loss_coef,
        "router_z_loss_coef": args.z_loss_coef,
    }
    if args.layers is not None:
        over["num_layers"] = args.layers
    if args.seq_len is not None:
        over["max_seq_len"] = args.seq_len
    if args.experts is not None:
        over["num_experts"] = args.experts
    if args.top_k is not None:
        over["top_k"] = args.top_k
    cfg = preset(**over)
    if args.model == "tiny" and args.attention == "auto":
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    return cfg


def make_data_iter(args, cfg, batch_size: int, seq_len: int,
                   include_step: bool = True):
    import numpy as np

    rng = np.random.default_rng(args.seed)
    step = 0
    while True:
        ids = rng.integers(0, cfg.vocab_size, (batch_size, seq_len + 1),
                           dtype=np.int32)
        batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
        if include_step:
            # "step" seeds the per-step shuffle/jitter rng streams inside the
            # jitted loss (scalars pass through shard_batch replicated; the
            # pipeline prepare_batch microbatches every leaf, so pp runs —
            # which forbid the stochastic paths anyway — omit it)
            batch["step"] = np.int32(step)
        yield batch
        step += 1


def main(argv=None):
    args = parse_args(argv)
    if args.resume and not args.ckpt_dir:
        raise SystemExit("--resume requires --ckpt-dir (nothing to resume from)")
    if args.force_cpu_devices:
        from neuronx_distributed_tpu.utils.platform import force_cpu_devices

        force_cpu_devices(args.force_cpu_devices)

    import jax

    # compile cache: where JAX_COMPILATION_CACHE_DIR says, else the fixed
    # <checkout>/.jax_cache (a path that moves never hits)
    from neuronx_distributed_tpu.inference import aot

    aot.enable_persistent_cache(
        os.path.join(_repo_root, ".jax_cache"), min_compile_time_secs=0.5
    )

    from neuronx_distributed_tpu.models.mixtral import MixtralForCausalLM
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib
    from neuronx_distributed_tpu.trainer import OptimizerConfig
    from neuronx_distributed_tpu.trainer.loop import (
        CheckpointCallback,
        MetricsLogger,
        Trainer,
    )
    from neuronx_distributed_tpu.utils.logger import get_logger

    logger = get_logger("examples.train_moe")
    if mesh_lib.model_parallel_is_initialized():
        mesh_lib.destroy_model_parallel()
    mesh_lib.initialize_model_parallel(
        tensor_model_parallel_size=args.tp,
        expert_model_parallel_size=args.ep,
        pipeline_model_parallel_size=args.pp,
    )
    dp = mesh_lib.get_data_parallel_size()
    cfg = build_config(args)
    if args.pp > 1:
        cfg = dataclasses.replace(cfg, scan_layers=True)
    seq_len = min(cfg.max_seq_len, args.seq_len or cfg.max_seq_len)
    if args.batch_size is None:
        batch_size = dp * (args.microbatches if args.pp > 1 else 1)
    else:
        batch_size = args.batch_size

    opt_cfg = OptimizerConfig(
        learning_rate=args.lr,
        total_steps=args.steps,
        zero1=not args.no_zero1,
        max_grad_norm=args.max_grad_norm,
    )
    model = MixtralForCausalLM(cfg, attention_impl=args.attention)
    callbacks = [MetricsLogger(log_every=args.log_every,
                               tensorboard_dir=args.tensorboard_dir)]
    if args.ckpt_dir:
        callbacks.append(
            CheckpointCallback(args.ckpt_dir, every=args.ckpt_every,
                               num_kept=args.ckpt_keep)
        )

    # token shuffling and router jitter only run under deterministic=False
    # with their rng streams provided (modules/moe/model.py make_rng calls)
    stochastic = cfg.token_shuffle or cfg.router_jitter_eps > 0.0
    rng_base = jax.random.PRNGKey(args.seed + 1)

    def moe_loss(params, batch):
        # CE + router aux/z losses (MixtralForCausalLM.loss — the trainer's
        # default loss fn only handles bare-logits models); packed-corpus
        # batches carry segment_ids/loss_mask and .loss forwards them
        extras = dict(
            segment_ids=batch.get("segment_ids"),
            loss_mask=batch.get("loss_mask"),
        )
        if stochastic:
            k = jax.random.fold_in(rng_base, batch["step"])
            rngs = {"token_shuffle": jax.random.fold_in(k, 0),
                    "jitter": jax.random.fold_in(k, 1)}
            return model.loss(params, batch["input_ids"], batch["labels"],
                              deterministic=False, rngs=rngs, **extras)
        return model.loss(params, batch["input_ids"], batch["labels"], **extras)

    pipeline = None
    if args.pp > 1:
        if stochastic:
            raise SystemExit(
                "--pp with --token-shuffle/jitter is unsupported: the "
                "pipeline adapters run layers without per-step rng streams"
            )
        from neuronx_distributed_tpu.pipeline.generic import (
            GenericPipelineAdapter,
        )
        from neuronx_distributed_tpu.pipeline.mixtral import mixtral_family

        pipeline = GenericPipelineAdapter(
            family=mixtral_family(cfg, attention_impl=args.attention),
            num_microbatches=args.microbatches,
            schedule=args.schedule,
            num_chunks=args.chunks if args.schedule == "interleaved" else 1,
        )

    trainer = Trainer(model=model, optimizer_config=opt_cfg,
                      callbacks=callbacks, loss_fn=moe_loss,
                      pipeline=pipeline)
    data = make_data_iter(args, cfg, batch_size, seq_len,
                          include_step=pipeline is None)
    logger.info(
        "training mixtral-%s: %d layers, %d experts top-%d, strategy=%s "
        "capacity=%s shuffle=%s tp=%d ep=%d dp=%d sp=%s batch=%d seq=%d",
        args.model, cfg.num_layers, cfg.num_experts, cfg.top_k,
        cfg.expert_strategy, cfg.capacity_factor, cfg.token_shuffle,
        args.tp, args.ep, dp, args.sp, batch_size, seq_len,
    )
    t0 = time.perf_counter()
    metrics = trainer.fit(
        data,
        jax.random.PRNGKey(args.seed),
        args.steps,
        resume_from=args.ckpt_dir if args.resume else None,
    )
    wall = time.perf_counter() - t0
    if "loss" not in metrics:
        print(f"nothing to do: resumed at step {trainer.step} >= --steps "
              f"{args.steps}")
        return metrics
    steps_run = trainer.steps_run
    tokens_per_step = batch_size * seq_len
    print(
        f"done: {steps_run} steps in {wall:.1f}s — "
        f"final loss {float(metrics['loss']):.4f}, "
        f"avg throughput {steps_run * tokens_per_step / wall:.0f} tokens/s"
    )
    return metrics


if __name__ == "__main__":
    sys.exit(0 if main() is not None else 1)
