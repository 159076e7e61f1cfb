#!/usr/bin/env python
"""Llama pretraining example — the runnable E2E harness (reference:
``examples/training/llama/tp_zero1_llama_hf_pretrain/run_llama_nxd.py`` and
the tp_pp variant: args → parallel init → dataloader → train loop →
throughput/TensorBoard logging → checkpointing).

Covers the BASELINE.md milestone configs:

  config 2 (7B TP8):         --model 7b  --tp 8
  config 3 (7B TP8+SP+Z1):   --model 7b  --tp 8 --sp            (zero1 default)
  config 4 (70B TP8 PP4):    --model 70b --tp 8 --pp 4 --schedule 1f1b

On a development host without TPUs, run the same configs on a virtual CPU
mesh (the test trick from SURVEY §4):

  python examples/train_llama.py --model tiny --tp 2 --sp --steps 4 \
      --force-cpu-devices 8
  python examples/train_llama.py --model tiny --tp 2 --pp 2 --microbatches 4 \
      --schedule 1f1b --steps 4 --force-cpu-devices 8

Data: ``--data synthetic`` (default, seeded random tokens), or
``--data npy:<path>`` — a memory-mapped ``.npy``/``.npz`` of token ids shaped
``(num_tokens,)`` or ``(num_seqs, seq_len)`` (produce one with any HF
tokenizer offline; this container has no network egress).
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time

# allow running straight from a source checkout: examples/ sits next to the package
_repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _repo_root not in sys.path:
    sys.path.insert(0, _repo_root)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    m = p.add_argument_group("model")
    m.add_argument("--model", default="tiny",
                   choices=["tiny", "7b", "70b", "llama3-8b"],
                   help="model preset (tiny = 4-layer test config)")
    m.add_argument("--layers", type=int, default=None,
                   help="override layer count (e.g. 4-layer 70B shape, the "
                        "reference integration trick)")
    m.add_argument("--seq-len", type=int, default=None, help="sequence length")
    m.add_argument("--attention", default="auto",
                   choices=["auto", "flash", "xla"], help="attention kernel")

    par = p.add_argument_group("parallelism")
    par.add_argument("--tp", type=int, default=1, help="tensor parallel size")
    par.add_argument("--pp", type=int, default=1, help="pipeline parallel size")
    par.add_argument("--cp", type=int, default=1, help="context parallel size")
    par.add_argument("--sp", action="store_true", help="Megatron sequence parallel")
    par.add_argument("--schedule", default="1f1b",
                     choices=["gpipe", "1f1b", "interleaved"],
                     help="pipeline schedule (pp > 1)")
    par.add_argument("--chunks", type=int, default=2,
                     help="virtual chunks per rank (interleaved schedule)")
    par.add_argument("--microbatches", type=int, default=4,
                     help="pipeline microbatches (pp > 1)")

    t = p.add_argument_group("training")
    t.add_argument("--batch-size", type=int, default=None,
                   help="global batch size (default: dp, or microbatches·dp under pp)")
    t.add_argument("--steps", type=int, default=10)
    t.add_argument("--lr", type=float, default=3e-4)
    t.add_argument("--warmup-steps", type=int, default=0)
    t.add_argument("--lr-schedule", default="constant", choices=["constant", "cosine"])
    t.add_argument("--grad-accum", type=int, default=1,
                   help="gradient accumulation microbatches (pp=1 path)")
    t.add_argument("--no-zero1", action="store_true", help="disable ZeRO-1")
    t.add_argument("--max-grad-norm", type=float, default=1.0)
    t.add_argument("--seed", type=int, default=0)

    d = p.add_argument_group("data")
    d.add_argument("--data", default="synthetic",
                   help="'synthetic', 'npy:<path>' (raw token stream, chopped "
                        "in file order), or 'packed:<path>' (.npy/.npz packed "
                        "corpus with per-epoch deterministic shuffle — see "
                        "neuronx_distributed_tpu/trainer/data.py for the "
                        "offline tokenization recipe)")
    d.add_argument("--eos-token-id", type=int, default=None,
                   help="document separator inserted while packing "
                        "('packed:' .npz corpora with offsets)")

    io = p.add_argument_group("io")
    io.add_argument("--ckpt-dir", default=None, help="checkpoint directory (local or gs://)")
    io.add_argument("--ckpt-every", type=int, default=100)
    io.add_argument("--ckpt-keep", type=int, default=3)
    io.add_argument("--resume", action="store_true",
                    help="resume from the newest checkpoint in --ckpt-dir")
    io.add_argument("--tensorboard-dir", default=None)
    io.add_argument("--log-every", type=int, default=1)
    io.add_argument("--timeline", default=None,
                    help="write a chrome-trace timeline JSON here")
    io.add_argument("--trace", default=None,
                    help="like --timeline, spelled as the observability "
                         "knob (open in ui.perfetto.dev)")
    io.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of fit() into DIR "
                         "(observability.profile_window; open with "
                         "TensorBoard/XProf): device ops and the "
                         "nxd.train.* spans on one clock")
    io.add_argument("--programs", action="store_true",
                    help="print the compiled-program ledger (dispatches, "
                         "compiler-reported FLOPs, per-step roofline) and "
                         "the HBM ledger after training")

    f = p.add_argument_group("fault injection (chaos demo)")
    f.add_argument("--inject-fault", default=None,
                   choices=["nan", "spike", "dispatch", "ckpt", "sigterm",
                            "bitflip"],
                   help="drive one deterministic fault through the trainer's "
                        "recovery machinery: 'nan' (NaN loss skipped on "
                        "device), 'spike' (grad-norm spike skipped), "
                        "'dispatch' (train-step dispatch failure, retried), "
                        "'ckpt' (checkpoint corrupted after save — resume "
                        "falls back), 'sigterm' (real SIGTERM: finish step, "
                        "checkpoint, exit cleanly), 'bitflip' (one silent "
                        "weight-bit flip — the SDC sentinel detects it, "
                        "rolls back to the last verified step, re-trains)")
    f.add_argument("--fault-at", type=int, default=2,
                   help="0-based step (or dispatch attempt) the fault fires at")
    f.add_argument("--anomaly-budget", type=int, default=25,
                   help="max anomalous (skipped) steps before the run halts "
                        "with an emergency checkpoint")

    e = p.add_argument_group("environment")
    e.add_argument("--force-cpu-devices", type=int, default=None,
                   help="run on N virtual CPU devices (development mode)")
    e.add_argument("--dcn-dp", type=int, default=1,
                   help="multi-slice: number of TPU slices; the data-parallel "
                        "dimension splits into dcn x ici so only DP gradient "
                        "reduction crosses DCN (see examples/README.md runbook)")
    e.add_argument("--distributed", action="store_true",
                   help="call jax.distributed.initialize() first (multi-host: "
                        "run one process per host under the TPU runtime; "
                        "coordinator/process env comes from the TPU metadata)")
    return p.parse_args(argv)


def build_config(args):
    import jax.numpy as jnp

    from neuronx_distributed_tpu.models import llama as llama_lib

    preset = {
        "tiny": llama_lib.tiny_llama,
        "7b": llama_lib.llama2_7b,
        "70b": llama_lib.llama2_70b,
        "llama3-8b": llama_lib.llama3_8b,
    }[args.model]
    over = {}
    if args.layers is not None:
        over["num_layers"] = args.layers
    if args.seq_len is not None:
        over["max_seq_len"] = args.seq_len
    over["sequence_parallel"] = args.sp
    if args.pp > 1:
        over["scan_layers"] = True  # pipeline layout needs stacked layer params
    cfg = preset(**over)
    if args.model == "tiny" and args.attention == "auto":
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    return cfg


def make_data_iter(args, cfg, batch_size: int, seq_len: int):
    """Host batches {input_ids, labels} forever (reference: the HF
    dataloader in run_llama_nxd.py; synthetic keeps the harness hermetic).
    Returns the SOURCE iterable — synthetic and packed sources carry the
    ``state()/restore()`` cursor, so ``--resume`` reproduces an interrupted
    run bit-identically (Trainer checkpoints the cursor)."""
    import numpy as np

    if args.data == "synthetic":
        from neuronx_distributed_tpu.trainer.data import SyntheticTokens

        # the always-present loss_mask also lets --inject-fault corrupt
        # batches without a retrace
        return SyntheticTokens(
            cfg.vocab_size, batch_size, seq_len, seed=args.seed
        )
    if args.data.startswith("packed:"):
        from neuronx_distributed_tpu.trainer.data import PackedCorpus

        corpus = PackedCorpus(
            args.data[len("packed:") :], seq_len=seq_len,
            batch_size=batch_size, seed=args.seed,
            eos_token_id=args.eos_token_id,
        )
        print(f"packed corpus: {len(corpus.windows)} windows, "
              f"{corpus.num_batches_per_epoch} batches/epoch")
        return corpus
    if args.data.startswith("npy:"):
        path = args.data[4:]
        tokens = np.load(path, mmap_mode="r")
        if hasattr(tokens, "files"):  # .npz archive: use its first array
            tokens = tokens[tokens.files[0]]
        if tokens.ndim == 2:
            tokens = tokens.reshape(-1)  # view on the memmap, stays lazy
        n = (len(tokens) - 1) // (batch_size * seq_len)
        if n == 0:
            raise ValueError(f"{path}: too few tokens for one batch")

        def stream():
            while True:
                for i in range(n):
                    lo = i * batch_size * seq_len
                    chunk = np.asarray(
                        tokens[lo : lo + batch_size * seq_len + 1],
                        dtype=np.int32,
                    )
                    ids = chunk[:-1].reshape(batch_size, seq_len)
                    lbl = chunk[1:].reshape(batch_size, seq_len)
                    yield {"input_ids": ids, "labels": lbl}

        return stream()
    raise ValueError(f"unknown --data {args.data!r}")


def main(argv=None):
    args = parse_args(argv)
    if args.resume and not args.ckpt_dir:
        raise SystemExit("--resume requires --ckpt-dir (nothing to resume from)")
    if args.force_cpu_devices:
        from neuronx_distributed_tpu.utils.platform import force_cpu_devices

        force_cpu_devices(args.force_cpu_devices)

    import jax

    if args.distributed:
        # multi-host: makes jax.devices() span every host of every slice
        # (reference analogue: torchrun + init_process_group("xla") across
        # nodes, examples/training/llama/tp_pp_llama_hf_pretrain)
        jax.distributed.initialize()

    # compile cache: where JAX_COMPILATION_CACHE_DIR says, else the fixed
    # <checkout>/.jax_cache (a path that moves never hits)
    from neuronx_distributed_tpu.inference import aot

    aot.enable_persistent_cache(
        os.path.join(_repo_root, ".jax_cache"), min_compile_time_secs=0.5
    )

    from neuronx_distributed_tpu.models.llama import LlamaForCausalLM
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib
    from neuronx_distributed_tpu.trainer import OptimizerConfig
    from neuronx_distributed_tpu.trainer.loop import (
        CheckpointCallback,
        MetricsLogger,
        Trainer,
    )
    from neuronx_distributed_tpu.utils.logger import get_logger
    from neuronx_distributed_tpu.utils.timeline import Timeline

    logger = get_logger("examples.train_llama")

    if mesh_lib.model_parallel_is_initialized():
        mesh_lib.destroy_model_parallel()
    mesh_lib.initialize_model_parallel(
        tensor_model_parallel_size=args.tp,
        pipeline_model_parallel_size=args.pp,
        context_parallel_size=args.cp,
        dcn_data_parallel_size=args.dcn_dp,
    )
    dp = mesh_lib.get_data_parallel_size()
    cfg = build_config(args)
    seq_len = min(cfg.max_seq_len, args.seq_len or cfg.max_seq_len)

    if args.batch_size is None:
        batch_size = dp * (args.microbatches if args.pp > 1 else 1)
    else:
        batch_size = args.batch_size

    opt_cfg = OptimizerConfig(
        learning_rate=args.lr,
        warmup_steps=args.warmup_steps,
        lr_schedule=args.lr_schedule,
        total_steps=args.steps,
        zero1=not args.no_zero1,
        max_grad_norm=args.max_grad_norm,
        grad_accum_steps=args.grad_accum if args.pp == 1 else 1,
    )
    model = LlamaForCausalLM(cfg, attention_impl=args.attention)
    pipeline = None
    if args.pp > 1:
        from neuronx_distributed_tpu.pipeline.llama import LlamaPipelineAdapter

        pipeline = LlamaPipelineAdapter(
            config=cfg,
            num_microbatches=args.microbatches,
            attention_impl=args.attention,
            schedule=args.schedule,
            num_chunks=args.chunks if args.schedule == "interleaved" else 1,
        )

    from neuronx_distributed_tpu.observability import MetricsCallback

    # the unified metrics registry: the per-step dict lands in log-bucketed
    # histograms/gauges (step-time percentiles printed at the end;
    # registry.prometheus_text() is the scrape payload)
    metrics_cb = MetricsCallback()
    callbacks = [MetricsLogger(log_every=args.log_every,
                               tensorboard_dir=args.tensorboard_dir),
                 metrics_cb]
    if args.ckpt_dir:
        callbacks.append(
            CheckpointCallback(args.ckpt_dir, every=args.ckpt_every,
                               num_kept=args.ckpt_keep,
                               # a ckpt-corruption demo must leave the
                               # corrupt tag in place — save_on_end would
                               # notice the missing done marker and heal it
                               save_on_end=args.inject_fault != "ckpt")
        )

    injector = None
    if args.inject_fault:
        from neuronx_distributed_tpu.trainer.faults import FaultInjector

        injector = FaultInjector()
        at = args.fault_at
        if args.inject_fault == "nan":
            injector.nan_loss(at=at)
        elif args.inject_fault == "spike":
            injector.spike_grads(at=at)
        elif args.inject_fault == "dispatch":
            injector.fail_dispatch(at=at, times=1)
        elif args.inject_fault == "ckpt":
            if not args.ckpt_dir:
                raise SystemExit("--inject-fault ckpt requires --ckpt-dir")
            # corrupt the LAST periodic save — the tag `newest` will point
            # at — so the following --resume exercises the fallback to the
            # newest COMPLETED tag (a mid-run tag would just be skipped)
            last_tag = (args.steps // args.ckpt_every) * args.ckpt_every
            if last_tag <= 0:
                raise SystemExit(
                    "--inject-fault ckpt needs at least one periodic save "
                    "(--steps >= --ckpt-every)"
                )
            injector.corrupt_checkpoint(f"step_{last_tag}")
        elif args.inject_fault == "sigterm":
            injector.deliver_sigterm(at=at)
        elif args.inject_fault == "bitflip":
            # under dp the vote localizes ONE corrupt device copy; solo
            # runs flip every copy and the canary's re-execution catches
            # the divergence at the (every-step) check
            injector.flip_bits("params", at=at,
                               device=1 if dp >= 2 else None)

    from neuronx_distributed_tpu.trainer import AnomalyGuardConfig

    integrity = None
    if args.inject_fault == "bitflip":
        from neuronx_distributed_tpu.integrity import SentinelConfig

        # SDC sentinel demo: every step is a check (detection latency 1
        # step in either mode) so the short chaos run detects, rolls
        # back to the last verified step, and re-trains
        integrity = SentinelConfig(check_every=1)

    trace_path = args.trace or args.timeline
    trainer = Trainer(
        model=model,
        optimizer_config=opt_cfg,
        callbacks=callbacks,
        pipeline=pipeline,
        timeline=Timeline(trace_path) if trace_path else None,
        fault_injector=injector,
        # chaos-demo warmup: under --inject-fault the spike detector arms
        # after 2 good steps so a spike at the default --fault-at 2 is
        # actually caught in a short run; clean runs keep the production
        # warmup (a 2-step EMA is hair-trigger on real early-training
        # grad-norm volatility and would silently skip legitimate steps)
        anomaly_guard=AnomalyGuardConfig(
            budget=args.anomaly_budget,
            warmup_steps=(
                2 if args.inject_fault
                else AnomalyGuardConfig.warmup_steps
            ),
        ),
        emergency_dir=args.ckpt_dir,
        integrity=integrity,
    )
    data = make_data_iter(args, cfg, batch_size, seq_len)

    logger.info(
        "training %s: %d layers, tp=%d pp=%d cp=%d dp=%d sp=%s zero1=%s "
        "batch=%d seq=%d steps=%d",
        args.model, cfg.num_layers, args.tp, args.pp, args.cp, dp, args.sp,
        not args.no_zero1, batch_size, seq_len, args.steps,
    )
    t0 = time.perf_counter()
    from neuronx_distributed_tpu.observability import profile_window
    from neuronx_distributed_tpu.trainer.loop import TrainerHalted

    try:
        with profile_window(args.profile):
            metrics = trainer.fit(
                data,
                jax.random.PRNGKey(args.seed),
                args.steps,
                resume_from=args.ckpt_dir if args.resume else None,
            )
    except TrainerHalted as e:
        print(
            f"HALTED at step {trainer.step}: {e.reason} "
            f"(emergency checkpoint: {e.emergency_tag or 'none'})"
        )
        return None
    wall = time.perf_counter() - t0
    if injector is not None or trainer.preempted:
        print(
            f"fault summary: health={trainer.health().value} "
            f"anomaly_skips={trainer.anomaly_skips} "
            f"dispatch_retries={trainer.dispatch_retries} "
            f"preempted={trainer.preempted} "
            f"injected={getattr(injector, 'counters', {})}"
        )
        sentinel = getattr(trainer, "_sentinel", None)
        if sentinel is not None:
            print(
                f"sdc summary: mode={sentinel.mode} "
                f"checks={sentinel.counters['integrity_checks']} "
                f"detected={sentinel.counters['sdc_detected']} "
                f"rollbacks={sentinel.counters['sdc_rollbacks']} "
                f"quarantined={sentinel.quarantined_devices}"
            )
    if trainer.preempted:
        print(
            f"preempted cleanly at step {trainer.step} — resume with "
            f"--resume --ckpt-dir {args.ckpt_dir or '<dir>'}"
        )
        return metrics
    if "loss" not in metrics:
        # resumed at/after --steps: nothing left to train
        print(f"nothing to do: resumed at step {trainer.step} >= --steps {args.steps}")
        return metrics
    # steps actually executed this run (resume starts past step 0)
    steps_run = trainer.steps_run
    tokens_per_step = batch_size * seq_len
    print(
        f"done: {steps_run} steps in {wall:.1f}s — "
        f"final loss {float(metrics['loss']):.4f}, "
        f"avg throughput {steps_run * tokens_per_step / wall:.0f} tokens/s "
        f"({metrics.get('throughput_seq_s', 0.0):.2f} seqs/s moving avg)"
    )
    st = metrics_cb.registry.get("train_step_time_s")
    if st is not None and st.count:
        print(
            f"step time p50 {st.percentile(0.5) * 1e3:.1f}ms / "
            f"p95 {st.percentile(0.95) * 1e3:.1f}ms over {st.count} steps "
            "(log-bucketed registry histogram)"
        )
    if args.programs:
        print("\n=== program ledger (compiler-reported cost) ===")
        print(trainer.programs.table())
        print("\n=== hbm ledger ===")
        for key, value in trainer.hbm.halt_summary().items():
            print(f"  {key:>28s}: {value:,d}" if isinstance(value, int)
                  else f"  {key:>28s}: {value}")
        entry = trainer.programs.snapshot()["by_program"].get("train_step", {})
        flops = entry.get("flops_per_dispatch")
        wall = entry.get("wall", {}).get("p50_s")
        if isinstance(flops, float) and wall:
            print(
                f"\ncompiler-reported step: {flops:.3e} FLOPs, "
                f"achieved {flops / wall:.3e} FLOP/s at p50 step wall "
                f"{wall * 1e3:.1f}ms "
                f"(mfu {entry.get('mfu_p50')})"
            )
    return metrics


if __name__ == "__main__":
    sys.exit(0 if main() is not None else 1)
