#!/usr/bin/env python
"""Does the system still start on the chip? One process, one TPU v5e.

Drives the main path once through the entry points a user calls, at
Llama-2-7B's published widths (hidden 4096, ffn 11008, 32 heads x 128, vocab
32000; bf16 compute). No width is cut; depth is cut as far as 16 GB forces
and is printed. Weights and requests come from ``--seed``.

What runs is one table, ``PHASES`` at the end of this file: a phase a row,
each phase's function saying in its docstring what it runs and what it is
compared with. The default run is the table's ``default`` rows of one chip in
order (``train``, ``serve``, ``mla``, ``dsa``, ``glm``); ``--only NAME`` runs
one row alone, among them the rows no default run includes (they serve
nothing, or repeat what a benchmark cell holds): ``moe``, ``trinity``,
``walk``, ``runahead``, ``flash``; ``--chips 4`` runs only the four-chip path and what it is
compared with, the table's ``default`` rows of four chips (``tp_train``,
``tp_serve``, ``remat``).

Every phase also asserts which implementation ``"auto"`` resolved to (the
program ledger's ``resolved`` record) and that the Pallas kernels are in the
compiled programs (``tpu_custom_call`` in ``lower().compile().as_text()``).

Exit code 0 and a last line ``{"ok": true, "device": {...}}`` only if every
phase passed on a TPU. Without an accelerator it exits non-zero and prints
no result. The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says,
or to ``.jax_cache`` next to this file.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import math
import os
import sys
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

KERNEL = "tpu_custom_call"


@dataclasses.dataclass(frozen=True)
class TrainSize:
    """What the train phase runs. The defaults are the chip run; the CPU
    rehearsal (tests/test_chip_smoke.py) passes a tiny one."""

    config: object = None  # LlamaConfig; None = llama2_7b at ``layers``
    layers: int = 2
    batch: int = 4
    seq: int = 2048
    steps: int = 3
    # bf16 compute, flash vs einsum accumulation order: |dloss| and the
    # relative grad-norm difference stay well inside these
    loss_tol: float = 2e-2
    gnorm_rtol: float = 2e-2
    # a randomly initialised model predicts ~uniformly: loss ~ ln(vocab),
    # plus ~var/2 for logits of about unit variance
    loss_ln_vocab_tol: float = 1.0


@dataclasses.dataclass(frozen=True)
class ServeSize:
    """What the serve phase runs (defaults: the chip run)."""

    config: object = None  # LlamaConfig; None = llama2_7b at ``layers``
    layers: int = 10
    max_seq_len: int = 2560
    slots: int = 8
    prompt_lens: Tuple[int, ...] = (150, 300, 520, 700, 1000, 1300, 1700, 2040)
    new_tokens: int = 32
    row_slots: int = 2
    row_prompt_lens: Tuple[int, ...] = (140, 260)
    row_new_tokens: int = 16
    # reference-logit gap allowed between an emitted token and the
    # reference argmax at its position: bf16 serving against an fp32
    # reference. Logits of a random-init model have a standard deviation
    # near 1 and a median top-1/top-2 margin of 0.03-0.3; the largest gap
    # seen on the chip is 0.009 (tp=4, 8 layers), a wrong token costs > 4
    logit_tol: float = 0.1


@dataclasses.dataclass(frozen=True)
class MoeSize:
    """What ``--only moe`` runs (defaults: the chip run): ``(name, experts,
    hidden, intermediate, top_k, rows of the cell's decode step)`` from the
    three published configurations."""

    shapes: Tuple[Tuple[str, int, int, int, int, int], ...] = (
        ("deepseek-v2-lite", 64, 2048, 1408, 6, 8),
        ("keye-vl2", 128, 2048, 768, 8, 8),
        ("mixtral-8x7b", 8, 4096, 14336, 2, 16),
    )
    tokens: Tuple[int, ...] = (1, 8, 16, 32, 64, 128, 256)
    # a prefill's expert layer: ``(name, experts, hidden, intermediate, top_k,
    # experts held (None: all), bucket, its cell's emptiest and fullest prompt
    # in that bucket)``; a full bucket is run besides
    prefill: Tuple[Tuple[str, int, int, int, int, Optional[int], int, Tuple[int, int]], ...] = (
        ("deepseek-v2-lite", 64, 2048, 1408, 6, None, 16384, (9003, 13950)),
        ("keye-vl2", 128, 2048, 768, 8, None, 16384, (9624, 15690)),
        ("mixtral-8x7b", 8, 4096, 14336, 2, None, 1024, (550, 826)),
        ("glm-5-held", 256, 6144, 2048, 8, 8, 16384, (8862, 12765)),
    )
    prefill_calls: int = 4
    sampled_rows: int = 256
    calls: int = 20
    dtype: str = "bfloat16"
    # the worst row's |form - float32 jnp| / |float32 jnp| (L2 over the hidden
    # vector) at the cell's rows, between the system's reading and the
    # controls' (PERF.md section 6, PR 33, has the readings)
    routed_tol: float = 0.015


@dataclasses.dataclass(frozen=True)
class WalkShape:
    """One cell's decode attention as the walking kernel meets it: the heads,
    the slots' contexts ending at a shared cursor, and the window of its
    window layers (``None``: it has none, and only a full layer is run)."""

    name: str
    q_heads: int
    kv_heads: int
    head_dim: int
    max_seq_len: int
    contexts: Tuple[int, ...]
    cursor: int
    window: Optional[int] = None
    window_pages: int = 0         # a window layer's pool, pages a slot


@dataclasses.dataclass(frozen=True)
class WalkSize:
    """What ``--only walk`` runs (defaults: the chip run). The Trinity cell's
    decode attention (``perfbench/configs/trinity-large-serve.json``'s heads,
    ``perfbench/traffic/mixedctx_closed.json``'s eight prompt lengths; a page
    of its ``(16, 128)`` leaf is 64 KB) and the ZAYA1 cell's
    (``zaya1-8b-serve.json``: 8 query heads against 2 kv heads, 32 slots of
    16,384 columns holding ``reasoning_closed.json``'s 2-6k tokens; a page of
    its ``(4, 128)`` leaf is 16 KB)."""

    shapes: Tuple[WalkShape, ...] = (
        WalkShape("trinity", 48, 8, 128, 32768,
                  tuple(n + 256 for n in (2799, 4402, 5818, 7338, 9146, 11534, 15244, 16384)), 21000,
                  window=4096, window_pages=272),
        WalkShape("zaya1", 8, 2, 128, 16384, tuple(2000 + 125 * i for i in range(32)), 12000),
    )
    page: int = 16
    calls: int = 20
    dtype: str = "bfloat16"
    # largest |kernel - float32 einsum| over the output: bf16 probabilities
    # and output against float32 (the chip reads 0.0003-0.0004 on unit-variance
    # values, PERF.md section 6, PR 44; a head taken from another's rows ~1)
    tol: float = 0.02


@dataclasses.dataclass(frozen=True)
class FlashSize:
    """What ``--only flash`` runs (defaults: the chip run): ``(name, q heads,
    kv heads, d_qk, d_v, batch, bucket, prompt, residuals)``, the flash
    forward's callers in the benchmark's cells: a prompt left-padded to its
    bucket; ``residuals``: the call keeps ``lse`` for a backward (training)."""

    shapes: Tuple[Tuple[str, int, int, int, int, int, int, int, bool], ...] = (
        ("dsv2lite 9,003 of 16,384", 16, 16, 192, 128, 1, 16384, 9003, False),
        ("dsv2lite 20,566 of 20,992", 16, 16, 192, 128, 1, 20992, 20566, False),
        ("trinity full layer 11,534 of 16,384", 48, 8, 128, 128, 1, 16384, 11534, False),
        ("codegen2 1,000 of 1,024", 16, 16, 256, 256, 1, 1024, 1000, False),
        ("codegen2 1,100 of 2,048", 16, 16, 256, 256, 1, 2048, 1100, False),
        ("codegen2 train 8 x 2,048", 4, 4, 256, 256, 8, 2048, 2048, True),
    )
    rows: int = 256               # content rows compared with the float32 reference, a shape
    calls: int = 10
    dtype: str = "bfloat16"
    # largest |kernel - float32 reference| over the compared rows: a bf16
    # output of unit-variance values against float32
    tol: float = 0.02
    # the shape whose bodies --bundles counts
    bundles_shape: int = 0


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def _llama(size, **over):
    from neuronx_distributed_tpu.models.llama import llama2_7b

    if size.config is not None:
        return dataclasses.replace(size.config, **over)
    return llama2_7b(num_layers=size.layers, **over)


def _published(size, config: str) -> dict:
    """``size.model`` (the CPU rehearsal's tiny configuration), or the
    ``model`` group of the benchmark configuration ``perfbench/configs/<config>``."""
    if size.model is not None:
        return size.model
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "perfbench", "configs", config)
    with open(path) as f:
        return json.load(f)["model"]


def _ledger_kernels(ledger, names: Sequence[str],
                    compiled: bool = True) -> Dict[str, bool]:
    """``{program: a Pallas kernel is in it}`` for the ledger's programs
    ``names`` (every compiled signature of each), re-lowered from the
    signatures the ledger captured. ``compiled=False`` reads the lowered
    text instead of compiling again."""
    out = {}
    for name, info in ledger.programs().items():
        if name not in names:
            continue
        for i, variant in enumerate(info.variants):
            lowered = variant.lower()
            text = lowered.compile().as_text() if compiled else lowered.as_text()
            out[f"{name}#{i}" if len(info.variants) > 1 else name] = KERNEL in text
    return out


def _hot_programs(engine) -> List[str]:
    """The decode chunk and every prefill bucket the engine compiled."""
    return ["decode_chunk"] + [
        n for n in engine.programs.programs() if n.startswith("prefill[")
    ]


def _bytes_on(tree, device) -> int:
    import jax

    return sum(
        shard.data.nbytes
        for leaf in jax.tree.leaves(tree)
        for shard in leaf.addressable_shards
        if shard.device == device
    )


# --- train ---------------------------------------------------------------------


def _fit_steps(model, vocab_size: int, batch: int, seq: int, seed: int,
               steps: int):
    """``steps`` Trainer steps of ``model`` on the mesh that is set up.
    Returns the trainer and per-step (loss, grad_norm, wall_s)."""
    import jax

    from neuronx_distributed_tpu.trainer import OptimizerConfig
    from neuronx_distributed_tpu.trainer.data import SyntheticTokens
    from neuronx_distributed_tpu.trainer.loop import Callback, Trainer

    rows: List[Tuple[float, float, float]] = []

    class Capture(Callback):
        def on_train_start(self, trainer):
            self.t = time.perf_counter()

        def on_step_end(self, trainer, metrics):
            # float() waits for the step: the wall below is a finished step
            loss, gnorm = float(metrics["loss"]), float(metrics["grad_norm"])
            now = time.perf_counter()
            rows.append((loss, gnorm, now - self.t))
            self.t = now

    trainer = Trainer(
        model=model, optimizer_config=OptimizerConfig(), callbacks=[Capture()]
    )
    data = SyntheticTokens(vocab_size, batch, seq, seed=seed)
    trainer.fit(data, jax.random.PRNGKey(seed), steps)
    return trainer, rows


def _train_steps(size: TrainSize, seed: int, devices, *, tp: int, sp: bool,
                 steps: int):
    """``steps`` Trainer steps on a (tp, dp=1) mesh over ``devices``.
    Returns the trainer, the config and per-step (loss, grad_norm, wall_s)."""
    from neuronx_distributed_tpu.models.llama import LlamaForCausalLM
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib

    mesh_lib.destroy_model_parallel()
    mesh_lib.initialize_model_parallel(
        tensor_model_parallel_size=tp, devices=list(devices)
    )
    cfg = _llama(size, max_seq_len=size.seq, sequence_parallel=sp)
    model = LlamaForCausalLM(cfg, attention_impl="auto")
    trainer, rows = _fit_steps(
        model, cfg.vocab_size, size.batch, size.seq, seed, steps
    )
    return trainer, cfg, rows


def _xla_reference_step(size: TrainSize, seed: int, devices):
    """Step-1 loss and grad-norm of the same seeded model and batch with
    ``attention_impl="xla"`` — no optimizer, so it fits beside nothing."""
    import jax
    from functools import partial

    from neuronx_distributed_tpu.models.llama import LlamaForCausalLM
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib
    from neuronx_distributed_tpu.parallel.grads import clip_grad_norm
    from neuronx_distributed_tpu.trainer.data import SyntheticTokens
    from neuronx_distributed_tpu.trainer.trainer import (
        default_loss_fn,
        initialize_parallel_model,
        shard_batch,
    )

    mesh_lib.destroy_model_parallel()
    mesh_lib.initialize_model_parallel(devices=list(devices))
    cfg = _llama(size, max_seq_len=size.seq)
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    batch = next(iter(
        SyntheticTokens(cfg.vocab_size, size.batch, size.seq, seed=seed)
    ))
    params, _ = initialize_parallel_model(
        model, jax.random.PRNGKey(seed), batch["input_ids"]
    )

    @jax.jit
    def step(params, batch):
        loss, grads = jax.value_and_grad(partial(default_loss_fn, model))(
            params, batch
        )
        return loss, clip_grad_norm(grads, 1.0)[1]

    loss, gnorm = step(params, shard_batch(batch))
    return float(loss), float(gnorm)


def _close(a: float, b: float, *, atol: float = 0.0, rtol: float = 0.0) -> bool:
    return math.isfinite(a) and math.isfinite(b) and (
        abs(a - b) <= atol + rtol * abs(b)
    )


def train_phase(size: TrainSize, seed: int, devices) -> Dict[str, bool]:
    """``Trainer`` takes 3 steps on one device, attention ``auto``, step 1
    against the ``xla`` reference. The ``examples/train_llama.py --model 7b
    --layers 2 --seq-len 2048 --batch-size 4`` path: step 1's loss and
    grad-norm are compared with the same seeded model under
    ``attention_impl="xla"``. Returns the named checks."""
    trainer, cfg, rows = _train_steps(
        size, seed, devices[:1], tp=1, sp=False, steps=size.steps
    )
    entry = trainer.programs.snapshot()["by_program"]["train_step"]
    resolved = trainer.programs.resolved.get("attention")
    log(
        f"train: widths hidden={cfg.hidden_size} ffn={cfg.intermediate_size} "
        f"heads={cfg.num_heads}x{cfg.head_dim_} vocab={cfg.vocab_size} "
        f"depth={cfg.num_layers} layers, batch={size.batch} seq={size.seq}, "
        f"attention auto -> {resolved}"
    )
    log(
        f"train: compile_s={entry['compile_wall_s']:.1f} "
        f"compiles={entry['compiles']} step_wall_s="
        f"{[round(r[2], 3) for r in rows]} (step 1 includes the compile) "
        f"loss={[round(r[0], 4) for r in rows]} "
        f"grad_norm={[round(r[1], 4) for r in rows]}"
    )
    kernels = _ledger_kernels(trainer.programs, ["train_step"])
    log(f"train: {KERNEL} in compiled program: {kernels}")
    trainer.state = None  # free params + Adam before the reference
    ref_loss, ref_gnorm = _xla_reference_step(size, seed, devices[:1])
    loss, gnorm = rows[0][0], rows[0][1]
    log(
        f"train: step 1 vs xla reference: loss {loss:.5f} vs {ref_loss:.5f} "
        f"(|d|={abs(loss - ref_loss):.2e}, tol {size.loss_tol:g}); grad_norm "
        f"{gnorm:.5f} vs {ref_gnorm:.5f} (rel "
        f"{abs(gnorm - ref_gnorm) / max(abs(ref_gnorm), 1e-30):.2e}, tol "
        f"{size.gnorm_rtol:g}); ln(vocab)={math.log(cfg.vocab_size):.4f}"
    )
    return {
        "train_steps_taken": len(rows) == size.steps,
        "train_finite": all(math.isfinite(v) for r in rows for v in r[:2]),
        "train_one_compile": entry["compiles"] == 1,
        "train_loss_near_ln_vocab": _close(
            loss, math.log(cfg.vocab_size), atol=size.loss_ln_vocab_tol
        ),
        "train_loss_matches_xla": _close(loss, ref_loss, atol=size.loss_tol),
        "train_gnorm_matches_xla": _close(gnorm, ref_gnorm, rtol=size.gnorm_rtol),
        "train_resolved_flash": resolved == "flash",
        "kernel_train_step": all(kernels.values()) and bool(kernels),
    }


def tp_train_phase(size: TrainSize, seed: int, devices) -> Dict[str, bool]:
    """One tp=4 + sequence-parallel train step over all ``devices`` against
    the same seeded step on one of them, in the same process (init is
    tp-degree invariant)."""
    from neuronx_distributed_tpu.observability.hbm import tree_nbytes

    solo, cfg, solo_rows = _train_steps(
        size, seed, devices[:1], tp=1, sp=False, steps=1
    )
    solo_bytes = tree_nbytes(solo.state.params)
    solo.state = None
    tp = len(devices)
    trainer, _, rows = _train_steps(size, seed, devices, tp=tp, sp=True, steps=1)
    params = trainer.state.params
    per_device = [_bytes_on(params, d) for d in devices]
    kernels = _ledger_kernels(trainer.programs, ["train_step"])
    (loss, gnorm, wall), (loss1, gnorm1, wall1) = rows[0], solo_rows[0]
    log(
        f"tp{tp} train: depth={cfg.num_layers} layers batch={size.batch} "
        f"seq={size.seq} sp=on zero1=on; loss {loss:.5f} vs one-device "
        f"{loss1:.5f} (|d|={abs(loss - loss1):.2e}, tol {size.loss_tol:g}); "
        f"grad_norm {gnorm:.5f} vs {gnorm1:.5f} (rel "
        f"{abs(gnorm - gnorm1) / max(abs(gnorm1), 1e-30):.2e}, tol "
        f"{size.gnorm_rtol:g}); first-step wall {wall:.1f}s vs {wall1:.1f}s "
        "(both include the compile)"
    )
    log(
        f"tp{tp} train: param bytes per device {per_device} of {solo_bytes} "
        f"on one device; {KERNEL} in compiled program: {kernels}"
    )
    trainer.state = None
    return {
        "tp_train_loss_matches_one_device": _close(loss, loss1, atol=size.loss_tol),
        "tp_train_gnorm_matches_one_device": _close(
            gnorm, gnorm1, rtol=size.gnorm_rtol
        ),
        # norms and the like replicate; the matmul weights — nearly all of
        # the bytes — must be split, not parked on device 0
        "tp_train_params_split": max(per_device) < 0.3 * solo_bytes
        and min(per_device) > 0.2 * solo_bytes,
        "kernel_tp_train_step": all(kernels.values()) and bool(kernels),
    }


@dataclasses.dataclass(frozen=True)
class RematSize:
    """What the remat phase runs (defaults: the training cell's model and
    step, ``perfbench/configs/codegen2-7b-train-tp4.json`` at 8 x 2048)."""

    model: object = None          # published config.json keys; None = the benchmark configuration's
    batch: int = 8
    seq: int = 2048
    steps: int = 4                # the first holds the compile; the time is the median of the rest
    # in the order of the memory each keeps: a chip's peak never falls
    policies: Tuple = (None, "mlp_up", "mlp_up+attn")
    # How far a step's loss may lie from "save nothing"'s. In float32 (the
    # CPU rehearsal) saving changes no arithmetic and the limit is 0. In bf16
    # a tensor that is SAVED is rounded to bf16 where the compiler, computing
    # it again inside one fusion, keeps the float32 it had: over 7 steps at
    # the cell's shape five policies' losses lay within 2.3e-4 of "save
    # nothing"'s, 6e-6 at step 1 (PERF.md section 6, PR 40). ONE reading: what
    # a wrong gradient would read was not measured, so the limit is ten times
    # it and no more.
    loss_tol: float = 2e-3


def remat_phase(size: RematSize, seed: int, devices) -> Dict[str, bool]:
    """The training cell's step under each remat policy: the same losses,
    each chip's peak bytes and the step's time printed. The trade a remat
    policy makes, so that it can be measured again in one call: the same
    seeded steps of CodeGen (CodeGen2-7B's widths at ten layers, 8 x 2048
    tokens) under tp + SP over all ``devices`` with the blocks rematerialised
    under each of ``size.policies`` ("save nothing" first, then the two named
    saves of ``modules/remat.py``). Saving a tensor in place of computing it
    twice changes no arithmetic, so every step's loss must be the same number
    to ``size.loss_tol`` (bf16's rounding; the same bits in float32); what
    differs is printed: the step's time, each chip's peak bytes
    (``memory_stats()["peak_bytes_in_use"]``: a process's high water, so a
    chip reads a LATER policy's peak only where it is the larger; device 0
    also held the whole float32 model at every init) and what the trainer
    recorded of the policy (``programs.resolved["remat"]``, gauge
    ``train_remat_saved_bytes``)."""
    import statistics

    import jax.numpy as jnp

    from neuronx_distributed_tpu.parallel import mesh as mesh_lib
    from perfbench.families import codegen as family

    published = _published(size, "codegen2-7b-train-tp4.json")
    tp = len(devices)
    mesh_lib.destroy_model_parallel()
    mesh_lib.initialize_model_parallel(tensor_model_parallel_size=tp, devices=list(devices))
    built = family.build(published, runner="train", max_seq_len=size.seq,
                         sequence_parallel=tp > 1, remat=True)
    if size.model is not None:    # the CPU rehearsal trains in float32
        built = built.clone(config=dataclasses.replace(built.config, dtype=jnp.float32))
    losses, names, saved = {}, {}, {}
    for policy in size.policies:
        model = built.clone(config=dataclasses.replace(built.config, remat_policy=policy))
        trainer, rows = _fit_steps(model, model.config.vocab_size, size.batch, size.seq, seed, size.steps)
        losses[policy] = [loss for loss, _, _ in rows]
        names[policy] = trainer.programs.resolved["remat"]
        saved[policy] = int(trainer.programs.registry.get("train_remat_saved_bytes").value)
        step_ms = 1e3 * statistics.median(wall for _, _, wall in rows[1:])
        trainer.state = trainer = None
        gc.collect()
        peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices]
        log(f"remat {policy!r}: tp{tp} + SP, {model.config.num_layers} layers, {size.batch} x {size.seq} tokens; "
            f"saved by name {names[policy]}, {saved[policy]} B a chip; losses {[f'{x:.6f}' for x in losses[policy]]}; "
            f"step {step_ms:.1f} ms (median of {len(rows) - 1}); peak bytes a chip so far {peaks} "
            f"({max(peaks) / 2**30:.2f} GiB the fullest)")
    nothing, *kept = size.policies
    apart = max(abs(a - b) for p in kept for a, b in zip(losses[p], losses[nothing]))
    log(f"remat: largest |loss - save nothing's| over {size.steps} steps and {len(kept)} policies "
        f"{apart:.2e} (tolerance {size.loss_tol:g})")
    per_layer = (size.batch * size.seq * model.config.intermediate_size // tp
                 * jnp.dtype(model.config.dtype).itemsize)
    return {
        "remat_policies_give_the_same_losses": apart <= size.loss_tol,
        "remat_save_nothing_is_recorded_empty": names[nothing] == [] and saved[nothing] == 0,
        "remat_named_saves_are_recorded": all(
            "mlp_up" in names[p] and saved[p] >= model.config.num_layers * per_layer for p in kept),
    }


# --- serve ---------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MlaSize:
    """What the MLA phase runs (defaults: the chip run, the published
    widths at the benchmark configuration's depth)."""

    model: object = None          # published config.json keys; None = the benchmark configuration's
    max_seq_len: int = 32768
    slots: int = 4
    prompt_lens: Tuple[int, ...] = (24576, 8192, 2048)
    tail: int = 256               # positions of the longest prompt compared in prefill logits
    new_tokens: int = 32
    # the kernel alone (``mla_kernel``): the slots' contexts, ending at a shared cursor 8 pages under the row's end
    # (eight prompt lengths of ``perfbench/traffic/docs_closed.json``'s kind + 256)
    kernel_contexts: Tuple[int, ...] = tuple(n + 256 for n in (2880, 4536, 6010, 7600, 9003, 11800, 15400, 20566))
    kernel_calls: int = 20
    # How far the system's prefill logits may lie from the reference's, bf16
    # serving against float32, over the positions compared (the largest
    # |difference| over the vocabulary at each). ``logit_tol`` bounds the
    # worst position, ``typical_tol`` the median one: a lower precision
    # moves EVERY position, so the median is where it shows first. The
    # phase proves on the chip that the pair can fail: the reference with
    # its latent and rotated key rounded to float8 (the nearest precision
    # below the configuration's bfloat16), and with one rope channel
    # dropped, must each lie outside them (``mla_*_is_caught``). Readings
    # in PERF.md section 6. Routing flips matter little here (the sixth and
    # seventh expert weigh ~0.03 each beside two shared experts), so no
    # position is excused for a router near-tie.
    logit_tol: float = 0.5
    typical_tol: float = 0.08
    gap_tol: float = 0.1          # a decoded token's reference-logit gap, as ServeSize.logit_tol
    near_tie: float = 0.05        # ... and its excuse, as the benchmark's own check (judge_gaps)


@dataclasses.dataclass(frozen=True)
class DsaSize:
    """What the sparse-attention phase runs (defaults: the chip run, the
    published widths at the benchmark configuration's depth)."""

    model: object = None          # published config.json keys; None = the benchmark configuration's
    max_seq_len: int = 32768
    slots: int = 4
    prompt_lens: Tuple[int, ...] = (24576, 8192, 2048)
    tail: int = 256               # positions of the longest prompt compared in prefill logits
    new_tokens: int = 32
    # the kernels alone: valid contexts of the slots (the longdocs_closed
    # tape's quantiles + 128 decoded), ending at this shared cursor
    kernel_contexts: Tuple[int, ...] = (5840, 8030, 9730, 11400, 13440, 15800, 19300, 24700)
    kernel_cursor: int = 27000
    kernel_calls: int = 20
    kernel_prefill: int = 12288   # the prompt whose learned mask is built both ways (the tape's median)
    # the prefill's byte-masked forward alone (frozen parent | new): the bucket, and the prompts
    # left-padded to it (a full bucket; one 44% empty)
    forward_bucket: int = 16384
    forward_prompts: Tuple[int, ...] = (16384, 9175)
    kernel_tol: float = 2e-2      # |kernel - float32 jnp| on bf16 inputs: rounding of the output type
    # Limits of the comparison with the reference (readings: PERF.md section
    # 6). As MlaSize: ``logit_tol`` the worst position's largest |difference|
    # over the vocabulary, ``typical_tol`` the median position's; and
    # ``selected_tol``: the share of layer 0's selected columns (rows past
    # ``topk``) that are not the reference's. With 128 experts top-8
    # renormalised and no shared expert a router flip moves a position's
    # logits by up to ~1.2 (the float32 reference against ITSELF with 1% of
    # its selected columns swapped reads 1.23), so the worst position bounds
    # only garbage; the median holds precision (the system 0.048-0.067, 1024
    # kept 0.61) and the selection holds the index keys' (the system 0.18%
    # of columns, float8 index keys 1.12%, 1024 kept 25%).
    logit_tol: float = 2.0
    typical_tol: float = 0.12
    selected_tol: float = 0.005
    # a differing column's index score lies within this of the row's
    # threshold, in units of the row's root-mean-square score
    index_near_tie: float = 0.1
    # the decoded tokens' gap and the router near-tie that excuses one: the
    # benchmark configuration's (its ``reference_check.why`` has the readings)
    gap_tol: float = 0.06
    near_tie: float = 0.035


@dataclasses.dataclass(frozen=True)
class GlmSize:
    """What the GLM-5 phase runs (defaults: the chip run, the published widths
    on the benchmark configuration's cut)."""

    model: object = None          # the configuration's ``model`` group; None = the benchmark configuration's
    max_seq_len: int = 32768
    slots: int = 4
    prompt_lens: Tuple[int, ...] = (16384, 8192, 2048)
    tail: int = 256
    new_tokens: int = 32
    # the kernel alone: tokens each of 8 slots holds (the agentdocs_closed
    # tape's quantiles + 320 decoded); each keeps min(held, topk)
    kernel_contexts: Tuple[int, ...] = (4500, 5600, 6700, 7800, 8900, 10400, 12600, 16700)
    kernel_calls: int = 20
    # the prefill's byte-masked forward alone, as DsaSize's
    forward_bucket: int = 16384
    forward_prompts: Tuple[int, ...] = (16384, 9175)
    kernel_tol: float = 2e-2      # |kernel - float32 jnp| on bf16 inputs: rounding of the output type
    # Limits of the comparison with the reference, each between the system's
    # reading and a control's (PERF.md section 6, PR 32, has the readings):
    # ``typical_tol`` the median position's largest |difference| of prefill
    # logits over the vocabulary, ``logit_tol`` the worst position's,
    # ``selected_tol`` the share of layer 0's selected columns (rows past
    # ``topk``) that are not the reference's
    logit_tol: float = 2.0
    typical_tol: float = 0.12
    selected_tol: float = 0.005
    # ONE block alone, which the layers after it cannot blur: the median
    # position's |system - reference| / |reference| (L2 over the hidden
    # vector) of what layer 0's attention adds to the stream, and of the held
    # experts' routed sum in the first sparse layer on the SYSTEM's own input
    # (positions where a held expert was chosen). A float8 latent and a bias
    # in the weights move the final logits by LESS than bf16 itself does;
    # here each stands alone
    attn_tol: float = 0.05
    routed_tol: float = 0.015
    # and what layer 0's CACHE holds of the prompt (the latent and the
    # rotated key of every token) against the reference's, the same ratio
    latent_tol: float = 0.01
    # the decoded tokens' gap and the router near-tie that excuses one: the
    # benchmark configuration's (its ``reference_check.why`` has the readings)
    gap_tol: float = 0.1
    near_tie: float = 0.006


def _prompts(lens: Sequence[int], vocab: int, seed: int):
    import numpy as np

    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab, size=n).astype(np.int32) for n in lens]


def _serve(engine, prompts, new_tokens: int, seed: int):
    """Submit every prompt (greedy), run to completion, return the requests
    and the wall. Raises if the engine did not answer every request."""
    import jax

    from neuronx_distributed_tpu.inference import GenerationConfig
    from neuronx_distributed_tpu.serving import RequestState

    gcfg = GenerationConfig(max_new_tokens=new_tokens, temperature=0.0)
    t0 = time.perf_counter()
    reqs = [
        engine.submit(p, gcfg, key=jax.random.PRNGKey(seed + i))
        for i, p in enumerate(prompts)
    ]
    engine.run()
    wall = time.perf_counter() - t0
    for r in reqs:
        if r.state is not RequestState.DONE or len(r.tokens) != new_tokens:
            raise RuntimeError(
                f"request {r.rid}: state={r.state.value} "
                f"tokens={len(r.tokens)}/{new_tokens} error={r.error!r} "
                f"(engine health {engine.health().value}, halt "
                f"{engine.halt_reason!r})"
            )
    return reqs, wall


class PlainReference:
    """Cache-free full forward of the same weights: ``attention_impl="xla"``,
    fp32 compute, matmul precision ``highest``; one program at a fixed padded
    length (right padding cannot reach earlier positions of a causal
    model)."""

    def __init__(self, cfg, params, length: int):
        import jax
        import jax.numpy as jnp

        from neuronx_distributed_tpu.models.llama import LlamaForCausalLM

        model = LlamaForCausalLM(
            dataclasses.replace(cfg, dtype=jnp.float32, max_seq_len=length),
            attention_impl="xla",
        )
        self.length = length
        self.params = params

        @jax.jit
        def gaps(params, ids, positions, tokens):
            with jax.default_matmul_precision("highest"):
                logits = model.apply(params, ids)[0].astype(jnp.float32)
            rows = logits[positions]                       # (n, vocab)
            top2 = jax.lax.top_k(rows, 2)[0]

            def gap(toks):
                chosen = jnp.take_along_axis(rows, toks[:, None], axis=1)[:, 0]
                return top2[:, 0] - chosen

            # every token swapped for its successor id: what a wrong answer
            # costs (never equal to the emitted token, whatever the stream)
            wrong = (tokens + 1) % rows.shape[1]
            return gap(tokens), gap(wrong), top2[:, 0] - top2[:, 1]

        self._gaps = gaps

    def gap(self, prompt, tokens) -> Tuple[float, float, float]:
        """``(gap, control, margin)``: the largest gap, over the emitted
        tokens, between the reference maximum at a position and the
        reference logit of the token the system emitted there; the same
        for every token swapped for its successor id (a deliberately wrong
        answer — the check must be able to fail); and the reference's
        median top-1/top-2 margin (how easy the argmax is to flip)."""
        import numpy as np

        p, n = len(prompt), len(tokens)
        ids = np.zeros((1, self.length), np.int32)
        ids[0, :p] = prompt
        ids[0, p:p + n] = tokens
        # token i was sampled from the logits at position p - 1 + i
        positions = np.arange(p - 1, p - 1 + n, dtype=np.int32)
        g, control, margin = (
            np.asarray(a) for a in
            self._gaps(self.params, ids, positions, np.asarray(tokens, np.int32))
        )
        return float(g.max()), float(control.max()), float(np.median(margin))


def _reference_check(tag: str, ref: PlainReference, reqs, prompts,
                     tol: float) -> bool:
    rows = [ref.gap(p, r.tokens) for p, r in zip(prompts, reqs)]
    gaps, controls, margins = zip(*rows)
    log(
        f"{tag}: reference-logit gap per request "
        f"{[round(g, 4) for g in gaps]} (max {max(gaps):.4f}, tol {tol:g}); "
        f"control (every token swapped for another) min {min(controls):.3f}; "
        f"reference top-1/top-2 margin, median per request "
        f"{[round(m, 3) for m in margins]}"
    )
    return all(math.isfinite(g) and g <= tol for g in gaps) and all(
        c > tol for c in controls
    )


def _serve_model(size: ServeSize, seed: int):
    import jax
    import jax.numpy as jnp

    from neuronx_distributed_tpu.models.llama import LlamaForCausalLM

    over = dict(max_seq_len=size.max_seq_len, scan_layers=False, remat=False)
    if size.config is None:
        over["param_dtype"] = jnp.bfloat16  # serving weights: bf16 in HBM
    cfg = _llama(size, **over)
    model = LlamaForCausalLM(cfg, attention_impl="auto")
    params = jax.jit(model.init)(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)
    )
    return cfg, model, params


_FAULT_EVENTS = (
    "dispatch_failure", "recovery", "prefill_failure", "page_exhausted",
    "quarantine", "page_quarantine", "halt",
)
_FAULT_COUNTERS = (
    "preemptions", "dispatch_retries", "recoveries", "prefill_failures",
    "quarantines", "page_quarantines", "failed", "timed_out",
)


def _engine_report(tag: str, engine, cfg, reqs, wall: float,
                   n_tokens: int) -> Dict[str, bool]:
    """Log what the engine did and return its cleanliness checks: the
    engine's fault tolerance recovers from a failed dispatch or prefill and
    still answers — here any such event is a failure of the run, and the
    continuous-batching invariant (ONE decode program) must hold."""
    from neuronx_distributed_tpu.serving import EngineHealth

    snap = engine.programs.snapshot(analyze=False)
    m = engine.metrics.snapshot(analyze_programs=False)
    log(
        f"{tag}: depth={cfg.num_layers} layers max_seq_len={cfg.max_seq_len} "
        f"slots={engine.num_slots} resolved={snap['resolved']} "
        f"programs={snap['totals']['programs']} compile_s="
        f"{snap['totals']['compile_wall_s']:.1f} "
        f"decode_compilations={engine.decode_compilations} "
        f"prefill_compilations={engine.prefill_compilations}"
    )
    decode_wall = m["decode_dispatch_s"] + m["decode_readback_s"]
    log(
        f"{tag}: {len(reqs)} requests, prompts "
        f"{[engine.metrics.request_snapshot(r.rid)['prompt_len'] for r in reqs]}"
        f" -> {n_tokens} tokens each in {wall:.1f}s wall (compiles included); "
        f"prefills={m['prefills']} chunks={m['chunks']} decode_tokens="
        f"{m['decode_tokens']} decode_wall_s={decode_wall:.2f} (first chunk "
        "includes its compile)"
    )
    recompiled = {
        name: {"compiles": e["compiles"], "signatures": e["variants"]}
        for name, e in snap["by_program"].items() if e["compiles"] > 1
    }
    if recompiled:
        log(f"{tag}: programs compiled more than once: {recompiled}")
    counters = {k: m[k] for k in _FAULT_COUNTERS}
    events = [
        e for e in (engine.flight.events() if engine.flight else [])
        if e.get("kind") in _FAULT_EVENTS
    ]
    log(f"{tag}: health={engine.health().value} fault counters {counters}")
    for e in events:
        log(f"{tag}: fault event {e}")
    return {
        "clean_run": engine.health() is EngineHealth.OK
        and not any(counters.values()) and not events,
        "one_decode_program": engine.decode_compilations == 1,
    }


def serve_phase(size: ServeSize, seed: int) -> Dict[str, bool]:
    """A ``ServingEngine`` with its defaults answers 8 requests whose prompts
    span 128-2048 tokens, then a short pass on the row-cache layout, each
    against the plain reference. The defaults: paged KV, page 16,
    ``paged_attention="auto"`` (fused on a TPU), decode chunk 8, prefix cache
    on; 32 new tokens each. Every emitted token is checked against a plain
    reference: a cache-free full forward of the same weights with
    ``attention_impl="xla"`` in fp32 under
    ``jax.default_matmul_precision("highest")``, teacher-forced on the
    emitted tokens. Logits are compared, not tokens: the reference logit of
    every emitted token must be within the printed tolerance of the reference
    maximum at its position."""
    from flax.core import meta

    from neuronx_distributed_tpu.observability.hbm import tree_nbytes
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib
    from neuronx_distributed_tpu.serving import ServingEngine

    # a mesh-free engine: the train phase's global mesh must be gone (under
    # a live mesh the kernels wrap themselves in manual regions over it, the
    # chunk's outputs come back typed with that mesh, and the next dispatch
    # retraces — 3 decode compiles instead of 1, seen on the chip)
    mesh_lib.destroy_model_parallel()
    cfg, model, params = _serve_model(size, seed)
    weights = tree_nbytes(meta.unbox(params))
    engine = ServingEngine(model, params, num_slots=size.slots, kv_page_size=16)
    prompts = _prompts(size.prompt_lens, cfg.vocab_size, seed)
    reqs, wall = _serve(engine, prompts, size.new_tokens, seed)
    paged_run = _engine_report("serve", engine, cfg, reqs, wall, size.new_tokens)
    log(
        f"serve: weights {weights / 2**30:.2f} GiB + KV pool "
        f"{engine.cache.nbytes / 2**30:.2f} GiB "
        f"({engine.cache.alloc.capacity} pages of 16)"
    )
    resolved = dict(engine.programs.resolved)
    kernels = _ledger_kernels(engine.programs, _hot_programs(engine))
    log(f"serve: {KERNEL} in compiled programs: {kernels}")
    engine.cache.check()  # the page-leak invariant, on the way out
    engine = None  # free the pool before the reference and the row pass
    gc.collect()

    longest = max(size.prompt_lens) + size.new_tokens
    ref = PlainReference(cfg, meta.unbox(params), -(-longest // 128) * 128)
    paged_ok = _reference_check("serve", ref, reqs, prompts, size.logit_tol)

    row_engine = ServingEngine(model, params, num_slots=size.row_slots)
    row_prompts = _prompts(size.row_prompt_lens, cfg.vocab_size, seed + 1)
    row_reqs, row_wall = _serve(
        row_engine, row_prompts, size.row_new_tokens, seed
    )
    row_run = _engine_report(
        "serve[row]", row_engine, cfg, row_reqs, row_wall, size.row_new_tokens
    )
    row_resolved = dict(row_engine.programs.resolved)
    row_kernels = _ledger_kernels(row_engine.programs, ["decode_chunk"])
    log(f"serve[row]: {KERNEL} in compiled programs: {row_kernels}")
    row_ok = _reference_check(
        "serve[row]", ref, row_reqs, row_prompts, size.logit_tol
    )
    return {
        **{f"serve_paged_{k}": v for k, v in paged_run.items()},
        **{f"serve_row_{k}": v for k, v in row_run.items()},
        "serve_paged_matches_reference": paged_ok,
        "serve_row_matches_reference": row_ok,
        "serve_several_prefill_buckets": sum(
            n.startswith("prefill[") for n in kernels
        ) >= 2,
        "serve_resolved_fused": resolved == {
            "attention": "flash", "decode_attention": "paged_fused",
            "paged_attention": "fused",
        },
        "serve_row_resolved_flash_decode": row_resolved == {
            "attention": "flash", "decode_attention": "flash_decode",
            "paged_attention": "none",
        },
        "kernel_serve_programs": all(kernels.values()) and bool(kernels),
        "kernel_serve_row_decode": all(row_kernels.values())
        and bool(row_kernels),
    }


def tp_serve_phase(size: ServeSize, seed: int, devices) -> Dict[str, bool]:
    """``ServingEngine(tp=len(devices))`` (tp=4) against the mesh-free engine
    on the same requests; both against the plain reference."""
    import jax
    from flax.core import meta

    from neuronx_distributed_tpu.observability.hbm import tree_nbytes
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib
    from neuronx_distributed_tpu.serving import ServingEngine

    tp = len(devices)
    mesh_lib.destroy_model_parallel()
    cfg, model, params = _serve_model(size, seed)
    prompts = _prompts(size.prompt_lens, cfg.vocab_size, seed)
    solo = ServingEngine(model, params, num_slots=size.slots, kv_page_size=16)
    solo_reqs, solo_wall = _serve(solo, prompts, size.new_tokens, seed)
    solo_run = _engine_report(
        "serve[one device]", solo, cfg, solo_reqs, solo_wall, size.new_tokens
    )
    solo = None
    gc.collect()

    engine = ServingEngine(
        model, params, num_slots=size.slots, kv_page_size=16, tp=tp
    )
    reqs, wall = _serve(engine, prompts, size.new_tokens, seed)
    tp_run = _engine_report(
        f"serve[tp{tp}]", engine, cfg, reqs, wall, size.new_tokens
    )
    resolved = dict(engine.programs.resolved)
    total = tree_nbytes(meta.unbox(params))
    per_device = [_bytes_on(engine._params, d) for d in devices]
    pool = engine.cache.cache["pool"]
    kv = [
        leaf for path, leaf in jax.tree_util.tree_flatten_with_path(pool)[0]
        if getattr(path[-1], "key", None) in ("k", "v")
    ]
    kv_heads = {leaf.addressable_shards[0].data.shape[-2] for leaf in kv}
    kv_devices = {len(leaf.sharding.device_set) for leaf in kv}
    log(
        f"serve[tp{tp}]: param bytes per device {per_device} of {total}; KV "
        f"pool leaves hold {sorted(kv_heads)} of {cfg.num_kv_heads} kv heads "
        f"per device over {sorted(kv_devices)} devices"
    )
    # lowered, not compiled: the ledger's signatures carry no shardings, so
    # a compile here would build a second, replicated program per entry
    kernels = _ledger_kernels(
        engine.programs, _hot_programs(engine), compiled=False
    )
    log(f"serve[tp{tp}]: {KERNEL} in lowered programs: {kernels}")
    engine.cache.check()
    engine = None
    gc.collect()
    mesh_lib.destroy_model_parallel()

    same = sum(
        a == b for r, s in zip(reqs, solo_reqs) for a, b in zip(r.tokens, s.tokens)
    )
    log(
        f"serve[tp{tp}]: {same}/{len(reqs) * size.new_tokens} tokens equal "
        "to the mesh-free engine's (bf16: compared by logits below)"
    )
    longest = max(size.prompt_lens) + size.new_tokens
    ref = PlainReference(cfg, meta.unbox(params), -(-longest // 128) * 128)
    solo_ok = _reference_check(
        "serve[one device]", ref, solo_reqs, prompts, size.logit_tol
    )
    tp_ok = _reference_check(
        f"serve[tp{tp}]", ref, reqs, prompts, size.logit_tol
    )
    return {
        **{f"tp_serve_one_device_{k}": v for k, v in solo_run.items()},
        **{f"tp_serve_{k}": v for k, v in tp_run.items()},
        "tp_serve_one_device_matches_reference": solo_ok,
        "tp_serve_matches_reference": tp_ok,
        "tp_serve_resolved_fused": resolved == {
            "attention": "flash", "decode_attention": "paged_fused",
            "paged_attention": "fused",
        },
        "tp_serve_params_split": max(per_device) < 0.3 * total
        and min(per_device) > 0.2 * total,
        "tp_serve_kv_split": kv_heads == {cfg.num_kv_heads // tp}
        and kv_devices == {tp},
        "kernel_tp_serve_programs": all(kernels.values()) and bool(kernels),
    }


# --- entry ---------------------------------------------------------------------

# four chips: the tp path and its one-device counterpart, nothing else — and
# smaller than the one-chip run (the mesh-free engine must fit ONE chip, and
# every second here is charged four times)
TP_SERVE = ServeSize(
    layers=8, slots=4, prompt_lens=(300, 500, 700, 1000), new_tokens=16,
)


# --- multi-head latent attention ------------------------------------------------------


def _prefill_rows(backbone, tail: int):
    """``fn(params, ids, lo)``: the system's prefill logits at ``tail`` positions
    from ``lo``. A ``*ForCausalLM`` in ``prefill`` mode applies its head to the
    LAST position alone (``models/__init__.py``), so every position is read
    from the headless ``backbone``'s hidden states through the head's kernel."""
    import jax
    from flax.core import meta

    @jax.jit
    def fn(params, ids, lo):
        hidden = backbone.apply({"params": params["params"]["model"]}, ids, mutable=["cache"])[0][0]
        rows = jax.lax.dynamic_slice_in_dim(hidden[0], lo, min(tail, ids.shape[1]), axis=0)
        return rows @ meta.unbox(params)["params"]["lm_head"]["kernel"]

    return fn


def mla_phase(size: MlaSize, seed: int) -> Dict[str, bool]:
    """DeepSeek-V2-Lite alone: 24,576 / 8,192 / 2,048-token prompts through
    the paged latent cache against the plain reference. The published widths
    at the benchmark configuration's depth
    (``perfbench/configs/deepseek-v2-lite-serve.json``), a ``ServingEngine``
    of 32,768-column slots: the seeded prompts are prefilled (materialised
    attention through the flash kernel) and 32 tokens decoded through the
    paged LATENT cache (absorbed attention through the paged latent kernel).
    Against ``perfbench/references/deepseek_v2.py`` (float32, ``highest``,
    materialised form, no cache, the whole context in query blocks): the
    system's prefill logits at every position of the two shorter prompts and
    at the last 256 of the longest, and the reference's logit of every
    decoded token."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax.core import meta

    from neuronx_distributed_tpu.models.deepseek_v2 import DeepseekV2Model
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib
    from neuronx_distributed_tpu.serving import ServingEngine
    from perfbench.families import deepseek_v2 as family
    from perfbench.references import common
    from perfbench.references.deepseek_v2 import Reference

    mesh_lib.destroy_model_parallel()
    published = _published(size, "deepseek-v2-lite-serve.json")
    model = family.build(published, runner="serve", max_seq_len=size.max_seq_len)
    if size.model is not None:    # the CPU rehearsal serves in float32
        model = model.clone(config=dataclasses.replace(model.config, dtype=jnp.float32))
    mla_kernel(size, published, seed, model.config.dtype)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))
    jax.block_until_ready(params)
    engine = ServingEngine(model, params, num_slots=size.slots, kv_page_size=16)
    prompts = _prompts(size.prompt_lens, int(published["vocab_size"]), seed + 7)
    reqs, wall = _serve(engine, prompts, size.new_tokens, seed)
    resolved = dict(engine.programs.resolved)
    kernels = _ledger_kernels(engine.programs, _hot_programs(engine))
    per_token = engine.metrics.snapshot()["kv_bytes_per_token_layer"]
    log(f"mla: {len(reqs)} requests, prompts {list(size.prompt_lens)} + {size.new_tokens} tokens in "
        f"{wall:.1f}s; resolved {resolved}; {KERNEL} in compiled programs: {kernels}; cache "
        f"{per_token:g} B a token a layer, pool {engine.cache.nbytes / 2**30:.2f} GiB")
    engine.cache.check()
    engine = None
    gc.collect()

    backbone = DeepseekV2Model(model.config, model.attention_impl, mode="prefill")

    prefill_rows = _prefill_rows(backbone, size.tail)

    ref = Reference(published, meta.unbox(params))
    dtype = jnp.dtype(model.config.dtype).itemsize
    want_bytes = (int(published["kv_lora_rank"]) + int(published["qk_rope_head_dim"])) * dtype
    worst_gap, worst_diff, worst_median, ok, caught = 0.0, 0.0, 0.0, True, {}
    for prompt, req in zip(prompts, reqs):
        p, n = len(prompt), len(req.tokens)
        ids = np.concatenate([prompt, np.asarray(req.tokens, np.int32)])[None]
        hidden, margins = ref._hidden(ids)            # the whole context, once
        head = lambda rows: np.asarray(ref._head(      # noqa: E731
            ref.p["model"]["final_norm"], ref.p["lm_head"], hidden[:, rows])[0], np.float32)
        # decoded tokens: token i was sampled from the logits at position p - 1 + i
        rows = head(np.arange(p - 1, p - 1 + n))
        toks = np.asarray(req.tokens)
        gaps = rows.max(1) - rows[np.arange(n), toks]
        wrong = rows.max(1) - rows[np.arange(n), (toks + 1) % rows.shape[1]]
        router = np.asarray(margins[0, p - 1:p - 1 + n])
        fine, over, excused = common.judge_gaps(gaps, router, size.gap_tol, size.near_tie)
        ok = ok and fine and wrong.min() > size.gap_tol
        worst_gap = max(worst_gap, float(gaps[router >= size.near_tie].max(initial=0.0)))
        # prefill logits: every position of the shorter prompts, the tail of the longest
        starts = range(0, p, size.tail) if p < max(size.prompt_lens) else [p - size.tail]
        blocks = [max(min(lo, p - size.tail), 0) for lo in starts]
        mine = [np.asarray(prefill_rows(params, prompt[None], lo), np.float32) for lo in blocks]
        theirs = [head(np.arange(lo, lo + m.shape[0])) for lo, m in zip(blocks, mine)]
        diffs = np.concatenate([np.abs(m - t).max(1) for m, t in zip(mine, theirs)])
        median, worst = float(np.median(diffs)), float(diffs.max())
        ok = ok and worst <= size.logit_tol and median <= size.typical_tol
        worst_diff, worst_median = max(worst_diff, worst), max(worst_median, median)
        log(f"mla: prompt {p}: decoded tokens' largest reference-logit gap {gaps.max():.4f} ({over} of {n} "
            f"fail {size.gap_tol:g}, {excused} excused by a router margin under {size.near_tie:g}; wrong "
            f"tokens' smallest gap {wrong.min():.3f}); prefill logits at {len(diffs)} positions, |system - "
            f"reference|: median {median:.4f}, 99th percentile {np.percentile(diffs, 99):.4f}, largest {worst:.4f}")
        if p == min(size.prompt_lens):
            # the comparison must be able to fail: the reference itself, a
            # precision lower and a mechanism short, against the plain one
            broken = meta.unbox(params)
            broken = jax.tree_util.tree_map_with_path(
                lambda path, a: a.at[:, -1].set(0) if "kv_a_proj" in str(path) else a, broken)
            for name, other in (("float8 latent", Reference(published, meta.unbox(params), jnp.float8_e4m3fn)),
                                ("dropped rope channel", Reference(published, broken))):
                h2, _ = other._hidden(ids)
                rows2 = [np.asarray(other._head(other.p["model"]["final_norm"], other.p["lm_head"],
                                                h2[:, lo:lo + m.shape[0]])[0], np.float32)
                         for lo, m in zip(blocks, mine)]
                d2 = np.concatenate([np.abs(r - t).max(1) for r, t in zip(rows2, theirs)])
                caught[name] = float(d2.max()) > size.logit_tol or float(np.median(d2)) > size.typical_tol
                log(f"mla: control, the reference with a {name} against the plain reference at {len(d2)} "
                    f"positions: median {np.median(d2):.4f}, 99th percentile {np.percentile(d2, 99):.4f}, largest "
                    f"{d2.max():.4f}: {'outside' if caught[name] else 'INSIDE'} the tolerances")
    log(f"mla: prefill logits against the reference: largest difference {worst_diff:.4f} (tolerance "
        f"{size.logit_tol:g}), largest median {worst_median:.4f} ({size.typical_tol:g}); largest decoded-token gap "
        f"outside router near-ties {worst_gap:.4f} ({size.gap_tol:g})")
    return {
        "mla_matches_reference": ok,
        "mla_resolved_latent_fused": resolved == {
            "attention": "flash", "decode_attention": "paged_latent_fused",
            "paged_attention": "fused", "moe_decode": "stream",
        },
        "mla_cache_is_latent_sized": per_token == want_bytes,
        "mla_float8_latent_is_caught": caught.get("float8 latent", False),
        "mla_dropped_rope_channel_is_caught": caught.get("dropped rope channel", False),
        "kernel_mla_programs": all(kernels.values()) and bool(kernels),
    }


def mla_kernel(size: MlaSize, published: dict, seed: int, dtype) -> None:
    """The paged latent decode kernel alone at the cell's shapes (8 slots of
    the row's columns, the docs tape's contexts ending at a shared cursor):
    ms a call and GB/s of the latent rows it needs, under a random block table
    and under the one the serving pool deals (two leaves: a run is one copy
    of 64 KB and one of 16 KB, where a page is 16 KB + 4 KB)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_tpu.kernels.flash_decode import LATENT_BLOCK_TOKENS, paged_latent_decode_attention

    h, d_c, d_r = (int(published[k]) for k in ("num_attention_heads", "kv_lora_rank", "qk_rope_head_dim"))
    scale = (int(published["qk_nope_head_dim"]) + d_r) ** -0.5
    page, length, item = 16, size.max_seq_len, jnp.dtype(dtype).itemsize
    cur = length - 8 * page
    ctx = _page_started(size.kernel_contexts, cur, page)
    b, n_log = len(ctx), length // page
    scattered, valid = _scattered_table(np.random.default_rng(seed), ctx, cur, length, page)
    key = jax.random.split(jax.random.PRNGKey(seed), 4)
    c_pool = jax.random.normal(key[0], (b * n_log + 1, page, 1, d_c), dtype)
    r_pool = jax.random.normal(key[1], (b * n_log + 1, page, 1, d_r), dtype)
    q_c, q_r = jax.random.normal(key[2], (b, 1, h, d_c), dtype), jax.random.normal(key[3], (b, 1, h, d_r), dtype)
    ok, pos = jnp.asarray(valid), jnp.asarray([cur], jnp.int32)
    _table_pair(
        f"mla kernel: latent decode attention, {b} slots holding {sum(ctx)} tokens, {h} heads",
        lambda bt: ((lambda qc, qr, c, r: paged_latent_decode_attention(
            qc, qr, c, r, bt, pos, ok, scale=scale, page_size=page)), (q_c, q_r, c_pool, r_pool)),
        {"random": scattered, "dealt": _dealt_tables(ctx, cur, length, page)[0]}, sum(ctx) * (d_c + d_r) * item, page,
        page * (d_c + -(-d_r // 128) * 128) * item, min(LATENT_BLOCK_TOKENS // page, n_log), pos[0] + 1,
        size.kernel_calls)


# --- learned sparse attention ---------------------------------------------------------


def _call_timer(fn, calls: int, repeats: int = 5) -> Callable[..., float]:
    """``args -> ms``: the median over ``repeats`` of the wall of ONE program
    that calls ``fn`` ``calls`` times (each call on inputs that depend on the
    last result, so none is folded away), over ``calls``. The program compiles
    once a shape, however many ``args`` are timed."""
    import jax
    import jax.numpy as jnp

    def many(*a):
        def body(carry, _):
            out = fn(*[x + carry.astype(x.dtype) if i == 0 and jnp.issubdtype(x.dtype, jnp.floating) else x
                       for i, x in enumerate(a)])
            return (jnp.sum(jax.tree.leaves(out)[0].astype(jnp.float32)) * 0).astype(jnp.float32), None
        return jax.lax.scan(body, jnp.zeros((), jnp.float32), None, length=calls)[0]

    run = jax.jit(many)

    def ms(*args) -> float:
        jax.block_until_ready(run(*args))
        walls = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(run(*args))
            walls.append(time.perf_counter() - t0)
        return 1e3 * sorted(walls)[len(walls) // 2] / calls

    return ms


def _median_call_ms(fn, args, calls: int, repeats: int = 5) -> float:
    return _call_timer(fn, calls, repeats)(*args)


def dsa_kernels(size: DsaSize, published: dict, seed: int, dtype) -> Dict[str, bool]:
    """The three decode kernels alone at the cell's shapes against float32
    ``jnp``, with their times against their bytes; the selection, ``top_k``
    against the bisection."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_tpu.kernels.flash_decode import (
        LATENT_BLOCK_TOKENS,
        SPARSE_CHUNK_TOKENS,
        paged_gather_leaf,
        paged_index_scores,
        paged_sparse_decode_attention,
    )
    from neuronx_distributed_tpu.kernels.flash_attention import sparse_keep_mask_kernel
    from neuronx_distributed_tpu.modules.attention import (
        _masked_gqa_attention,
        index_scores,
        sparse_keep_mask,
        split_kv,
        topk_mask,
    )

    sa = published["sa_config"]
    h, hkv, d = (int(published[k]) for k in ("num_attention_heads", "num_key_value_heads", "head_dim"))
    h_i, d_i, keep = int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"]), int(sa["topk"])
    page, length = 16, size.max_seq_len
    cur = size.kernel_cursor
    ctx = _page_started(size.kernel_contexts, cur, page)
    b, n_log = len(ctx), length // page
    table, valid = _scattered_table(np.random.default_rng(seed), ctx, cur, length, page)   # contexts END at the cursor
    key = jax.random.split(jax.random.PRNGKey(seed), 6)
    pages = b * n_log + 1
    # the indexed cache's joined leaf: a token's K heads, then its V heads
    kv_pool = jnp.concatenate([jax.random.normal(key[0], (pages, page, hkv, d), dtype),
                               jax.random.normal(key[1], (pages, page, hkv, d), dtype)], axis=2)
    i_pool = jax.random.normal(key[2], (pages, page, 1, d_i), dtype)
    q = jax.random.normal(key[3], (b, 1, h, d), dtype)
    q_idx = jax.random.normal(key[4], (b, 1, h_i, d_i), dtype)
    w_idx = jax.random.normal(key[5], (b, 1, h_i), dtype)
    table, valid, pos = jnp.asarray(table), jnp.asarray(valid), jnp.asarray([cur], jnp.int32)
    f32 = lambda a: a.astype(jnp.float32)    # noqa: E731

    # every array is an ARGUMENT: closed over, a pool would be a constant of the program
    score = lambda qi, w, pool: paged_index_scores(qi, w, pool, table, pos, valid, page_size=page)   # noqa: E731
    got = jax.jit(score)(q_idx, w_idx, i_pool)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda qi, w, pool: index_scores(
            f32(qi), f32(w), f32(paged_gather_leaf(pool, table, page)[:, :, 0])))(q_idx, w_idx, i_pool)[:, 0]
    ok_cols = np.asarray(valid)
    score_err = float(np.abs(np.asarray(got)[ok_cols] - np.asarray(want)[ok_cols]).max())
    score_ok = score_err <= size.kernel_tol * float(np.abs(np.asarray(want)[ok_cols]).max()) and bool(
        np.isneginf(np.asarray(got)[~ok_cols]).all())
    score_bytes = sum(ctx) * d_i * 2
    # the same scores under the table the serving pool deals: runs of adjacent pages, one copy a run
    score_ms = _table_pair(
        f"dsa kernels: index scores over the {d_i}-wide leaf, {b} slots holding {sum(ctx)} tokens",
        lambda bt: ((lambda qi, w, pool: paged_index_scores(qi, w, pool, bt, pos, valid, page_size=page)),
                    (q_idx, w_idx, i_pool)),
        {"random": table, "dealt": _dealt_tables(ctx, cur, length, page)[0]}, score_bytes, page,
        page * -(-d_i // 128) * 128 * 2, min(LATENT_BLOCK_TOKENS // page, n_log), pos[0] + 1,
        size.kernel_calls)["random"][0]

    top = lambda s: jax.lax.top_k(s, keep)   # noqa: E731
    vals, cols = jax.jit(top)(got)
    top_ms = _median_call_ms(top, (got,), size.kernel_calls)
    bisect = lambda s: topk_mask(s, s > -jnp.inf, keep)   # noqa: E731
    same = bool((np.asarray(jax.jit(bisect)(got)).sum(1) == np.minimum(ctx, keep)).all()) and all(
        set(np.asarray(cols[i]).tolist()) == set(np.flatnonzero(np.asarray(jax.jit(bisect)(got))[i]).tolist())
        for i in range(b))
    bisect_ms = _median_call_ms(bisect, (got,), size.kernel_calls)

    n_sel = jnp.sum(vals > -jnp.inf, axis=1).astype(jnp.int32)
    attend = lambda qq, kvp: paged_sparse_decode_attention(qq, kvp, table, cols, n_sel, page_size=page)   # noqa: E731
    out = jax.jit(attend)(q, kv_pool)
    keep_mask = np.zeros((b, 1, length), bool)
    for i in range(b):
        keep_mask[i, 0, np.asarray(cols[i])[: int(n_sel[i])]] = True
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda qq, kvp: _masked_gqa_attention(
            f32(qq), *split_kv(f32(paged_gather_leaf(kvp, table, page))), jnp.asarray(keep_mask)))(q, kv_pool)
    attend_err = float(np.abs(np.asarray(f32(out)) - np.asarray(ref)).max())
    attend_ms = _median_call_ms(attend, (q, kv_pool), size.kernel_calls)
    attend_bytes = int(sum(min(n, keep) for n in ctx)) * 2 * hkv * d * 2
    chunk = min(SPARSE_CHUNK_TOKENS, keep)
    copies = sum(-(-int(n) // chunk) * chunk for n in np.asarray(n_sel))   # a slot's count, rounded up to the chunk
    # the prefill's learned mask: one kernel against the einsum + bisection
    sp = size.kernel_prefill
    pk = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    pq = jax.random.normal(pk[0], (1, sp, h_i, d_i), dtype)
    pw = jax.random.normal(pk[1], (1, sp, h_i), dtype)
    pkey = jax.random.normal(pk[2], (1, sp, d_i), dtype)
    all_valid = jnp.ones((1, sp), bool)
    rows_pos = jnp.arange(sp, dtype=jnp.int32)[None]
    fused = lambda a, w_, k_: sparse_keep_mask_kernel(a, w_, k_, all_valid, keep)   # noqa: E731
    plain = lambda a, w_, k_: sparse_keep_mask(a, w_, k_, rows_pos, all_valid, keep, jnp.int8)   # noqa: E731
    m_fused, m_plain = jax.jit(fused)(pq, pw, pkey), jax.jit(plain)(pq, pw, pkey)
    mask_differ = float(jnp.mean((m_fused != m_plain).astype(jnp.float32)) * sp / (2.0 * min(keep, sp)))
    fused_ms = _median_call_ms(fused, (pq, pw, pkey), 3, repeats=3)
    plain_ms = _median_call_ms(plain, (pq, pw, pkey), 3, repeats=3)
    log(f"dsa kernels: the learned mask of a {sp}-token prompt, {keep} kept: one kernel {fused_ms:.1f} ms, the einsum "
        f"and bisection {plain_ms:.1f} ms; {100 * mask_differ:.4f}% of the kept columns differ (summation order)")
    log(f"dsa kernels: {b} slots, contexts {ctx} ({sum(ctx)} tokens) ending at cursor {cur}, page {page}, "
        f"{jnp.dtype(dtype).name}; index scores {score_ms:.3f} ms a call for {score_bytes / 1e6:.1f} MB "
        f"({score_bytes / score_ms / 1e6:.1f} GB/s), max |kernel - float32 jnp| {score_err:.5f}; selection of "
        f"{keep} of {length}: top_k {top_ms:.3f} ms, bisection mask {bisect_ms:.3f} ms (the same sets: {same}); "
        f"sparse attention {attend_ms:.3f} ms a call for {attend_bytes / 1e6:.1f} MB "
        f"({attend_bytes / attend_ms / 1e6:.1f} GB/s, {int(n_sel.sum())} tokens; {copies} copies of "
        f"{2 * hkv * d * 2} B, {1e6 * attend_ms / copies:.1f} ns each), max |kernel - float32 jnp| {attend_err:.5f}")
    return {
        "dsa_index_kernel_matches_jnp": score_ok,
        "dsa_bisection_selects_what_top_k_selects": same,
        "dsa_sparse_kernel_matches_jnp": attend_err <= size.kernel_tol,
        "dsa_mask_kernel_keeps_the_einsums_columns": mask_differ <= size.selected_tol,
    }


def dsa_phase(size: DsaSize, seed: int) -> Dict[str, bool]:
    """Keye-VL-2.0's language model alone: the sparse decode kernels by
    themselves, then the same prompts through the paged indexed cache against
    the plain reference. The published widths at the benchmark
    configuration's depth (``perfbench/configs/keye-vl2-30b-a3b-serve.json``):
    first the three decode kernels alone at the serve cell's shapes (8 slots
    whose contexts end at a shared cursor) against float32 ``jnp``, each with
    its time against its bytes, and the selection, ``top_k`` against a
    bisection; and the prefill's byte-masked forward alone against PR 49's
    kernel (:func:`grouped_forward_pair`; with ``--bundles DIR`` ``main`` first
    prints a step's instruction bundles beside PR 49's from a described-v5e
    compile). Then a ``ServingEngine`` of 32,768-column slots: prompts of
    24,576, 8,192 and 2,048 tokens are prefilled (the learned mask, the
    byte-masked flash kernel) and 32 tokens decoded through the paged INDEXED
    cache (index scores, ``top_k``, the sparse kernel). Against
    ``perfbench/references/keye_vl2.py``: prefill logits and the reference's
    logit of every decoded token, as ``mla``; the share of layer 0's selected
    columns that differ from the reference's and how near the threshold they
    lie; and two controls that must fail: index keys in float8, and 1024
    kept."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax.core import meta

    from neuronx_distributed_tpu.models.keye_vl2 import KeyeVL2Model, mrope_angles, rotate
    from neuronx_distributed_tpu.modules.attention import sparse_keep_mask
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib
    from neuronx_distributed_tpu.serving import ServingEngine
    from perfbench.families import keye_vl2 as family
    from perfbench.references import common
    from perfbench.references.keye_vl2 import Reference

    mesh_lib.destroy_model_parallel()
    published = _published(size, "keye-vl2-30b-a3b-serve.json")
    model = family.build(published, runner="serve", max_seq_len=size.max_seq_len)
    if size.model is not None:    # the CPU rehearsal serves in float32
        model = model.clone(config=dataclasses.replace(model.config, dtype=jnp.float32))
    cfg = model.config
    checks = dsa_kernels(size, published, seed, cfg.dtype)
    checks.update(grouped_forward_pair(
        "dsa", (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.head_dim), None, size.forward_bucket,
        size.forward_prompts, size.kernel_calls, cfg.dtype, seed))
    params = jax.jit(model.init)(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))
    jax.block_until_ready(params)
    engine = ServingEngine(model, params, num_slots=size.slots, kv_page_size=16)
    prompts = _prompts(size.prompt_lens, int(published["vocab_size"]), seed + 7)
    reqs, wall = _serve(engine, prompts, size.new_tokens, seed)
    resolved = dict(engine.programs.resolved)
    kernels = _ledger_kernels(engine.programs, _hot_programs(engine))
    per_token = engine.metrics.snapshot()["kv_bytes_per_token_layer"]
    log(f"dsa: {len(reqs)} requests, prompts {list(size.prompt_lens)} + {size.new_tokens} tokens in "
        f"{wall:.1f}s; resolved {resolved}; {KERNEL} in compiled programs: {kernels}; cache "
        f"{per_token:g} B a token a layer, pool {engine.cache.nbytes / 2**30:.2f} GiB")
    engine.cache.check()
    engine = None
    gc.collect()

    backbone = KeyeVL2Model(cfg, model.attention_impl, mode="prefill")

    prefill_rows = _prefill_rows(backbone, size.tail)

    @jax.jit
    def layer0_keep(params, ids):
        """The mask the SYSTEM's first layer keeps, from its own projections."""
        _, state = backbone.apply(
            {"params": params["params"]["model"]}, ids, mutable=["cache", "intermediates"],
            capture_intermediates=lambda mdl, _: mdl.name in ("idx_q_proj", "idx_k_norm", "idx_w_proj")
            and "layers_0" in "/".join(mdl.path))
        got = state["intermediates"]["layers_0"]["attn"]
        b, s = ids.shape
        pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        ang = mrope_angles(pos, cfg.indexer_head_dim, cfg.rope_theta)
        q_idx = rotate(got["idx_q_proj"]["__call__"][0].reshape(b, s, cfg.indexer_num_heads, -1), ang)
        k_idx = rotate(got["idx_k_norm"]["__call__"][0][:, :, None, :], ang)[:, :, 0]
        return sparse_keep_mask(q_idx, got["idx_w_proj"]["__call__"][0], k_idx, pos,
                                jnp.ones((b, s), bool), cfg.index_topk)

    ref = Reference(published, meta.unbox(params))
    want_bytes = (2 * cfg.num_kv_heads * cfg.head_dim + cfg.indexer_head_dim) * jnp.dtype(cfg.dtype).itemsize
    keep = cfg.index_topk
    worst_gap, worst_diff, worst_median, ok, caught = 0.0, 0.0, 0.0, True, {}
    differing = near = None
    for prompt, req in zip(prompts, reqs):
        p, n = len(prompt), len(req.tokens)
        ids = np.concatenate([prompt, np.asarray(req.tokens, np.int32)])[None]
        hidden, router, index, _ = ref._hidden(ids)            # the whole context, once
        head = lambda rows, r=ref, hid=hidden: np.asarray(r._head(      # noqa: E731
            r.p["model"]["final_norm"], r.p["lm_head"], hid[:, rows])[0], np.float32)
        rows = head(np.arange(p - 1, p - 1 + n))
        toks = np.asarray(req.tokens)
        gaps = rows.max(1) - rows[np.arange(n), toks]
        wrong = rows.max(1) - rows[np.arange(n), (toks + 1) % rows.shape[1]]
        margins = np.asarray(router[0, p - 1:p - 1 + n])
        fine, over, excused = common.judge_gaps(gaps, margins, size.gap_tol, size.near_tie)
        ok = ok and fine and wrong.min() > size.gap_tol
        worst_gap = max(worst_gap, float(gaps[margins >= size.near_tie].max(initial=0.0)))
        starts = range(0, p, size.tail) if p < max(size.prompt_lens) else [p - size.tail]
        blocks = [max(min(lo, p - size.tail), 0) for lo in starts]
        mine = [np.asarray(prefill_rows(params, prompt[None], lo), np.float32) for lo in blocks]
        theirs = [head(np.arange(lo, lo + m.shape[0])) for lo, m in zip(blocks, mine)]
        diffs = np.concatenate([np.abs(m - t).max(1) for m, t in zip(mine, theirs)])
        median, worst = float(np.median(diffs)), float(diffs.max())
        ok = ok and worst <= size.logit_tol and median <= size.typical_tol
        worst_diff, worst_median = max(worst_diff, worst), max(worst_median, median)
        log(f"dsa: prompt {p}: decoded tokens' largest reference-logit gap {gaps.max():.4f} ({over} of {n} "
            f"fail {size.gap_tol:g}, {excused} excused by a router margin under {size.near_tie:g}; wrong "
            f"tokens' smallest gap {wrong.min():.3f}); narrowest index margin at those positions "
            f"{float(np.asarray(index[0, p - 1:p - 1 + n]).min()):.5f}; prefill logits at {len(diffs)} positions, "
            f"|system - reference|: median {median:.4f}, 99th percentile {np.percentile(diffs, 99):.4f}, "
            f"largest {worst:.4f}")
        if p == sorted(size.prompt_lens)[len(size.prompt_lens) // 2]:
            # the middle prompt: selection is at work and an S x S mask fits.
            # The system's layer-0 sets against the reference's, then the
            # controls: the reference itself, a precision lower in its index
            # keys, and half the columns kept, against the plain one
            sel, scores = ref.selected_and_scores(prompt[None])[0]
            sparse_rows = np.arange(p) >= keep

            def share_differing(other):
                return float((other[0][sparse_rows] != sel[0][sparse_rows]).sum() / (2.0 * keep * sparse_rows.sum()))

            mine_keep = np.asarray(layer0_keep(params, prompt[None]))
            differing = share_differing(mine_keep)
            causal = np.tril(np.ones((p, p), bool))
            rms = np.sqrt((np.where(causal, scores[0], 0.0) ** 2).sum(1) / causal.sum(1))
            thr = np.where(sel[0], scores[0], np.inf).min(1)
            off = (mine_keep[0] != sel[0]) & sparse_rows[:, None]
            near = float((np.abs(scores[0] - thr[:, None]) / rms[:, None])[off].max(initial=0.0))
            log(f"dsa: prompt {p}, layer 0: {100 * differing:.3f}% of the selected columns differ from the "
                f"reference's (rows past {keep}); the farthest of them lies {near:.5f} of the row's rms score from "
                f"the row's threshold (near-tie margin {size.index_near_tie:g}, limit {100 * size.selected_tol:g}%)")
            for name, other in (("float8 index keys", Reference(published, meta.unbox(params), jnp.float8_e4m3fn)),
                                ("1024 columns kept", Reference(published, meta.unbox(params), topk=keep // 2))):
                h2 = other._hidden(ids)[0]
                rows2 = [np.asarray(other._head(other.p["model"]["final_norm"], other.p["lm_head"],
                                                h2[:, lo:lo + m.shape[0]])[0], np.float32)
                         for lo, m in zip(blocks, mine)]
                d2 = np.concatenate([np.abs(r - t).max(1) for r, t in zip(rows2, theirs)])
                share = share_differing(other.selected(prompt[None])[0])
                caught[name] = (float(d2.max()) > size.logit_tol or float(np.median(d2)) > size.typical_tol
                                or share > size.selected_tol)
                log(f"dsa: control, the reference with {name} against the plain reference: {100 * share:.3f}% of "
                    f"layer 0's selected columns differ; logits at {len(d2)} positions: median {np.median(d2):.4f}, "
                    f"99th percentile {np.percentile(d2, 99):.4f}, largest {d2.max():.4f}: "
                    f"{'outside' if caught[name] else 'INSIDE'} the limits")
    log(f"dsa: prefill logits against the reference: largest difference {worst_diff:.4f} (tolerance "
        f"{size.logit_tol:g}), largest median {worst_median:.4f} ({size.typical_tol:g}); largest decoded-token gap "
        f"outside router near-ties {worst_gap:.4f} ({size.gap_tol:g})")
    return {
        **checks,
        "dsa_matches_reference": ok,
        "dsa_selects_the_references_columns": differing is not None and differing <= size.selected_tol
        and near <= size.index_near_tie,
        "dsa_resolved_sparse_fused": resolved == {
            "attention": "flash", "decode_attention": "paged_sparse_fused",
            "paged_attention": "fused", "moe_decode": "stream",
        },
        "dsa_cache_is_indexed_sized": per_token == want_bytes,
        "dsa_float8_index_keys_are_caught": caught.get("float8 index keys", False),
        "dsa_1024_kept_is_caught": caught.get("1024 columns kept", False),
        "kernel_dsa_programs": all(kernels.values()) and bool(kernels),
    }


def glm_kernel(size: GlmSize, published: dict, seed: int, dtype) -> Dict[str, bool]:
    """The sparse latent decode kernel alone at the cell's shapes against
    float32 ``jnp``, with its time against its bytes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_tpu.kernels.flash_decode import (
        SPARSE_CHUNK_TOKENS,
        paged_gather_leaf,
        paged_sparse_latent_decode_attention,
    )
    from neuronx_distributed_tpu.modules.attention import (
        _masked_latent_attention,
        latent_leaf_shape,
        split_latent,
    )

    h, d_c, d_r = (int(published[k]) for k in ("num_attention_heads", "kv_lora_rank", "qk_rope_head_dim"))
    keep, page, length = int(published["index_topk"]), 16, size.max_seq_len
    scale = (int(published["qk_nope_head_dim"]) + d_r) ** -0.5
    ctx = list(size.kernel_contexts)
    b, n_log = len(ctx), length // page
    rows, lanes = latent_leaf_shape(d_c, d_r)
    rng = np.random.default_rng(seed)
    table = jnp.asarray(1 + rng.permutation(b * n_log).reshape(b, n_log), jnp.int32)
    k_sel = min(keep, max(ctx))
    cols = np.zeros((b, k_sel), np.int32)
    for i, n in enumerate(ctx):
        picked = rng.permutation(n)[:min(n, k_sel)]
        cols[i, :len(picked)] = picked
    n_sel = jnp.asarray([min(n, k_sel) for n in ctx], jnp.int32)
    key = jax.random.split(jax.random.PRNGKey(seed), 3)
    pool = jax.random.normal(key[0], (b * n_log + 1, page, rows, lanes), dtype)
    q_c = jax.random.normal(key[1], (b, 1, h, d_c), dtype)
    q_r = jax.random.normal(key[2], (b, 1, h, d_r), dtype)
    cols = jnp.asarray(cols)
    # every array is an ARGUMENT: closed over, the pool would be a constant of the program
    attend = lambda qc, qr, kv: paged_sparse_latent_decode_attention(   # noqa: E731
        qc, qr, kv, table, cols, n_sel, scale=scale, page_size=page)
    out = jax.jit(attend)(q_c, q_r, pool)
    keep_mask = np.zeros((b, 1, length), bool)
    for i in range(b):
        keep_mask[i, 0, np.asarray(cols[i])[: int(n_sel[i])]] = True
    f32 = lambda a: a.astype(jnp.float32)    # noqa: E731
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda qc, qr, kv: _masked_latent_attention(
            f32(qc), f32(qr), *split_latent(f32(paged_gather_leaf(kv, table, page)), d_c, d_r),
            jnp.asarray(keep_mask), scale))(q_c, q_r, pool)
    err = float(np.abs(np.asarray(f32(out)) - np.asarray(ref)).max())
    ms = _median_call_ms(attend, (q_c, q_r, pool), size.kernel_calls)
    used = int(n_sel.sum()) * (d_c + d_r) * 2
    chunk = min(SPARSE_CHUNK_TOKENS, k_sel)
    copies = sum(-(-int(n) // chunk) * chunk for n in np.asarray(n_sel))
    log(f"glm kernel: {b} slots holding {ctx}, {int(n_sel.sum())} tokens selected, {h} heads, page {page}, "
        f"{jnp.dtype(dtype).name}: sparse latent attention {ms:.3f} ms a call for {used / 1e6:.1f} MB held by the "
        f"selected rows ({used / ms / 1e6:.1f} GB/s; {copies} copies of {rows * lanes * 2} B, "
        f"{1e6 * ms / copies:.1f} ns each), max |kernel - float32 jnp| {err:.5f}")
    # the index-score kernel over GLM-5's 128-wide index keys, the same contexts ending at a shared cursor: under
    # a random table and under the one the serving pool deals (runs of adjacent pages, one copy a run)
    from neuronx_distributed_tpu.kernels.flash_decode import LATENT_BLOCK_TOKENS, paged_index_scores

    h_i, d_i = int(published["index_n_heads"]), int(published["index_head_dim"])
    cur = length - 8 * page
    ends = _page_started(ctx, cur, page)
    scattered, valid = _scattered_table(rng, ends, cur, length, page)
    key = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    i_pool = jax.random.normal(key[0], (b * n_log + 1, page, 1, d_i), dtype)
    q_idx, w_idx = jax.random.normal(key[1], (b, 1, h_i, d_i), dtype), jax.random.normal(key[2], (b, 1, h_i), dtype)
    ok, pos = jnp.asarray(valid), jnp.asarray([cur], jnp.int32)
    _table_pair(
        f"glm kernel: index scores over the {d_i}-wide leaf, {b} slots holding {sum(ends)} tokens",
        lambda bt: ((lambda qi, w, pool_: paged_index_scores(qi, w, pool_, bt, pos, ok, page_size=page)),
                    (q_idx, w_idx, i_pool)),
        {"random": scattered, "dealt": _dealt_tables(ends, cur, length, page)[0]}, sum(ends) * d_i * 2, page,
        page * -(-d_i // 128) * 128 * 2, min(LATENT_BLOCK_TOKENS // page, n_log), pos[0] + 1, size.kernel_calls)
    return {"glm_sparse_latent_kernel_matches_jnp": err <= size.kernel_tol}


def glm_phase(size: GlmSize, seed: int) -> Dict[str, bool]:
    """GLM-5's language model alone (8 of 256 experts held, a vocabulary
    slice): the sparse latent kernel by itself, then 16,384 / 8,192 /
    2,048-token prompts through the paged indexed latent cache against the
    plain reference and its four controls. The published widths on the
    benchmark configuration's cut (``perfbench/configs/glm-5-serve.json``: its
    depth, 8 of 256 experts held, a slice of the vocabulary): the sparse
    LATENT decode kernel alone at the serve cell's shapes against float32
    ``jnp`` and the prefill's byte-masked forward alone against PR 49's kernel
    (:func:`grouped_forward_pair`; ``--bundles`` as ``dsa``), then a
    ``ServingEngine`` of 32,768-column slots: the prompts are
    prefilled (the learned mask over materialised MLA) and 32 tokens decoded
    through the paged indexed LATENT cache (index scores, ``top_k``, the
    absorbed form over the selected rows). Against
    ``perfbench/references/glm_moe_dsa.py``, which is given the same share:
    prefill logits and the reference's logit of every decoded token, as
    ``mla``; the share of layer 0's selected columns that differ; and four
    controls that must fail: index keys in float8, 1024 kept, the latent in
    float8, and the selection bias added to the weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax.core import meta

    from neuronx_distributed_tpu.models.glm_moe_dsa import GlmMoeDsaModel
    from neuronx_distributed_tpu.modules.attention import (
        apply_rope,
        latent_leaf_shape,
        rope_frequencies,
        sparse_keep_mask,
        split_latent,
    )
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib
    from neuronx_distributed_tpu.serving import ServingEngine
    from perfbench.families import glm_moe_dsa as family
    from perfbench.references import common
    from perfbench.references.glm_moe_dsa import Reference

    mesh_lib.destroy_model_parallel()
    published = _published(size, "glm-5-serve.json")
    model = family.build(published, runner="serve", max_seq_len=size.max_seq_len)
    if size.model is not None:    # the CPU rehearsal serves in float32
        model = model.clone(config=dataclasses.replace(model.config, dtype=jnp.float32))
    cfg = model.config
    checks = glm_kernel(size, published, seed, cfg.dtype)
    checks.update(grouped_forward_pair(
        "glm", (cfg.num_heads, cfg.num_heads, cfg.qk_head_dim, cfg.v_head_dim), None, size.forward_bucket,
        size.forward_prompts, size.kernel_calls, cfg.dtype, seed))
    params = jax.jit(model.init)(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))
    jax.block_until_ready(params)
    engine = ServingEngine(model, params, num_slots=size.slots, kv_page_size=16)
    prompts = _prompts(size.prompt_lens, int(published["vocab_size"]), seed + 7)
    reqs, wall = _serve(engine, prompts, size.new_tokens, seed)
    resolved = dict(engine.programs.resolved)
    kernels = _ledger_kernels(engine.programs, _hot_programs(engine))
    per_token = engine.metrics.snapshot()["kv_bytes_per_token_layer"]
    log(f"glm: {len(reqs)} requests, prompts {list(size.prompt_lens)} + {size.new_tokens} tokens in "
        f"{wall:.1f}s; resolved {resolved}; {KERNEL} in compiled programs: {kernels}; cache "
        f"{per_token:g} B a token a layer, pool {engine.cache.nbytes / 2**30:.2f} GiB; held experts "
        f"{cfg.held_experts} of {cfg.num_experts}, vocabulary {cfg.vocab_size}")
    engine.cache.check()
    engine = None
    gc.collect()

    backbone = GlmMoeDsaModel(cfg, model.attention_impl, mode="prefill")

    prefill_rows = _prefill_rows(backbone, size.tail)

    @jax.jit
    def layer0_keep(params, ids):
        """The mask the SYSTEM's first layer keeps, from its own projections."""
        _, state = backbone.apply(
            {"params": params["params"]["model"]}, ids, mutable=["cache", "intermediates"],
            capture_intermediates=lambda mdl, _: mdl.name in ("idx_q_proj", "idx_k_norm", "idx_w_proj")
            and "layers_0" in "/".join(mdl.path))
        got = state["intermediates"]["layers_0"]["attn"]
        b, s = ids.shape
        pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        freqs = rope_frequencies(cfg.qk_rope_head_dim, cfg.max_seq_len, cfg.rope_theta)
        d_r = cfg.qk_rope_head_dim
        first = lambda t: jnp.concatenate([apply_rope(t[..., :d_r], freqs, pos), t[..., d_r:]], -1)   # noqa: E731
        q_idx = first(got["idx_q_proj"]["__call__"][0].reshape(b, s, cfg.index_n_heads, -1))
        k_idx = first(got["idx_k_norm"]["__call__"][0][:, :, None, :])[:, :, 0]
        return sparse_keep_mask(q_idx, got["idx_w_proj"]["__call__"][0], k_idx, pos,
                                jnp.ones((b, s), bool), cfg.index_topk)

    sparse = cfg.first_k_dense      # the first sparse layer

    @jax.jit
    def blocks_of(params, ids):
        """What the SYSTEM's layer-0 attention adds to the stream, and its
        first sparse layer's normed input and routed sum (held experts)."""
        def wanted(mdl, _):
            path = "/".join(mdl.path)
            return (mdl.name == "attn" and "layers_0/" in path + "/") or (
                mdl.name in ("post_attn_norm", "experts") and f"layers_{sparse}/" in path + "/")

        _, state = backbone.apply(
            {"params": params["params"]["model"]}, ids, mutable=["cache", "intermediates"],
            capture_intermediates=wanted)
        got = state["intermediates"]
        layer = got[f"layers_{sparse}"]
        held = split_latent(state["cache"]["layers_0"]["attn"]["kv"][:, :ids.shape[1]],
                            cfg.kv_lora_rank, cfg.qk_rope_head_dim)
        return (got["layers_0"]["attn"]["__call__"][0], layer["post_attn_norm"]["__call__"][0],
                layer["moe"]["experts"]["__call__"][0].reshape(ids.shape + (-1,)), jnp.concatenate(held, axis=-1))

    def rel(mine, theirs):
        """Median over positions of |mine - theirs| / |theirs| (L2 over the
        hidden vector), where ``theirs`` is not zero."""
        mine, theirs = np.asarray(mine, np.float32)[0], np.asarray(theirs, np.float32)[0]
        size_ = np.linalg.norm(theirs, axis=-1)
        live = size_ > 0
        return float(np.median(np.linalg.norm(mine - theirs, axis=-1)[live] / size_[live])), int(live.sum())

    plain = meta.unbox(params)
    ref = Reference(published, plain)
    d_i = cfg.index_head_dim
    rows_, lanes_ = latent_leaf_shape(cfg.kv_lora_rank, cfg.qk_rope_head_dim)
    want_bytes = (rows_ * lanes_ + d_i) * jnp.dtype(cfg.dtype).itemsize
    keep = cfg.index_topk
    worst_gap, worst_diff, worst_median, ok, caught = 0.0, 0.0, 0.0, True, {}
    differing, blocks_ok = None, False
    for prompt, req in zip(prompts, reqs):
        p, n = len(prompt), len(req.tokens)
        ids = np.concatenate([prompt, np.asarray(req.tokens, np.int32)])[None]
        hidden, router, _ = ref._hidden(ids)            # the whole context, once
        head = lambda rows, r=ref, hid=hidden: np.asarray(r._head(      # noqa: E731
            r.p["model"]["final_norm"], r.p["lm_head"], hid[:, rows])[0], np.float32)
        rows = head(np.arange(p - 1, p - 1 + n))
        toks = np.asarray(req.tokens)
        gaps = rows.max(1) - rows[np.arange(n), toks]
        wrong = rows.max(1) - rows[np.arange(n), (toks + 1) % rows.shape[1]]
        margins = np.asarray(router[0, p - 1:p - 1 + n])
        fine, over, excused = common.judge_gaps(gaps, margins, size.gap_tol, size.near_tie)
        ok = ok and fine and wrong.min() > size.gap_tol
        worst_gap = max(worst_gap, float(gaps[margins >= size.near_tie].max(initial=0.0)))
        starts = range(0, p, size.tail) if p < max(size.prompt_lens) else [p - size.tail]
        blocks = [max(min(lo, p - size.tail), 0) for lo in starts]
        mine = [np.asarray(prefill_rows(params, prompt[None], lo), np.float32) for lo in blocks]
        theirs = [head(np.arange(lo, lo + m.shape[0])) for lo, m in zip(blocks, mine)]
        diffs = np.concatenate([np.abs(m - t).max(1) for m, t in zip(mine, theirs)])
        median, worst = float(np.median(diffs)), float(diffs.max())
        ok = ok and worst <= size.logit_tol and median <= size.typical_tol
        worst_diff, worst_median = max(worst_diff, worst), max(worst_median, median)
        log(f"glm: prompt {p}: decoded tokens' largest reference-logit gap {gaps.max():.4f} ({over} of {n} "
            f"fail {size.gap_tol:g}, {excused} excused by a router margin under {size.near_tie:g} where a held "
            f"expert is at the edge; wrong tokens' smallest gap {wrong.min():.3f}); prefill logits at "
            f"{len(diffs)} positions, |system - reference|: median {median:.4f}, 99th percentile "
            f"{np.percentile(diffs, 99):.4f}, largest {worst:.4f}")
        if p == sorted(size.prompt_lens)[len(size.prompt_lens) // 2]:
            # the middle prompt: selection is at work and an S x S mask fits.
            # The system's layer-0 sets against the reference's, then the
            # controls, each the reference changed in ONE way against the plain one
            sel = ref.selected(prompt[None])[0]
            sparse_rows = np.arange(p) >= keep

            def share_differing(other):
                return float((other[0][sparse_rows] != sel[0][sparse_rows]).sum() / (2.0 * keep * sparse_rows.sum()))

            differing = share_differing(np.asarray(layer0_keep(params, prompt[None])))
            sys_attn, sys_h, sys_routed, sys_latent = blocks_of(params, prompt[None])
            x0 = ref.embed(prompt[None])
            ref_attn, ref_routed = ref.attention_part(0, x0), ref.routed_part(sparse, sys_h)
            ref_latent = ref.latent_part(0, x0)
            (attn_rel, _), (routed_rel, hit) = rel(sys_attn, ref_attn), rel(sys_routed, ref_routed)
            latent_rel = rel(sys_latent, ref_latent)[0]
            blocks_ok = (attn_rel <= size.attn_tol and routed_rel <= size.routed_tol
                         and latent_rel <= size.latent_tol)
            log(f"glm: prompt {p}, layer 0: {100 * differing:.3f}% of the selected columns differ from the "
                f"reference's (rows past {keep}; limit {100 * size.selected_tol:g}%); one block alone, median "
                f"|system - reference| / |reference|: layer 0's attention {attn_rel:.4f} (limit {size.attn_tol:g}), "
                f"layer {sparse}'s routed sum on the system's own input {routed_rel:.4f} at the {hit} of {p} "
                f"positions where a held expert was chosen (limit {size.routed_tol:g}), what layer 0's cache holds "
                f"{latent_rel:.5f} (limit {size.latent_tol:g})")
            controls = (
                ("float8 index keys", dict(index_dtype=jnp.float8_e4m3fn)),
                ("1024 columns kept", dict(topk=keep // 2)),
                ("a float8 latent", dict(latent_dtype=jnp.float8_e4m3fn)),
                ("the bias in the weights", dict(bias_in_weights=True)),
            )
            for name, kw in controls:
                other = Reference(published, plain, **kw)
                h2 = other._hidden(ids)[0]
                rows2 = [np.asarray(other._head(other.p["model"]["final_norm"], other.p["lm_head"],
                                                h2[:, lo:lo + m.shape[0]])[0], np.float32)
                         for lo, m in zip(blocks, mine)]
                d2 = np.concatenate([np.abs(r - t).max(1) for r, t in zip(rows2, theirs)])
                share = share_differing(other.selected(prompt[None])[0])
                a_rel = rel(other.attention_part(0, x0), ref_attn)[0]
                r_rel = rel(other.routed_part(sparse, sys_h), ref_routed)[0]
                l_rel = rel(other.latent_part(0, x0), ref_latent)[0]
                caught[name] = (float(d2.max()) > size.logit_tol or float(np.median(d2)) > size.typical_tol
                                or share > size.selected_tol or a_rel > size.attn_tol or r_rel > size.routed_tol
                                or l_rel > size.latent_tol)
                log(f"glm: control, the reference with {name} against the plain reference: {100 * share:.3f}% of "
                    f"layer 0's selected columns differ; logits at {len(d2)} positions: median {np.median(d2):.4f}, "
                    f"99th percentile {np.percentile(d2, 99):.4f}, largest {d2.max():.4f}; layer 0's attention "
                    f"{a_rel:.4f}, layer {sparse}'s routed sum {r_rel:.4f}, layer 0's cache {l_rel:.5f}: "
                    f"{'outside' if caught[name] else 'INSIDE'} the limits")
    log(f"glm: prefill logits against the reference: largest difference {worst_diff:.4f} (tolerance "
        f"{size.logit_tol:g}), largest median {worst_median:.4f} ({size.typical_tol:g}); largest decoded-token gap "
        f"outside router near-ties {worst_gap:.4f} ({size.gap_tol:g})")
    return {
        **checks,
        "glm_matches_reference": ok,
        "glm_selects_the_references_columns": differing is not None and differing <= size.selected_tol,
        "glm_attention_routed_sum_and_cache_alone_match_reference": blocks_ok,
        "glm_resolved_sparse_latent_fused": resolved == {
            "attention": "flash", "decode_attention": "paged_sparse_latent_fused",
            "paged_attention": "fused", "moe_decode": "held",
        },
        "glm_cache_is_a_tile_and_an_index_key": per_token == want_bytes,
        "glm_float8_index_keys_are_caught": caught.get("float8 index keys", False),
        "glm_1024_kept_is_caught": caught.get("1024 columns kept", False),
        "glm_float8_latent_is_caught": caught.get("a float8 latent", False),
        "glm_bias_in_the_weights_is_caught": caught.get("the bias in the weights", False),
        "kernel_glm_programs": all(kernels.values()) and bool(kernels),
    }


@dataclasses.dataclass(frozen=True)
class TrinitySize:
    """What ``--only trinity`` runs (defaults: the chip run, the published
    widths on the benchmark configuration's cut)."""

    model: object = None          # the configuration's ``model`` group; None = the benchmark configuration's
    max_seq_len: int = 32768
    slots: int = 4
    page: int = 16
    prompt_lens: Tuple[int, ...] = (9146, 4402, 2048)
    tail: int = 256
    new_tokens: int = 32
    pool_tokens: int = 24         # decoded before the window layer's pool is read
    # a window layer's banded forward alone, as DsaSize's byte-masked one
    forward_bucket: int = 16384
    forward_prompts: Tuple[int, ...] = (16384, 9175)
    kernel_calls: int = 20
    # Limits of the comparison with the reference, each between the system's
    # reading and a control's (PERF.md section 6, PR 39, has the readings):
    # the median position's largest |difference| of prefill logits over the
    # vocabulary and the worst position's
    logit_tol: float = 1.0
    typical_tol: float = 0.1
    # ONE block alone, which the layers after it cannot blur: the median
    # position's |system - reference| / |reference| (L2 over the hidden
    # vector) of what a window layer's and the full layer's attention add to
    # the stream (positions past the window), of the held experts' routed sum
    # in the first sparse layer on the SYSTEM's own input, and of what a window
    # layer's pool holds of a served context
    attn_tol: float = 0.03
    routed_tol: float = 0.015
    cache_tol: float = 0.01
    # the decoded tokens' gap and the router near-tie that excuses one: the
    # benchmark configuration's (its ``reference_check.why`` has the readings)
    gap_tol: float = 0.065
    near_tie: float = 0.006


def trinity_phase(size: TrinitySize, seed: int) -> Dict[str, bool]:
    """Trinity-Large-Preview alone: window and full attention layers in one
    paged cache, against the plain reference, each mechanism held on one block
    against its control. Not part of the default run. First a window
    layer's banded forward alone against PR 49's kernel
    (:func:`grouped_forward_pair`; ``--bundles`` as ``dsa``); then the language model at
    its published widths on the benchmark configuration's cut
    (``perfbench/configs/trinity-large-serve.json``: a dense window layer and
    one period of three window layers and a full one, 32 of 256 experts held,
    a slice of the vocabulary) through a ``ServingEngine`` of 32,768-column
    slots whose paged cache has a block table and a pool a layer KIND:
    prompts of 9,146, 4,402 and 2,048 tokens are prefilled (the banded flash
    forward on the window layers), admitted shortest first and an engine step
    apart so that each longer prompt's admission jumps the shared cursor over
    the slots already decoding, and 32 tokens decoded over those gap columns
    through the kernel that walks the blocks a slot maps. Against
    ``perfbench/references/afmoe.py``, which is given the same share: prefill
    logits and the reference's logit of every decoded token; then each
    mechanism held on ONE block, every limit between the system's reading and
    a control's: a window layer's attention (| no window | a window of 2048 |
    the gate left out), the full layer's on the system's own input (| rotary
    applied), what a window layer's POOL holds of a served context after
    pages were freed behind the window (| a float8 cache), and the held
    experts' routed sum (| the bias added to the weights)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax.core import meta

    from neuronx_distributed_tpu.inference import GenerationConfig
    from neuronx_distributed_tpu.models.afmoe import SLIDING, AfmoeModel
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib
    from neuronx_distributed_tpu.serving import ServingEngine
    from perfbench.families import afmoe as family
    from perfbench.references import common
    from perfbench.references.afmoe import Reference

    mesh_lib.destroy_model_parallel()
    published = _published(size, "trinity-large-serve.json")
    model = family.build(published, runner="serve", max_seq_len=size.max_seq_len)
    if size.model is not None:    # the CPU rehearsal serves in float32
        model = model.clone(config=dataclasses.replace(model.config, dtype=jnp.float32))
    cfg = model.config
    window, ps = cfg.sliding_window, size.page
    forward = grouped_forward_pair(
        "trinity", (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.head_dim), window, size.forward_bucket,
        size.forward_prompts, size.kernel_calls, cfg.dtype, seed)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))
    jax.block_until_ready(params)
    plain = meta.unbox(params)
    ref = Reference(published, plain)
    engine = ServingEngine(model, params, num_slots=size.slots, kv_page_size=ps)
    prompts = _prompts(size.prompt_lens, int(published["vocab_size"]), seed + 7)
    middle = prompts[0]           # the longest: past two windows at the chip's size
    window_layer = cfg.layer_types.index(SLIDING)
    if window_layer != 0:
        raise NotImplementedError("the window layer compared alone is the stack's first: its input is the embedding")
    full_layer = next(i for i, k in enumerate(cfg.layer_types) if k != SLIDING)
    sparse = cfg.num_dense_layers   # the first sparse layer

    def rel(mine, theirs, rows=slice(None)):
        """Median over positions of |mine - theirs| / |theirs| (L2 over the
        last axes), where ``theirs`` is not zero."""
        mine = np.asarray(mine, np.float32)[0][rows].reshape(-1, np.prod(np.shape(mine)[2:]))
        theirs = np.asarray(theirs, np.float32)[0][rows].reshape(mine.shape)
        size_ = np.linalg.norm(theirs, axis=-1)
        live = size_ > 0
        return float(np.median(np.linalg.norm(mine - theirs, axis=-1)[live] / size_[live])), int(live.sum())

    # --- what a window layer's POOL holds of a served context, pages freed on the way
    req = engine.submit(middle, GenerationConfig(max_new_tokens=size.pool_tokens + 8, temperature=0.0),
                        key=jax.random.PRNGKey(seed))
    while len(req.tokens) < size.pool_tokens:
        engine.step()
    mgr = engine.cache
    slot, cursor = req.slot, mgr.cursor
    start, floor = mgr._slot_start[slot], mgr._window_floor(slot)
    table = mgr._tables_w[slot].copy()
    mapped = np.flatnonzero(table)
    freed_ok = (mapped.min() == floor // ps and len(mapped) <= mgr.window_pages_per_slot
                and mgr.window_pages_freed_total > 0 and (mgr._tables[slot] != 0).sum() > len(mapped))
    pool = np.asarray(jax.device_get(
        mgr.cache["pool"]["model"][f"layers_{window_layer}"]["attn"]["kv"]).astype(jnp.float32))
    context = np.concatenate([middle, np.asarray(req.tokens, np.int32)])
    cols = np.arange(floor, cursor)                       # the columns the next query attends
    held = pool[table[cols // ps], cols % ps][None]       # (1, n, 2 Hkv, D)
    tokens = cols - start
    x_ctx = ref.embed(context[None])
    sys_cache = rel(held, np.asarray(ref.cache_part(window_layer, x_ctx))[:, tokens])[0]
    f8_cache = rel(np.asarray(Reference(published, plain, kv_dtype=jnp.float8_e4m3fn).cache_part(
        window_layer, x_ctx))[:, tokens], np.asarray(ref.cache_part(window_layer, x_ctx))[:, tokens])[0]
    log(f"trinity: a context of {len(context)} tokens, cursor {cursor}: the window kind's table maps "
        f"{len(mapped)} pages of slot {slot} (the full kind's {int((mgr._tables[slot] != 0).sum())}; "
        f"{mgr.window_pages_freed_total} freed so far; at most {mgr.window_pages_per_slot} a slot), the first at "
        f"the window's floor {floor}: {'as the window says' if freed_ok else 'NOT as the window says'}; what "
        f"layer {window_layer}'s pool holds of the {len(cols)} columns the next query attends, median |pool - "
        f"reference| / |reference| {sys_cache:.5f} (limit {size.cache_tol:g}); control, a float8 cache: {f8_cache:.5f}")
    engine.run()
    engine.step()     # drained: the cursor rewinds, so the prompts below are admitted from column 0
    del pool, held

    # shortest first and an engine step apart: each longer prompt's admission
    # jumps the shared cursor and leaves gap columns inside the contexts
    # already decoding
    gcfg = GenerationConfig(max_new_tokens=size.new_tokens, temperature=0.0)
    t0, reqs = time.perf_counter(), []
    for i, prompt in enumerate(prompts[::-1]):
        reqs.append(engine.submit(prompt, gcfg, key=jax.random.PRNGKey(seed + i)))
        engine.step()
    engine.run()
    wall = time.perf_counter() - t0
    if any(len(r.tokens) != size.new_tokens for r in reqs):
        raise RuntimeError(f"trinity: tokens {[len(r.tokens) for r in reqs]} of {size.new_tokens} (halt {engine.halt_reason!r})")
    reqs, jumps = reqs[::-1], engine.cache.cursor_jumps_total
    resolved = dict(engine.programs.resolved)
    kernels = _ledger_kernels(engine.programs, _hot_programs(engine))
    per_token = engine.metrics.snapshot()["kv_bytes_per_token_layer"]
    log(f"trinity: {len(reqs)} requests, prompts {list(size.prompt_lens)} + {size.new_tokens} tokens in "
        f"{wall:.1f}s; resolved {resolved}; {KERNEL} in compiled programs: {kernels}; cache {per_token:g} B a "
        f"token a layer, pools {engine.cache.nbytes / 2**30:.2f} GiB; held experts {cfg.held_experts} of "
        f"{cfg.num_experts}, vocabulary {cfg.vocab_size}; window pages freed {engine.cache.window_pages_freed_total}; "
        f"{jumps} cursor jumps left gap columns in a held context")
    engine.cache.check()
    leak_free = (engine.cache.alloc_w.free_pages == engine.cache.alloc_w.num_pages - 1
                 and engine.cache.alloc.free_pages == engine.cache.alloc.num_pages - 1)
    engine = mgr = None
    gc.collect()

    backbone = AfmoeModel(cfg, model.attention_impl, mode="prefill")

    prefill_rows = _prefill_rows(backbone, size.tail)

    @jax.jit
    def blocks_of(params, ids):
        """From the SYSTEM's prefill: what the window layer's and the full
        layer's attention add to the stream, the full layer's input, and the
        first sparse layer's normed input and routed sum (held experts)."""
        def wanted(mdl, _):
            path = "/".join(mdl.path) + "/"
            return ((mdl.name == "post_attn_norm" and (f"layers_{window_layer}/" in path or f"layers_{full_layer}/" in path))
                    or mdl.name == f"layers_{full_layer - 1}"
                    or (mdl.name in ("pre_mlp_norm", "experts") and f"layers_{sparse}/" in path))

        _, state = backbone.apply(
            {"params": params["params"]["model"]}, ids, mutable=["cache", "intermediates"],
            capture_intermediates=wanted)
        got = state["intermediates"]
        layer = got[f"layers_{sparse}"]
        return (got[f"layers_{window_layer}"]["post_attn_norm"]["__call__"][0],
                got[f"layers_{full_layer}"]["post_attn_norm"]["__call__"][0],
                got[f"layers_{full_layer - 1}"]["__call__"][0][0],
                layer["pre_mlp_norm"]["__call__"][0],
                layer["moe"]["experts"]["__call__"][0].reshape(ids.shape + (-1,)))

    def tiled(prompt):
        """``prompt`` (S,) as a batch of one, padded on the right to whole
        tiles of 512 (the engine's buckets are; the full layers' flash forward
        takes no other length): no row reads a key after it."""
        return np.pad(prompt, (0, -len(prompt) % 512))[None]

    worst_gap, worst_diff, worst_median, ok, caught = 0.0, 0.0, 0.0, True, {}
    blocks_ok = False
    for prompt, req in zip(prompts, reqs):
        p, n = len(prompt), len(req.tokens)
        ids = np.concatenate([prompt, np.asarray(req.tokens, np.int32)])[None]
        hidden, router = ref._hidden(ids)               # the whole context, once
        head = lambda rows, r=ref, hid=hidden: np.asarray(r._head(      # noqa: E731
            r.p["model"]["final_norm"], r.p["lm_head"], hid[:, rows])[0], np.float32)
        rows = head(np.arange(p - 1, p - 1 + n))
        toks = np.asarray(req.tokens)
        gaps = rows.max(1) - rows[np.arange(n), toks]
        wrong = rows.max(1) - rows[np.arange(n), (toks + 1) % rows.shape[1]]
        margins = np.asarray(router[0, p - 1:p - 1 + n])
        fine, over, excused = common.judge_gaps(gaps, margins, size.gap_tol, size.near_tie)
        ok = ok and fine and wrong.min() > size.gap_tol
        worst_gap = max(worst_gap, float(gaps[margins >= size.near_tie].max(initial=0.0)))
        starts = range(0, p, size.tail) if p < max(size.prompt_lens) else [p - size.tail]
        blocks = [max(min(lo, p - size.tail), 0) for lo in starts]
        mine = [np.asarray(prefill_rows(params, tiled(prompt), lo), np.float32) for lo in blocks]
        theirs = [head(np.arange(lo, lo + m.shape[0])) for lo, m in zip(blocks, mine)]
        diffs = np.concatenate([np.abs(m - t).max(1) for m, t in zip(mine, theirs)])
        median, worst = float(np.median(diffs)), float(diffs.max())
        ok = ok and worst <= size.logit_tol and median <= size.typical_tol
        worst_diff, worst_median = max(worst_diff, worst), max(worst_median, median)
        log(f"trinity: prompt {p}: decoded tokens' largest reference-logit gap {gaps.max():.4f} ({over} of {n} "
            f"fail {size.gap_tol:g}, {excused} excused by a router margin under {size.near_tie:g} where a held "
            f"expert is at the edge; wrong tokens' smallest gap {wrong.min():.3f}); prefill logits at "
            f"{len(diffs)} positions, |system - reference|: median {median:.4f}, 99th percentile "
            f"{np.percentile(diffs, 99):.4f}, largest {worst:.4f}")
        if prompt is middle:
            # each mechanism on ONE block: the system against the reference,
            # then the controls, each the reference changed in ONE way
            past = slice(min(window, p - 1), None)      # the rows whose window is cut
            sys_win, sys_full, x_full, sys_h, sys_routed = (a[:, :p] for a in blocks_of(params, tiled(prompt)))
            x0 = ref.embed(prompt[None])
            ref_win = ref.attention_part(window_layer, x0)
            ref_full = ref.attention_part(full_layer, x_full)
            ref_routed = ref.routed_part(sparse, sys_h)
            (win_rel, _), (full_rel, _) = rel(sys_win, ref_win, past), rel(sys_full, ref_full, past)
            routed_rel, hit = rel(sys_routed, ref_routed)
            blocks_ok = (win_rel <= size.attn_tol and full_rel <= size.attn_tol
                         and routed_rel <= size.routed_tol and sys_cache <= size.cache_tol)
            log(f"trinity: prompt {p}, one block alone, median |system - reference| / |reference| over the "
                f"positions past the window: layer {window_layer}'s (window) attention {win_rel:.4f}, layer "
                f"{full_layer}'s (full) on the system's own input {full_rel:.4f} (limit {size.attn_tol:g}); layer "
                f"{sparse}'s routed sum on the system's own input {routed_rel:.4f} at the {hit} of {p} positions "
                f"where a held expert was chosen (limit {size.routed_tol:g})")
            controls = (
                ("no window", dict(window="none"), "win"),
                ("a window of half the width", dict(window=window // 2), "win"),
                ("the gate left out", dict(gate=False), "win"),
                ("rotary on the full layer", dict(rope_full=True), "full"),
                ("the bias in the weights", dict(bias_in_weights=True), "routed"),
            )
            for name, kw, block in controls:
                other = Reference(published, plain, **kw)
                if block == "win":
                    reading, limit = rel(other.attention_part(window_layer, x0), ref_win, past)[0], size.attn_tol
                elif block == "full":
                    reading, limit = rel(other.attention_part(full_layer, x_full), ref_full, past)[0], size.attn_tol
                else:
                    reading, limit = rel(other.routed_part(sparse, sys_h), ref_routed)[0], size.routed_tol
                caught[name] = reading > limit
                log(f"trinity: control, the reference with {name} against the plain reference, that block: "
                    f"{reading:.4f} (limit {limit:g}): {'outside' if caught[name] else 'INSIDE'} the limit")
            caught["a float8 cache"] = f8_cache > size.cache_tol
    log(f"trinity: prefill logits against the reference: largest difference {worst_diff:.4f} (tolerance "
        f"{size.logit_tol:g}), largest median {worst_median:.4f} ({size.typical_tol:g}); largest decoded-token gap "
        f"outside router near-ties {worst_gap:.4f} ({size.gap_tol:g})")
    return {
        **forward,
        "trinity_matches_reference": ok,
        "trinity_window_full_routed_and_pool_alone_match_reference": blocks_ok,
        "trinity_frees_pages_behind_the_window_and_leaks_none": bool(freed_ok) and leak_free,
        # every longer prompt's admission jumped the cursor over a decoding slot:
        # the tokens compared above were decoded over gap columns
        "trinity_cursor_jumps_leave_gap_columns": jumps >= len(prompts) - 1,
        "trinity_resolved_paged_walk_fused": resolved == {
            "attention": "flash", "decode_attention": "paged_walk_fused",
            "paged_attention": "fused", "moe_decode": "held",
        },
        "trinity_cache_is_k_and_v_of_every_kv_head": per_token == 2 * cfg.num_kv_heads * cfg.head_dim
        * jnp.dtype(cfg.dtype).itemsize,
        "trinity_no_window_is_caught": caught.get("no window", False),
        "trinity_half_window_is_caught": caught.get("a window of half the width", False),
        "trinity_missing_gate_is_caught": caught.get("the gate left out", False),
        "trinity_rotary_on_the_full_layer_is_caught": caught.get("rotary on the full layer", False),
        "trinity_float8_cache_is_caught": caught.get("a float8 cache", False),
        "trinity_bias_in_the_weights_is_caught": caught.get("the bias in the weights", False),
        "kernel_trinity_programs": all(kernels.values()) and bool(kernels),
    }


def _serve_shortest_first(tag: str, engine, prompts, new_tokens: int, seed: int):
    """``(requests in ``prompts``' order, cursor jumps, wall seconds)``: the
    prompts (longest first in ``prompts``) admitted SHORTEST first and an
    engine step apart, so that each longer prompt's admission jumps the shared
    cursor over the slots already decoding and leaves gap columns between a
    decoding slot's next token and its predecessor; then run to the end."""
    import jax

    from neuronx_distributed_tpu.inference import GenerationConfig

    gcfg = GenerationConfig(max_new_tokens=new_tokens, temperature=0.0)
    t0, reqs, jumps = time.perf_counter(), [], 0
    for i, prompt in enumerate(prompts[::-1]):
        before = engine.cache.cursor
        reqs.append(engine.submit(prompt, gcfg, key=jax.random.PRNGKey(seed + i)))
        engine.step()
        # past what the step's own chunk writes, under a slot already decoding
        jumps += bool(i and engine.cache.cursor - before > engine.decode_chunk_size)
    engine.run()
    wall = time.perf_counter() - t0
    if any(len(r.tokens) != new_tokens for r in reqs):
        raise RuntimeError(f"{tag}: tokens {[len(r.tokens) for r in reqs]} of {new_tokens} (halt {engine.halt_reason!r})")
    return reqs[::-1], jumps, wall


def _decoded_token_gaps(tag: str, ref, prompts, reqs, gap_tol: float, near_tie: float):
    """``(every decoded token within ``gap_tol`` of the reference's largest
    logit but where its router's margin is under ``near_tie``, and every wrong
    token outside it; the largest gap not excused)``, a line a request."""
    from perfbench.references import common

    ok, worst_gap = True, 0.0
    for prompt, req in zip(prompts, reqs):
        pad_to = -(-(len(prompt) + len(req.tokens)) // 128) * 128
        gaps, wrong, _, margins = common.emitted_token_gaps(ref, prompt, req.tokens, pad_to)
        fine, over, excused = common.judge_gaps(gaps, margins, gap_tol, near_tie)
        ok = ok and fine and wrong.min() > gap_tol
        judged = gaps if margins is None else gaps[margins >= near_tie]    # a reference without a router excuses nothing
        worst_gap = max(worst_gap, float(judged.max(initial=0.0)))
        log(f"{tag}: prompt {len(prompt)}: decoded tokens' largest reference-logit gap {gaps.max():.4f} ({over} of "
            f"{len(gaps)} fail {gap_tol:g}, {excused} excused by a router margin under {near_tie:g}; wrong "
            f"tokens' smallest gap {wrong.min():.3f})")
    return ok, worst_gap


@dataclasses.dataclass(frozen=True)
class ZayaSize:
    """What ``--only zaya`` runs (defaults: the chip run, the published widths
    on the benchmark configuration's cut)."""

    model: object = None          # the configuration's ``model`` group; None = the benchmark configuration's
    max_seq_len: int = 16384
    slots: int = 4
    page: int = 16
    prompt_lens: Tuple[int, ...] = (3000, 1100, 300)
    new_tokens: int = 32
    # ONE block alone, which the layers after it cannot blur: the median
    # position's |system - reference| / |reference| (L2 over the vector) of the
    # stream after layer 0 and after layer 1 and of layer 1's router state,
    # layer 1 on the SYSTEM's own input; each limit between the system's
    # reading and a control's (PERF.md section 6, PR 47, has the readings)
    block_tol: float = 0.02
    state_tol: float = 0.02
    # the decoded tokens' gap and the router near-tie that excuses one: the
    # benchmark configuration's (its ``reference_check.why`` has the readings)
    gap_tol: float = 0.1
    near_tie: float = 0.002


def zaya_phase(size: ZayaSize, seed: int) -> Dict[str, bool]:
    """ZAYA1-8B alone: attention in a compressed latent whose convolutions and
    value shift read a per-slot state beside the paged cache, and a router with
    a state of its own through the layers, against the plain reference, each
    mechanism held on one block against its control. Not part of the default
    run. The language model at its published widths on the benchmark
    configuration's cut (``perfbench/configs/zaya1-8b-serve.json``) through a
    ``ServingEngine`` whose paged cache holds 1,024 B a token a layer and whose
    slots each keep 5,376 B of state a layer: prompts of 3,000, 1,100 and 300
    tokens are prefilled, admitted shortest first and an engine step apart so
    that each longer prompt's admission jumps the shared cursor over the slots
    already decoding (their next token then lies columns away from its
    predecessor), and 32 tokens decoded through the state and the kernel that
    walks the blocks a slot maps. Against ``perfbench/references/zaya.py``
    (no cache, no state): the reference's logit of every decoded token; then
    layers 0 and 1 each alone, every limit between the system's reading and a
    control's: the stream after layer 0 (| no convolution | the second value
    head unshifted | the whole reference in float8), layer 1's router state on
    the system's own input (| no state mixed in)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax.core import meta

    from neuronx_distributed_tpu.models.zaya import ZayaModel
    from neuronx_distributed_tpu.modules.attention import slot_state_bytes_per_layer
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib
    from neuronx_distributed_tpu.serving import ServingEngine
    from perfbench import cca_costs
    from perfbench.families import zaya as family
    from perfbench.references.zaya import Reference

    mesh_lib.destroy_model_parallel()
    published = _published(size, "zaya1-8b-serve.json")
    model = family.build(published, runner="serve", max_seq_len=size.max_seq_len)
    if size.model is not None:    # the CPU rehearsal serves in float32
        model = model.clone(config=dataclasses.replace(model.config, dtype=jnp.float32))
    cfg = model.config
    params = jax.jit(model.init)(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))
    jax.block_until_ready(params)
    plain = meta.unbox(params)
    ref = Reference(published, plain)
    engine = ServingEngine(model, params, num_slots=size.slots, kv_page_size=size.page)
    prompts = _prompts(size.prompt_lens, int(published["vocab_size"]), seed + 7)

    reqs, jumps, wall = _serve_shortest_first("zaya", engine, prompts, size.new_tokens, seed)
    resolved = dict(engine.programs.resolved)
    kernels = _ledger_kernels(engine.programs, _hot_programs(engine))
    per_token = engine.metrics.snapshot()["kv_bytes_per_token_layer"]
    state_bytes = slot_state_bytes_per_layer(engine.cache.cache)
    log(f"zaya: {len(reqs)} requests, prompts {list(size.prompt_lens)} + {size.new_tokens} tokens in {wall:.1f}s; "
        f"resolved {resolved}; {KERNEL} in compiled programs: {kernels}; cache {per_token:g} B a token a layer, "
        f"state {state_bytes:g} B a slot a layer, pool {engine.cache.nbytes / 2**30:.2f} GiB; {jumps} cursor jumps "
        f"left gap columns in a held context")
    engine.cache.check()
    leak_free = engine.cache.alloc.free_pages == engine.cache.alloc.num_pages - 1
    engine = None
    gc.collect()

    ok, worst_gap = _decoded_token_gaps("zaya", ref, prompts, reqs, size.gap_tol, size.near_tie)

    backbone = ZayaModel(cfg, model.attention_impl, mode="prefill")

    @jax.jit
    def two_layers(params, ids):
        """From the SYSTEM's prefill: ``(stream, router state)`` after layer 0 and after layer 1."""
        _, state = backbone.apply(
            {"params": params["params"]["model"]}, ids, mutable=["cache", "intermediates"],
            capture_intermediates=lambda mdl, _: mdl.name in ("layers_0", "layers_1"))
        got = state["intermediates"]
        return tuple(got[f"layers_{i}"]["__call__"][0][:2] for i in (0, 1))

    def rel(mine, theirs):
        """Median over positions of |mine - theirs| / |theirs| (L2 over the last axis)."""
        mine, theirs = (np.asarray(a, np.float32)[0] for a in (mine, theirs))
        return float(np.median(np.linalg.norm(mine - theirs, axis=-1) / np.linalg.norm(theirs, axis=-1)))

    prompt = prompts[0]
    p = len(prompt)
    tiled = np.pad(prompt, (0, -p % 512))[None]          # whole tiles, as the engine's buckets are
    (x0_sys, r0_sys), (x1_sys, r1_sys) = (tuple(a[:, :p] for a in pair) for pair in two_layers(params, tiled))
    emb = ref.embed(prompt[None])
    x0_ref = ref.block(0, emb)[0]
    x1_ref, r1_ref = ref.block(1, x0_sys, r0_sys)[:2]
    readings = {"layer 0's stream": (rel(x0_sys, x0_ref), size.block_tol),
                "layer 1's stream on the system's own input": (rel(x1_sys, x1_ref), size.block_tol),
                "layer 1's router state on the system's own input": (rel(r1_sys, r1_ref), size.state_tol)}
    blocks_ok = all(reading <= limit for reading, limit in readings.values())
    log(f"zaya: prompt {p}, one block alone, median |system - reference| / |reference|: "
        + "; ".join(f"{name} {reading:.5f} (limit {limit:g})" for name, (reading, limit) in readings.items()))
    controls = (
        ("no convolution", dict(conv="none"), 0), ("the second value head unshifted", dict(value_shift=False), 0),
        ("the whole reference in float8", dict(dtype=jnp.float8_e4m3fn), 0), ("no state mixed in", dict(eda=False), 1),
    )
    caught = {}
    for name, kw, layer in controls:
        other = Reference(published, plain, **kw)
        if layer == 0:
            reading, limit, what = rel(other.block(0, emb)[0], x0_ref), size.block_tol, "layer 0's stream"
        else:
            reading, limit, what = rel(other.block(1, x0_sys, r0_sys)[1], r1_ref), size.state_tol, "layer 1's router state"
        caught[name] = reading > limit
        log(f"zaya: control, the reference with {name} against the plain reference, {what}: {reading:.5f} "
            f"(limit {limit:g}): {'outside' if caught[name] else 'INSIDE'} the limit")
    log(f"zaya: largest decoded-token gap outside router near-ties {worst_gap:.4f} ({size.gap_tol:g})")
    widths = dict(num_q_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim)
    itemsize = jnp.dtype(cfg.dtype).itemsize
    return {
        "zaya_matches_reference": ok,
        "zaya_two_blocks_alone_match_reference": blocks_ok,
        # every longer prompt's admission jumped the cursor over a decoding slot:
        # the tokens compared above were decoded columns away from their predecessors
        "zaya_cursor_jumps_leave_gap_columns": jumps >= len(prompts) - 1,
        "zaya_leaks_no_page": leak_free,
        "zaya_resolved_paged_walk_fused": {k: resolved[k] for k in ("attention", "decode_attention", "paged_attention")} == {
            "attention": "flash", "decode_attention": "paged_walk_fused", "paged_attention": "fused"},
        "zaya_cache_is_a_kib_a_token_and_the_state_five_and_a_quarter_a_slot": (
            per_token == 2 * cfg.num_kv_heads * cfg.head_dim * itemsize
            and state_bytes == cca_costs.slot_state_bytes(**widths, act_bytes=itemsize)),
        "zaya_no_convolution_is_caught": caught["no convolution"],
        "zaya_unshifted_value_head_is_caught": caught["the second value head unshifted"],
        "zaya_float8_is_caught": caught["the whole reference in float8"],
        "zaya_no_router_state_is_caught": caught["no state mixed in"],
        "kernel_zaya_programs": all(kernels.values()) and bool(kernels),
    }


@dataclasses.dataclass(frozen=True)
class SolarSize:
    """What ``--only solar`` runs (defaults: the chip run, the published widths
    on the benchmark configuration's cut)."""

    model: object = None          # the configuration's ``model`` group; None = the benchmark configuration's
    max_seq_len: int = 16384
    slots: int = 4
    page: int = 16
    prompt_lens: Tuple[int, ...] = (3000, 1100, 300)
    new_tokens: int = 32
    # ONE mixer alone, on the SYSTEM's own input, which the layers after it
    # cannot blur: the median position's |system - reference| / |reference| (L2
    # over the hidden vector) of what layer 1's linear attention and layer 0's
    # GQA attention add to the stream; each limit between the system's reading
    # and the controls' (PERF.md section 6, PR 51, has the readings)
    linear_tol: float = 0.03
    full_tol: float = 0.03
    # the decoded tokens' gap and the router near-tie that excuses one: the
    # benchmark configuration's (its ``reference_check.why`` has the readings)
    gap_tol: float = 0.15
    near_tie: float = 0.02


def solar_phase(size: SolarSize, seed: int) -> Dict[str, bool]:
    """Solar-Open2-250B alone: gated delta-rule linear-attention layers whose
    float32 state lives per slot beside the paged K/V of the gated NoPE GQA
    layers, against the plain reference (the token-by-token recurrence), each
    mechanism held on one block against its control. Not part of the default
    run. The language model at its published widths on the benchmark
    configuration's cut (``perfbench/configs/solar-open2-250b-serve.json``)
    through a ``ServingEngine``: prompts of 3,000, 1,100 and 300 tokens are
    prefilled through the chunked kernel (left-padded in their buckets),
    admitted shortest first and an engine step apart so that each longer
    prompt's admission jumps the shared cursor over the slots already decoding,
    and 32 tokens decoded through the state in place. Against
    ``perfbench/references/solar_open2.py``: the reference's logit of every
    decoded token; then ONE mixer alone on the system's own input, each limit
    between the system's reading and a control's: layer 1's linear attention
    (| the decay off | beta without its factor 2 | the convolutions removed |
    the output gate left out | the state kept in float8 | the whole reference
    in float8; a state kept in bf16 is read and not judged: it is at the bf16
    system's own rounding), layer 0's GQA attention
    (| rotary applied | the gate left out); and the compiled decode chunk
    copies no array of the state's size."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax.core import meta

    from neuronx_distributed_tpu.models.solar_open2 import SolarOpen2Model
    from neuronx_distributed_tpu.modules.attention import slot_state_bytes_per_layer
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib
    from neuronx_distributed_tpu.serving import ServingEngine
    from perfbench import kda_costs
    from perfbench.families import solar_open2 as family
    from perfbench.references.solar_open2 import Reference

    mesh_lib.destroy_model_parallel()
    published = _published(size, "solar-open2-250b-serve.json")
    model = family.build(published, runner="serve", max_seq_len=size.max_seq_len)
    if size.model is not None:    # the CPU rehearsal serves in float32
        model = model.clone(config=dataclasses.replace(model.config, dtype=jnp.float32))
    cfg = model.config
    params = jax.jit(model.init)(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))
    jax.block_until_ready(params)
    plain = meta.unbox(params)
    ref = Reference(published, plain)
    engine = ServingEngine(model, params, num_slots=size.slots, kv_page_size=size.page)
    prompts = _prompts(size.prompt_lens, int(published["vocab_size"]), seed + 7)

    reqs, jumps, wall = _serve_shortest_first("solar", engine, prompts, size.new_tokens, seed)
    resolved = dict(engine.programs.resolved)
    kernels = _ledger_kernels(engine.programs, _hot_programs(engine))
    per_token = engine.metrics.snapshot()["kv_bytes_per_token_layer"]
    state_bytes = slot_state_bytes_per_layer(engine.cache.cache)
    # the decode chunk as compiled: its temporaries, and whether any instruction copies an array of one layer's
    # state (the state rides the scan's carry and the kernel in place)
    chunk = engine.programs.programs()["decode_chunk"].variants[0].lower().compile()
    temp = chunk.memory_analysis().temp_size_in_bytes
    one_state = size.slots * cfg.linear_num_heads * cfg.linear_head_dim ** 2 * 4
    state_shape = f"f32[{size.slots},{cfg.linear_num_heads},{cfg.linear_head_dim},{cfg.linear_head_dim}]"
    state_copies = [ln for ln in chunk.as_text().splitlines() if f"= {state_shape}" in ln and " copy(" in ln]
    log(f"solar: {len(reqs)} requests, prompts {list(size.prompt_lens)} + {size.new_tokens} tokens in {wall:.1f}s; "
        f"resolved {resolved}; {KERNEL} in compiled programs: {kernels}; cache {per_token:g} B a token a GQA layer, "
        f"state {state_bytes:g} B a slot a linear layer, pool {engine.cache.nbytes / 2**30:.2f} GiB; {jumps} cursor "
        f"jumps; the decode chunk's temporaries {temp / 2**20:.1f} MiB, one layer's state {one_state / 2**20:.1f} MiB, "
        f"{len(state_copies)} copies of an array of its shape")
    engine.cache.check()
    leak_free = engine.cache.alloc.free_pages == engine.cache.alloc.num_pages - 1
    engine = chunk = None
    gc.collect()

    ok, worst_gap = _decoded_token_gaps("solar", ref, prompts, reqs, size.gap_tol, size.near_tie)

    backbone = SolarOpen2Model(cfg, model.attention_impl, mode="prefill")
    wanted = ("layers_0", "attn", "linear_attn")

    @jax.jit
    def mixers(params, ids, mask):
        """From the SYSTEM's prefill (the chunked kernel, a left-padded bucket):
        what layer 0's GQA attention adds, the stream after layer 0, what layer
        1's linear attention adds to it."""
        _, state = backbone.apply(
            {"params": params["params"]["model"]}, ids, padding_mask=mask, mutable=["cache", "intermediates"],
            capture_intermediates=lambda mdl, _: mdl.name in wanted)
        got = state["intermediates"]
        return (got["layers_0"]["attn"]["__call__"][0], got["layers_0"]["__call__"][0][0],
                got["layers_1"]["linear_attn"]["__call__"][0])

    def rel(mine, theirs):
        """Median over positions of |mine - theirs| / |theirs| (L2 over the last axis)."""
        mine, theirs = (np.asarray(a, np.float32)[0] for a in (mine, theirs))
        return float(np.median(np.linalg.norm(mine - theirs, axis=-1) / np.linalg.norm(theirs, axis=-1)))

    prompt = prompts[0]
    p = len(prompt)
    pad = -p % 512                                        # whole tiles, LEFT-padded as the engine's buckets are
    ids = np.pad(prompt, (pad, 0))[None]
    mask = (np.arange(p + pad) >= pad)[None]
    full_sys, x0_sys, linear_sys = (a[:, pad:] for a in mixers(params, ids, mask))
    emb = ref.embed(prompt[None])
    full_ref, linear_ref = ref.mixer_part(0, emb), ref.mixer_part(1, x0_sys)
    readings = {"layer 0's GQA attention": (rel(full_sys, full_ref), size.full_tol),
                "layer 1's linear attention on the system's own input": (rel(linear_sys, linear_ref), size.linear_tol)}
    blocks_ok = all(reading <= limit for reading, limit in readings.values())
    log(f"solar: prompt {p} (left-padded by {pad}), one mixer alone, median |system - reference| / |reference|: "
        + "; ".join(f"{name} {reading:.5f} (limit {limit:g})" for name, (reading, limit) in readings.items()))
    controls = (
        ("the decay off", dict(decay=False), 1), ("beta without its factor 2", dict(beta_factor=1.0), 1),
        ("the convolutions removed", dict(conv=False), 1), ("the output gate left out", dict(gate=False), 1),
        ("the state kept in float8", dict(state_dtype=jnp.float8_e4m3fn), 1),
        # read and not judged: a bf16 state is at the bf16 system's own rounding (PERF.md section 6, PR 51)
        ("the state kept in bf16", dict(state_dtype=jnp.bfloat16), 1),
        ("the whole reference in float8", dict(dtype=jnp.float8_e4m3fn), 1),
        ("rotary applied", dict(rope_full=True), 0), ("the GQA gate left out", dict(gate=False), 0),
    )
    caught = {}
    for name, kw, layer in controls:
        other = Reference(published, plain, **kw)
        if layer == 1:
            reading, limit = rel(other.mixer_part(1, x0_sys), linear_ref), size.linear_tol
        else:
            reading, limit = rel(other.mixer_part(0, emb), full_ref), size.full_tol
        caught[name] = reading > limit
        log(f"solar: control, the reference with {name} against the plain reference, layer {layer}'s mixer: "
            f"{reading:.5f} (limit {limit:g}): {'outside' if caught[name] else 'INSIDE'} the limit")
    log(f"solar: largest decoded-token gap outside router near-ties {worst_gap:.4f} ({size.gap_tol:g})")
    itemsize = jnp.dtype(cfg.dtype).itemsize
    return {
        "solar_matches_reference": ok,
        "solar_two_mixers_alone_match_reference": blocks_ok,
        "solar_cursor_jumps_leave_gap_columns": jumps >= len(prompts) - 1,
        "solar_leaks_no_page": leak_free,
        "solar_resolved_paged_walk_fused": {k: resolved[k] for k in ("attention", "decode_attention", "paged_attention")} == {
            "attention": "flash", "decode_attention": "paged_walk_fused", "paged_attention": "fused"},
        "solar_cache_is_four_kib_a_token_and_the_state_four_mib_a_slot": (
            per_token == 2 * cfg.num_kv_heads * cfg.head_dim * itemsize
            and state_bytes == kda_costs.slot_state_bytes(
                heads=cfg.linear_num_heads, head_dim=cfg.linear_head_dim, taps=cfg.conv_kernel - 1, act_bytes=itemsize)),
        "solar_decode_chunk_copies_no_array_of_the_states_size": not state_copies,
        "solar_decay_off_is_caught": caught["the decay off"],
        "solar_beta_without_its_factor_is_caught": caught["beta without its factor 2"],
        "solar_no_convolution_is_caught": caught["the convolutions removed"],
        "solar_no_output_gate_is_caught": caught["the output gate left out"],
        "solar_float8_state_is_caught": caught["the state kept in float8"],
        "solar_float8_is_caught": caught["the whole reference in float8"],
        "solar_rotary_on_the_gqa_layer_is_caught": caught["rotary applied"],
        "solar_no_gqa_gate_is_caught": caught["the GQA gate left out"],
        "kernel_solar_programs": all(kernels.values()) and bool(kernels),
    }


@dataclasses.dataclass(frozen=True)
class OuroSize:
    """What ``--only ouro`` runs (defaults: the chip run, the published widths
    on the benchmark configuration's cut)."""

    model: object = None          # the configuration's ``model`` group; None = the benchmark configuration's
    # the benchmark cell's rows and slots: a prefill's row has its BUCKET's
    # columns (``bucket_prefill_rows``), so beside this pool there is room for
    # a prompt's and for a prefix hit's seeded row
    max_seq_len: int = 6144
    slots: int = 2
    page: int = 16
    prompt_lens: Tuple[int, ...] = (900, 300)
    shared_tokens: int = 512      # of the longest prompt, sent again under another tail: a prefix hit
    new_tokens: int = 48
    # the decoded tokens' gap: the benchmark configuration's (its ``reference_check.why`` has the readings)
    gap_tol: float = 0.45


def ouro_phase(size: OuroSize, seed: int) -> Dict[str, bool]:
    """Ouro-2.6B alone: a stack run four times over ONE set of weights, each
    pass on a K/V cache node of its own under one block table, against the
    plain reference's full forward of every pass. Not part of the default run.
    The language model at its published widths on the benchmark configuration's
    cut (``perfbench/configs/ouro-2.6b-serve.json``) through a
    ``ServingEngine`` with its defaults (paged, fused transport, prefix cache
    on) at the benchmark cell's rows (2 slots of 6,144 columns: the pool is 9.0
    GiB): prompts of 900 and 300 tokens are prefilled through the flash forward
    (rolled over the passes; its output a row of the bucket's columns),
    admitted shortest first an engine step apart (a cursor jump under the
    decoding slot), and 48 tokens decoded through the walking kernel at 32 head
    rows a token; then the longest prompt's first 512 tokens under another tail:
    a prefix hit that maps every pass's pages and copies no byte. Against
    ``perfbench/references/ouro.py``: the reference's logit of every decoded
    token; the same tokens judged by the reference whose passes SHARE the first
    pass's cache and by the whole reference in float8 must fail; the pool
    counts a node a layer a pass."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax.core import meta

    from neuronx_distributed_tpu.inference import GenerationConfig
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib
    from neuronx_distributed_tpu.serving import ServingEngine
    from perfbench.families import ouro as family
    from perfbench.references.ouro import Reference

    mesh_lib.destroy_model_parallel()
    published = _published(size, "ouro-2.6b-serve.json")
    model = family.build(published, runner="serve", max_seq_len=size.max_seq_len)
    if size.model is not None:    # the CPU rehearsal serves in float32
        model = model.clone(config=dataclasses.replace(model.config, dtype=jnp.float32))
    cfg = model.config
    params = jax.jit(model.init)(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))
    jax.block_until_ready(params)
    plain = meta.unbox(params)
    engine = ServingEngine(model, params, num_slots=size.slots, kv_page_size=size.page)
    prompts = _prompts(size.prompt_lens, int(published["vocab_size"]), seed + 7)
    reqs, jumps, wall = _serve_shortest_first("ouro", engine, prompts, size.new_tokens, seed)
    # the longest prompt's head again, under another tail
    again = np.concatenate([prompts[0][:size.shared_tokens], _prompts((37,), int(published["vocab_size"]), seed + 11)[0]])
    hit = engine.submit(again, GenerationConfig(max_new_tokens=size.new_tokens, temperature=0.0),
                        key=jax.random.PRNGKey(seed + 99))
    engine.run()
    snap = engine.metrics.snapshot()
    resolved = dict(engine.programs.resolved)
    kernels = _ledger_kernels(engine.programs, _hot_programs(engine))
    log(f"ouro: {len(reqs) + 1} requests, prompts {list(size.prompt_lens)} + {len(again)} (its first {size.shared_tokens} "
        f"shared) + {size.new_tokens} tokens in {wall:.1f}s; resolved {resolved}; {KERNEL} in compiled programs: "
        f"{kernels}; {snap['kv_cache_nodes']} cache nodes over {cfg.num_layers} weight layers, "
        f"{snap['kv_cache_nodes'] * snap['kv_bytes_per_token_layer']:g} B a token, pool {engine.cache.nbytes / 2**30:.2f} GiB; {jumps} cursor jumps; "
        f"prefix hits {snap['prefix_hits']}, pages shared {snap['prefix_pages_shared']}, bytes copied "
        f"{engine.cache.alloc.copy_bytes}")
    engine.cache.check()
    copied = engine.cache.alloc.copy_bytes
    engine = None
    gc.collect()

    served_prompts, served = prompts + [again], reqs + [hit]

    def judged(tag, ref):
        """Every decoded token within ``gap_tol`` of ``ref``'s largest logit (and every wrong token outside it)."""
        return _decoded_token_gaps(f"ouro: {tag}", ref, served_prompts, served, size.gap_tol, 0.0)

    ok, worst = judged("the plain reference", Reference(published, plain))
    shared_ok, _ = judged("control, passes that share the first pass's cache", Reference(published, plain, shared_cache=True))
    float8_ok, _ = judged("control, the whole reference in float8", Reference(published, plain, dtype=jnp.float8_e4m3fn))
    log(f"ouro: largest decoded-token gap {worst:.4f} ({size.gap_tol:g})")
    itemsize = jnp.dtype(cfg.dtype).itemsize
    return {
        "ouro_matches_reference": ok,
        "ouro_shared_cache_is_caught": not shared_ok,
        "ouro_float8_is_caught": not float8_ok,
        "ouro_cursor_jump_leaves_gap_columns": jumps >= len(prompts) - 1,
        "ouro_prefix_hit_maps_every_passes_pages_and_copies_nothing": (
            snap["prefix_hits"] >= 1 and snap["prefix_pages_shared"] >= size.shared_tokens // size.page - 1 and copied == 0),
        "ouro_resolved_paged_walk_fused": resolved == {
            "attention": "flash", "decode_attention": "paged_walk_fused", "paged_attention": "fused"},
        "ouro_cache_is_a_node_a_layer_a_pass": (
            snap["kv_cache_nodes"] == cfg.num_layers * cfg.total_ut_steps
            and snap["kv_bytes_per_token_layer"] == 2 * cfg.num_kv_heads * cfg.head_dim * itemsize),
        "kernel_ouro_programs": all(kernels.values()) and bool(kernels),
    }


@dataclasses.dataclass(frozen=True)
class RunAheadSize:
    """What ``--only runahead`` runs (defaults: the chip run, the Trinity
    cell's engine: the benchmark's shortest decode step, so the cell in which
    the host's turn between two chunks weighs most)."""

    model: object = None          # the configuration's ``model`` group; None = the benchmark configuration's
    config: str = "trinity-large-serve.json"
    max_seq_len: int = 32768
    slots: int = 8
    page: int = 16
    prompt_len: int = 2048
    probe_pairs: int = 6          # chained pairs of the decode chunk, traced
    requests: int = 24            # three a slot: a queue stands behind the full slots
    answers: Tuple[int, int] = (400, 1200)   # spread evenly between: budgets end in different chunks
    # the second call returns while the first still runs: both calls back in
    # under this share of the first chunk's wall, and the device's hole between
    # the two runs of a pair under this share of the hole a host turn leaves
    call_share: float = 0.5
    hole_share: float = 0.25
    trace: bool = True            # the CPU rehearsal opens no profiler session (a pytest worker must not)


def _chunk_runs(trace_dir: str) -> List[Tuple[int, int]]:
    """``(start ns, end ns)`` of every run of the decode chunk's module on
    device 0 of the trace under ``trace_dir``, in time order."""
    from perfbench import xplane

    runs = []
    for plane in xplane.read_planes(xplane.find_xplane(trace_dir)):
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name == xplane.MODULES_LINE:
                runs += [(a, b) for a, b, name, _ in xplane._events(line)
                         if xplane.module_base(name) == "jit_chunk_fn"]
    return sorted(runs)


def runahead_probe(engine, size: RunAheadSize) -> Dict[str, bool]:
    """Two chained calls of the engine's donated decode chunk, ``probe_pairs``
    times under one trace: the second call takes the first's output cache and
    state, not yet computed, donated. Both must be back on the host while the
    first still runs, and the device must leave no hole between the two runs
    (held against the hole the host's turn between two PAIRS leaves in the same
    trace: block, install, deal pages, call). The engine's slots all decode
    when this is called; its requests' streams are not kept up (the caller
    cancels them)."""
    import shutil
    import tempfile

    import jax
    import numpy as np

    from neuronx_distributed_tpu.observability import profile_window

    fn, chunk = engine._nonspec_chunk(), engine.decode_chunk_size
    calls, firsts = [], []
    trace_dir = tempfile.mkdtemp(prefix="runahead_probe_")
    try:
        with profile_window(trace_dir if size.trace else None):
            for _ in range(size.probe_pairs):
                if not engine.cache.ensure_decode_window(np.flatnonzero(engine._active), 2 * chunk):
                    raise RuntimeError("runahead: the pool cannot back two write windows")
                cache = engine.cache.take()
                t0 = time.perf_counter()
                one = fn(engine._params, cache, engine._state)
                two = fn(engine._params, one[0], one[1])
                t2 = time.perf_counter()
                jax.block_until_ready(one[2])
                t3 = time.perf_counter()
                jax.block_until_ready(two[2])
                calls.append(t2 - t0)
                firsts.append(t3 - t0)
                engine._state = two[1]
                engine.cache.update_after_decode(two[0], int(one[4]) + int(two[4]))
        runs = _chunk_runs(trace_dir) if size.trace else []
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    calls_back = max(c / f for c, f in zip(calls, firsts)) < size.call_share
    if not size.trace:
        return {"runahead_second_call_returns_while_the_first_runs_on_the_chips_clock": calls_back,
                "runahead_device_leaves_no_hole_between_chained_chunks_on_the_chips_clock": False}
    if len(runs) != 2 * size.probe_pairs:
        raise RuntimeError(f"runahead: {len(runs)} runs of the decode chunk in the trace, not {2 * size.probe_pairs}")
    holes = [1e-6 * (runs[i + 1][0] - runs[i][1]) for i in range(len(runs) - 1)]
    chained, turns = holes[0::2], holes[1::2]
    run_ms = float(np.median([1e-6 * (b - a) for a, b in runs]))
    log(f"runahead probe: {size.probe_pairs} chained pairs of the donated decode chunk ({run_ms:.2f} ms a run on the "
        f"device): both calls back on the host after {1e3 * np.median(calls):.2f} ms (largest {1e3 * max(calls):.2f}), "
        f"the first chunk's readback after {1e3 * np.median(firsts):.2f} ms; the device's hole between the two runs of "
        f"a pair, ms: median {np.median(chained):.3f}, largest {max(chained):.3f}; between two pairs (the host blocks, "
        f"installs, deals pages and calls): median {np.median(turns):.3f}, smallest {min(turns):.3f}")
    return {
        "runahead_second_call_returns_while_the_first_runs_on_the_chips_clock": calls_back,
        "runahead_device_leaves_no_hole_between_chained_chunks_on_the_chips_clock": (
            max(chained) < size.hole_share * min(turns)),
    }


def _one_chip_engine(size: RunAheadSize, seed: int):
    """``(engine, vocabulary)``: the configuration's engine as the benchmark
    builds it (``perfbench/runners/serve.py::build``), at ``size``'s rows."""
    from perfbench.runners import serve
    from perfbench.spans import Spans

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "perfbench", "configs", size.config)) as f:
        config = json.load(f)
    config["model"] = _published(size, size.config)
    config["serving"] = {"num_slots": size.slots, "max_seq_len": size.max_seq_len, "kv_page_size": size.page}
    engine, _, _, vocab = serve.build(config, seed, Spans(), log)
    return engine, vocab


def runahead_phase(size: RunAheadSize, seed: int) -> Dict[str, bool]:
    """The decode chunk run ahead by one, on one cell's engine (Trinity's: the
    shortest step): first the probe, two chained calls of the donated chunk
    under a trace (both back on the host while the first runs, no hole on the
    device between them); then the same closed traffic twice through the one
    engine, one chunk at a time (the rule patched to say no, from here) and as
    the engine decides: 24 requests of 2,048-token prompts and 400-1,200-token
    answers over 8 slots, a few hundred chunks, with the share of chunks run
    ahead, the chunk's wall and the device's idle share of a traced stretch
    side by side. Every request's tokens and final key must be equal between
    the two passes, and the pages' invariant hold. Not part of the default
    run."""
    import shutil
    import tempfile

    import jax
    import numpy as np

    from neuronx_distributed_tpu.inference import GenerationConfig
    from neuronx_distributed_tpu.observability import profile_window
    from perfbench import xplane

    engine, vocab = _one_chip_engine(size, seed)
    prompts = _prompts((size.prompt_len,) * size.requests, vocab, seed + 11)
    lo, hi = size.answers
    answers = [lo + (hi - lo) * i // max(size.requests - 1, 1) for i in range(size.requests)]

    # --- the probe: every slot decoding, nothing queued
    long_answer = GenerationConfig(max_new_tokens=hi, temperature=0.0)
    probed = [engine.submit(p, long_answer, key=jax.random.PRNGKey(seed + i)) for i, p in enumerate(prompts[:size.slots])]
    while not engine._active.all():
        engine.step()
    checks = runahead_probe(engine, size)
    for r in probed:
        engine.cancel(r.rid)
    engine.run()
    engine.step()

    def serve(ahead: bool):
        """One pass: ``(requests, chunk walls in s, share run ahead, late-found ends, idle share of a traced stretch)``."""
        if not ahead:
            engine._can_run_ahead = lambda: False      # one chunk at a time: the rule says no
        before = engine.metrics.snapshot(analyze_programs=False)
        reqs = [engine.submit(p, GenerationConfig(max_new_tokens=n, temperature=0.0), key=jax.random.PRNGKey(seed + i))
                for i, (p, n) in enumerate(zip(prompts, answers))]
        walls, steps, idle = [], 0, None
        trace_dir = tempfile.mkdtemp(prefix="runahead_pass_")
        try:
            while engine.has_work:
                traced = size.trace and steps == 4 * size.slots          # past the first wave of prefills: 40 steps under a trace
                with profile_window(trace_dir if traced else None):
                    for _ in range(40 if traced else 1):
                        prefills, chunks = engine.metrics.prefills, engine.metrics.chunks
                        t0 = time.perf_counter()
                        engine.step()
                        if engine.metrics.prefills == prefills and engine.metrics.chunks > chunks:
                            walls.append(time.perf_counter() - t0)
                        steps += 1
                if traced:
                    got = xplane.reduce_trace(trace_dir, require_device=size.model is None)
                    idle = 1.0 - got["busy_s"] / got["window_s"] if got["devices"] else None
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
            engine.__dict__.pop("_can_run_ahead", None)
        engine.step()
        after = engine.metrics.snapshot(analyze_programs=False)
        chunks = after["chunks"] - before["chunks"]
        ran = after["chunks_run_ahead"] - before["chunks_run_ahead"]
        late = after["late_found_ends"] - before["late_found_ends"]
        return reqs, walls, ran / max(chunks, 1), late, idle, chunks

    passes = {}
    for name, ahead in (("one at a time", False), ("run ahead", True)):
        reqs, walls, share, late, idle, chunks = passes[name] = serve(ahead)
        engine.cache.check()
        said = "not traced" if idle is None else f"{100.0 * idle:.2f}% of 40 traced steps"
        log(f"runahead, {name}: {chunks} chunks, {100.0 * share:.1f}% run ahead, {late} ran over a slot that had ended; "
            f"a decode-only step's wall {1e3 * float(np.median(walls)):.2f} ms (median of {len(walls)}); device idle {said}")
    plain, ahead = passes["one at a time"], passes["run ahead"]
    same = all(a.tokens == b.tokens and np.array_equal(a.key, b.key) and len(a.tokens) == n
               for a, b, n in zip(plain[0], ahead[0], answers))
    leak_free = engine.cache.alloc.free_pages == engine.cache.alloc.num_pages - 1
    checks.update({
        "runahead_streams_equal_one_chunk_at_a_time": same,
        "runahead_engages_with_every_slot_held": ahead[2] > 0.5 and plain[2] == 0.0,
        "runahead_leaks_no_page": leak_free,
        # only the chip's clock can say
        "runahead_chunk_wall_is_shorter_on_the_chips_clock": float(np.median(ahead[1])) < float(np.median(plain[1])),
    })
    return checks


def _walk_listing_compile(size: WalkSize = WalkSize()) -> None:
    """Compile the walking kernel at ``size`` for a described v5e. Run in a
    process of its own with ``LIBTPU_INIT_ARGS`` naming the dump directory
    (:func:`walk_block_bundles`): the compiler aborts the process once the
    listing is written (it looks for a report template this install lacks)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from neuronx_distributed_tpu.kernels import backend
    from neuronx_distributed_tpu.kernels.flash_decode import paged_walk_decode_attention

    backend.INTERPRET, backend.on_tpu = False, lambda: True
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    s = lambda shape, dtype=jnp.dtype(size.dtype): jax.ShapeDtypeStruct(shape, dtype, sharding=one)   # noqa: E731
    shape = size.shapes[0]
    b, page, n_log = len(shape.contexts), size.page, shape.max_seq_len // size.page

    def walk_step(q, pool, bt, pos, ok, lo):
        return paged_walk_decode_attention(q, pool, bt, pos, kv_valid=ok, floor=lo, page_size=page)

    jax.jit(walk_step).lower(
        s((b, 1, shape.q_heads, shape.head_dim)),
        s((b * shape.window_pages + 1, page, 2 * shape.kv_heads, shape.head_dim)),
        s((b, n_log), jnp.int32), s((1,), jnp.int32), s((b, n_log * page), jnp.bool_), s((b,), jnp.int32)).compile()


def walk_block_bundles(directory: str, size: WalkSize = WalkSize()) -> int:
    """Instruction bundles of ONE block's body in the walking kernel's final
    schedule, compiled for a described v5e (no chip): in the listing of the
    custom call named after ``walk_step``, the region from the second
    innermost loop body (the block's wait, then its heads) to the predicated
    region's end after it."""
    import glob
    import re
    import subprocess

    os.makedirs(directory, exist_ok=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled",
               LIBTPU_INIT_ARGS=f"--xla_jf_dump_to={directory} --xla_jf_dump_llo_text=true")
    code = f"import chip_smoke; from chip_smoke import WalkShape, WalkSize; chip_smoke._walk_listing_compile({size!r})"
    subprocess.run([sys.executable, "-c", code], env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
                   stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL, timeout=600)
    listings = [f for f in glob.glob(os.path.join(directory, "*walk_step*final_bundles.txt"))
                if "schedule-analysis" not in f]
    if not listings:
        raise RuntimeError(f"the compile left no listing of walk_step under {directory}")
    address = re.compile(r"^\s*(0x[0-9a-f]+)\s+(LB: >>> |PF: >> )")
    marks = [(int(m.group(1), 16), m.group(2)) for m in map(address.match, open(max(listings, key=os.path.getmtime)))
             if m]
    bodies = [i for i, (_, kind) in enumerate(marks) if kind.startswith("LB")]
    start = bodies[1]
    end = next(i for i in range(start + 1, len(marks)) if marks[i][1].startswith("PF"))
    return marks[end][0] - marks[start][0]


def _dealt_tables(contexts: Sequence[int], cursor: int, max_seq_len: int, page: int,
                  window: Optional[int] = None, chunk: int = 8):
    """``(full table, window table or None, pool pages of each kind)`` as the
    serving pool deals them: a ``PagedCacheManager`` (over leaves of a few
    bytes a token) taken through the life that leaves ``contexts`` ending at
    ``cursor``. Each slot is admitted with the first third of its context as
    its prompt (in whole 512s) once the shared cursor reaches the prompt's end,
    and decodes the rest in chunks of ``chunk`` columns beside the others, a
    decode window asked for before every chunk as the engine does. Contexts
    start on a page (``cursor + 1 - n`` a multiple of ``page``)."""
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_tpu.serving.paging import PagedCacheManager

    mgr = PagedCacheManager(len(contexts), max_seq_len, page, window=window, window_write_cols=chunk)

    def row(padded):
        def node(w):
            leaves = {"kv": jnp.zeros((1, max_seq_len, 2, 4), jnp.float32), "index": jnp.asarray(padded, jnp.int32),
                      "kv_valid": jnp.arange(max_seq_len)[None] < padded}
            return leaves if w is None else {**leaves, "window": jnp.zeros((0, w), bool)}
        return {"full": {"attn": node(None)}, **({} if window is None else {"windowed": {"attn": node(window)}})}

    starts = [cursor + 1 - n for n in contexts]
    assert all(st % page == 0 and st >= 0 for st in starts), "contexts start on a page"
    prompts = [max(n // 3 // 512 * 512, page) for n in contexts]
    slot_of, active = {}, []

    def decode_to(column):
        while active and mgr.cursor < column:
            assert mgr.ensure_decode_window(active, chunk), "the pool holds every slot's whole row"
            mgr.update_after_decode(mgr.cache, min(chunk, column - mgr.cursor))

    for i in sorted(range(len(contexts)), key=lambda i: starts[i] + prompts[i]):
        decode_to(starts[i] + prompts[i])
        slot_of[i] = mgr.acquire()
        mgr.admit(row(prompts[i]), slot_of[i], prompts[i], cursor=starts[i] + prompts[i], p=prompts[i])
        active.append(slot_of[i])
    decode_to(cursor)
    assert mgr.cursor == cursor and mgr.ensure_decode_window(active, chunk)
    mgr.check()
    order = [slot_of[i] for i in range(len(contexts))]
    full = mgr._tables[order].copy()
    if window is None:
        return full, None, (mgr.alloc.num_pages, 0)
    return full, mgr._tables_w[order].copy(), (mgr.alloc.num_pages, mgr.alloc_w.num_pages)


def _page_started(contexts: Sequence[int], cursor: int, page: int) -> List[int]:
    """``contexts`` that end at ``cursor``, each lengthened to START on a page
    (what :func:`_dealt_tables` can deal)."""
    return [cursor + 1 - (cursor + 1 - n) // page * page for n in contexts]


def _scattered_table(rng, contexts: Sequence[int], cursor: int, length: int, page: int):
    """``(table, valid)``: the pages under ``contexts`` ending at ``cursor``
    drawn as a random permutation of a pool of ``slots x length / page + 1``
    pages (no two pages of a slot adjacent), and the columns they hold."""
    import numpy as np

    b, n_log = len(contexts), length // page
    ids, at = 1 + rng.permutation(b * n_log), 0
    table, valid = np.zeros((b, n_log), np.int32), np.zeros((b, length), bool)
    for i, n in enumerate(contexts):
        lo, hi = (cursor + 1 - n) // page, cursor // page + 1
        valid[i, cursor + 1 - n:cursor + 1], table[i, lo:hi] = True, ids[at:at + hi - lo]
        at += hi - lo
    return table, valid


def _block_copies(table, live, group: int) -> int:
    """Copies a leaf that the block-walking kernels start for ``table`` over
    its ``live`` blocks of ``group`` pages: one a run of ``PAGE_RUN`` pages in
    a trip whose entries all read runs (``flash_decode._trip_runs``), one a
    page in any other (and in a tree from before runs, measured beside this
    one: there the kernels have no ``_trip_runs``)."""
    import numpy as np

    from neuronx_distributed_tpu.kernels import flash_decode

    live = np.asarray(live)
    if not hasattr(flash_decode, "_trip_runs"):
        return int(live.sum()) * group
    trip = flash_decode._issue_trip(group)
    whole = np.asarray(flash_decode._trip_runs(np.asarray(table), group)).reshape(live.shape[0], live.shape[1], -1)
    return int((np.where(whole != 0, trip // flash_decode.PAGE_RUN, trip).sum(axis=2) * live).sum())


def _table_pair(tag: str, call_of, tables, needed: int, page: int, page_bytes: int, group: int, bound,
                calls: int, want=None) -> Dict[str, Tuple[float, Optional[float]]]:
    """One block-walking kernel under each of ``tables`` (name -> block
    table: ``random``, a permutation of the pool, no two pages of a slot
    adjacent; ``dealt``, as the serving pool deals them, :func:`_dealt_tables`):
    ms a call, GB/s of the ``needed`` bytes and of the bytes its blocks fetch
    (``page_bytes`` a page: every leaf's), and the copies a leaf a call
    starts. ``call_of(table)`` -> ``(fn, args)``; with ``want`` (``table ->
    array``) the largest ``|result - want|`` too. ``{table name: (ms, that
    difference or None)}``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_tpu.kernels import flash_decode

    out = {}
    for name, table in tables.items():
        fn, args = call_of(jnp.asarray(table))
        err = None
        if want is not None:
            got = jax.jit(fn)(*args).astype(jnp.float32)
            err = float(np.abs(np.asarray(got) - np.asarray(want(jnp.asarray(table)))).max())
        ms = _median_call_ms(fn, args, calls)
        live = flash_decode._latent_block_walk(jnp.asarray(table), bound, group, page)[0]
        blocks, copies = int(np.asarray(live).sum()), _block_copies(table, live, group)
        log(f"{tag}, {name} table: {ms:.4f} ms a call, {needed / ms / 1e6:.1f} GB/s of {needed / 1e6:.1f} MB needed; "
            f"{blocks} blocks of {group} pages of {page_bytes} B fetched ({blocks * group * page_bytes / ms / 1e6:.1f} "
            f"GB/s) with {copies} copies a leaf ({1e6 * ms / max(copies, 1):.1f} ns each)"
            + ("" if err is None else f"; largest |kernel - float32 reference| {err:.5f}"))
        out[name] = (ms, err)
    return out


def walk_phase(size: WalkSize, seed: int) -> Dict[str, bool]:
    """The walking GQA decode kernel alone at the Trinity cell's shapes, a
    window layer and a full layer, and at the ZAYA1 cell's, under a random
    block table and under one the serving pool dealt: against the float32
    einsum, ms a call, GB/s of needed and fetched bytes and the copies a call
    starts. Not part of the default run: it serves
    nothing. ``kernels/flash_decode.paged_walk_decode_attention``, a call =
    one layer of a decode step: Trinity's 8 slots of 32,768 columns, page 16,
    48 query heads against 8 kv heads of 128 (a page is 64 KB), the tape's
    eight prompt lengths + 256 as contexts that end at a shared cursor, once
    as a window layer (272 pages a slot, ``floor`` set) and once as a full
    layer; ZAYA1's 32 slots of 16,384 columns, 8 query heads against 2 kv
    heads (a page is 16 KB), a full layer. Each under two block tables: a
    random permutation of the pool (no two pages of a slot adjacent: a copy a
    page) and the table a ``PagedCacheManager`` deals over the slots' lives
    (:func:`_dealt_tables`: runs of adjacent pages, one copy a run). Each
    against the float32 einsum under an index mask, then ms a call with the
    GB/s of the bytes the call needs (``perfbench/swa_costs.py``) and of the
    bytes its blocks fetch.
    With ``--bundles DIR`` ``main`` first compiles the kernel for a described
    v5e in a process of its own with the compiler's listing dumped to ``DIR``,
    and prints how many instruction bundles one block's body is (no chip
    needed for that part: it is printed before the device is asked for)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_tpu.kernels import flash_decode
    from neuronx_distributed_tpu.kernels.flash_decode import paged_gather_leaf, paged_walk_decode_attention
    from neuronx_distributed_tpu.modules.attention import _masked_gqa_attention, split_kv
    from perfbench.swa_costs import swa_decode_cost

    dtype, page = jnp.dtype(size.dtype), size.page
    f32 = lambda a: a.astype(jnp.float32)    # noqa: E731
    checks: Dict[str, bool] = {}
    for shape in size.shapes:
        h, hkv, d, cur = shape.q_heads, shape.kv_heads, shape.head_dim, shape.cursor
        ctx = _page_started(shape.contexts, cur, page)
        b, n_log = len(ctx), shape.max_seq_len // page
        group = min(max(flash_decode.walk_block_tokens(
            2 * hkv * d * dtype.itemsize, page, flash_decode.walk_row_heads(h, hkv, dtype.itemsize)) // page, 1), n_log)
        pos = jnp.asarray([cur], jnp.int32)
        valid = np.zeros((b, shape.max_seq_len), bool)
        for i, n in enumerate(ctx):            # contexts END at the shared cursor
            valid[i, cur + 1 - n:cur + 1] = True
        dealt = _dealt_tables(ctx, cur, shape.max_seq_len, page, shape.window)
        for kind, window in (("window", shape.window), ("full", None)):
            if kind == "window" and window is None:
                continue
            rng = np.random.default_rng(seed)
            lowest = [cur + 1 - (n if window is None else min(n, window)) for n in ctx]
            held = [range(lo // page, cur // page + 1) for lo in lowest]
            pages = dealt[2][kind == "window"]
            assert pages > sum(len(r) for r in held), "the pool does not hold the contexts"
            ids = 1 + rng.permutation(pages - 1)
            table, at = np.zeros((b, n_log), np.int32), 0
            for i, r in enumerate(held):
                table[i, r.start:r.stop] = ids[at:at + len(r)]
                at += len(r)
            keys = jax.random.split(jax.random.PRNGKey(seed), 3)
            # the joined leaf: a token's K heads, then its V heads
            pool = jax.random.normal(keys[0], (pages, page, 2 * hkv, d), dtype)
            q = jax.random.normal(keys[1], (b, 1, h, d), dtype)
            floor = None if window is None else jnp.asarray(lowest, jnp.int32)
            ok = jnp.asarray(valid)
            keep = jnp.asarray((valid & (np.arange(shape.max_seq_len)[None] >= np.asarray(lowest)[:, None]))[:, None])

            # every array is an ARGUMENT: closed over, the pool would be a constant of the program
            def call_of(bt):
                return (lambda qq, kvp: paged_walk_decode_attention(
                    qq, kvp, bt, pos, kv_valid=ok, floor=floor, page_size=page)), (q, pool)

            def want(bt):
                with jax.default_matmul_precision("highest"):
                    return jax.jit(lambda qq, kvp: _masked_gqa_attention(
                        f32(qq), *split_kv(f32(paged_gather_leaf(kvp, bt, page))), keep))(q, pool)

            needed = swa_decode_cost(ctx, num_q_heads=h, num_kv_heads=hkv, head_dim=d, window=window,
                                     act_bytes=dtype.itemsize)[1]
            got = _table_pair(
                f"walk {shape.name} {kind}: {b} slots, contexts {min(ctx)}-{max(ctx)} ending at {cur} (limit {size.tol})",
                call_of, {"random": table, "dealt": dealt[kind == "window"]}, needed, page,
                page * 2 * hkv * d * dtype.itemsize, group, pos[0] + 1, size.calls, want)
            for name, (_, err) in got.items():
                checks[f"walk_{shape.name}_{kind}_{name}_table_matches_the_float32_einsum"] = err <= size.tol
            del pool
    return checks


# --- the grouped prefill forwards alone: the frozen parent | the kernel --------------


def _grouped_forward(parent: bool, window: Optional[int]):
    """A prefill's grouped forward as ``(q, k, v, valid[, keep]) -> out``: the
    banded one under ``window``, else the byte-masked one; the tree's, or with
    ``parent`` PR 49's (``tests/kernels/_group_fwd_parent.py``: the two kernels
    before they took the flash forward's form, which knew no prompt's extent)."""
    import importlib

    if parent:
        tests = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tests", "kernels")
        if tests not in sys.path:
            sys.path.insert(0, tests)
    kernels = importlib.import_module(    # the package exports the function under the module's name
        "_group_fwd_parent" if parent else "neuronx_distributed_tpu.kernels.flash_attention")
    if window is not None:
        return lambda q, k, v, valid: kernels.banded_flash_attention(q, k, v, window, valid)
    if parent:
        return lambda q, k, v, valid, keep: kernels.masked_flash_attention(q, k, v, keep)
    return lambda q, k, v, valid, keep: kernels.masked_flash_attention(q, k, v, keep, valid)


def grouped_forward_pair(tag: str, heads: Tuple[int, int, int, int], window: Optional[int], bucket: int,
                         prompts: Sequence[int], calls: int, dtype, seed: int) -> Dict[str, bool]:
    """A prefill's grouped forward alone at ``heads`` = (q heads, kv heads,
    d_qk, d_v), one layer of one prefill a call: the byte-masked forward
    (``window`` None; a random causal mask of the prompt's keys) or a window
    layer's banded one, each prompt of ``prompts`` left-padded to ``bucket``.
    The kernel against PR 49's on the same inputs: content rows equal bit for
    bit, query blocks wholly past the prompt's end zero, and both forms' ms a
    call beside the tiles the plan counts."""
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    fa = importlib.import_module("neuronx_distributed_tpu.kernels.flash_attention")
    h, hkv, d, dv = heads
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(keys[0], (1, bucket, h, d), dtype)
    k = jax.random.normal(keys[1], (1, bucket, hkv, d), dtype)
    v = jax.random.normal(keys[2], (1, bucket, hkv, dv), dtype)
    equal, zeros = True, True
    for prompt in prompts:
        pad = bucket - prompt
        valid = jnp.arange(bucket)[None] >= pad
        args = (q, k, v, valid)
        if window is None:
            rows = jnp.arange(bucket)
            args += ((jax.random.bernoulli(keys[3], 0.125, (1, bucket, bucket)) & (rows[:, None] >= rows[None])
                      & valid[:, None, :]).astype(jnp.int8),)
        forms = {"PR 49's": _grouped_forward(True, window), "the kernel": _grouped_forward(False, window)}
        was, new = (np.asarray(jax.jit(fn)(*args).astype(jnp.float32))[0] for fn in forms.values())
        bq = fa._group_blocks(bucket, h // hkv)[0]
        past = pad // bq * bq                          # the rows of query blocks wholly of padding
        equal &= bool((new[pad:] == was[pad:]).all()) and bool(np.abs(was[pad:]).max() > 0)
        zeros &= not new[:past].any()
        del was, new
        ms = [_median_call_ms(fn, args, calls) for fn in forms.values()]
        log(f"{tag}: the prefill forward alone, {h}/{hkv} heads of {d}/{dv}"
            + (f", window {window}" if window else ", byte mask") + f", {prompt} of {bucket}: "
            + ", ".join(f"{name} {t:.3f} ms a call" for name, t in zip(forms, ms)) + f" (x{ms[1] / ms[0]:.3f}); "
            f"grid steps, bodies, edge bodies, needed a KV head = {fa.group_tile_plan(bucket, prompt, h // hkv, window)}; "
            f"content rows {'equal' if equal else 'DIFFER'} bit for bit, {past} rows of blocks past the prompt's end "
            f"{'zero' if zeros else 'NOT zero'}")
    return {f"{tag}_prefill_forward_equals_pr49s_bit_for_bit": equal,
            f"{tag}_prefill_forward_blocks_past_the_prompt_are_zero": zeros}


def _group_listing_compile(heads, window, bucket: int, parent: bool) -> None:
    """Compile a grouped forward at ``heads`` for a described v5e (a process of
    its own, as :func:`_walk_listing_compile`)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from neuronx_distributed_tpu.kernels import backend

    backend.INTERPRET, backend.on_tpu = False, lambda: True
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    s = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one)   # noqa: E731
    h, hkv, d, dv = heads
    forward = _grouped_forward(parent, window)
    args = (s((1, bucket, h, d)), s((1, bucket, hkv, d)), s((1, bucket, hkv, dv)), s((1, bucket), jnp.bool_))
    if window is None:
        args += (s((1, bucket, bucket), jnp.int8),)

    def group_step(*a):
        return forward(*a)

    jax.jit(group_step).lower(*args).compile()


def _listed_regions(directory: str, code: str, name: str) -> List[int]:
    """Instruction bundles of each predicated region of the custom call named
    after ``name`` in the final schedule ``code`` leaves (a process of its own
    that compiles for a described v5e with the listing dumped to
    ``directory``), in program order: each region ends at its fallthrough
    (``PF:``), and the address counts bundles."""
    import glob
    import re
    import subprocess

    os.makedirs(directory, exist_ok=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled",
               LIBTPU_INIT_ARGS=f"--xla_jf_dump_to={directory} --xla_jf_dump_llo_text=true")
    subprocess.run([sys.executable, "-c", code], env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
                   stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL, timeout=600)
    listings = [f for f in glob.glob(os.path.join(directory, f"*{name}*final_bundles.txt"))
                if "schedule-analysis" not in f]
    if not listings:
        raise RuntimeError(f"the compile left no listing of {name} under {directory}")
    address = re.compile(r"^\s*(0x[0-9a-f]+) PF: ")
    ends = [int(m.group(1), 16) for m in map(address.match, open(max(listings, key=os.path.getmtime))) if m]
    return [b - a for a, b in zip(ends, ends[1:])]


def group_body_bundles(directory: str, heads, window, bucket: int, parent: bool = False) -> List[int]:
    """Instruction bundles of a grouped forward's bodies (one step: a KV
    head's whole group on one tile) in its final schedule, compiled for a
    described v5e (no chip): the regions between the kernel's init and its
    finish, in program order (the byte-masked body; the interior and the edge
    body under a window; PR 49's one body with ``parent``). A kernel over the
    scoped VMEM limit leaves no listing: that raises."""
    code = f"import chip_smoke; chip_smoke._group_listing_compile({tuple(heads)!r}, {window!r}, {bucket}, {parent})"
    # the listing's regions end: ..., the bodies, the finish, the step's epilogue
    return _listed_regions(os.path.join(directory, "parent" if parent else "kernel"), code, "group_step")[:-2]


# what ``--bundles`` compiles with ``--only dsa | glm | trinity``: the cell's head geometry and window
GROUP_BUNDLES = {"dsa": ((32, 4, 128, 128), None), "glm": ((64, 64, 256, 256), None), "trinity": ((48, 8, 128, 128), 4096)}


def _flash_forward(q, k, v, seg, residuals):
    """The forward under test on (B, H, S, D) arrays: ``kernels/flash_attention
    ._flash_fwd`` with the blocks ``flash_attention`` picks."""
    import importlib

    fa = importlib.import_module("neuronx_distributed_tpu.kernels.flash_attention")   # the package exports the function under this name
    block = fa._pick_block(q.shape[2])
    return fa._flash_fwd(q, k, v, True, block, block, fa.interpret_mode(None), q_seg=seg, k_seg=seg,
                         residuals=residuals)[0]


# what --only flash measures besides the kernel as it is: the same call with
# the tile classes held back a stage at a time (ISSUE 45's stages)
_FLASH_STAGES = ("triangle only", "+ padded rows", "+ interior body")


def _flash_stage_classes(stage: str, rule):
    """``_tile_classes`` as stage ``stage`` would have it: 'triangle only'
    keeps padding meeting padding and every live pair in the masked body,
    '+ padded rows' drops the all-padding blocks, '+ interior body' is the rule."""
    def classes(xp, *args):
        *head, residuals = args
        found = rule(xp, *head, True if stage == _FLASH_STAGES[0] else residuals)
        return found if stage == _FLASH_STAGES[2] else xp.minimum(found * 2, 2)   # interior -> edge
    return classes


def _flash_listing_compile(shape) -> None:
    """Compile the flash forward at ``shape`` for a described v5e (a process of
    its own, as :func:`_walk_listing_compile`)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from neuronx_distributed_tpu.kernels import backend

    backend.INTERPRET, backend.on_tpu = False, lambda: True
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    s = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one)   # noqa: E731
    _, h, hkv, d, dv, b, seq, _, residuals = shape

    def flash_step(q, k, v, seg):
        return _flash_forward(q, k, v, seg, residuals)

    jax.jit(flash_step).lower(s((b, h, seq, d)), s((b, hkv, seq, d)), s((b, hkv, seq, dv)),
                              s((b, seq), jnp.int32)).compile()


def flash_body_bundles(directory: str, size: FlashSize = FlashSize()) -> Tuple[int, int]:
    """Instruction bundles of the interior and of the edge body in the flash
    forward's final schedule, compiled for a described v5e (no chip): in the
    listing of the custom call named after ``flash_step``, the two largest of
    the kernel's four predicated regions (init, interior, edge and finish, in
    that order)."""
    code = f"import chip_smoke; chip_smoke._flash_listing_compile({size.shapes[size.bundles_shape]!r})"
    spans = _listed_regions(directory, code, "flash_step")
    interior, edge = [n for n in spans if n in sorted(spans)[-2:]]     # in program order
    return interior, edge


def flash_phase(size: FlashSize, seed: int, forward=None) -> Dict[str, bool]:
    """The flash forward alone at the cells' prefill and training shapes: ms
    a call and share of the bf16 peak, a stage of its tile classes at a time.
    Not part of the default run: it serves nothing.
    ``kernels/flash_attention._flash_fwd`` (the prefill attention of every
    model but the sparse ones and a window layer, and the training forward),
    a call = one layer of one prefill (or of one training microbatch), a
    prompt left-padded to its bucket: DeepSeek-V2-Lite's 16 heads of 192 / 128
    at 9,003 tokens of a 16,384 bucket and 20,566 of 20,992, Trinity's full
    layer (48 / 8 heads of 128) at 11,534 of 16,384, CodeGen2's 16 heads of
    256 at 1,000 of 1,024 and 1,100 of 2,048, and the training cell's 4 heads
    a chip at 8 x 2,048 with the residuals kept. Sampled content rows against
    a float32 reference, padded rows zero, then ms a call and the share of
    the bf16 peak of the NEEDED work (the prompt's own causal triangle), and
    the same with the kernel's tile classes held back a stage at a time (the
    causal triangle's pairs only; + blocks that are all padding dropped; +
    the unmasked interior body = the kernel), each stage's content rows equal
    to the kernel's bit for bit. ``forward``: another ``(q, k, v, seg,
    residuals) -> out`` to measure in the kernel's place (an earlier
    tree's), without the stages. With ``--bundles DIR`` ``main`` first prints
    the instruction bundles of the interior and of the edge body from a
    described-v5e compile (no chip needed)."""
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench.peaks import peaks_for

    fa = importlib.import_module("neuronx_distributed_tpu.kernels.flash_attention")

    peak = peaks_for(jax.devices()[0].device_kind)["flops_bf16"] if jax.devices()[0].platform == "tpu" else float("nan")
    dtype, f32 = jnp.dtype(size.dtype), lambda a: a.astype(jnp.float32)   # noqa: E731
    rule, checks = fa._tile_classes, {}
    for n, (name, h, hkv, d, dv, b, seq, prompt, residuals) in enumerate(size.shapes):
        keys = jax.random.split(jax.random.PRNGKey(seed + n), 3)
        q = jax.random.normal(keys[0], (b, h, seq, d), dtype)
        k = jax.random.normal(keys[1], (b, hkv, seq, d), dtype)
        v = jax.random.normal(keys[2], (b, hkv, seq, dv), dtype)
        pad = seq - prompt
        seg = jnp.broadcast_to(jnp.where(jnp.arange(seq) < pad, -1, 0).astype(jnp.int32), (b, seq)) if pad else None
        rows = np.sort(np.random.default_rng(seed).choice(np.arange(pad, seq), min(size.rows, prompt), replace=False))

        def reference(q, k, v):   # float32, the sampled content rows against every key
            s = jnp.einsum("bhqd,bhkd->bhqk", f32(q[:, :, rows]), jnp.repeat(f32(k), h // hkv, axis=1)) / math.sqrt(d)
            cols = jnp.arange(seq)[None]
            s = jnp.where((cols <= rows[:, None]) & (cols >= pad), s, -jnp.inf)
            return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), jnp.repeat(f32(v), h // hkv, axis=1))

        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(reference)(q, k, v))
        def call():   # a new function a trace: jit caches by identity, and a stage patches what a trace reads
            return lambda qq, kk, vv: (forward or _flash_forward)(qq, kk, vv, seg, residuals)

        got = np.asarray(f32(jax.jit(call())(q, k, v)))
        err = float(np.abs(got[:, :, rows] - want).max())
        checks[f"flash_{n}_content_rows_match_float32"] = err <= size.tol
        checks[f"flash_{n}_padded_rows_are_zero"] = residuals or forward is not None or not got[:, :, :pad].any()
        needed = b * 2.0 * prompt * prompt * h * (d + dv) / 2.0
        ms = {}
        if forward is None:
            try:
                for stage in _FLASH_STAGES[:2]:
                    fa._tile_classes = _flash_stage_classes(stage, rule)
                    held = np.asarray(f32(jax.jit(call())(q, k, v)))
                    checks[f"flash_{n}_equals_the_stage_{stage.strip('+ ').replace(' ', '_')}_bit_for_bit"] = bool(
                        (held[:, :, pad:] == got[:, :, pad:]).all())
                    ms[stage] = _median_call_ms(call(), (q, k, v), size.calls)
            finally:
                fa._tile_classes = rule
            log(f"flash {name}: grid steps, bodies, edge bodies, needed a head = {fa.flash_tile_plan(seq, prompt)}")
        ms["the kernel" if forward is None else "the given forward"] = _median_call_ms(call(), (q, k, v), size.calls)
        log(f"flash {name}: {h}/{hkv} heads of {d}/{dv}, batch {b}: " + "; ".join(
            f"{stage} {t:.3f} ms a call, {100 * needed / (t * 1e-3) / peak:.1f}% of the bf16 peak"
            for stage, t in ms.items())
            + f"; largest |kernel - float32| over {len(rows)} content rows {err:.5f} (limit {size.tol})")
        del q, k, v, got, want
    return checks


def moe_phase(size: MoeSize, seed: int) -> Dict[str, bool]:
    """The streamed expert MLP alone at DeepSeek-V2-Lite's, Keye's and
    Mixtral's expert shapes: checks against float32 ``jnp`` and two controls,
    then both forms' ms a call over 1-256 rows. Not part of the default run:
    it serves nothing. ``kernels/moe_stream.py``, a call = one layer of a
    decode step: against the float32 ``jnp`` routed sum and against the
    grouped-matmul (``ragged_dot``) form, with two controls that must fail
    (the experts' weights in float8; one hit expert dropped), then ms a call
    of both forms over ``size.tokens`` rows: the sweep that sets
    ``modules/moe/expert_mlps.MOE_STREAM_MAX_TOKENS``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_tpu.kernels.moe_stream import hit_experts, moe_stream_mlp
    from neuronx_distributed_tpu.modules.moe.expert_mlps import (
        MOE_STREAM_MAX_TOKENS,
        ExpertMLPs,
        _ragged_routed_mlp,
    )

    dtype = jnp.dtype(size.dtype)
    f32 = lambda a: a.astype(jnp.float32)    # noqa: E731
    stream = moe_stream_mlp
    ragged = functools.partial(_ragged_routed_mlp, act="silu")

    @jax.jit
    def exact(x, e, w, g, u, d):
        """float32 ``jnp``, expert by expert (no (T, E, I) array at 14336)."""
        def one(acc, args):
            i, g_, u_, d_ = args
            y = (jax.nn.silu(f32(x) @ f32(g_)) * (f32(x) @ f32(u_))) @ f32(d_)
            return acc + y * jnp.sum(jnp.where(e == i, w, 0.0), axis=1)[:, None], None
        with jax.default_matmul_precision("highest"):
            return jax.lax.scan(one, jnp.zeros(x.shape, jnp.float32),
                                (jnp.arange(u.shape[0]), g, u, d))[0]

    def worst_row(got, want):
        return float(jnp.max(jnp.linalg.norm(f32(got) - want, axis=1) / jnp.linalg.norm(want, axis=1)))

    checks: Dict[str, bool] = {}
    wins: Dict[str, Dict[int, bool]] = {}
    for name, n_e, hid, inter, k, cell_rows in size.shapes:
        keys = jax.random.split(jax.random.PRNGKey(seed), 5)
        gate = jax.random.normal(keys[0], (n_e, hid, inter), dtype) * hid ** -0.5
        up = jax.random.normal(keys[1], (n_e, hid, inter), dtype) * hid ** -0.5
        down = jax.random.normal(keys[2], (n_e, inter, hid), dtype) * inter ** -0.5

        def routed(rows):
            x = jax.random.normal(jax.random.fold_in(keys[3], rows), (rows, hid), dtype)
            top_w, top_e = jax.lax.top_k(jax.nn.softmax(
                jax.random.normal(jax.random.fold_in(keys[4], rows), (rows, n_e))), k)
            return x, top_e, top_w

        x, top_e, top_w = routed(cell_rows)
        want = exact(x, top_e, top_w, gate, up, down)
        ids, count = hit_experts(top_e, n_e, min(n_e, cell_rows * k))
        dropped = jnp.where(top_e == ids[0], 0.0, top_w)   # the first hit expert's rows lose it
        as_f8 = lambda a: a.astype(jnp.float8_e4m3fn).astype(dtype)   # noqa: E731
        reads = {
            "stream": worst_row(jax.jit(stream)(x, top_e, top_w, gate, up, down), want),
            "ragged_dot": worst_row(jax.jit(ragged)(x, top_e, top_w, gate, up, down), want),
            "float8 weights": worst_row(jax.jit(stream)(
                x, top_e, top_w, as_f8(gate), as_f8(up), as_f8(down)), want),
            "one hit expert dropped": worst_row(jax.jit(stream)(x, top_e, dropped, gate, up, down), want),
        }
        log(f"moe {name}: {n_e} experts of {hid} x {inter}, top-{k}, {cell_rows} rows hit {int(count)}: worst row's "
            f"|form - float32 jnp| / |float32 jnp| " + ", ".join(f"{n} {v:.4f}" for n, v in reads.items())
            + f" (limit {size.routed_tol})")
        checks[f"moe_{name}_stream_matches_jnp"] = reads["stream"] <= size.routed_tol
        checks[f"moe_{name}_ragged_dot_matches_jnp"] = reads["ragged_dot"] <= size.routed_tol
        checks[f"moe_{name}_float8_weights_are_caught"] = reads["float8 weights"] > size.routed_tol
        checks[f"moe_{name}_a_dropped_expert_is_caught"] = reads["one hit expert dropped"] > size.routed_tol

        wins[name] = {}
        expert_bytes = 3 * hid * inter * dtype.itemsize
        for rows in size.tokens:
            x, top_e, top_w = routed(rows)
            hit = int(hit_experts(top_e, n_e, min(n_e, rows * k))[1])
            ms = {form: _median_call_ms(fn, (x, top_e, top_w, gate, up, down), size.calls)
                  for form, fn in (("stream", stream), ("ragged_dot", ragged))}
            wins[name][rows] = ms["stream"] < ms["ragged_dot"]
            log(f"moe {name}: {rows} rows, {hit} hit ({hit * expert_bytes / 1e6:.0f} MB): "
                + ", ".join(f"{form} {v:.3f} ms a call ({hit * expert_bytes / v / 1e6:.0f} GB/s)"
                            for form, v in ms.items()))
        del gate, up, down
    # the rule the layer applies: the streamed form up to MOE_STREAM_MAX_TOKENS rows
    for name, by_rows in wins.items():
        checks[f"moe_{name}_stream_wins_where_the_rule_takes_it"] = all(
            won for rows, won in by_rows.items() if rows <= MOE_STREAM_MAX_TOKENS)

    # a PREFILL's expert layer: a left-padded prompt in its bucket through the parent's
    # form (no mask: every row routed) and through the masked form (the padded rows' slots
    # absent), as the layer itself dispatches them
    for name, n_e, hid, inter, k, held, bucket, prompts in size.prefill:
        count = held or n_e
        layer = ExpertMLPs(num_experts=n_e, hidden_size=hid, intermediate_size=inter, top_k=k, strategy="blockwise",
                           held_experts=(0, held) if held else None, dtype=dtype, param_dtype=dtype)
        keys = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(seed), bucket), 5)
        gate = jax.random.normal(keys[0], (count, hid, inter), dtype) * hid ** -0.5
        up = jax.random.normal(keys[1], (count, hid, inter), dtype) * hid ** -0.5
        down = jax.random.normal(keys[2], (count, inter, hid), dtype) * inter ** -0.5
        x = jax.random.normal(keys[3], (bucket, hid), dtype)
        top_w, top_e = jax.lax.top_k(jax.nn.softmax(jax.random.normal(keys[4], (bucket, n_e))), k)
        n_slots = bucket * k
        present = top_e < count     # the held experts are the first ``count``

        def weights(g, u, d):
            return {"params": {"gate_proj": g, "up_proj": u, "down_proj": d}}

        # the routing rides as arguments: its sort is then work of the call in both forms
        parent = lambda x_, e, w, m, g, u, d: layer.apply(weights(g, u, d), x_, e, w)  # noqa: E731
        masked = lambda x_, e, w, m, g, u, d: layer.apply(weights(g, u, d), x_, e, w, m)  # noqa: E731
        worst, zero, same, equal, blind, ms_of = 0.0, True, 0.0, True, True, {}
        rng = np.random.default_rng(seed)
        forms = {"parent": parent, "masked": masked}
        run = {form: jax.jit(fn) for form, fn in forms.items()}      # a program a form: the mask is an argument
        timer = {form: _call_timer(fn, size.prefill_calls) for form, fn in forms.items()}
        for prompt in tuple(prompts) + (bucket,):
            mask = jnp.arange(bucket) >= bucket - prompt
            args = (x, top_e, top_w, mask, gate, up, down)
            got, before = run["masked"](*args), run["parent"](*args)
            # content rows that some expert here serves (a held share serves a few of them)
            served = np.flatnonzero(np.asarray(mask & jnp.any(present, axis=1)))
            rows = np.sort(rng.choice(served, min(size.sampled_rows, served.size), replace=False))
            want = exact(x[rows], jnp.where(present, top_e, count)[rows], top_w[rows], gate, up, down)
            worst = max(worst, worst_row(got[rows], want))
            zero &= bool(jnp.all(got[:bucket - prompt] == 0))
            same = max(same, worst_row(got[rows], f32(before[rows])))
            equal &= bool(jnp.all(got[bucket - prompt:] == before[bucket - prompt:]))
            # other values and another routing in the padded rows: no content row may notice
            pad = jnp.logical_not(mask)[:, None]
            other = run["masked"](jnp.where(pad, 3 * x, x), jnp.where(pad, jnp.roll(top_e, 1, axis=0), top_e), *args[2:])
            blind &= bool(jnp.all(other == got))
            ms_of[prompt] = {form: timer[form](*args) for form in forms}
            log(f"moe prefill {name}: {prompt} of {bucket} rows ({n_slots} slots, {'held ' if held else ''}{count} experts): "
                "ms a layer " + ", ".join(f"{form} {v:.3f}" for form, v in ms_of[prompt].items()))
        if not held:
            # the probe: does one ragged_dot's time follow sum(group_sizes), the rows being what they are?
            xs = jax.random.normal(keys[3], (n_slots, hid), dtype)
            alone, probe = _call_timer(jax.lax.ragged_dot, size.prefill_calls), {}
            for prompt in tuple(prompts) + (bucket,):     # the groups its content rows' routing makes
                sizes = jnp.bincount(top_e[bucket - prompt:].reshape(-1), length=n_e).astype(jnp.int32)
                probe[prompt] = alone(xs, up, sizes)
            log(f"moe prefill {name}: ragged_dot alone, {n_slots} x {hid} rows, group sizes summing to "
                + ", ".join(f"{p * k}: {v:.3f} ms" for p, v in probe.items()))
        log(f"moe prefill {name}: worst content row |masked - float32 jnp| / |float32 jnp| {worst:.4f} (limit "
            f"{size.routed_tol}), |masked - parent's form| / |parent's form| {same:.4f} (every content row equal bit for "
            f"bit: {equal}); padded rows zero: {zero}; blind to what the padded rows hold and route to: {blind}")
        checks[f"moe_{name}_prefill_content_rows_match_jnp"] = worst <= size.routed_tol
        checks[f"moe_{name}_prefill_content_rows_match_the_parents_form"] = same <= size.routed_tol
        checks[f"moe_{name}_prefill_padded_rows_are_zero"] = zero
        checks[f"moe_{name}_prefill_is_blind_to_its_padding"] = blind
        # the chip's to say: a full bucket costs no more than the parent's form (2% for the clock),
        # and the emptiest prompt costs less
        checks[f"moe_{name}_prefill_full_bucket_costs_no_more"] = ms_of[bucket]["masked"] <= 1.02 * ms_of[bucket]["parent"]
        checks[f"moe_{name}_prefill_emptiest_prompt_costs_less"] = (
            ms_of[prompts[0]]["masked"] < ms_of[prompts[0]]["parent"])
        del gate, up, down
    return checks


@dataclasses.dataclass(frozen=True)
class Phase:
    """One row of ``PHASES``. Its help line is the first sentence of ``fn``'s
    docstring."""

    fn: Callable[..., Dict[str, bool]]   # (size, seed) or, with ``devices``, (size, seed, devices) -> the named checks
    size: object                         # the chip run's size; the CPU rehearsal passes a tiny one
    devices: bool = False                # ``fn`` takes the devices
    chips: int = 1                       # the ``--chips`` it runs under
    default: bool = False                # the run of its ``--chips`` includes it
    only: bool = False                   # ``--only`` may name it

    @property
    def help(self) -> str:
        return " ".join(self.fn.__doc__.split()).split(". ")[0].rstrip(".")


# in the order they run
PHASES: Dict[str, Phase] = {
    "train": Phase(train_phase, TrainSize(), devices=True, default=True),
    "serve": Phase(serve_phase, ServeSize(), default=True),
    "mla": Phase(mla_phase, MlaSize(), default=True, only=True),
    "dsa": Phase(dsa_phase, DsaSize(), default=True, only=True),
    "glm": Phase(glm_phase, GlmSize(), default=True, only=True),
    "moe": Phase(moe_phase, MoeSize(), only=True),
    "trinity": Phase(trinity_phase, TrinitySize(), only=True),
    "zaya": Phase(zaya_phase, ZayaSize(), only=True),
    "solar": Phase(solar_phase, SolarSize(), only=True),
    "ouro": Phase(ouro_phase, OuroSize(), only=True),
    "walk": Phase(walk_phase, WalkSize(), only=True),
    "runahead": Phase(runahead_phase, RunAheadSize(), only=True),
    "flash": Phase(flash_phase, FlashSize(), only=True),
    "tp_train": Phase(tp_train_phase, TrainSize(), devices=True, chips=4, default=True),
    "tp_serve": Phase(tp_serve_phase, TP_SERVE, devices=True, chips=4, default=True),
    "remat": Phase(remat_phase, RematSize(), devices=True, chips=4, default=True),
}


def default_run(chips: int) -> List[str]:
    """The phases ``--chips chips`` runs when ``--only`` names none."""
    return [name for name, phase in PHASES.items() if phase.default and phase.chips == chips]


def run(names: Sequence[str], seed: int, devices, sizes=None) -> Dict[str, bool]:
    """The phases ``names`` in that order, in one process; ``sizes``: phase
    name -> a size in place of the table's."""
    checks: Dict[str, bool] = {}
    for name in names:
        phase = PHASES[name]
        size = (sizes or {}).get(name, phase.size)
        checks.update(phase.fn(size, seed, devices) if phase.devices else phase.fn(size, seed))
    return checks


def parse_args(argv=None):
    only = [name for name, phase in PHASES.items() if phase.only]
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help=f"1 (default): {' + '.join(default_run(1))} on one chip. 4: only "
                        f"{' + '.join(default_run(4))}, the four-chip paths and their one-device counterparts")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--only", default="all", choices=("all", *only),
                   help="one chip: every default phase (default), or one phase alone. "
                        + " ".join(f"{name}: {PHASES[name].help}." for name in only))
    p.add_argument("--bundles", metavar="DIR", default=None,
                   help="with --only walk, flash, dsa, glm or trinity: first compile the kernel for a described v5e "
                        "with the compiler's listing dumped to DIR, and print the instruction bundles of one block's "
                        "body (walk), of the interior and the edge body (flash) or of the prefill's grouped forward's "
                        "bodies beside PR 49's (dsa, glm, trinity)")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.bundles is not None:
        if args.only not in ("walk", "flash", *GROUP_BUNDLES):
            print("chip_smoke: --bundles goes with --only walk, flash, dsa, glm or trinity", file=sys.stderr)
            return 2
        # before this process touches JAX: the compile's process loads the TPU's library itself
        if args.only == "walk":
            log(f"walk: one block's body is {walk_block_bundles(args.bundles)} instruction bundles in the "
                f"described-v5e listing under {args.bundles}")
        elif args.only in GROUP_BUNDLES:
            heads, window = GROUP_BUNDLES[args.only]
            bucket = PHASES[args.only].size.forward_bucket
            log(f"{args.only}: a step of the prefill's grouped forward ({heads[0]}/{heads[1]} heads of {heads[2]}/"
                f"{heads[3]}, a {bucket} bucket) is {group_body_bundles(args.bundles, heads, window, bucket)} "
                f"instruction bundles a body, PR 49's {group_body_bundles(args.bundles, heads, window, bucket, True)}, "
                f"in the described-v5e listings under {args.bundles}")
        else:
            interior, edge = flash_body_bundles(args.bundles)
            log(f"flash: the interior body is {interior} instruction bundles and the edge body {edge} in the "
                f"described-v5e listing under {args.bundles}")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, found platform "
            f"{devices[0].platform!r} — no result", file=sys.stderr,
        )
        return 2
    if len(devices) != args.chips:
        print(
            f"chip_smoke: --chips {args.chips} but JAX reports "
            f"{len(devices)} devices — no result", file=sys.stderr,
        )
        return 2

    from neuronx_distributed_tpu.inference import aot

    cache = aot.enable_persistent_cache(
        os.path.join(os.path.dirname(os.path.abspath(__file__)), ".jax_cache")
    )
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    log(f"device {device}, jax {jax.__version__}, compile cache {cache}")
    t0 = time.perf_counter()
    names = [args.only] if args.chips == 1 and args.only != "all" else default_run(args.chips)
    checks = run(names, args.seed, devices)
    failed = sorted(name for name, ok in checks.items() if not ok)
    log(
        f"{len(checks) - len(failed)}/{len(checks)} checks passed in "
        f"{time.perf_counter() - t0:.0f}s"
        + (f"; FAILED: {failed}" if failed else "")
    )
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
