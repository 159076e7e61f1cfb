"""One run of a training cell: ``Trainer.fit`` over seeded batches.

The benchmark calls ``Trainer.fit`` once, as a user does, and hands it a data
iterator of its own. The iterator is where the benchmark's clock lives:

* the first ``warmup_steps`` batches are set-up (the first holds the compile);
* before it hands out the first measured batch it waits for the device
  (``block_until_ready`` on the trainer's state) and opens the window;
* it hands out batches until ``--seconds`` have passed, then raises
  ``WindowClosed`` to end ``fit``; the runner waits for the last dispatched
  step and closes the window there. ``train_tokens_per_s`` is the tokens of
  every step of the window over that whole time.

Losses are kept as device scalars and read after the window, so the
trainer's one deferred readback per step is not disturbed.

Correctness, outside the window: step 1's loss against the plain float32
reference's loss on the same batch and the same seeded initial weights; and a
loss that falls over the run.
"""

from __future__ import annotations

import importlib
import os
import shutil
import time

import numpy as np

from perfbench import peaks, xplane
from perfbench.spans import Spans
from perfbench.tracing import TRACE_SECONDS, TraceWindow, seed_key

SPAN_DATA = "data.next"
SPAN_CALLBACK = "callback.on_step_end"
SPAN_NAMES = (SPAN_DATA, SPAN_CALLBACK)


class WindowClosed(Exception):
    """Raised by the data iterator when the measured window is over."""


def run(*, config, traffic, seed, seconds, trace, devices, t_start, out_dir, log) -> dict:
    import jax
    from flax.core import meta

    from neuronx_distributed_tpu.parallel import mesh as mesh_lib
    from neuronx_distributed_tpu.trainer import OptimizerConfig
    from neuronx_distributed_tpu.trainer.loop import Callback, Trainer
    from neuronx_distributed_tpu.trainer.trainer import initialize_parallel_model

    spans = Spans()
    family = importlib.import_module(f"perfbench.families.{config['family']}")
    layout = config["training"]
    tp = int(layout["tensor_parallel"])
    batch, seq = int(traffic["batch_sequences"]), int(traffic["sequence_length"])
    warmup = int(traffic["warmup_steps"])
    trace_s = min(TRACE_SECONDS, seconds)
    geometry = family.geometry(config["model"])
    vocab = geometry["vocab_size"]

    mesh_lib.destroy_model_parallel()
    mesh_lib.initialize_model_parallel(tensor_model_parallel_size=tp, devices=list(devices))
    model = family.build(
        config["model"], runner="train", max_seq_len=seq,
        sequence_parallel=bool(layout.get("sequence_parallel", False)) and tp > 1,
        remat=bool(layout.get("remat", False)),
    )

    # token ids follow a Zipf law over the vocabulary, as a corpus's do: the
    # unigram is something to learn, so the loss of a correct step falls
    zipf = traffic["token_zipf"]
    weights = 1.0 / (np.arange(vocab) + float(zipf["shift"])) ** float(zipf["exponent"])
    cdf = np.cumsum(weights / weights.sum())

    def batch_at(i: int) -> dict:
        """Batch ``i`` of the seed: token ids, next-token labels."""
        rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x7124, i]))
        ids = np.minimum(np.searchsorted(cdf, rng.random((batch, seq + 1))), vocab - 1).astype(np.int32)
        return {"input_ids": ids[:, :-1], "labels": ids[:, 1:],
                "loss_mask": np.ones((batch, seq), np.float32)}

    # --- the reference's loss on batch 0, before the trainer takes the memory --
    first = batch_at(0)
    key = seed_key(seed, 0)
    with spans.span("reference_check"):
        params, _ = initialize_parallel_model(model, key, first["input_ids"])
        n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
        ref_mod = importlib.import_module(f"perfbench.references.{family.reference}")
        ref = ref_mod.Reference(config["model"], meta.unbox(params))
        check = config["reference_check"]
        ref_loss = ref.loss(first["input_ids"], first["labels"])
        del ref, params

    trace_dir = os.path.join(out_dir, "trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)

    tracer = TraceWindow(trace_dir) if trace else None
    state = {"i": 0, "t_open": None, "step_t": []}
    losses = []

    class Data:
        def __iter__(self):
            return self

        def __next__(self):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(SPAN_DATA):
                i = state["i"]
                if i == warmup:
                    # everything before this is set-up; the window opens on an idle device
                    jax.block_until_ready(trainer.state)
                    state["t_open"] = time.perf_counter()
                if state["t_open"] is not None:
                    now = time.perf_counter()
                    if now - state["t_open"] >= seconds:
                        raise WindowClosed
                    if tracer and not tracer.on and now - state["t_open"] >= seconds - trace_s:
                        tracer.start()
                        state["t_trace"] = time.perf_counter()
                state["i"] = i + 1
                out = batch_at(i)
            spans.add(SPAN_DATA, t0, time.perf_counter())
            return out

    class Capture(Callback):
        def on_step_end(self, trainer, metrics):
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation(SPAN_CALLBACK):
                losses.append(metrics["loss"])     # a device scalar: read after the window
                state["step_t"].append(t)

    trainer = Trainer(model=model, optimizer_config=OptimizerConfig(), callbacks=[Capture()],
                      handle_signals=False)
    t_fit = time.perf_counter()
    try:
        trainer.fit(Data(), key, max_steps=10**9)
    except WindowClosed:
        pass
    jax.block_until_ready(trainer.state)
    t_close = time.perf_counter()
    if tracer and tracer.on:
        tracer.stop()
    t_open = state["t_open"]
    if t_open is None:
        raise RuntimeError("the run ended before the window opened")

    spans.add("warmup", t_fit, t_open)               # init, the compile and the first steps
    steps = state["i"] - warmup                     # batches handed out inside the window
    window_s = t_close - t_open
    tokens = steps * batch * seq
    losses = [float(x) for x in losses]
    compiles = int(trainer.programs.snapshot(analyze=False)["by_program"]["train_step"]["compiles"])

    tol = float(check["loss_tolerance"])
    loss_ok = abs(losses[0] - ref_loss) <= tol
    falls = float(np.mean(losses[-3:])) < float(np.mean(losses[:3]))
    finite = all(np.isfinite(losses))
    skipped = int(trainer.anomaly_skips)
    notes = [
        f"window {window_s:.3f}s: {steps} steps of {batch} x {seq} tokens; step 1 loss {losses[0]:.5f} vs "
        f"reference {ref_loss:.5f} (|d| {abs(losses[0] - ref_loss):.2e}, tolerance {tol:g}); loss "
        f"{np.mean(losses[:3]):.4f} -> {np.mean(losses[-3:]):.4f}; train_step compiles {compiles}; "
        f"anomaly skips {skipped}; set-up {t_open - t_start:.1f}s "
        f"(reference check {spans.total('reference_check'):.1f}s); parameters {n_params}"
    ]
    correct = loss_ok and falls and finite and compiles == 1 and skipped == 0 and steps > 0
    step_t = [t for t in state["step_t"] if t >= t_open]
    run_record = {
        "correct": correct,
        "attempted": steps + 1,
        "failed": skipped + (0 if loss_ok else 1),
        "end_to_end": {
            "setup_s": t_open - t_start,
            "train_tokens_per_s": tokens / window_s,
        },
        "notes": notes,
        "kind": "train",
        "config": config, "traffic": traffic, "geometry": geometry,
        "device_kind": devices[0].device_kind, "chips": len(devices),
        "spans": spans, "window": (t_open, t_close), "seconds": seconds,
        "step_ms": [1e3 * (b - a) for a, b in zip(step_t, step_t[1:])],
        "tokens_per_s": tokens / window_s,
        "flops_per_token": peaks.train_flops_per_token(
            n_params, family.embed_table_params(config["model"]),
            num_layers=geometry["num_layers"], seq=seq, hidden=geometry["hidden"]),
        "batch": batch, "seq": seq, "n_params": n_params,
        "compiles_in_window": compiles - 1,
        # steps dispatched while the trace was on (each ends inside it or at its close)
        "steps_in_trace": sum(1 for t in state["step_t"] if t >= state.get("t_trace", float("inf"))),
    }
    if trace:
        run_record["trace"] = xplane.reduce_trace(
            trace_dir, SPAN_NAMES, require_device=devices[0].platform == "tpu")
    return run_record
