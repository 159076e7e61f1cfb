"""Training-loop adapter tests (reference analogue: lightning strategy/module
unit tests, test/unit_test/wrapper/)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.models.llama import LlamaForCausalLM, tiny_llama
from neuronx_distributed_tpu.parallel import mesh as mesh_lib
from neuronx_distributed_tpu.trainer import OptimizerConfig
from neuronx_distributed_tpu.trainer.loop import (
    Callback,
    CheckpointCallback,
    MetricsLogger,
    ThroughputMeter,
    Trainer,
    TrainerHealth,
)
from neuronx_distributed_tpu.utils.timeline import Timeline


def _batches(cfg, n=100, bs=8, seq=16):
    key = jax.random.PRNGKey(0)
    for i in range(n):
        ids = jax.random.randint(jax.random.fold_in(key, i), (bs, seq), 0, cfg.vocab_size)
        yield {"input_ids": ids, "labels": jnp.roll(ids, -1, 1)}


class _Recorder(Callback):
    def __init__(self):
        self.events = []
        self.losses = []

    def on_train_start(self, trainer):
        self.events.append("start")

    def on_step_end(self, trainer, metrics):
        self.events.append(trainer.step)
        self.losses.append(float(metrics["loss"]))

    def on_train_end(self, trainer):
        self.events.append("end")


def test_trainer_fit_runs_and_loss_decreases(tmp_path):
    mesh_lib.initialize_model_parallel(tensor_model_parallel_size=2)
    cfg = tiny_llama()
    rec = _Recorder()
    tl = Timeline(str(tmp_path / "trace.json"))
    trainer = Trainer(
        model=LlamaForCausalLM(cfg, attention_impl="xla"),
        optimizer_config=OptimizerConfig(learning_rate=1e-3, zero1=True),
        callbacks=[rec, MetricsLogger(log_every=2)],
        timeline=tl,
    )
    metrics = trainer.fit(_batches(cfg), jax.random.PRNGKey(1), max_steps=6)
    assert rec.events[0] == "start" and rec.events[-1] == "end"
    assert trainer.step == 6
    assert rec.losses[-1] < rec.losses[0]
    assert "throughput_seq_s" in metrics and metrics["throughput_seq_s"] > 0
    assert (tmp_path / "trace.json").exists()
    # the attention implementation the step traced is on the ledger (what
    # "auto" resolves to is pinned in tests/kernels/test_backend.py and the
    # chip_smoke rehearsal), and so is what the model's remat policy keeps by
    # name (nothing: this model does not remat; tests/trainer/test_remat_record.py)
    assert trainer.programs.snapshot(analyze=False)["resolved"] == {
        "attention": "xla", "remat": [],
    }


def test_trainer_checkpoint_callback(tmp_path):
    cfg = tiny_llama(num_layers=2)
    ckpt_dir = str(tmp_path / "ckpts")
    trainer = Trainer(
        model=LlamaForCausalLM(cfg, attention_impl="xla"),
        optimizer_config=OptimizerConfig(zero1=False),
        callbacks=[CheckpointCallback(ckpt_dir, every=2, async_save=False)],
    )
    trainer.fit(_batches(cfg), jax.random.PRNGKey(1), max_steps=4)
    from neuronx_distributed_tpu.trainer.checkpoint import create_checkpoint_storage

    tags = create_checkpoint_storage(ckpt_dir).list_checkpoint_tags()
    assert "step_2" in tags and "step_4" in tags


def test_throughput_meter():
    m = ThroughputMeter(batch_size=8, window=4)
    import time

    for _ in range(5):
        time.sleep(0.01)
        t = m.update()
    assert 0 < t < 8 / 0.01 * 2


def test_progress_and_hooks_callbacks(tmp_path):
    """ProgressBar + HooksCallback run through fit (reference Lightning TQDM
    bar + NeuronHooksCallback plugins)."""
    from neuronx_distributed_tpu.trainer.loop import HooksCallback, ProgressBar

    mesh_lib.initialize_model_parallel()
    cfg = tiny_llama(max_seq_len=32)
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    seen = []
    trainer = Trainer(
        model=model,
        optimizer_config=OptimizerConfig(zero1=False),
        callbacks=[
            ProgressBar(total_steps=2),
            HooksCallback(every=1, sink=seen.append),
        ],
    )
    ids = jax.random.randint(jax.random.PRNGKey(0), (8, 16), 0, cfg.vocab_size)

    def data():
        while True:
            yield {"input_ids": ids, "labels": jnp.roll(ids, -1, 1)}

    trainer.fit(data(), jax.random.PRNGKey(0), max_steps=2)
    assert len(seen) == 2
    assert all(v > 0 for v in seen[0].values())


def test_callback_exception_isolated(tmp_path):
    """Satellite: one raising callback must not kill the run — the error is
    counted (``callback_errors``), the other callbacks keep firing, and
    ``on_train_end`` reaches EVERY callback including the raiser."""
    mesh_lib.initialize_model_parallel()
    cfg = tiny_llama(num_layers=2, max_seq_len=32)
    model = LlamaForCausalLM(cfg, attention_impl="xla")

    class Raiser(Callback):
        def __init__(self):
            self.ended = False

        def on_step_end(self, trainer, metrics):
            raise RuntimeError("boom")

        def on_train_end(self, trainer):
            self.ended = True

    raiser = Raiser()
    rec = _Recorder()
    trainer = Trainer(
        model=model,
        optimizer_config=OptimizerConfig(zero1=False),
        callbacks=[raiser, rec],
    )
    ids = jax.random.randint(jax.random.PRNGKey(0), (8, 16), 0, cfg.vocab_size)

    def data():
        while True:
            yield {"input_ids": ids, "labels": jnp.roll(ids, -1, 1)}

    metrics = trainer.fit(data(), jax.random.PRNGKey(0), max_steps=3)
    assert trainer.step == 3  # training survived every raise
    assert trainer.callback_errors == 3
    # mirrored into the metrics dict (assembled BEFORE the step's own
    # callbacks fire, so the last dict carries the first two raises)
    assert metrics["callback_errors"] == 2
    assert len(rec.losses) == 3  # the healthy callback kept firing
    assert raiser.ended and rec.events[-1] == "end"
    # a failing callback is a FAULT for health(): a broken checkpoint save
    # means no durable progress — unattended monitoring must not read OK
    assert trainer.health() is TrainerHealth.DEGRADED


def test_train_end_epilogue_runs_on_non_halt_failure():
    """``on_train_end`` (TensorBoard flush, async-save drain) and the
    timeline save run even when fit() dies on a NON-TrainerHalted error —
    e.g. a deterministic failure while preparing a batch."""
    mesh_lib.initialize_model_parallel()
    cfg = tiny_llama(num_layers=2, max_seq_len=32)
    model = LlamaForCausalLM(cfg, attention_impl="xla")

    class ExplodingSource:
        def corrupt_batch(self, step, batch):
            if step == 2:
                raise ValueError("poisoned batch")
            return batch

        def on_step_start(self, step):
            pass

        def on_dispatch(self, attempt):
            pass

    rec = _Recorder()
    trainer = Trainer(
        model=model,
        optimizer_config=OptimizerConfig(zero1=False),
        callbacks=[rec],
        fault_injector=ExplodingSource(),
    )
    ids = jax.random.randint(jax.random.PRNGKey(0), (8, 16), 0, cfg.vocab_size)

    def data():
        while True:
            yield {"input_ids": ids, "labels": jnp.roll(ids, -1, 1)}

    with pytest.raises(ValueError, match="poisoned batch"):
        trainer.fit(data(), jax.random.PRNGKey(0), max_steps=5)
    assert len(rec.losses) == 2  # two clean steps before the failure
    assert rec.events[-1] == "end"  # the epilogue still ran


def test_restore_signal_handlers_tolerates_none_original():
    """When a handler was installed by non-Python code, ``signal.signal``
    returns ``None`` at install time — the fit epilogue must skip it
    (nothing Python can restore it to) instead of crashing with a
    TypeError, while still restoring the handlers it CAN."""
    import signal

    mesh_lib.initialize_model_parallel()
    cfg = tiny_llama(num_layers=2, max_seq_len=32)
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    trainer = Trainer(model=model, optimizer_config=OptimizerConfig(zero1=False))

    prev = signal.getsignal(signal.SIGINT)
    try:
        trainer._restore_signal_handlers(
            {signal.SIGTERM: None, signal.SIGINT: signal.SIG_DFL}
        )
        # the None entry was skipped, the real one was restored
        assert signal.getsignal(signal.SIGINT) is signal.SIG_DFL
    finally:
        signal.signal(signal.SIGINT, prev)


def test_checkpoint_callback_save_on_end(tmp_path):
    """Satellite: ``save_on_end`` writes the final step_N checkpoint when
    max_steps doesn't land on the ``every`` boundary, and skips it when a
    periodic save already covered that step."""
    import os

    from neuronx_distributed_tpu.trainer.checkpoint import (
        DONE_MARKER,
        create_checkpoint_storage,
        load_checkpoint,
    )

    cfg = tiny_llama(num_layers=2)
    model = LlamaForCausalLM(cfg, attention_impl="xla")

    d1 = str(tmp_path / "odd")
    t1 = Trainer(
        model=model, optimizer_config=OptimizerConfig(zero1=False),
        callbacks=[CheckpointCallback(d1, every=2, async_save=False)],
    )
    t1.fit(_batches(cfg), jax.random.PRNGKey(1), max_steps=3)
    tags = create_checkpoint_storage(d1).list_checkpoint_tags()
    assert "step_3" in tags  # 3 % 2 != 0 — written by on_train_end
    _, uc, _ = load_checkpoint(d1, tag="step_3")
    assert uc["step"] == 3 and "rng_key" in uc  # full exact-resume payload

    import json

    d2 = str(tmp_path / "even")
    trace = str(tmp_path / "trace.json")
    t2 = Trainer(
        model=model, optimizer_config=OptimizerConfig(zero1=False),
        callbacks=[CheckpointCallback(d2, every=2, async_save=False)],
        timeline=Timeline(trace),
    )
    t2.fit(_batches(cfg), jax.random.PRNGKey(1), max_steps=4)
    storage = create_checkpoint_storage(d2)
    assert storage.file_exists(os.path.join("step_4", DONE_MARKER))
    # the periodic save covered step 4; on_train_end must not save it AGAIN
    saves = [
        e["args"]["tag"]
        for e in json.load(open(trace))["traceEvents"]
        if e["name"] == "checkpoint"
    ]
    assert saves.count("step_4") == 1


def test_trainer_evaluate():
    """evaluate(): mean loss with current params, no updates (Lightning
    validation-loop parity)."""
    mesh_lib.initialize_model_parallel()
    cfg = tiny_llama(max_seq_len=32)
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    trainer = Trainer(model=model, optimizer_config=OptimizerConfig(zero1=False))
    ids = jax.random.randint(jax.random.PRNGKey(0), (8, 16), 0, cfg.vocab_size)

    def data():
        while True:
            yield {"input_ids": ids, "labels": jnp.roll(ids, -1, 1)}

    trainer.fit(data(), jax.random.PRNGKey(0), max_steps=2)
    params_before = jax.tree.map(lambda a: np.asarray(a).copy(), trainer.state.params)
    report = trainer.evaluate(data(), max_steps=3)
    assert report["eval_steps"] == 3 and report["eval_loss"] > 0
    for a, b in zip(jax.tree.leaves(params_before), jax.tree.leaves(trainer.state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_trainer_evaluate_under_interleaved_pp():
    """evaluate() under pp=2 with the interleaved schedule uses the
    forward-only cycle loop (VERDICT r3 next #6) — and must agree with the
    training step's loss on identical params/batch."""
    from neuronx_distributed_tpu.pipeline.llama import LlamaPipelineAdapter

    mesh_lib.initialize_model_parallel(
        pipeline_model_parallel_size=2, tensor_model_parallel_size=2
    )
    cfg = tiny_llama(max_seq_len=32, scan_layers=True, num_layers=4)
    adapter = LlamaPipelineAdapter(
        config=cfg, num_microbatches=4, attention_impl="xla",
        schedule="interleaved", num_chunks=2,
    )
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    trainer = Trainer(
        model=model, optimizer_config=OptimizerConfig(zero1=False),
        pipeline=adapter,
    )
    ids = jax.random.randint(jax.random.PRNGKey(0), (8, 16), 0, cfg.vocab_size)

    def data():
        while True:
            yield {"input_ids": ids, "labels": jnp.roll(ids, -1, 1)}

    metrics = trainer.fit(data(), jax.random.PRNGKey(0), max_steps=1)
    report = trainer.evaluate(data(), max_steps=1)
    assert report["eval_steps"] == 1
    # fit's reported loss is computed BEFORE its update; evaluate runs AFTER
    # one step, so it must be <= that first-step loss on this deterministic
    # batch (and > 0)
    assert 0 < report["eval_loss"] < float(metrics["loss"])
