"""High-level training loop with callbacks (reference:
``src/neuronx_distributed/lightning/`` — ``NeuronXLAStrategy``,
``NeuronLTModule``, the rank-0 TensorBoard logger with step gating
(logger.py:24), TQDM bar, and ``NeuronHooksCallback``; plus the examples'
``Throughput`` moving-average meter, training_utils.py:338).

Lightning's role in the reference — wiring parallel init, precision, the
train loop, logging, and checkpoint IO — collapses here into one plain
``Trainer`` class over the jitted train step. Callbacks get the same hook
points the reference's Lightning plugins use.

Fault tolerance — the unattended-safety contract the serving engine carries
(``serving/engine.py``), training-side (chaos-tested in
``tests/trainer/test_faults.py``):

* **Anomaly guards** — a ``good_step`` flag computed INSIDE the jitted step
  (``build_train_step(anomaly_guard=...)``): non-finite loss/grad-norm or a
  grad-norm spike vs the device-carried EMA skips the update on device with
  params/opt-state bit-identical, no host round-trip, no recompile. The
  host budgets cumulative skips against ``AnomalyGuardConfig.budget`` by
  reading the previous step's tiny flag pair AFTER the next step has been
  dispatched (the readback overlaps device compute — the clean path never
  stalls on the guard); exceeding the budget HALTS with an emergency
  checkpoint instead of silently training on garbage.
* **Dispatch recovery** — a failed train-step dispatch retries against the
  last known-good ``(state, step)`` snapshot (the pre-dispatch state:
  host-side failures leave donated buffers unconsumed) with the shared
  decrementing-jitter :class:`~neuronx_distributed_tpu.utils.retry.
  RetryPolicy`; ``max_attempts`` CONSECUTIVE failures — or a failure that
  consumed the donated buffers — land in HALTED with an emergency
  checkpoint of the surviving state (mirroring serving's
  ``dispatch_retry`` semantics).
* **Exact resume** — checkpoints carry the base RNG key, the data
  iterator's cursor (``state()/restore()`` protocol, trainer/data.py), and
  the throughput/step bookkeeping, so ``fit(resume_from=...)`` after a
  mid-run kill reproduces the uninterrupted run's loss curve
  bit-identically.
* **Graceful preemption** — SIGTERM/SIGINT finish the in-flight step,
  write a final ``step_N`` checkpoint through the done-marker protocol,
  and return cleanly (``trainer.preempted``); a second signal falls
  through to the original handler.
* **Callback isolation** — one callback raising in a hook is logged with
  its class name and counted (``callback_errors``), never fatal;
  ``on_train_end`` still runs for every callback.
* **Health** — ``trainer.health()`` reports ``OK/DEGRADED/HALTED``
  (DEGRADED = anomaly skip or dispatch retry within
  ``degraded_cooldown_steps``); counters mirror into the per-step metrics
  dict and Timeline instants.

Every fault path is drivable deterministically through
:class:`~neuronx_distributed_tpu.trainer.faults.FaultInjector`; with no
injector the hooks are no-ops."""

from __future__ import annotations

import dataclasses
import enum
import os
import signal as _signal
import threading
import time
from collections import deque
from typing import Any, Callable, Iterable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from neuronx_distributed_tpu.trainer.checkpoint import save_checkpoint
from neuronx_distributed_tpu.trainer.trainer import (
    AnomalyGuardConfig,
    OptimizerConfig,
    build_train_step,
    create_train_state,
    init_anomaly_guard_state,
    make_optimizer,
    shard_batch,
)
from neuronx_distributed_tpu.observability import tracing
from neuronx_distributed_tpu.utils.logger import get_logger
from neuronx_distributed_tpu.utils.retry import RetryPolicy
from neuronx_distributed_tpu.utils.timeline import Timeline

logger = get_logger(__name__)


class TrainerHealth(enum.Enum):
    """Trainer health snapshot (``Trainer.health()``) — the serving
    engine's surface, training-side. ``OK`` — training normally.
    ``DEGRADED`` — progressing, but an anomaly skip or dispatch retry
    happened within the last ``degraded_cooldown_steps`` steps. ``HALTED``
    — the anomaly budget or dispatch retry budget is exhausted; the run
    stopped with an emergency checkpoint and ``halt_reason`` says why."""

    OK = "ok"
    DEGRADED = "degraded"
    HALTED = "halted"


class TrainerHalted(RuntimeError):
    """Raised by ``fit`` when the run halts (anomaly budget exceeded or
    dispatch retries exhausted). ``emergency_tag`` names the emergency
    checkpoint written before halting (``None`` if no checkpoint directory
    was known or the state was unusable)."""

    def __init__(self, reason: str, emergency_tag: Optional[str] = None):
        super().__init__(reason)
        self.reason = reason
        self.emergency_tag = emergency_tag


class Callback:
    """Hook points (reference: Lightning callback surface used by NxD)."""

    def on_train_start(self, trainer: "Trainer") -> None: ...

    def on_step_end(self, trainer: "Trainer", metrics: dict) -> None: ...

    def on_train_end(self, trainer: "Trainer") -> None: ...


class ThroughputMeter:
    """Moving-average seqs/s over the last N steps (reference Throughput,
    examples/training/llama/training_utils.py:338)."""

    def __init__(self, batch_size: int, window: int = 10):
        self.batch_size = batch_size
        self.window = window
        self._times: deque = deque(maxlen=window + 1)
        self.throughput = 0.0

    def update(self) -> float:
        self._times.append(time.perf_counter())
        if len(self._times) >= 2:
            dt = self._times[-1] - self._times[0]
            steps = len(self._times) - 1
            self.throughput = self.batch_size * steps / max(dt, 1e-9)
        return self.throughput


class MetricsLogger(Callback):
    """Rank-0 step-gated metric logging, optionally into TensorBoard
    (reference lightning/logger.py:24 NeuronTensorBoardLogger). Robustness
    counters (``anomaly_skips``/``dispatch_retries``/
    ``emergency_checkpoints``/``callback_errors``) ride the same metrics
    dict, so a fault-injected run's log explains itself."""

    def __init__(self, log_every: int = 10, tensorboard_dir: Optional[str] = None):
        self.log_every = log_every
        self._tb = None
        if tensorboard_dir is not None:
            try:
                from torch.utils.tensorboard import SummaryWriter

                self._tb = SummaryWriter(tensorboard_dir)
            except Exception as e:  # tensorboard is optional
                logger.warning("tensorboard unavailable (%s); file logging only", e)

    def on_step_end(self, trainer, metrics):
        if trainer.step % self.log_every != 0:
            return
        scalars = {k: float(v) for k, v in metrics.items()}
        logger.info(
            "step %d: %s", trainer.step,
            " ".join(f"{k}={v:.4f}" for k, v in scalars.items()),
        )
        if self._tb is not None:
            for k, v in scalars.items():
                self._tb.add_scalar(k, v, trainer.step)

    def on_train_end(self, trainer):
        if self._tb is not None:
            self._tb.flush()


class ProgressBar(Callback):
    """TQDM-style progress (reference: the Lightning TQDM bar plugin). Falls
    back to plain line logging when tqdm is unavailable."""

    def __init__(self, total_steps: Optional[int] = None):
        self.total_steps = total_steps
        self._bar = None

    def on_train_start(self, trainer):
        try:
            from tqdm import tqdm

            self._bar = tqdm(total=self.total_steps, desc="train", unit="step")
        except Exception:
            self._bar = None

    def on_step_end(self, trainer, metrics):
        if self._bar is not None:
            self._bar.update(1)
            self._bar.set_postfix(
                {k: f"{float(v):.4f}" for k, v in metrics.items() if k == "loss"}
            )

    def on_train_end(self, trainer):
        if self._bar is not None:
            self._bar.close()


class HooksCallback(Callback):
    """Per-parameter-group gradient/param-norm dumps (reference
    ``lightning/neuron_hooks_callback.py:8`` NeuronHooksCallback — activation
    and grad-norm debugging). Computes top-level-group param norms from the
    train state every ``every`` steps and hands them to ``sink`` (default:
    the module logger)."""

    def __init__(self, every: int = 50, sink: Optional[Callable] = None):
        self.every = every
        self.sink = sink or (lambda d: logger.info("param norms: %s", d))

    def on_step_end(self, trainer, metrics):
        if trainer.step % self.every != 0:
            return
        params = trainer.state.params
        groups = params.items() if isinstance(params, dict) else [("params", params)]
        norms = {}
        for name, tree in groups:
            leaves = jax.tree.leaves(tree)
            if leaves:
                # graftlint: ok[GL02] debug hook, gated to every `every`
                # steps — one norm scalar per param group is its contract
                norms[name] = float(
                    jax.numpy.sqrt(
                        sum(jax.numpy.sum(l.astype(jax.numpy.float32) ** 2)
                            for l in leaves)
                    )
                )
        self.sink(norms)


class CheckpointCallback(Callback):
    """Periodic async checkpoint with retention (reference
    lightning/checkpoint_io.py + trainer/checkpoint.py save path).

    ``save_on_end`` writes a final ``step_N`` checkpoint from
    ``on_train_end`` when the last step did not land on the ``every``
    boundary (skipped when one for that step already committed — e.g. the
    graceful-preemption save — and when the trainer halted, which wrote an
    emergency checkpoint instead). The saved ``user_content`` is the
    trainer's full exact-resume payload (step, RNG, data cursor,
    bookkeeping)."""

    def __init__(self, checkpoint_dir: str, every: int = 100,
                 num_kept: Optional[int] = 3, async_save: bool = True,
                 save_on_end: bool = True):
        self.checkpoint_dir = checkpoint_dir
        self.every = every
        self.num_kept = num_kept
        self.async_save = async_save
        self.save_on_end = save_on_end

    def _save(self, trainer, async_save: bool) -> None:
        trainer.save_tagged_checkpoint(
            self.checkpoint_dir, f"step_{trainer.step}",
            num_kept=self.num_kept, async_save=async_save,
        )

    def on_step_end(self, trainer, metrics):
        if trainer.step % self.every != 0:
            return
        self._save(trainer, self.async_save)

    def on_train_end(self, trainer):
        from neuronx_distributed_tpu.trainer.checkpoint import (
            DONE_MARKER,
            create_checkpoint_storage,
            finalize_checkpoints,
        )

        # drain in-flight async saves FIRST: a pending step_N commit must
        # win over (not race) the save_on_end rewrite of the same tag
        finalize_checkpoints()
        if (
            not self.save_on_end
            or trainer.step == 0
            or getattr(trainer, "halt_reason", None) is not None
        ):
            return
        storage = create_checkpoint_storage(self.checkpoint_dir)
        if storage.file_exists(os.path.join(f"step_{trainer.step}", DONE_MARKER)):
            return  # periodic/preemption save already covered this step
        self._save(trainer, async_save=False)
        finalize_checkpoints()


@dataclasses.dataclass
class Trainer:
    """Plain training loop over the jitted SPMD step (the reference's
    Lightning strategy+module+launcher collapse into this), carrying the
    fault-tolerance layer described in the module docstring."""

    model: Any
    optimizer_config: OptimizerConfig = dataclasses.field(default_factory=OptimizerConfig)
    callbacks: List[Callback] = dataclasses.field(default_factory=list)
    loss_fn: Optional[Callable] = None
    timeline: Optional[Timeline] = None
    # Pipeline-parallel adapter (e.g. pipeline.llama.LlamaPipelineAdapter).
    # When set, fit() builds the pipelined train step (GPipe scan or explicit
    # 1F1B per the adapter's schedule) instead of the monolithic one — the
    # reference's NxDPPModel wrap inside initialize_parallel_model
    # (trainer/trainer.py:147).
    pipeline: Optional[Any] = None
    # --- fault tolerance ----------------------------------------------------
    # On-device anomaly guard config (None disables; pipeline adapters build
    # their own step, so the guard covers the monolithic path only).
    anomaly_guard: Optional[AnomalyGuardConfig] = dataclasses.field(
        default_factory=AnomalyGuardConfig
    )
    # Bounded retries for a failed train-step dispatch (serving's defaults).
    dispatch_retry: Optional[RetryPolicy] = None
    # Deterministic chaos source (trainer/faults.py); None = all hooks no-op.
    fault_injector: Optional[Any] = None
    # Where emergency/preemption checkpoints go; falls back to the first
    # CheckpointCallback's directory.
    emergency_dir: Optional[str] = None
    # DEGRADED window after an anomaly skip / dispatch retry.
    degraded_cooldown_steps: int = 20
    # Flight recorder (observability/flight_recorder.py): bounded ring of
    # recent structured events, auto-dumped as a redacted JSON post-mortem
    # when the run halts. None = fit() builds one whose dumps land next to
    # the checkpoints (memory-only when no checkpoint dir is known).
    flight_recorder: Optional[Any] = None
    # Install SIGTERM/SIGINT graceful-preemption handlers during fit()
    # (main thread only; a second signal falls through to the original
    # handler).
    handle_signals: bool = True
    # SDC sentinel (integrity/sentinel.SentinelConfig; None = off). Covers
    # the monolithic step only — pipeline adapters build their own step,
    # exactly like the anomaly guard's scope. Fingerprints are read
    # through the guard's deferred readback, so the host-sync budget
    # stays one device_get call per step with the sentinel fully ON.
    integrity: Optional[Any] = None

    step: int = 0
    state: Any = None
    steps_run: int = 0  # steps executed by the last fit() (excludes resumed ones)
    # Host-mirrored robustness counters. dispatch_retries /
    # emergency_checkpoints / callback_errors accumulate across the
    # Trainer's life; anomaly_skips MIRRORS the checkpoint-carried device
    # counter the budget reads — it continues across resume_from but
    # restarts with the fresh guard state of a new (non-resumed) fit.
    anomaly_skips: int = 0
    dispatch_retries: int = 0
    emergency_checkpoints: int = 0
    callback_errors: int = 0
    # restores that failed digest verification and fell back (ISSUE 20)
    checkpoint_integrity_failures: int = 0
    tokens_seen: int = 0
    train_seconds: float = 0.0
    halt_reason: Optional[str] = None
    preempted: bool = False
    # AOT prewarm manifest recorded by prewarm() BEFORE fit() built the
    # step — consumed right after the train step exists (ISSUE 17)
    _aot_pending: Optional[Any] = dataclasses.field(default=None, repr=False)

    # --- AOT serving (inference/aot.py, ISSUE 17) ---------------------------

    def manifest(self):
        """AOT :class:`~..inference.aot.ProgramManifest` of this trainer's
        compiled programs (train/eval step) — available once fit() has
        run; persist it next to the checkpoints for the next process."""
        if getattr(self, "programs", None) is None:
            raise RuntimeError("manifest() needs a fitted Trainer")
        return self.programs.manifest()

    def prewarm(self, manifest=None, cache_dir: Optional[str] = None) -> dict:
        """Trainer equivalent of ``ServingEngine.prewarm``: point the
        persistent compile cache at ``cache_dir`` (the next compile of a
        known step becomes a disk hit) and, given a manifest (object,
        path, or ``cache_dir/manifest.json``), replay the train/eval-step
        entries with pedigree-faithful dummies so the first real step's
        wall contains zero compiles. The step is built inside fit(), so a
        pre-fit prewarm defers the replay until fit() has built it —
        still BEFORE the first batch dispatches."""
        import os as _os

        from neuronx_distributed_tpu.inference import aot

        if cache_dir is not None:
            aot.enable_persistent_cache(
                _os.path.join(cache_dir, aot.XLA_SUBDIR)
            )
            if manifest is None:
                p = _os.path.join(cache_dir, aot.MANIFEST_NAME)
                if _os.path.exists(p):
                    manifest = aot.ProgramManifest.load(p)
        if isinstance(manifest, (str, _os.PathLike)):
            manifest = aot.ProgramManifest.load(_os.fspath(manifest))
        if manifest is None:
            return {"deferred": False, "replayed": [], "skipped": {}}
        if getattr(self, "_train_step", None) is not None:
            return self._replay_aot_manifest(manifest)
        self._aot_pending = manifest
        return {"deferred": True}

    def _replay_aot_manifest(self, manifest) -> dict:
        from neuronx_distributed_tpu.inference import aot

        live = {
            "train_step": getattr(self, "_train_step", None),
            "eval_step": getattr(self, "_eval_step", None),
        }
        return aot.prewarm_programs(
            manifest, lambda name: live.get(name),
            ledger=self.programs, mode="trace",
            flight=getattr(self, "_flight", None),
        )

    # --- health -------------------------------------------------------------

    def health(self) -> TrainerHealth:
        """Current health (``OK/DEGRADED/HALTED``)."""
        if self.halt_reason is not None:
            return TrainerHealth.HALTED
        last = getattr(self, "_last_fault_step", None)
        if last is not None and self.step - last < self.degraded_cooldown_steps:
            return TrainerHealth.DEGRADED
        return TrainerHealth.OK

    # --- exact-resume payload -----------------------------------------------

    def step_rng(self) -> jax.Array:
        """Deterministic per-step key — ``fold_in(base, step)``. The base
        key is checkpointed, so a resumed run's step keys are identical to
        the uninterrupted run's."""
        return jax.random.fold_in(self._rng_base, self.step)

    def checkpoint_user_content(self, extra: Optional[dict] = None) -> dict:
        """The exact-resume payload every checkpoint carries: step, base
        RNG key, data-iterator cursor, and throughput/step bookkeeping."""
        uc = {
            "step": int(self.step),
            "tokens_seen": int(self.tokens_seen),
            "train_seconds": float(
                self.train_seconds
                + (time.perf_counter() - getattr(self, "_fit_t0", time.perf_counter()))
            ),
            "anomaly_skips": int(self.anomaly_skips),
            "dispatch_retries": int(self.dispatch_retries),
        }
        base = getattr(self, "_rng_base", None)
        if base is not None:
            raw = base
            if jnp.issubdtype(raw.dtype, jax.dtypes.prng_key):
                raw = jax.random.key_data(raw)
            # graftlint: ok[GL02] checkpoint serialization — runs per save,
            # not per step; explicit so guards can tell it from a stray sync
            raw = jax.device_get(raw)
            uc["rng_key"] = np.asarray(raw).astype(np.uint32).tolist()
        src = getattr(self, "_data_source", None)
        if src is not None:
            # a checkpoint written MID-step (emergency halt from a failed
            # dispatch) or while a pulled batch is still PENDING (preemption
            # before the first dispatch — the shape probe was drawn but
            # never trained) must point at the batch the next step was
            # GOING to train on: the live cursor is one ahead of the truth
            if getattr(self, "_mid_step", False) or getattr(
                self, "_pending_untrained", False
            ):
                uc["data_state"] = self._data_state_prepull
            else:
                uc["data_state"] = src.state()
        guard = getattr(self.state, "guard", None) if self.state is not None else None
        if guard is not None:
            # the anomaly-guard carry rides the checkpoint: without it a
            # resumed run re-warms the spike EMA from zero and diverges
            # from the uninterrupted run at the next spike (and the device
            # skips counter the budget reads would restart at 0)
            uc["guard"] = {
                "gnorm_ema": float(np.asarray(guard["gnorm_ema"])),
                "good_steps": int(np.asarray(guard["good_steps"])),
                "skips": int(np.asarray(guard["skips"])),
            }
        if extra:
            uc.update(extra)
        return uc

    def save_tagged_checkpoint(
        self,
        checkpoint_dir: str,
        tag: str,
        *,
        extra: Optional[dict] = None,
        num_kept: Optional[int] = None,
        async_save: bool = False,
    ) -> None:
        """The one save path every tagged checkpoint goes through —
        periodic (:class:`CheckpointCallback`), graceful preemption, and
        emergency halt all write the same item set and the full
        exact-resume ``user_content``, then run the post-save hooks."""
        save_checkpoint(
            checkpoint_dir, tag=tag,
            items={"model": self.state.params, "optimizer": self.state.opt_state},
            user_content=self.checkpoint_user_content(extra),
            num_kept_ckpts=num_kept,
            async_save=async_save,
        )
        inj = self.fault_injector
        if (
            async_save
            and inj is not None
            and (
                getattr(inj, "pending_corruption", lambda _: False)(tag)
                or getattr(inj, "pending_shard_flip", lambda: False)()
            )
        ):
            # a scheduled corrupt_checkpoint or checkpoint_shard flip must
            # hit a COMMITTED save — drain the async commit first
            # (chaos-only; clean saves keep the non-blocking path)
            from neuronx_distributed_tpu.trainer.checkpoint import (
                finalize_checkpoints,
            )

            finalize_checkpoints()
        self.notify_checkpoint_saved(checkpoint_dir, tag)

    def notify_checkpoint_saved(self, checkpoint_dir: str, tag: str) -> None:
        """Post-save hook: timeline instant + fault-injector consultation
        (``corrupt_checkpoint`` fires here)."""
        tl = getattr(self, "_tl", None)
        if tl is not None:
            tl.instant("checkpoint", "trainer", args={"tag": tag})
        self._flight_record("checkpoint", tag=tag, step=self.step)
        if self.fault_injector is not None:
            self.fault_injector.on_checkpoint_saved(checkpoint_dir, tag)

    def _checkpoint_dir(self) -> Optional[str]:
        if self.emergency_dir is not None:
            return self.emergency_dir
        for cb in self.callbacks:
            d = getattr(cb, "checkpoint_dir", None)
            if d is not None:
                return d
        return None

    # --- fault machinery ----------------------------------------------------

    def _flight_record(self, kind: str, **fields) -> None:
        fl = getattr(self, "_flight", None)
        if fl is not None:
            fl.record(kind, **fields)

    def _save_emergency_checkpoint(self, reason: str) -> Optional[str]:
        d = self._checkpoint_dir()
        if d is None:
            logger.warning(
                "halting without an emergency checkpoint — no checkpoint "
                "directory known (set Trainer.emergency_dir)"
            )
            return None
        tag = f"emergency_step_{self.step}"
        self.save_tagged_checkpoint(d, tag, extra={"emergency": reason})
        self.emergency_checkpoints += 1
        self._tl.instant("emergency_checkpoint", "trainer", args={"tag": tag})
        self._flight_record("emergency_checkpoint", tag=tag, dir=d)
        logger.warning("emergency checkpoint '%s' written to %s", tag, d)
        return tag

    def _halt(self, reason: str, save: bool = True) -> None:
        self.halt_reason = reason
        tag = self._save_emergency_checkpoint(reason) if save else None
        self._tl.instant("halted", "trainer", args={"reason": reason})
        # post-mortem: the last N structured events (anomaly skips, dispatch
        # failures, checkpoints, callback errors) + the halt context, dumped
        # atomically next to the checkpoints BEFORE TrainerHalted unwinds —
        # the unattended-death record PRs 3/5 left missing
        fl = getattr(self, "_flight", None)
        if fl is not None:
            fl.record("halt", reason=reason, step=self.step,
                      emergency_tag=tag)
            extra = {
                "step": self.step,
                "emergency_tag": tag,
                "anomaly_skips": self.anomaly_skips,
                "dispatch_retries": self.dispatch_retries,
                "callback_errors": self.callback_errors,
                "tokens_seen": self.tokens_seen,
                "checkpoint_integrity_failures":
                    self.checkpoint_integrity_failures,
            }
            sentinel = getattr(self, "_sentinel", None)
            if sentinel is not None:
                # flat scalars (survive the recorder's depth-3 redaction)
                extra["integrity"] = dict(sentinel.counters)
                extra["integrity"]["quarantined_devices"] = ",".join(
                    str(d) for d in sentinel.quarantined_devices
                )
            # device-efficiency context (ISSUE 12): where HBM went and
            # which programs were hot when training died — flat scalar
            # tables (survive the recorder's depth-3 redaction); cost
            # analysis is NOT started on this error path
            hbm = getattr(self, "hbm", None)
            if hbm is not None:
                extra["hbm"] = hbm.halt_summary()
            programs = getattr(self, "programs", None)
            if programs is not None:
                extra["programs"] = programs.halt_summary()
            fl.dump(reason, extra=extra)
        logger.error("training HALTED: %s", reason)
        raise TrainerHalted(reason, emergency_tag=tag)

    @staticmethod
    def _state_consumed(state) -> bool:
        return any(
            getattr(leaf, "is_deleted", lambda: False)()
            for leaf in jax.tree.leaves(state)
        )

    def _dispatch(self, train_step, prepared):
        """Run one train-step dispatch with bounded recovery: a host-side
        failure leaves the donated buffers unconsumed, so the retry runs
        against the last known-good ``(state, step)`` snapshot —
        ``self.state``, unchanged since the last successful step. Bounded
        CONSECUTIVE failures (or consumed buffers, which make retry
        impossible) halt with an emergency checkpoint."""
        policy = self._dispatch_policy
        while True:
            attempt = self._dispatch_attempts
            self._dispatch_attempts += 1
            try:
                if self.fault_injector is not None:
                    self.fault_injector.on_dispatch(attempt)
                out = train_step(self.state, prepared)
                if self._consecutive_dispatch_failures:
                    self._tl.instant(
                        "recovery", "trainer",
                        args={"after_failures": self._consecutive_dispatch_failures,
                              "step": self.step},
                    )
                    self._flight_record(
                        "recovery", step=self.step,
                        after_failures=self._consecutive_dispatch_failures,
                    )
                    self._consecutive_dispatch_failures = 0
                return out
            except KeyboardInterrupt:
                raise
            except (TypeError, ValueError, NotImplementedError):
                raise  # deterministic programming errors — retrying is noise
            except Exception as e:
                n = self._consecutive_dispatch_failures = (
                    self._consecutive_dispatch_failures + 1
                )
                self.dispatch_retries += 1
                self._last_fault_step = self.step
                self._tl.instant(
                    "dispatch_failure", "trainer",
                    args={"error": str(e)[:200], "consecutive": n,
                          "step": self.step},
                )
                self._flight_record("dispatch_failure", step=self.step,
                                    error=str(e), consecutive=n)
                logger.warning(
                    "train-step dispatch failed at step %d (%s: %s) — "
                    "consecutive failure %d/%d",
                    self.step, type(e).__name__, e, n, policy.max_attempts,
                )
                if self._state_consumed(self.state):
                    # the donated buffers are gone: nothing to retry with
                    # and nothing to checkpoint — resume from the last
                    # on-disk checkpoint instead
                    self._halt(
                        f"dispatch failed with consumed donated buffers "
                        f"({type(e).__name__}: {e}) — resume from the last "
                        "checkpoint",
                        save=False,
                    )
                if n >= policy.max_attempts:
                    self._halt(
                        f"{n} consecutive dispatch failures "
                        f"(last: {type(e).__name__}: {e})"
                    )
                # shared decrementing-jitter wait (0-based attempt index)
                time.sleep(policy.wait(n - 1))

    def _account_guard(self) -> None:
        """Budget accounting for the DEFERRED guard flags: reads the
        previous step's ``(good_step, anomaly_skips)`` scalars AFTER the
        next step has been dispatched, so the tiny readback overlaps device
        compute — the clean path adds no stall, and the jitted step itself
        never syncs. Detection therefore lags one step; an anomalous step
        is already harmless (its update was skipped on device).

        The SDC sentinel's fingerprint scalars (ISSUE 20) ride the SAME
        ``device_get`` call: a check step appends a few uint32 leaves to
        this readback, so the pinned one-call-per-step budget holds with
        the sentinel fully ON."""
        pending = self._pending_guard
        ipending = self._pending_integrity
        if pending is None and ipending is None:
            return
        self._pending_guard = None
        self._pending_integrity = None
        guard_leaves = () if pending is None else (pending[1], pending[2])
        fp_leaves = () if ipending is None else tuple(ipending)
        try:
            # graftlint: ok[GL02] the PR 5 deferred guard readback: the
            # PREVIOUS step's tiny flag pair (plus, on sentinel check
            # steps, its uint32 fingerprint scalars), read only after the
            # next step dispatched so it overlaps device compute — tests/
            # trainer/test_faults.py pins it at exactly one get per step
            vals = jax.device_get(guard_leaves + fp_leaves)
        except (KeyboardInterrupt, TrainerHalted):
            raise
        except Exception as e:
            # async dispatch means a DEVICE-side execution failure surfaces
            # here, not at the dispatch call — the step's outputs (now
            # self.state) are poisoned and the donated inputs are gone, so
            # there is nothing to retry or checkpoint: halt for cause
            # instead of leaking a raw backend error past the halt/
            # on_train_end machinery
            self._halt(
                f"train-step execution failed (surfaced at the deferred "
                f"guard readback): {type(e).__name__}: {e} — resume from "
                "the last checkpoint",
                save=False,
            )
        fp_vals = vals[len(guard_leaves):]
        if pending is not None:
            at_step = pending[0]
            good, skips = vals[0], vals[1]
            skips = int(skips)
            if not bool(good):
                self._last_fault_step = self.step
                self._tl.instant(
                    "anomaly_skip", "trainer",
                    args={"step": at_step, "skips": skips},
                )
                self._flight_record("anomaly_skip", step=at_step, skips=skips)
                logger.warning(
                    "anomalous step %d skipped on device (%d skips total)",
                    at_step, skips,
                )
            self.anomaly_skips = skips
            budget = self.anomaly_guard.budget if self.anomaly_guard else None
            if budget is not None and skips > budget:
                self._halt(
                    f"anomaly budget exceeded: {skips} skipped steps > "
                    f"budget {budget}"
                )
        if fp_leaves:
            verdict = self._sentinel.judge(fp_vals)
            if verdict is not None and verdict.detected:
                self._handle_sdc(verdict)

    def _handle_sdc(self, verdict) -> None:
        """A fingerprint check failed: silent corruption is live in the
        TrainState. Fence and continue — quarantine the convicted devices
        in the flight recorder, restore the last verified known-good
        ``(state, step, data cursor, tokens)``, and keep looping (the
        discarded steps re-run deterministically, so the final state is
        bit-identical to a run that never saw the corruption). No rollback
        point → the TrainerHalted/resume contract takes over."""
        s = self._sentinel
        self._last_fault_step = self.step
        convicted = ",".join(str(d) for d in verdict.convicted_devices)
        self._tl.instant(
            "sdc_detected", "trainer",
            args={"step": verdict.step, "mode": verdict.mode,
                  "localized": verdict.localized, "devices": convicted},
        )
        self._flight_record(
            "sdc_detected", step=verdict.step, mode=verdict.mode,
            localized=verdict.localized, devices=convicted,
        )
        for d in verdict.convicted_devices:
            # the quarantine record: which physical device returned wrong
            # bits — the post-mortem's pointer for draining/replacing it
            self._flight_record("device_quarantined", device=int(d),
                                step=verdict.step)
        logger.error(
            "silent data corruption detected at step %d (%s vote%s) — "
            "convicted device(s): %s",
            verdict.step, verdict.mode,
            "" if verdict.localized else ", UNLOCALIZED",
            convicted or "<none>",
        )
        if not s.can_rollback() or self._data_source is None:
            self._halt(
                f"silent data corruption detected at step {verdict.step} "
                f"({verdict.mode} check) with no in-memory rollback point "
                "— resume from the last verified checkpoint"
            )
        rb = s.rollback()
        self.state = rb["state"]
        self.step = rb["step"]
        self.tokens_seen = rb["tokens_seen"]
        if rb["data_state"] is not None:
            self._data_source.restore(rb["data_state"])
            self._data_state_prepull = rb["data_state"]
        # the in-flight guard flags / metrics belong to a discarded step
        self._pending_guard = None
        self._drop_pending_guard = True
        self._flight_record("sdc_rollback", to_step=self.step,
                            detected_at=verdict.step)
        self._tl.instant(
            "sdc_rollback", "trainer",
            args={"to_step": self.step, "detected_at": verdict.step},
        )
        logger.warning(
            "rolled back to verified step %d — re-training the discarded "
            "window (bit-identical by determinism)", self.step,
        )

    def _on_checkpoint_corrupt(self, tag: str, detail: str) -> None:
        """load_checkpoint's on_corrupt hook: a tag failed digest
        verification at resume and was quarantined."""
        self.checkpoint_integrity_failures += 1
        self._last_fault_step = self.step
        self._tl.instant(
            "checkpoint_integrity_failure", "trainer",
            args={"tag": tag, "detail": str(detail)[:200]},
        )
        self._flight_record("checkpoint_integrity_failure", tag=tag,
                            detail=str(detail))
        logger.error(
            "checkpoint '%s' failed integrity verification (%s) — "
            "falling back to the previous completed tag", tag, detail,
        )

    # --- signals ------------------------------------------------------------

    def _install_signal_handlers(self) -> dict:
        self._preempt_signum = None
        if not self.handle_signals:
            return {}
        if threading.current_thread() is not threading.main_thread():
            return {}  # signal.signal is main-thread-only
        orig = {}

        def handler(signum, frame):
            if self._preempt_signum is None:
                self._preempt_signum = signum
                logger.warning(
                    "signal %d received — finishing the in-flight step, "
                    "checkpointing, then exiting cleanly (send again to "
                    "force)", signum,
                )
            else:  # second signal: fall through to the original behavior
                prev = orig.get(signum)
                if prev is None:  # None = handler installed by non-Python
                    prev = _signal.SIG_DFL  # code — closest we can restore
                _signal.signal(signum, prev)
                os.kill(os.getpid(), signum)

        for sig in (_signal.SIGTERM, _signal.SIGINT):
            try:
                orig[sig] = _signal.signal(sig, handler)
            except (ValueError, OSError):  # non-main thread or exotic host
                pass
        return orig

    def _restore_signal_handlers(self, orig: dict) -> None:
        for sig, h in orig.items():
            if h is None:
                # signal.signal returned None for a handler installed by
                # non-Python code (embedded interpreter) — there is nothing
                # Python can restore it to; leave ours in place rather
                # than crash the fit epilogue with a TypeError
                continue
            try:
                _signal.signal(sig, h)
            except (ValueError, OSError, TypeError):
                pass

    def _graceful_preempt(self) -> None:
        """The in-flight step finished; write a final tagged checkpoint
        through the done-marker protocol and leave the loop cleanly."""
        self.preempted = True
        d = self._checkpoint_dir()
        tag = f"step_{self.step}"
        if d is not None:
            from neuronx_distributed_tpu.trainer.checkpoint import (
                DONE_MARKER,
                create_checkpoint_storage,
                finalize_checkpoints,
            )

            finalize_checkpoints()  # a pending async save of this tag wins
            storage = create_checkpoint_storage(d)
            if not storage.file_exists(os.path.join(tag, DONE_MARKER)):
                self.save_tagged_checkpoint(
                    d, tag,
                    extra={"preempted": int(self._preempt_signum or 0)},
                )
        self._tl.instant(
            "preempted", "trainer",
            args={"signal": int(self._preempt_signum or 0), "step": self.step},
        )
        self._flight_record("preempted", step=self.step,
                            signal=int(self._preempt_signum or 0))
        logger.warning(
            "preempted by signal %s at step %d — checkpoint %s; exiting "
            "cleanly", self._preempt_signum, self.step,
            tag if d is not None else "SKIPPED (no checkpoint dir)",
        )

    # --- callbacks ------------------------------------------------------------

    def _safe_callback(self, cb, method: str, *args) -> None:
        """One misbehaving callback must not kill the run: exceptions are
        logged with the callback's class name, counted in
        ``callback_errors``, and swallowed — and because each callback is
        isolated individually, ``on_train_end`` still reaches every one."""
        try:
            getattr(cb, method)(*args)
        except (KeyboardInterrupt, TrainerHalted):
            raise
        except Exception as e:
            self.callback_errors += 1
            # a failing callback counts as a fault for health(): a broken
            # CheckpointCallback save means the run is progressing WITHOUT
            # durable checkpoints — unattended monitoring must see
            # DEGRADED, not OK, for as long as the errors keep coming
            self._last_fault_step = self.step
            self._tl.instant(
                "callback_error", "trainer",
                args={"callback": type(cb).__name__, "hook": method,
                      "error": str(e)[:200]},
            )
            self._flight_record("callback_error", callback=type(cb).__name__,
                                hook=method, error=str(e))
            logger.exception(
                "callback %s.%s raised (%s: %s) — training continues",
                type(cb).__name__, method, type(e).__name__, e,
            )

    # --- the loop -------------------------------------------------------------

    def fit(
        self,
        data_iter: Iterable[dict],
        rng_key: jax.Array,
        max_steps: int,
        sample_batch: Optional[dict] = None,
        resume_from: Optional[str] = None,
    ) -> dict:
        """Run ``max_steps`` over ``data_iter`` (an iterable of host batches
        with at least ``input_ids``/``labels``). Returns the last metrics.

        Raises :class:`TrainerHalted` when the anomaly or dispatch-retry
        budget is exhausted (after writing an emergency checkpoint and
        running every callback's ``on_train_end``)."""
        from neuronx_distributed_tpu.parallel import mesh as mesh_lib

        if not mesh_lib.model_parallel_is_initialized():
            # data-parallel-only default (reference neuronx_distributed_config
            # initializes parallel state the same way when sizes are 1)
            mesh_lib.initialize_model_parallel()
        # exact-resume data protocol: the SOURCE object carries the cursor
        self._data_source = (
            data_iter
            if hasattr(data_iter, "state") and hasattr(data_iter, "restore")
            else None
        )
        self._mid_step = False
        self._data_state_prepull = (
            self._data_source.state() if self._data_source is not None else None
        )
        data_iter = iter(data_iter)
        self.steps_run = 0  # per-fit counter (profiler window + throughput)
        self._eval_step = None  # rebuilt lazily against this fit's wiring
        self._eval_prepare = None
        self.halt_reason = None
        self.preempted = False
        self._rng_base = rng_key
        self._dispatch_attempts = 0
        self._consecutive_dispatch_failures = 0
        self._last_fault_step = None
        self._pending_guard = None
        self._pending_integrity = None
        self._drop_pending_guard = False
        self._sentinel = None
        self._dispatch_policy = self.dispatch_retry or RetryPolicy(
            max_attempts=3, first_wait=0.05, min_wait=0.01
        )
        self._tl = tl = self.timeline or Timeline(None)
        if self.flight_recorder is not None:
            self._flight = self.flight_recorder
        else:
            from neuronx_distributed_tpu.observability.flight_recorder import (
                FlightRecorder,
            )

            # default recorder: post-mortems land next to the checkpoints
            # (memory-only when no directory is known — last_postmortem
            # still carries the ring for an operator holding the object)
            self._flight = FlightRecorder(
                dump_dir=self._checkpoint_dir(), subsystem="trainer"
            )
        # compiled-program ledger (ISSUE 12): created once per Trainer so
        # re-fits ACCUMULATE (a rebuilt train step wraps the same record);
        # rides a MetricsCallback's registry when one is attached so the
        # per-step achieved-FLOPs/MFU gauges share the scrape surface
        if getattr(self, "programs", None) is None:
            from neuronx_distributed_tpu.observability.callback import (
                MetricsCallback,
            )
            from neuronx_distributed_tpu.observability.programs import (
                ProgramLedger,
            )

            reg = None
            for cb in self.callbacks:
                if isinstance(cb, MetricsCallback):
                    reg = cb.registry
                    break
            self.programs = ProgramLedger(
                registry=reg, prefix="train", subsystem="trainer",
                timeline=tl,
            )
        impl = getattr(self.model, "attention_impl", None)
        if impl is not None:
            # what "auto" resolved to on THIS device and mesh — the
            # snapshot a run asserts its kernels from (chip_smoke.py)
            from neuronx_distributed_tpu.kernels.backend import (
                resolve_attention_impl,
            )

            self.programs.resolved["attention"] = resolve_attention_impl(
                impl, mesh_lib.get_context_parallel_size()
            )
        inj = self.fault_injector
        first = sample_batch if sample_batch is not None else next(data_iter)
        optimizer = make_optimizer(self.optimizer_config)
        if self.pipeline is not None and self.optimizer_config.grad_accum_steps > 1:
            raise ValueError(
                "grad_accum_steps does not apply under a pipeline adapter — "
                "pipeline microbatches already accumulate; raise "
                "num_microbatches instead"
            )
        guard_cfg = self.anomaly_guard if self.pipeline is None else None
        if self.pipeline is not None:
            self.state, train_step, engine = self.pipeline.build_state_and_step(
                self.model, optimizer, rng_key, first["input_ids"],
                zero1=self.optimizer_config.zero1,
                max_grad_norm=self.optimizer_config.max_grad_norm,
            )
            self._pipeline_engine = engine
            prepare = self.pipeline.prepare_batch
        else:
            self.state, p_sh, s_sh = create_train_state(
                self.model, optimizer, rng_key, first["input_ids"],
                zero1=self.optimizer_config.zero1,
            )
            accum = self.optimizer_config.grad_accum_steps
            train_step = build_train_step(
                self.model, optimizer, p_sh, s_sh,
                max_grad_norm=self.optimizer_config.max_grad_norm,
                loss_fn=self.loss_fn,
                grad_accum_steps=accum,
                anomaly_guard=guard_cfg,
            )
            self._record_remat_saves(first, accum)
            if guard_cfg is not None:
                self.state = self.state.replace(guard=init_anomaly_guard_state())
            if accum > 1:
                from neuronx_distributed_tpu.pipeline.model import (
                    microbatch,
                    shard_microbatched_batch,
                )

                def prepare(batch):
                    return shard_microbatched_batch(microbatch(batch, accum))
            else:
                prepare = shard_batch
        # ledger proxy: dispatch counts + compile detection, zero syncs
        # (the proxy forwards _cache_size(), so the compile-budget guard
        # below keeps reading through)
        train_step = self.programs.wrap("train_step", train_step)
        # exposed for the compile-budget guard (one program must serve clean
        # AND anomalous batches — tests/trainer/test_faults.py)
        self._train_step = train_step
        if self._aot_pending is not None:
            # deferred AOT prewarm (ISSUE 17): the step now exists — eat
            # the compile (a disk hit under the persistent cache) BEFORE
            # the first batch dispatches
            pending, self._aot_pending = self._aot_pending, None
            self._replay_aot_manifest(pending)
        # HBM ledger (ISSUE 12): the trainer's static residents as weakref
        # closures over the live TrainState — params, optimizer state, the
        # anomaly-guard carry — reconciled against device limits
        from neuronx_distributed_tpu.observability.hbm import (
            HBMLedger,
            tree_nbytes,
        )
        from neuronx_distributed_tpu.observability.programs import weak_reader

        self.hbm = HBMLedger(view=self.programs.view)

        def _res(fn):
            # state=None (pre-fit reads) falls to 0 via the resident
            # reader's exception guard — tree_nbytes(None.state) raises
            return weak_reader(self, fn)

        self.hbm.add_resident("params", _res(
            lambda t: tree_nbytes(t.state.params)
        ))
        self.hbm.add_resident("opt_state", _res(
            lambda t: tree_nbytes(t.state.opt_state)
        ))
        self.hbm.add_resident("anomaly_guard", _res(
            lambda t: tree_nbytes(t.state.guard)
        ))
        # SDC sentinel (ISSUE 20): jitted fingerprint + state-copy programs
        # registered through the ledger like every other program; the
        # sentinel itself never syncs — its scalars ride _account_guard's
        # one deferred device_get
        if self.integrity is not None and self.pipeline is not None:
            logger.warning(
                "integrity sentinel covers the monolithic step only — "
                "disabled under a pipeline adapter (like the anomaly guard)"
            )
        elif self.integrity is not None:
            from neuronx_distributed_tpu.integrity.sentinel import (
                TrainerSentinel,
            )
            from neuronx_distributed_tpu.utils.fingerprint import (
                tree_fingerprint,
            )

            _jit_fp = self.programs.wrap(
                "integrity_fingerprint",
                jax.jit(lambda t: tree_fingerprint(t)),
            )
            dp_size = mesh_lib.get_data_parallel_size()
            _mode = self.integrity.mode
            if _mode == "auto":
                _mode = "vote" if dp_size > 1 else "canary"
            if _mode == "vote" and dp_size > 1:
                # ZeRO-1 shards opt-state leaves over the dp axes; a
                # fingerprint touching such a leaf needs a CROSS-replica
                # reduction, and that one collective uniformizes the whole
                # combined scalar — every device reports the same value
                # even when one replica's (replicated!) params copy is
                # corrupt, blinding the vote entirely. Strip dp-sharded
                # leaves from the vote fingerprint; they are covered by
                # checkpoint shard digests instead. Shardings are stable
                # across steps, so the stripped structure jit-caches once.
                from neuronx_distributed_tpu.parallel.mesh import DATA_AXES

                _dp_names = set(DATA_AXES)

                def _dp_sharded(leaf):
                    spec = getattr(
                        getattr(leaf, "sharding", None), "spec", None
                    )
                    if spec is None:
                        return False
                    names = set()
                    for entry in spec:
                        if entry is None:
                            continue
                        if isinstance(entry, (tuple, list)):
                            names.update(entry)
                        else:
                            names.add(entry)
                    return bool(names & _dp_names)

                def fp_fn(tree):
                    return _jit_fp(jax.tree.map(
                        lambda l: None if _dp_sharded(l) else l, tree
                    ))
            else:
                fp_fn = _jit_fp
            copy_fn = self.programs.wrap(
                "integrity_copy",
                jax.jit(lambda t: jax.tree.map(jnp.copy, t)),
            )
            self._sentinel = TrainerSentinel(
                self.integrity,
                dp_size=mesh_lib.get_data_parallel_size(),
                fingerprint_fn=fp_fn,
                copy_fn=copy_fn,
            )
            # the retained known-good/candidate snapshots are real HBM:
            # account for them next to params/opt-state
            self.hbm.add_resident("integrity_snapshots", _res(
                lambda t: sum(
                    tree_nbytes(s) for s in t._sentinel.snapshot_states()
                )
            ))
        pending = first if sample_batch is None else None
        # the probe pull advanced the cursor past a batch nothing has
        # trained on yet — checkpoints written before it is consumed must
        # save the pre-pull cursor (checkpoint_user_content)
        self._pending_untrained = pending is not None
        if resume_from is not None:
            from neuronx_distributed_tpu.trainer.checkpoint import (
                latest_checkpoint_tag,
                load_checkpoint,
            )

            # resolve the newest COMPLETED tag once (this walk also repairs
            # a corrupt `newest` pointer) and hand it to load_checkpoint —
            # passing no tag would redo the same walk
            tag = latest_checkpoint_tag(resume_from)
            if tag is not None:
                items, user_content, tag = load_checkpoint(
                    resume_from,
                    tag=tag,
                    items_target={
                        "model": self.state.params,
                        "optimizer": self.state.opt_state,
                    },
                    # digest verification with quarantine-and-fall-back
                    # (ISSUE 20): never donate restored garbage
                    on_corrupt=self._on_checkpoint_corrupt,
                )
                self.state = self.state.replace(
                    params=items["model"], opt_state=items["optimizer"]
                )
                uc = user_content or {}
                self.step = int(uc.get("step", 0))
                # the device step scalar drives nothing numerically (the LR
                # schedule reads the optimizer count) but must agree for
                # bit-identical bookkeeping
                self.state = self.state.replace(
                    step=jax.device_put(
                        jnp.asarray(self.step, self.state.step.dtype),
                        jax.sharding.NamedSharding(
                            mesh_lib.get_mesh(),
                            jax.sharding.PartitionSpec(),
                        ),
                    )
                )
                if uc.get("rng_key") is not None:
                    self._rng_base = jnp.asarray(uc["rng_key"], jnp.uint32)
                self.tokens_seen = int(uc.get("tokens_seen", self.tokens_seen))
                self.train_seconds = float(
                    uc.get("train_seconds", self.train_seconds)
                )
                gc = uc.get("guard")
                if gc is not None and self.state.guard is not None:
                    # restore the anomaly-guard carry (EMA, warmup count,
                    # device skips counter) so spike detection and budget
                    # accounting continue exactly where the interrupted
                    # run left off — built by the same owner as the fresh
                    # tree so the layout always matches what the jitted
                    # step was traced with
                    self.state = self.state.replace(
                        guard=init_anomaly_guard_state(gc)
                    )
                    self.anomaly_skips = int(gc["skips"])
                self.dispatch_retries = int(
                    uc.get("dispatch_retries", self.dispatch_retries)
                )
                ds = uc.get("data_state")
                if ds is not None and self._data_source is not None:
                    self._data_source.restore(ds)
                    self._data_state_prepull = ds
                    # the shape-probe batch was drawn from the PRE-restore
                    # cursor — drop it; the next pull follows the cursor
                    pending = None
                    self._pending_untrained = False
                logger.info("resumed from '%s' at step %d", tag, self.step)
        if self._sentinel is not None:
            # first known-good point: the verified state this fit starts
            # from (fresh init, or a digest-verified checkpoint restore);
            # the cursor pairs it with the batch step self.step will pull
            self._sentinel.set_baseline(
                self.state, self.step,
                self._data_state_prepull
                if self._data_source is not None else None,
                self.tokens_seen,
            )
        meter = ThroughputMeter(batch_size=first["input_ids"].shape[0])
        # shape is host metadata on np AND jax arrays — np.asarray here used
        # to copy the whole batch to host just to read it (GL02-class bug)
        batch_tokens = int(np.prod(first["input_ids"].shape))
        for cb in self.callbacks:
            self._safe_callback(cb, "on_train_start", self)
        metrics = {}
        self._fit_t0 = time.perf_counter()
        self._step_wall_t0 = time.perf_counter()
        orig_handlers = self._install_signal_handlers()
        halted: Optional[TrainerHalted] = None
        error: Optional[BaseException] = None
        try:
            while True:
                while self.step < max_steps:
                    if inj is not None:
                        inj.on_step_start(self.step)
                    if self._preempt_signum is not None:
                        self._graceful_preempt()
                        break
                    # one iteration = one step event on the profiler's
                    # clock (and the timeline), its phases inside it
                    with tracing.span(
                        tracing.TRAIN_STEP, tl,
                        annotation=jax.profiler.StepTraceAnnotation,
                        step_num=self.step,
                    ):
                        with tracing.span(tracing.TRAIN_FETCH, tl):
                            if pending is not None:
                                batch = pending
                                pending = None
                                # the probe batch is now entering training;
                                # from here _mid_step/_data_state_prepull
                                # carry the truth
                                self._pending_untrained = False
                            else:
                                if self._data_source is not None:
                                    self._data_state_prepull = (
                                        self._data_source.state()
                                    )
                                batch = next(data_iter)
                            # the batch has left the iterator: from here until
                            # the dispatch lands, any exit (corrupt_batch
                            # raising, dispatch halt) must checkpoint the
                            # PRE-pull cursor or resume would silently skip
                            # this batch
                            self._mid_step = True
                            if inj is not None:
                                batch = inj.corrupt_batch(self.step, batch)
                            prepared = prepare(batch)
                        if (
                            self._sentinel is not None
                            and self._sentinel.wants_pre_copy(self.step)
                        ):
                            # canary mode: retain the pre-step state + batch
                            # so post_dispatch can re-execute this exact step
                            self._sentinel.pre_dispatch(self.state, prepared)
                        with tracing.span(tracing.TRAIN_DISPATCH, tl):
                            self.state, metrics = self._dispatch(
                                train_step, prepared
                            )
                        if inj is not None and hasattr(inj, "on_state"):
                            # chaos (ISSUE 20): scheduled silent bit flips land
                            # on the live state here — after dispatch, before
                            # the sentinel's check — so detection latency is
                            # measured from the step the corruption struck
                            self.state = inj.on_state(self.step, self.state)
                        self._mid_step = False
                        self.step += 1
                        self.steps_run += 1
                        self.tokens_seen += batch_tokens
                        # per-step roofline feed: the inter-step wall (host
                        # clock the loop already owns — dispatch is async, so
                        # steady-state iteration time IS the step wall). The
                        # first iteration and any compile-bearing step are
                        # skipped so MFU never averages in trace+compile time
                        now_wall = time.perf_counter()
                        if self.steps_run > 1 and not getattr(
                            train_step, "last_call_compiled", True
                        ):
                            self.programs.observe_wall(
                                "train_step", now_wall - self._step_wall_t0
                            )
                        self._step_wall_t0 = now_wall
                        # budget-check the PREVIOUS step's guard flags now that
                        # this step is dispatched — the readback overlaps
                        # device compute
                        with tracing.span(tracing.TRAIN_READBACK, tl):
                            self._account_guard()
                        metrics = dict(metrics)
                        metrics["throughput_seq_s"] = meter.update()
                        metrics["dispatch_retries"] = self.dispatch_retries
                        metrics["emergency_checkpoints"] = (
                            self.emergency_checkpoints
                        )
                        metrics["callback_errors"] = self.callback_errors
                        # a rollback inside _account_guard discarded this step
                        # — its metrics/flags describe state that no longer
                        # exists
                        sdc_rolled = self._drop_pending_guard
                        self._drop_pending_guard = False
                        if guard_cfg is not None and not sdc_rolled:
                            self._pending_guard = (
                                self.step - 1,
                                metrics["good_step"],
                                metrics["anomaly_skips"],
                            )
                        if (
                            self._sentinel is not None
                            and not sdc_rolled
                            and self._sentinel.is_check_step(self.step - 1)
                        ):
                            # stage this check's fingerprint scalars; they
                            # ride the NEXT _account_guard's single device_get
                            self._pending_integrity = (
                                self._sentinel.post_dispatch(
                                    train_step, self.state, self.step,
                                    self._data_source.state()
                                    if self._data_source is not None else None,
                                    self.tokens_seen,
                                )
                            )
                        with tracing.span(tracing.TRAIN_CALLBACKS, tl):
                            for cb in self.callbacks:
                                self._safe_callback(
                                    cb, "on_step_end", self, metrics
                                )
                    if self._preempt_signum is not None:
                        self._graceful_preempt()
                        break
                # the final step's flags (and any staged fingerprint): a
                # sentinel rollback HERE lowers self.step below max_steps, so
                # the outer loop re-enters training and the run still
                # completes its full schedule bit-identically
                self._account_guard()
                if self.preempted or self.step >= max_steps:
                    break
        except TrainerHalted as e:
            halted = e
        except KeyboardInterrupt:
            raise  # force-exit: skip the epilogue, the user wants OUT now
        except BaseException as e:
            # any other failure (preemption-save IOError, deterministic
            # dispatch error, ...) still owes the callbacks their
            # on_train_end (TensorBoard flush, async-save drain) and the
            # timeline its save — run the epilogue, then re-raise
            error = e
        finally:
            self.train_seconds += time.perf_counter() - self._fit_t0
            # re-anchor so later checkpoint_user_content calls (e.g. the
            # save_on_end path) don't double-count the elapsed wall
            self._fit_t0 = time.perf_counter()
            self._restore_signal_handlers(orig_handlers)
        for cb in self.callbacks:
            self._safe_callback(cb, "on_train_end", self)
        tl.save()
        if error is not None:
            raise error
        if halted is not None:
            raise halted
        return metrics

    def _record_remat_saves(self, first: dict, accum: int) -> None:
        """What the model's remat policy keeps through the backward pass of
        the train program just built (``modules/remat.py``):
        ``programs.resolved["remat"]`` the saved names, gauge
        ``train_remat_saved_bytes`` the bytes a chip keeps for them (named
        tensors x layers, of one microbatch; an activation a column-parallel
        layer hands on is sharded over every axis of the mesh: batch over dp,
        sequence over cp, features over tp). ``[]`` and 0 under "save
        nothing" and for a model without remat. One abstract trace of the
        forward, no compute."""
        from functools import partial

        from neuronx_distributed_tpu.modules.remat import saved_by_name
        from neuronx_distributed_tpu.parallel import mesh as mesh_lib
        from neuronx_distributed_tpu.trainer.trainer import default_loss_fn

        saved: dict = {}
        if getattr(getattr(self.model, "config", None), "remat", False):
            saved = saved_by_name(
                self.loss_fn or partial(default_loss_fn, self.model),
                jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                    self.state.params,
                ),
                {
                    k: jax.ShapeDtypeStruct(
                        (v.shape[0] // accum, *v.shape[1:]), v.dtype
                    )
                    for k, v in first.items()
                },
            )
        self.programs.resolved["remat"] = sorted(saved)
        self.programs.view.gauge(
            "train_remat_saved_bytes",
            help="bytes a chip keeps through the backward pass for the "
                 "tensors the remat policy saves by name",
        ).set(sum(saved.values()) // mesh_lib.get_mesh().size)

    def evaluate(self, data_iter: Iterable[dict], max_steps: int) -> dict:
        """Mean loss over ``max_steps`` eval batches with the CURRENT params,
        no updates (the reference's Lightning validation loop). Requires a
        prior fit() (the jitted loss reuses its model/loss wiring)."""
        if self.state is None:
            raise RuntimeError("evaluate() needs a fitted Trainer (state is None)")
        if getattr(self, "_eval_step", None) is None:
            if self.pipeline is not None:
                loss_fn = self._pipeline_engine.loss_fn
                self._eval_prepare = self.pipeline.prepare_batch
            else:
                from functools import partial

                from neuronx_distributed_tpu.trainer.trainer import default_loss_fn

                loss_fn = self.loss_fn or partial(default_loss_fn, self.model)
                self._eval_prepare = shard_batch
            # cached: a fresh jit wrapper per call would retrace every time
            self._eval_step = jax.jit(loss_fn)
            if getattr(self, "programs", None) is not None:
                self._eval_step = self.programs.wrap(
                    "eval_step", self._eval_step
                )
        data_iter = iter(data_iter)
        total, n = 0.0, 0
        while n < max_steps:
            try:
                batch = next(data_iter)  # never pull past max_steps
            except StopIteration:
                break
            # graftlint: ok[GL02] eval loop: one loss scalar per batch is
            # its contract (no overlap to protect — nothing else is queued)
            total += float(
                jax.device_get(
                    self._eval_step(self.state.params, self._eval_prepare(batch))
                )
            )
            n += 1
        if n == 0:
            raise ValueError("evaluate(): data_iter yielded no batches")
        return {"eval_loss": total / n, "eval_steps": n}
