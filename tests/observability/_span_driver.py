"""Run as a process of its own by test_spans.py: a tiny engine and a tiny
trainer under ``observability.profile_window``, so that the profiler's
session never opens inside a pytest worker (a worker that had traced
in-process aborted in a later, unrelated test: tests/benchmark/
test_rehearsal.py). Writes the trace under ``<out>/trace``, that of a paged
engine's decode-only steps under ``<out>/paged`` and what it counted to
``<out>/facts.json``."""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("NXD_TPU_PERSISTENT_CACHE", "0")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from neuronx_distributed_tpu.inference import GenerationConfig  # noqa: E402
from neuronx_distributed_tpu.models.llama import LlamaForCausalLM, tiny_llama  # noqa: E402
from neuronx_distributed_tpu.observability import profile_window  # noqa: E402
from neuronx_distributed_tpu.serving import ServingEngine  # noqa: E402


class SyncCounter:
    def __init__(self):
        self.calls = 0
        self._real = jax.device_get

    def __enter__(self):
        jax.device_get = self._counting
        return self

    def __exit__(self, *exc):
        jax.device_get = self._real

    def _counting(self, x):
        self.calls += 1
        return self._real(x)


def serve(model, params):
    """Three requests on two slots with eager admission and a 48-column row:
    the cursor reaches the wall, so every phase of step() runs, the
    preemption included. Returns (device_get calls, rids, prefills, chunks)."""
    engine = ServingEngine(model, params, num_slots=2, admission="eager", prefix_cache=None)
    prompts = ([3, 5, 7, 11], [13, 17, 19, 23], [29, 31, 37, 41])
    new = (30, 20, 25)
    # the second request samples: the dispatch span's ``sampled_slots`` reads 1
    # while it decodes and 0 in the chunks after it
    sampling = ({}, {"temperature": 0.8, "top_k": 17}, {})
    with SyncCounter() as c:
        reqs = [
            engine.submit(np.asarray(p, np.int32),
                          GenerationConfig(max_new_tokens=n, **{"temperature": 0.0, **how}),
                          key=jax.random.PRNGKey(60 + i))
            for i, (p, n, how) in enumerate(zip(prompts, new, sampling))
        ]
        engine.run()
    m = engine.metrics
    return {"syncs": c.calls, "rids": [r.rid for r in reqs], "prefills": int(m.prefills),
            "chunks": int(m.chunks), "preemptions": int(m.preemptions),
            "chunks_dispatched": int(m.chunks_dispatched),
            "greedy_chunks_dispatched": int(m.greedy_chunks_dispatched),
            "tokens": [list(map(int, r.tokens)) for r in reqs]}


def serve_paged(model, params, engine=None):
    """One request on a PAGED engine, decode-only steps after the first: the
    page dealing's span, and every ledgered call of the run counted by its
    ledger. Called once to warm (returns the engine) and once traced."""
    if engine is None:
        engine = ServingEngine(model, params, num_slots=2, prefix_cache=None, kv_page_size=8)
    before = {name: info.dispatches for name, info in engine.programs.programs().items()}
    engine.submit(np.asarray([3, 5, 7, 11, 13], np.int32), GenerationConfig(max_new_tokens=40, temperature=0.0))
    engine.run()
    calls = {name: info.dispatches - before.get(name, 0) for name, info in engine.programs.programs().items()}
    return engine, {name: n for name, n in calls.items() if n}


def train(steps):
    from neuronx_distributed_tpu.parallel import mesh as mesh_lib
    from neuronx_distributed_tpu.trainer.loop import Trainer
    from neuronx_distributed_tpu.trainer.trainer import OptimizerConfig

    mesh_lib.destroy_model_parallel()
    mesh_lib.initialize_model_parallel(tensor_model_parallel_size=1, devices=jax.devices()[:1])
    model = LlamaForCausalLM(tiny_llama(), attention_impl="xla")
    rng = np.random.default_rng(0)

    def batches():
        while True:
            ids = rng.integers(1, 200, size=(2, 17)).astype(np.int32)
            yield {"input_ids": ids[:, :-1], "labels": ids[:, 1:],
                   "loss_mask": np.ones((2, 16), np.float32)}

    trainer = Trainer(model=model, optimizer_config=OptimizerConfig(), handle_signals=False)
    trainer.fit(batches(), jax.random.PRNGKey(0), max_steps=steps)
    mesh_lib.destroy_model_parallel()


def main(out):
    cfg = tiny_llama(max_seq_len=48)
    model = LlamaForCausalLM(cfg, attention_impl="xla")
    params = model.init(jax.random.PRNGKey(1), jnp.ones((1, 8), jnp.int32))
    facts = {"off": serve(model, params)}                 # no session: also warms every program
    # a session that saw no engine activity holds no nxd.* event: spans
    # emitted while no session was open were recorded nowhere
    with profile_window(os.path.join(out, "empty")):
        pass
    with profile_window(os.path.join(out, "trace")):
        facts["on"] = serve(model, params)
        train(3)
    paged, _ = serve_paged(model, params)
    with profile_window(os.path.join(out, "paged")):
        _, facts["paged_calls"] = serve_paged(model, params, paged)
    with open(os.path.join(out, "facts.json"), "w") as f:
        json.dump(facts, f)


if __name__ == "__main__":
    main(sys.argv[1])
