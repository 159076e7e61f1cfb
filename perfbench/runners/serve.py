"""One run of a serving cell: a ``ServingEngine`` under a tape.

The client lives here. It stamps every token on its own side, in the
``on_token`` callback of ``submit()``, on the wall clock; the engine's own
TTFT is not used (it is taken at the start of the step that admits a request
and so leaves out the prefill the request waited for).

Set-up (all of it counted in ``setup_s``): weights made on the device from the
seed in one jitted call, in the type they are served in; the engine built with
its defaults (paged KV, page 16, ``paged_attention="auto"``, chunk 8); every
(prompt, answer) pair of one block of the tape sent once so that every shape
the tape can reach is compiled (each cancelled after one chunk); a seeded
sample served in full and compared with the plain reference on logits; in a
closed loop, the ramp.

The loop is one thread: submit what is due, ``engine.step()``, repeat. That is
how a user of ``ServingEngine`` drives it, and it keeps the load generator off
other threads. How late it ran is reported (``gen_lag_p90_ms``).

* open loop: every request of the tape is due inside the window; requests are
  timed from when they were DUE; after the window closes the run drains them
  (inside ``drain_cap_s``) instead of dropping the unfinished.
* closed loop: as many clients as the engine has slots, each sending its next
  request the moment its last one is answered; the window opens after
  ``ramp_s`` seconds of the same traffic.

``correct`` is about outputs: the served sample against the plain reference,
every request answered in full, the engine not halted. A closed loop is held
to its load besides (:func:`load_faults`); an open loop's stalls are part of
its numbers and are named on stderr.
"""

from __future__ import annotations

import importlib
import os
import shutil
import time
from typing import Dict, List, Optional

import numpy as np

from perfbench import stats, tape, xplane
from perfbench.spans import Spans
from perfbench.tracing import TRACE_SECONDS, CompileLog, TraceWindow, seed_key

SPAN_STEP = "engine.step"
SPAN_SUBMIT = "generator.submit"
SPAN_WAIT = "generator.wait"
SPAN_NAMES = (SPAN_STEP, SPAN_SUBMIT, SPAN_WAIT)
# a closed loop's tape: more than a client can have answered in any window
TAPE_REQUESTS_PER_CLIENT = 64
MIN_CLOSED_OCCUPANCY_PCT = 95.0


class Client:
    """Client-side record of one request."""

    __slots__ = ("index", "t_due", "t_sent", "n_out", "prompt_len", "stamps", "req")

    def __init__(self, index, t_due, n_out, prompt_len):
        self.index, self.t_due, self.n_out, self.prompt_len = index, t_due, n_out, prompt_len
        self.t_sent = None
        self.stamps: List[float] = []
        self.req = None

    def on_token(self, req, tok):
        self.stamps.append(time.perf_counter())

    @property
    def done(self) -> bool:
        return self.req is not None and self.req.finished

    @property
    def ok(self) -> bool:
        from neuronx_distributed_tpu.serving import RequestState

        return (self.req is not None and self.req.state is RequestState.DONE
                and len(self.stamps) == self.n_out)

    def record(self) -> dict:
        return {
            "t_due": self.t_due, "t_sent": self.t_sent, "n_out": len(self.stamps),
            "t_first": self.stamps[0] if self.stamps else None,
            "t_last": self.stamps[-1] if self.stamps else None,
            "prompt_len": self.prompt_len, "stamps": self.stamps, "ok": self.ok,
        }


def _submit(engine, client: Client, prompt, seed: int, cancel_after: Optional[int] = None):
    from neuronx_distributed_tpu.inference import GenerationConfig

    cb = client.on_token
    if cancel_after is not None:
        def cb(req, tok, _inner=client.on_token):
            _inner(req, tok)
            if len(req.tokens) >= cancel_after:
                engine.cancel(req.rid)

    client.t_sent = time.perf_counter()
    client.req = engine.submit(
        prompt, GenerationConfig(max_new_tokens=int(client.n_out), temperature=0.0),
        key=seed_key(seed, 1000 + client.index), on_token=cb,
    )


def _compiles(engine) -> Dict[str, int]:
    """Compiles the engine's ledger has counted, by program."""
    by_program = engine.programs.snapshot(analyze=False)["by_program"]
    return {name: int(entry["compiles"]) for name, entry in by_program.items()}


def _counters(engine) -> Dict[str, float]:
    m = engine.metrics
    by_program = _compiles(engine)
    return {
        "steps": m.steps, "chunks": m.chunks, "occupied_slot_steps": m.occupied_slot_steps,
        "preemptions": m.preemptions, "prefills": m.prefills, "decode_tokens": m.decode_tokens,
        "compiles": sum(by_program.values()), "compiles_by_program": by_program, "t": time.perf_counter(),
    }


def _warm_up(engine, traffic, vocab, seed, spans):
    """Every (prompt, answer) pair of one block, once: the engine picks its
    prefill program from both lengths, so both are the tape's. Each request is
    cancelled after its first decode chunk."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x3A93]))
    chunk = int(engine.decode_chunk_size)
    for i, (p, a) in enumerate(sorted(set(tape.block_lengths(traffic)))):
        c = Client(-1 - i, 0.0, a, p)
        prompt = tape.random_ids(rng, vocab, p)
        _submit(engine, c, prompt, seed, cancel_after=min(a, chunk + 1))
    with spans.span("warmup"):
        engine.run()
        engine.step()  # the drained engine rewinds its cursor: that program too


def _reference_check(engine, family, config, params, traffic, vocab, seed, log):
    """A seeded sample through prefill and decode in the engine; the
    reference's logit of every emitted token must be within the tolerance of
    the reference's maximum at that position (``common.judge_gaps``). Logits,
    not tokens: with random weights the largest logit changes on rounding."""
    from flax.core import meta

    from perfbench.references import common

    check = config["reference_check"]
    pairs = sorted(set(tape.block_lengths(traffic)))
    picks = [pairs[int(round(q * (len(pairs) - 1)))] for q in check["sample_quantiles"]]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x5EF]))
    clients, prompts = [], []
    for i, (p, a) in enumerate(picks):
        a = min(a, int(check["max_answer_tokens"]))
        c = Client(-100 - i, 0.0, a, p)
        prompt = tape.random_ids(rng, vocab, p)
        _submit(engine, c, prompt, seed)
        clients.append(c)
        prompts.append(prompt)
    engine.run()
    failed = [c for c in clients if not c.ok]
    ref_mod = importlib.import_module(f"perfbench.references.{family.reference}")
    ref = ref_mod.Reference(config["model"], meta.unbox(params))
    pad_to = -(-max(len(p) + c.n_out for p, c in zip(prompts, clients)) // 128) * 128
    tol = float(check["logit_tolerance"])
    # only a configuration with a sparse router names a near-tie; see judge_gaps
    near_tie = float(check.get("router_near_tie", 0.0))
    ok, controls = not failed, []
    for prompt, c in zip(prompts, clients):
        if not c.ok:
            continue
        g, wrong, margin, router = common.emitted_token_gaps(ref, prompt, c.req.tokens, pad_to)
        fine, over, exempt = common.judge_gaps(g, router, tol, near_tie)
        ok = ok and fine
        controls.extend(wrong.tolist())
        log(f"reference: prompt {len(prompt)} + {c.n_out} tokens: largest gap {g.max():.4f}, {over} of {len(g)} "
            f"fail the tolerance {tol:g}; control (wrong tokens) smallest gap {wrong.min():.3f}; reference "
            f"top-1/top-2 margin (median) {margin:.3f}")
        if router is not None:
            log(f"reference: router margins under {near_tie:g} at {exempt} of {len(g)} positions; gaps over the "
                f"tolerance (gap, router margin): "
                f"{[(round(float(a), 3), round(float(b), 4)) for a, b in zip(g, router) if not a <= tol]}")
    # every wrong token must miss the tolerance, or the check could not fail
    ok = ok and bool(controls) and min(controls) > tol
    return ok, len(clients), len(failed)


def build(config, seed, spans, log):
    """The engine of a configuration, its weights made on the device from the
    seed in one jitted call. Returns ``(engine, family, params, vocab)``."""
    import jax
    import jax.numpy as jnp

    from neuronx_distributed_tpu.parallel import mesh as mesh_lib
    from neuronx_distributed_tpu.serving import ServingEngine

    family = importlib.import_module(f"perfbench.families.{config['family']}")
    serving = config["serving"]
    mesh_lib.destroy_model_parallel()    # a mesh-free engine retraces under a live global mesh
    model = family.build(config["model"], runner="serve", max_seq_len=int(serving["max_seq_len"]))
    vocab = int(family.geometry(config["model"])["vocab_size"])
    with spans.span("init_weights"):
        params = jax.jit(model.init)(seed_key(seed, 0), jnp.zeros((1, 8), jnp.int32))
        jax.block_until_ready(params)
    engine = ServingEngine(
        model, params, num_slots=int(serving["num_slots"]),
        kv_page_size=int(serving["kv_page_size"]),
    )
    log(f"engine: slots {engine.num_slots} max_seq_len {engine.max_seq_len} resolved "
        f"{dict(engine.programs.resolved)}")
    return engine, family, params, vocab


def window(engine, traffic, seed, seconds, vocab, spans, trace_dir=None) -> dict:
    """One measured window of ``traffic`` on a warm engine: the loop of the
    module's docstring. Returns the raw record; :func:`run` and the sweep
    reduce it."""
    import jax

    from neuronx_distributed_tpu.serving import EngineHealth

    open_loop = traffic["loop"] == "open"
    n_clients = int(engine.num_slots)
    ramp_s = 0.0 if open_loop else float(traffic["ramp_s"])
    the_tape = tape.make_tape(
        traffic, seed, vocab_size=vocab, seconds=seconds,
        max_requests=None if open_loop else n_clients * TAPE_REQUESTS_PER_CLIENT,
    )
    trace_s = min(TRACE_SECONDS, seconds)
    drain_cap = float(traffic["drain_cap_s"]) if open_loop else 0.0

    clients: List[Client] = []
    in_flight: List[Client] = []
    step_rows = []                       # (t0, t1, prefills admitted, chunks decoded)
    depth_rows = []                      # (t, queue depth) before every step
    left_rows = []                       # (t0, t1, requests the step left queued)
    marks = {}                           # engine counters at the window's and the trace's ends
    nxt = 0
    closed = halted = False
    tracer = TraceWindow(trace_dir) if trace_dir else None
    backlog_end = None
    t_open = time.perf_counter() + ramp_s
    t_close = t_open + seconds

    def send(now):
        nonlocal nxt
        r = the_tape[nxt]
        c = Client(r.index, t_open + r.t_due if open_loop else now, r.n_out, len(r.prompt))
        if not open_loop and nxt < n_clients:
            # the first wave would otherwise prefill and finish in lockstep
            c.n_out = max(int(engine.decode_chunk_size) + 1, r.n_out * (nxt + 1) // n_clients)
        _submit(engine, c, r.prompt, seed)
        clients.append(c)
        in_flight.append(c)
        nxt += 1

    def close_trace():
        # only the span ends here: writing the trace would stall the drain for seconds
        marks["stop"] = _counters(engine)
        tracer.close()

    while True:
        now = time.perf_counter()
        if "before" not in marks and now >= t_open:
            marks["before"] = _counters(engine)
        if tracer and not tracer.on and not closed and now >= t_close - trace_s:
            tracer.start()
            marks["start"] = _counters(engine)
            now = time.perf_counter()
        if not closed and now >= t_close:
            closed = True
            backlog_end = int(engine.queue_depth)
            marks["after"] = _counters(engine)
            if tracer and tracer.on:
                close_trace()
        # the generator: everything that is due, before the next step
        with spans.span(SPAN_SUBMIT):
            if open_loop:
                while nxt < len(the_tape) and t_open + the_tape[nxt].t_due <= now:
                    send(now)
            elif not closed:
                in_flight[:] = [c for c in in_flight if not c.done]
                while len(in_flight) < n_clients and nxt < len(the_tape):
                    send(now)
        if closed and (not open_loop or all(c.done for c in clients) or now > t_close + drain_cap):
            break
        if engine.health() is EngineHealth.HALTED:
            halted = True
            break
        if engine.has_work:
            depth_rows.append((now, int(engine.queue_depth)))
            prefills, chunks = engine.metrics.prefills, engine.metrics.chunks
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(SPAN_STEP):
                engine.step()
            t1 = time.perf_counter()
            spans.add(SPAN_STEP, t0, t1)
            step_rows.append((t0, t1, engine.metrics.prefills - prefills, engine.metrics.chunks - chunks))
            left_rows.append((t0, t1, int(engine.queue_depth)))
        else:
            due = t_open + the_tape[nxt].t_due if open_loop and nxt < len(the_tape) else t_close
            with spans.span(SPAN_WAIT):
                time.sleep(max(0.0, min(due, t_close) - time.perf_counter()))
    t_end = time.perf_counter()
    if tracer and tracer.on:  # a halted engine left the loop with the span still open
        close_trace()
    if tracer:
        tracer.stop()
    t_written = time.perf_counter()
    marks.setdefault("before", _counters(engine))
    marks.setdefault("after", _counters(engine))

    everyone = [c.record() for c in clients]
    if open_loop:
        # the sample is every request due in the window, answered or not
        sample = everyone
        records = [r for r in sample if r["ok"]]
        failed = len(sample) - len(records)
    else:
        # the sample is every request answered inside the window; one that
        # ended without its whole answer, at any time, is a failure
        records = sample = [r for r in everyone if r["ok"] and t_open <= r["t_last"] <= t_close]
        failed = sum(1 for c in clients if c.done and not c.ok)
    tokens = stats.tokens_in_window((t for r in everyone for t in r["stamps"]), t_open, t_close)
    before, after = marks["before"]["compiles_by_program"], marks["after"]["compiles_by_program"]
    compiled = {name: n - before.get(name, 0) for name, n in after.items() if n > before.get(name, 0)}
    compile_log = CompileLog.get()
    decode_steps = marks["after"]["steps"] - marks["before"]["steps"]
    held = marks["after"]["occupied_slot_steps"] - marks["before"]["occupied_slot_steps"]
    ttfts = stats.ttft_ms(sample) if open_loop else []
    return {
        "open_loop": open_loop, "window": (t_open, t_close), "t_end": t_end, "seconds": seconds,
        "trace_write_s": t_written - t_end,
        "halted": halted, "sample": len(sample), "failed": failed, "records": records,
        "clients": everyone, "tokens": tokens, "ttfts": ttfts,
        "lags_ms": [1e3 * (r["t_sent"] - r["t_due"]) for r in sample] if open_loop else [],
        "steps": step_rows, "depths": depth_rows, "counters": marks,
        "backlog_end": backlog_end,
        "backlog_last_quarter": stats.mean_left_queued(left_rows, t_close - seconds / 4.0, t_close),
        # slots held per executed decode step over the engine's slots, inside the window
        "slot_occupancy_pct": 100.0 * held / (decode_steps * engine.num_slots) if decode_steps else None,
        "compiles_in_window": marks["after"]["compiles"] - marks["before"]["compiles"],
        "compiled_in_window": compiled,
        # every lowering and backend compile of the process inside the window, ledgered or not
        "lowered_in_window": compile_log.between(CompileLog.LOWERED, t_open, t_close),
        "backend_compiles_in_window": compile_log.between(CompileLog.COMPILED, t_open, t_close),
        "preemptions_in_window": marks["after"]["preemptions"] - marks["before"]["preemptions"],
        "tpot_mean_ms": stats.tpot_mean_ms(records),
        "serve_tokens_per_s": tokens / seconds,
        "ttft_p90_ms": stats.tail_with_missing(ttfts, 90) if open_loop else None,
    }


def load_faults(w: dict, traffic: dict) -> List[str]:
    """Why the closed-loop window ``w`` is not a measurement of its cell, one
    line each; empty for a sound run, and for every open-loop run.

    Closed loop: a client for every slot keeps every slot full and leaves
    nothing queued after a step, whatever the seed. A compile inside the
    window, a queue that stood above the traffic file's ``overload_backlog``
    on average over the window's last quarter (``stats.mean_left_queued``), or
    slots left empty mean that the run hit something outside its load (the
    end of the cache row), and make it ``correct: false``.

    Open loop: NOT judged. Where the engine's shared write cursor reaches
    the end of the cache row the engine stalls by itself: a request whose
    last column is the row's last gets every active request preempted, and
    their contexts come back through programs compiled when first needed
    (``paged_seed`` per number of prefix pages, ``suffix_prefill``); a prompt
    laid out past the projection compiles a prefill of its exact length,
    which may fail. Some ten seconds in which requests queue, in 5 of 18
    runs at 0.75 of the knee (PR 23). The harness cannot warm those shapes,
    and a run it called incorrect for them would refuse every PR whose check
    draws one; a request the engine FAILS is incorrect all the same. The
    counts are reported per layer (``compiles_in_window``,
    ``preemptions_in_window``, ``backlog_last_quarter``) and named on
    stderr."""
    if w["open_loop"]:
        return []
    faults = []
    if w["compiles_in_window"]:
        faults.append(f"COMPILED INSIDE THE WINDOW: {w['compiles_in_window']} programs: "
                      f"{w.get('compiled_in_window', '')}")
    queued = w["backlog_last_quarter"]
    if queued is not None and queued > float(traffic["overload_backlog"]):
        faults.append(f"OVERLOADED: {queued:.1f} requests queued on average over the window's last quarter, "
                      f"{w['backlog_end']} when it closed (the traffic file allows {traffic['overload_backlog']})")
    held = w["slot_occupancy_pct"]
    if held is None or held < MIN_CLOSED_OCCUPANCY_PCT:
        faults.append(f"STARVED: the closed loop held {held or 0.0:.1f}% of its slot-steps, under "
                      f"{MIN_CLOSED_OCCUPANCY_PCT:g}%")
    return faults


def run(*, config, traffic, seed, seconds, trace, devices, t_start, out_dir, log) -> dict:
    spans = Spans()
    CompileLog.get()
    engine, family, params, vocab = build(config, seed, spans, log)
    _warm_up(engine, traffic, vocab, seed, spans)
    with spans.span("reference_check"):
        ref_ok, ref_n, ref_failed = _reference_check(
            engine, family, config, params, traffic, vocab, seed, log)
    engine.step()

    trace_dir = os.path.join(out_dir, "trace") if trace else None
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
    w = window(engine, traffic, seed, seconds, vocab, spans, trace_dir)
    t_open, t_close = w["window"]

    end_to_end = {
        "setup_s": t_open - t_start,
        "tpot_mean_ms": w["tpot_mean_ms"],
        "serve_tokens_per_s": w["serve_tokens_per_s"],
    }
    if w["open_loop"]:
        end_to_end["ttft_p90_ms"] = w["ttft_p90_ms"]
    faults = load_faults(w, traffic)
    notes = [
        f"window {seconds:g}s: {w['sample']} requests in the sample, {len(w['records'])} answered in full, "
        f"{w['tokens']} tokens inside the window, backlog at close {w['backlog_end']} (largest before a step "
        f"{max((d for t, d in w['depths'] if t_open <= t <= t_close), default=0)}, mean left queued over the last "
        f"quarter {w['backlog_last_quarter'] or 0.0:.2f}), slot occupancy "
        f"{w['slot_occupancy_pct'] or 0.0:.1f}%, compiles in window "
        f"{w['compiles_in_window']}, preemptions {w['preemptions_in_window']}, "
        f"drain {w['t_end'] - t_close:.2f}s, trace written in {w['trace_write_s']:.2f}s, set-up {t_open - t_start:.1f}s "
        f"(warm-up {spans.total('warmup'):.1f}s, reference check {spans.total('reference_check'):.1f}s)"
    ]
    lowered, compiled = w["lowered_in_window"], w["backend_compiles_in_window"]
    if w["compiles_in_window"] or w["preemptions_in_window"]:
        notes.append(f"STALLED BY THE ENGINE: {w['preemptions_in_window']} requests preempted, "
                     f"{w['compiles_in_window']} programs compiled inside the window: {w['compiled_in_window']}")
    if lowered or compiled:
        notes.append(
            f"lowered inside the window: {len(lowered)} programs in {sum(s for _, _, s in lowered):.2f}s; handed to "
            f"the backend's compiler: {len(compiled)} in {sum(s for _, _, s in compiled):.2f}s; (seconds after the "
            f"window opened, function, seconds): "
            f"{[(round(t - t_open, 1), fn, round(s, 2)) for t, fn, s in (compiled or lowered)[:12]]}")
    slow = [(round(t0 - t_open, 1), round(t1 - t0, 2), p, c) for t0, t1, p, c in w["steps"]
            if t_open <= t0 <= t_close and t1 - t0 > 1.0]
    if slow:
        notes.append(f"engine steps over 1 s (seconds after the window opened, seconds, prefills, chunks): {slow[:12]}")
    if engine.flight is not None:
        kinds = {}
        for event in engine.flight.events():
            kinds[event.get("kind")] = kinds.get(event.get("kind"), 0) + 1
        notes.append(f"flight recorder, whole run: {kinds}")
    # every reason a run is not correct, by name, as the last lines of stderr
    reasons = list(faults)
    if not ref_ok:
        reasons.append("REFERENCE: the served sample is not the plain reference's (lines above)")
    if ref_failed or w["failed"]:
        reasons.append(f"FAILED REQUESTS: {ref_failed} of the reference sample, {w['failed']} of the window's "
                       f"(ended without the whole answer, or unanswered when the drain's cap ran out)")
    if w["halted"]:
        reasons.append("HALTED: the engine's health went to HALTED")
    if not w["records"]:
        reasons.append("NOTHING ANSWERED in the window")
    correct = not reasons
    notes.extend(reasons)
    run_record = {
        **w,
        "correct": correct,
        "attempted": w["sample"] + (0 if w["open_loop"] else w["failed"]) + ref_n,
        "failed": w["failed"] + ref_failed,
        "end_to_end": end_to_end,
        "notes": notes,
        # for the per-layer readers
        "kind": "serve",
        "config": config, "traffic": traffic, "geometry": family.geometry(config["model"]),
        "device_kind": devices[0].device_kind, "chips": len(devices),
        "spans": spans, "num_slots": engine.num_slots, "chunk": int(engine.decode_chunk_size),
    }
    if trace:
        run_record["trace"] = xplane.reduce_trace(
            trace_dir, SPAN_NAMES, require_device=devices[0].platform == "tpu")
    return run_record
