#!/usr/bin/env python
"""Continuous-batching serving demo: a staggered stream of variable-length
requests through a slot-based ``ServingEngine`` (reference analogue: the
request-level serving loop the NxD stack delegates to vLLM; here it is
native — serving/engine.py).

Submits ``--requests`` requests with random prompt lengths and per-request
sampling configs, trickling them in while the engine steps (a Poisson-ish
open-loop arrival pattern), then prints each stream and the engine metrics
snapshot: TTFT, queue wait, decode tokens/s, slot occupancy, preemptions,
and the decode-step compile count (always 1 — the continuous-batching
invariant).

``--decode-chunk`` sets the engine's fused decode chunk size: that many
tokens per slot decode as ONE jitted scan with a single host sync at the
end (donated cache and slot state update in place). Bigger chunks buy
decode throughput; the cost is latency granularity — admission, streaming
callbacks, and cancellation all land at chunk boundaries, so TTFT for a
request arriving mid-chunk grows by up to a chunk of decode steps.
``--decode-chunk 1`` is the per-token loop. Streams are bit-identical
either way.

``--shared-prefix N`` prepends the same N-token "system prompt" to every
request — the workload shape the engine's prefix cache is built for. The
first admission prefills (and stores) the shared prefix; every later one
reuses it and prefills only its unique tail, visible in the summary's
``prefix_hits`` / ``prefix_tokens_reused`` counters and the per-request
TTFTs. ``--no-prefix-cache`` disables the store (today's full-prefill
path); streams are bit-identical either way.

``--inject-fault`` drives the fault-tolerance layer end to end through the
deterministic ``FaultInjector`` harness: ``dispatch`` injects one decode
dispatch failure mid-run (the engine requeues in-flight requests and
recovers, streams intact), ``halt`` fails every dispatch until the engine
lands in HALTED with the work requeued, ``poison`` corrupts one slot's
readback (quarantined out of the rotation, victim resumes elsewhere),
``prefill`` OOM-fails one admission (that request FAILS for cause, the
loop survives). ``--deadline``/``--queue-timeout`` attach per-request
deadlines so sheds show up in the summary (pair with ``--inject-fault
skew`` to jump the engine clock past them without waiting).

``--traffic {steady,bursty}`` switches the demo into SLO-observability
mode (ISSUE 11): a seeded multi-tenant arrival tape (Poisson or
bursty/diurnal) replays through the engine on a VIRTUAL clock —
``--tenants N`` alternating interactive-chat / batch-long-doc tenants,
``--slo-ttft-ms``/``--slo-tpot-ms`` the interactive per-request bounds
(batch gets 4x) — and prints the per-tenant p50/p99 TTFT, TPOT, goodput,
and SLO attainment report. The same ``--seed`` replays byte-identically;
compare steady vs bursty to watch bursts break an SLO the mean load meets.

``--draft-layers N`` turns on SPECULATIVE serving: the draft model is the
target's first N layers (early-exit weight sharing — the smaller N, the
cheaper the draft; the later layers are eps-scaled so the draft actually
agrees with the target and acceptance is visibly high). Every decode chunk
becomes ``--decode-chunk`` fused draft–verify rounds, each emitting up to
``--gamma`` tokens per slot (per-slot variable advance). Greedy streams
are bit-identical to the non-speculative engine; the summary gains
``spec_accept_rate`` / ``spec_accept_len_p50`` / ``draft_tokens_wasted``.
``--inject-fault draft`` injects a speculative-dispatch failure: the
affected chunk decodes non-speculatively (stream intact) and the draft
cache resyncs.

``--kill-replica K`` (with ``--replicas N``) is the elastic-fabric demo
(ISSUE 18): replica K is fenced mid-run, after half the requests have been
submitted. By default the router notices the halt on its next step and
RE-HOMES the orphaned work to the survivors through the halt/adopt
contract (original deadlines and tokens intact). With ``--restart`` the
killed replica is WARM-RESTARTED instead: its host serving state (queue,
per-request tokens/keys/cursors, deadlines, tenant attribution — never a
device pytree) is snapshotted, a fresh replica spawns from the build
recipe, the snapshot restores into it, and every stream continues
bit-identically from where it stopped.

``--prewarm [--aot-cache DIR]`` is the AOT cold-start path (ISSUE 17):
the first run of a cache dir serves cold and writes the AOT bundle
(manifest + serialized executables + persistent XLA cache) at the end;
a rerun restores every program BEFORE the first request — deserialized
executables where the environment matches (zero compiles), trace replay
backed by the disk cache otherwise — so the first request's TTFT carries
no compile bill. Streams are bit-identical either way.

CPU-runnable out of the box:

  python examples/serving_demo.py
  python examples/serving_demo.py --requests 12 --slots 2 --admission eager
  python examples/serving_demo.py --decode-chunk 1   # per-token stepping
  python examples/serving_demo.py --shared-prefix 24 # system-prompt reuse
  python examples/serving_demo.py --shared-prefix 24 --no-prefix-cache
  python examples/serving_demo.py --row-cache        # legacy row-per-slot KV
  python examples/serving_demo.py --kv-pages 24 --slots 8  # paged (default)
  python examples/serving_demo.py --inject-fault page
  python examples/serving_demo.py --quantize int8    # weight-only int8
  python examples/serving_demo.py --quantize fp8
  python examples/serving_demo.py --quantize int8 --kv-quant  # + int8 KV pages
  python examples/serving_demo.py --traffic steady --tenants 2
  python examples/serving_demo.py --traffic bursty --slo-ttft-ms 100
  python examples/serving_demo.py --draft-layers 1 --gamma 4  # speculative
  python examples/serving_demo.py --draft-layers 1 --inject-fault draft
  python examples/serving_demo.py --prewarm --aot-cache /tmp/aot  # x2: warm
  python examples/serving_demo.py --replicas 3 --kill-replica 0
  python examples/serving_demo.py --replicas 3 --kill-replica 0 --restart
  python examples/serving_demo.py --inject-fault dispatch
  python examples/serving_demo.py --inject-fault poison --slots 4
  python examples/serving_demo.py --deadline 0.5 --inject-fault skew
  python examples/serving_demo.py --timeline /tmp/serving_trace.json
"""

from __future__ import annotations

import argparse
import os
import sys

_repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _repo_root not in sys.path:
    sys.path.insert(0, _repo_root)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--model", default="tiny",
                   choices=["tiny", "7b", "llama3-8b"],
                   help="model preset (tiny = the 4-layer CPU test config; "
                        "the real widths need a TPU — cut depth with "
                        "--layers to fit one chip's HBM)")
    p.add_argument("--layers", type=int, default=None,
                   help="override the preset's layer count")
    p.add_argument("--attention", default="auto",
                   choices=["auto", "flash", "xla"],
                   help="attention implementation (auto = the Pallas "
                        "kernels on a TPU, the XLA einsum elsewhere)")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--max-new-tokens", type=int, default=12)
    p.add_argument("--admission", default="conservative",
                   choices=["conservative", "eager"])
    p.add_argument("--max-tokens-in-flight", type=int, default=None)
    p.add_argument("--decode-chunk", type=int, default=8,
                   help="fused decode steps per host sync (1 = per-token "
                        "loop; higher = more decode throughput, coarser "
                        "TTFT/cancel granularity at chunk boundaries)")
    p.add_argument("--shared-prefix", type=int, default=0,
                   help="prepend the same N-token system prompt to every "
                        "request (N=0 disables) — the prefix cache serves "
                        "every request after the first from its stored KV")
    p.add_argument("--no-prefix-cache", action="store_true",
                   help="disable the prefix cache (full prefill for every "
                        "admission — today's legacy path; streams are "
                        "bit-identical either way)")
    p.add_argument("--draft-layers", type=int, default=0,
                   help="speculative serving: draft = the target's first N "
                        "layers (0 disables). Greedy streams stay "
                        "bit-identical; acceptance stats land in the "
                        "summary")
    p.add_argument("--gamma", type=int, default=4,
                   help="draft tokens proposed per speculative round (each "
                        "round emits 1..gamma tokens per slot)")
    p.add_argument("--kv-page-size", type=int, default=16,
                   help="PAGED KV cache pool page size in cache columns — "
                        "the DEFAULT layout (ISSUE 13 fold-in): admission "
                        "packs by actual page footprint, prefix hits share "
                        "pages copy-on-write (zero KV bytes copied), poison "
                        "quarantine is page-granular; streams are "
                        "bit-identical to the row layout either way. 0 or "
                        "--row-cache restores row-per-slot")
    p.add_argument("--row-cache", action="store_true",
                   help="row-per-slot KV layout (the pre-paging default; "
                        "one max_seq_len row of HBM per slot)")
    p.add_argument("--quantize", default=None, choices=["int8", "fp8"],
                   help="weight-only quantized serving: the engine "
                        "converts the float params once at construction "
                        "(per-channel scales) and every decode/prefill "
                        "matmul dequantizes-on-load — HBM holds 1-byte "
                        "weights, decode_compilations stays 1. Streams "
                        "follow the logit-divergence contract instead of "
                        "bit-identity (greedy smoke stays token-identical "
                        "on this tiny model)")
    p.add_argument("--kv-quant", action="store_true",
                   help="quantize the PAGED KV pool to int8 pages + "
                        "per-page scales (needs the paged layout; "
                        "~2-4x pages at a fixed HBM budget). Implies "
                        "--quantize int8 unless --quantize is given")
    p.add_argument("--kv-pages", type=int, default=None,
                   help="pool size in pages (default: the row-equivalent "
                        "HBM). Size it DOWN to see free-page admission "
                        "packing and the page-pressure wall")
    p.add_argument("--kv-host-pages", type=int, default=None,
                   help="host-RAM page tier size (tiered KV, ISSUE 19): "
                        "the reclaim valve SPILLS cold prefix pages here "
                        "instead of evicting, and admission prefetches "
                        "them back on a match. Pair with a small "
                        "--kv-pages to watch the eviction cliff become a "
                        "host-tier hit-rate slope")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--inject-fault", default="none",
                   choices=["none", "dispatch", "halt", "poison", "prefill",
                            "skew", "draft", "page", "bitflip"],
                   help="drive a recovery path through the FaultInjector: "
                        "one dispatch failure (recover), all dispatches "
                        "(HALTED), a poisoned readback (quarantine), a "
                        "prefill OOM (fail one request), clock skew "
                        "(trip --deadline/--queue-timeout instantly), or "
                        "'bitflip' — one silent bit flipped inside a "
                        "pooled KV page; the reuse-time page fingerprints "
                        "reject it and the engine falls back to a full "
                        "prefill (needs --shared-prefix > 0)")
    p.add_argument("--deadline", type=float, default=None,
                   help="per-request end-to-end deadline in seconds "
                        "(missed → TIMED_OUT at the next chunk boundary, "
                        "partial stream kept)")
    p.add_argument("--queue-timeout", type=float, default=None,
                   help="per-request admission timeout in seconds (missed "
                        "→ shed before prefill)")
    p.add_argument("--timeline", default=None,
                   help="write a chrome://tracing JSON of the serving loop")
    p.add_argument("--trace", default=None,
                   help="like --timeline, spelled as the observability "
                        "knob: the trace carries per-request Perfetto "
                        "FLOW events (one connected arrow chain per "
                        "request: submit -> admission -> prefill -> "
                        "decode chunks -> retire) — open in ui.perfetto.dev")
    p.add_argument("--profile", default=None, metavar="DIR",
                   help="capture a jax.profiler trace of the serving loop "
                        "into DIR (observability.profile_window; open with "
                        "TensorBoard/XProf): device ops and the engine's "
                        "nxd.step* spans on one clock")
    p.add_argument("--programs", action="store_true",
                   help="print the compiled-program ledger (dispatches, "
                        "compiler-reported FLOPs/bytes, roofline) and the "
                        "HBM ledger (residents, limits, capacity plan) "
                        "after the run")
    p.add_argument("--prometheus", action="store_true",
                   help="print the metrics registry in Prometheus text "
                        "exposition format after the run (what a scrape "
                        "endpoint would serve)")
    p.add_argument("--traffic", default="none",
                   choices=["none", "steady", "bursty"],
                   help="SLO observability mode (ISSUE 11): replay a "
                        "seeded multi-tenant arrival tape through the "
                        "engine on a VIRTUAL clock (steady = Poisson, "
                        "bursty = diurnal square-wave bursts) and print "
                        "the per-tenant TTFT/TPOT/goodput/attainment "
                        "report — byte-identical for the same --seed")
    p.add_argument("--tenants", type=int, default=2,
                   help="tenant count for --traffic (alternating chat/"
                        "long-doc workloads, interactive/batch priority)")
    p.add_argument("--traffic-duration", type=float, default=6.0,
                   help="virtual seconds of arrivals to generate")
    p.add_argument("--slo-ttft-ms", type=float, default=150.0,
                   help="per-request TTFT bound for interactive tenants "
                        "(batch tenants get 4x); violations show in the "
                        "attainment report")
    p.add_argument("--slo-tpot-ms", type=float, default=20.0,
                   help="per-request mean-TPOT bound for interactive "
                        "tenants (batch tenants get 4x)")
    p.add_argument("--scheduler", default="fifo",
                   choices=["fifo", "slo"],
                   help="admission policy for --traffic (ISSUE 16): "
                        "'fifo' is the classic arrival-order engine; "
                        "'slo' replays the SAME tape twice — FIFO "
                        "baseline first, then the SLO-aware policy "
                        "(priority tiers + aging, per-tenant DWRR token "
                        "fairness, attainment-feedback admission/"
                        "preemption) — and prints the before/after "
                        "per-tenant attainment tables plus deltas")
    p.add_argument("--priority", action="append", default=None,
                   metavar="TENANT=TIER",
                   help="override a --traffic tenant's priority class "
                        "(repeatable), e.g. --priority tenant0-chat="
                        "realtime; tiers: realtime > interactive > "
                        "standard > batch")
    p.add_argument("--tp", type=int, default=0,
                   help="shard the engine over a tensor-parallel mesh of "
                        "this many devices (ISSUE 14; CPU hosts fan out "
                        "virtual devices automatically — streams stay "
                        "bit-identical to tp=0/1)")
    p.add_argument("--tp-comms-quantized", action="store_true",
                   help="route the TP row-parallel all-reduces through "
                        "the EQuARX int8 ring (approximate; ~4x fewer "
                        "wire bytes per decode step)")
    p.add_argument("--paged-attention", default="auto",
                   choices=["auto", "gather", "fused"],
                   help="paged decode transport: 'fused' streams K/V "
                        "straight from pool pages through the paged "
                        "flash-decode kernel (a TPU kernel: asking for it "
                        "elsewhere fails); auto = fused on a TPU, gather "
                        "elsewhere")
    p.add_argument("--replicas", type=int, default=0,
                   help="serve through a ReplicaRouter over this many "
                        "engine replicas (queue-depth + page-pressure "
                        "balancing, shared-prefix affinity, halt "
                        "re-homing)")
    p.add_argument("--kill-replica", type=int, default=None, metavar="K",
                   help="fence replica K mid-run (after half the requests "
                        "have been submitted); the router re-homes its "
                        "work to the survivors — streams intact, original "
                        "deadlines kept. Needs --replicas > 1")
    p.add_argument("--restart", action="store_true",
                   help="with --kill-replica: warm-restart the killed "
                        "replica instead of re-homing — snapshot its host "
                        "serving state, spawn a fresh replica, restore, "
                        "reattach streams (tokens continue, never replay)")
    p.add_argument("--disaggregate", action="store_true",
                   help="split prefill from decode: dedicated prefill "
                        "workers hand contexts to the decode engine as "
                        "zero-copy page-table handoffs (paged layout "
                        "only)")
    p.add_argument("--prefill-workers", type=int, default=1,
                   help="prefill workers under --disaggregate")
    p.add_argument("--prewarm", action="store_true",
                   help="AOT cold-start path (ISSUE 17): restore-or-replay "
                        "every program in the cache dir's manifest BEFORE "
                        "the first request (serialized executables when "
                        "fresh, trace replay backed by the persistent "
                        "compile cache otherwise). The first run of a "
                        "cache dir serves cold and writes the bundle; "
                        "rerun to see the first request's TTFT without "
                        "the compile bill")
    p.add_argument("--aot-cache", default=None, metavar="DIR",
                   help="AOT cache dir for --prewarm (manifest + "
                        "serialized executables + persistent XLA cache); "
                        "default: ~/.cache/nxd-tpu-aot-demo. The bundle "
                        "is (re)written at the end of every run")
    p.add_argument("--force-cpu-devices", type=int, default=None)
    return p.parse_args(argv)


def build_model(args):
    """``(cfg, model)`` for the demo's ``--model``/``--layers``/
    ``--attention`` flags — the presets
    ``examples/run_inference.py`` takes. Real widths serve bf16 weights;
    the tiny default keeps the fp32 CPU-test config."""
    import jax.numpy as jnp

    from neuronx_distributed_tpu.models import llama as llama_lib

    over = {}
    if args.layers is not None:
        over["num_layers"] = args.layers
    if args.model == "tiny":
        cfg = llama_lib.tiny_llama(**over)
    else:
        preset = {"7b": llama_lib.llama2_7b,
                  "llama3-8b": llama_lib.llama3_8b}[args.model]
        # the fused paged transport pairs pool leaves with layers by name
        cfg = preset(scan_layers=False, remat=False,
                     param_dtype=jnp.bfloat16, **over)
    return cfg, llama_lib.LlamaForCausalLM(cfg, attention_impl=args.attention)


def _engine_layout(args):
    """(kv_page_size, QuantConfig-or-None) from the demo flags: paged by
    default (ISSUE 13 fold-in), ``--row-cache``/``--kv-page-size 0`` for
    the legacy row layout, ``--quantize``/``--kv-quant`` for the quantized
    serving path."""
    page = (
        None if (args.row_cache or not args.kv_page_size)
        else args.kv_page_size
    )
    if args.kv_quant and page is None:
        raise SystemExit("--kv-quant needs the paged layout (drop "
                         "--row-cache / use --kv-page-size > 0)")
    quant = None
    if args.quantize or args.kv_quant:
        from neuronx_distributed_tpu.serving import QuantConfig

        quant = QuantConfig(
            weights=args.quantize or "int8",
            kv="int8" if args.kv_quant else None,
        )
    return page, quant


def _run_traffic(args, cfg, model, params):
    """``--traffic``: seeded multi-tenant replay + per-tenant SLO report.

    Even-indexed tenants are interactive chat under the tight
    ``--slo-ttft-ms``/``--slo-tpot-ms`` bounds; odd ones are batch
    long-doc under 4x-looser bounds — re-run with ``--traffic bursty``
    (same seed) to watch the same tape's bursts blow the interactive
    attainment that the steady replay meets."""
    from neuronx_distributed_tpu.observability import SLOSpec
    from neuronx_distributed_tpu.serving import (
        ServingEngine,
        TenantProfile,
        VirtualClock,
        generate_tape,
        replay,
    )

    from neuronx_distributed_tpu.serving.sched import TIER_RANK

    arrival = "poisson" if args.traffic == "steady" else "bursty"
    tenants, slo = [], {}
    for i in range(max(1, args.tenants)):
        interactive = i % 2 == 0
        name = f"tenant{i}-{'chat' if interactive else 'docs'}"
        tenants.append(
            TenantProfile(
                name,
                rate_rps=3.0 if interactive else 0.8,
                arrival=arrival,
                workload="chat" if interactive else "longdoc",
                priority="interactive" if interactive else "batch",
                burst_factor=4.0, burst_period_s=4.0, burst_duty=0.25,
            )
        )
        scale = 1.0 if interactive else 4.0
        slo[name] = SLOSpec(
            ttft_p99_s=args.slo_ttft_ms * scale / 1e3,
            tpot_p99_s=args.slo_tpot_ms * scale / 1e3,
        )
    names = {t.name: i for i, t in enumerate(tenants)}
    for override in args.priority or []:
        tenant, sep, tier = override.partition("=")
        if not sep or tenant not in names or tier not in TIER_RANK:
            raise SystemExit(
                f"--priority {override!r}: expected TENANT=TIER with "
                f"TENANT in {sorted(names)} and TIER in "
                f"{sorted(TIER_RANK, key=TIER_RANK.get)}"
            )
        import dataclasses as _dc

        tenants[names[tenant]] = _dc.replace(
            tenants[names[tenant]], priority=tier
        )
    tape = generate_tape(
        tenants, duration_s=args.traffic_duration, seed=args.seed,
        vocab_size=cfg.vocab_size,
    )

    def run_once(scheduling):
        clock = VirtualClock()
        page, quant = _engine_layout(args)
        engine = ServingEngine(
            model, params,
            num_slots=args.slots,
            admission=args.admission,
            decode_chunk_size=args.decode_chunk,
            scheduling=scheduling,
            prefix_cache=None if args.no_prefix_cache else "auto",
            kv_page_size=page,
            kv_num_pages=args.kv_pages,
            kv_host_pages=args.kv_host_pages,
            quantize=quant,
            slo=slo,
            time_fn=clock,
            sleep_fn=lambda s: None,
        )
        target = engine
        if args.disaggregate:
            from neuronx_distributed_tpu.serving import DisaggregatedServer

            target = DisaggregatedServer(
                engine, n_workers=args.prefill_workers
            )
        return engine, replay(target, tape, clock, step_dt=0.05)

    def show(report, label):
        print(f"=== traffic replay [{label}]: {args.traffic} ({arrival}), "
              f"{len(tape)} arrivals / {len(tenants)} tenants, seed "
              f"{args.seed}, {report['replay']['steps']} engine steps over "
              f"{report['replay']['virtual_end_s']:.2f} virtual s ===")
        for name, row in report["tenants"].items():
            spec = slo[name]
            print(
                f"{name:>16s}  submitted={row['submitted']:>3d} "
                f"done={row['completed']:>3d} shed={row['sheds']:>2d} "
                f"rej={row['rejects']:>2d} | "
                f"ttft p50/p99 {row['ttft_p50_s'] * 1e3:6.1f}/"
                f"{row['ttft_p99_s'] * 1e3:6.1f}ms "
                f"(SLO {spec.ttft_p99_s * 1e3:.0f}ms) | "
                f"tpot p99 {row['tpot_p99_s'] * 1e3:5.2f}ms "
                f"(SLO {spec.tpot_p99_s * 1e3:.0f}ms) | "
                f"attain {row.get('attainment', 1.0):5.1%} "
                f"goodput {row.get('goodput_tok_s', 0.0):7.1f} tok/s"
            )
        s = report["slo"]
        print(f"\n=== SLO totals [{label}]: attained {s['attained']} / "
              f"violated {s['violated']} (attainment {s['attainment']:.1%}),"
              f" goodput {s['goodput_tok_s']:.1f} tok/s over "
              f"{s['span_s']:.2f} virtual s ===")
        if s["violation_reasons"]:
            print(f"violation reasons: {s['violation_reasons']}")

    baseline = None
    if args.scheduler == "slo":
        # before/after on the SAME tape: FIFO baseline first, then the
        # SLO-aware policy — the deltas are the subsystem's deliverable
        _, baseline = run_once("fifo")
        show(baseline, "fifo baseline")
        print()
    engine, report = run_once(args.scheduler)
    show(report, args.scheduler)
    if baseline is not None:
        print(f"\n=== fifo -> slo deltas (policy "
              f"{engine.policy.snapshot()}) ===")
        for name in report["tenants"]:
            b, a = baseline["tenants"][name], report["tenants"][name]
            print(
                f"{name:>16s}  attain {b.get('attainment', 1.0):5.1%} -> "
                f"{a.get('attainment', 1.0):5.1%} | goodput "
                f"{b.get('goodput_tok_s', 0.0):7.1f} -> "
                f"{a.get('goodput_tok_s', 0.0):7.1f} tok/s"
            )
        report["fifo_baseline"] = baseline
    if args.prometheus:
        print("\n=== prometheus exposition ===")
        print(engine.metrics.registry.prometheus_text())
    return report


def _run_router(args, cfg, model, params):
    """``--replicas N``: N engines behind one router — balanced routing,
    shared-prefix affinity, and one labeled registry scrape."""
    import jax
    import numpy as np

    from neuronx_distributed_tpu.inference import GenerationConfig
    from neuronx_distributed_tpu.observability import MetricsRegistry
    from neuronx_distributed_tpu.serving import RejectedError, ReplicaRouter
    from neuronx_distributed_tpu.serving.router import RID_STRIDE

    rng = np.random.RandomState(args.seed)
    page, quant = _engine_layout(args)
    registry = MetricsRegistry()
    router = ReplicaRouter.build(
        model, params, args.replicas, registry=registry,
        num_slots=args.slots, admission=args.admission,
        decode_chunk_size=args.decode_chunk,
        prefix_cache=None if args.no_prefix_cache else "auto",
        kv_page_size=page, kv_num_pages=args.kv_pages,
        kv_host_pages=args.kv_host_pages, quantize=quant,
        tp=args.tp if args.tp > 1 else None,
    )
    shared = (
        rng.randint(1, cfg.vocab_size, size=args.shared_prefix).astype(
            np.int32
        )
        if args.shared_prefix > 0 else None
    )
    kill_at = None
    if args.kill_replica is not None:
        if not 0 <= args.kill_replica < args.replicas:
            raise SystemExit(
                f"--kill-replica must be in [0, {args.replicas})"
            )
        kill_at = max(1, args.requests // 2)
    reqs = []
    for i in range(args.requests):
        plen = int(rng.randint(3, 17))
        prompt = rng.randint(1, cfg.vocab_size, size=plen).astype(np.int32)
        if shared is not None:
            prompt = np.concatenate([shared, prompt])
        gcfg = GenerationConfig(
            max_new_tokens=int(rng.randint(4, args.max_new_tokens + 1)),
            temperature=float(rng.choice([0.0, 0.7])),
        )
        try:
            reqs.append(
                router.submit(prompt, gcfg, key=jax.random.PRNGKey(100 + i))
            )
        except RejectedError as e:
            print(f"r{i} rejected: {e}")
        if kill_at is not None and i + 1 == kill_at:
            k = args.kill_replica
            router.replicas[k].fence("demo kill")
            if args.restart:
                new_idx = router.restart_replica(k)
                print(f"\n*** replica{k} killed after {kill_at} submits "
                      f"-> warm-restarted as replica{new_idx} (queue + "
                      f"streams restored from its host-state snapshot)\n")
            else:
                router.step()  # the step notices the halt and re-homes
                print(f"\n*** replica{k} killed after {kill_at} submits "
                      f"-> {router.stats['rehomed_requests']} requests "
                      f"re-homed to the survivors\n")
        router.step()
    router.run()
    snap = router.snapshot()
    print(f"\n=== {len(reqs)} requests through {args.replicas} replicas "
          f"x {args.slots} slots (affinity "
          f"{'on' if not args.no_prefix_cache else 'off'}) ===")
    for req in reqs:
        # look the final object up through the router: across a warm
        # restart the restored replica owns a NEW Request under the same
        # rid and the submit-time handle stops updating
        final = router.requests.get(req.rid, req)
        replica = req.rid // RID_STRIDE
        print(f"r{req.rid % RID_STRIDE:<3d} -> replica{replica} "
              f"{final.state.value:<9s} new={len(final.tokens):>2d}")
    r = snap["router"]
    print(f"\nrouted={r['routed']} by_replica={r['routed_by_replica']} "
          f"affinity_hits={r['affinity_hits']} "
          f"spillovers={r['spillovers']} rehomed={r['rehomed_requests']} "
          f"restarted={r['replicas_restarted']}")
    print(f"health: {r['health']}")
    for name, rep in snap["replicas"].items():
        print(f"  {name}: completed={rep['completed']} "
              f"prefix_hits={rep.get('prefix_hits', 0)} "
              f"preemptions={rep['preemptions']}")
    if args.prometheus:
        print("\n=== one scrape, all replicas (engine-labeled) ===")
        print(registry.prometheus_text())
    return snap


def main(argv=None):
    args = parse_args(argv)
    if args.force_cpu_devices:
        from neuronx_distributed_tpu.utils.platform import force_cpu_devices

        force_cpu_devices(args.force_cpu_devices)
    elif args.tp > 1:
        # the CPU fan-out dryrun_multichip uses — a TP mesh needs devices
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={max(args.tp, 8)}"
        )

    import jax
    import numpy as np

    # compile cache: where JAX_COMPILATION_CACHE_DIR says, else the fixed
    # <checkout>/.jax_cache (a path that moves never hits)
    from neuronx_distributed_tpu.inference import aot

    aot.enable_persistent_cache(
        os.path.join(_repo_root, ".jax_cache"), min_compile_time_secs=0.5
    )

    from neuronx_distributed_tpu.inference import GenerationConfig
    from neuronx_distributed_tpu.models.llama import LlamaForCausalLM
    from neuronx_distributed_tpu.serving import FaultInjector, ServingEngine
    from neuronx_distributed_tpu.utils.timeline import Timeline

    cfg, model = build_model(args)
    rng = np.random.RandomState(args.seed)
    init_ids = rng.randint(1, cfg.vocab_size, size=(1, 8)).astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), init_ids)

    if args.traffic != "none":
        return _run_traffic(args, cfg, model, params)
    if args.replicas > 1:
        if args.disaggregate:
            raise SystemExit(
                "--replicas and --disaggregate are separate demos — pick one"
            )
        return _run_router(args, cfg, model, params)

    draft_model, draft_params = None, None
    if args.draft_layers > 0:
        from neuronx_distributed_tpu.models.llama import (
            early_exit_draft_params,
        )

        if not 0 < args.draft_layers < cfg.num_layers:
            raise SystemExit(
                f"--draft-layers must be in [1, {cfg.num_layers - 1}]"
            )
        # early-exit draft: the target's first N layers (shared embed/
        # norm/head), with the target's LATER layers eps-scaled so draft
        # and target actually agree — the synthetic-acceptance dial
        # (random tiny-model weights would accept ~nothing and show
        # speculation at its worst, which is not the demo's job).
        # eps=0.02 gives ~0.8 per-round acceptance on GREEDY slots; the demo's mixed workload also carries sampled requests,
        # which accept nothing BY DESIGN (one exactly-sampled token per
        # round) and dilute the headline rate
        params, draft_params = early_exit_draft_params(
            params, cfg.num_layers, args.draft_layers, eps=0.02
        )
        import dataclasses

        draft_model = LlamaForCausalLM(
            dataclasses.replace(cfg, num_layers=args.draft_layers),
            attention_impl=args.attention,
        )

    injector = None
    if args.inject_fault != "none":
        injector = FaultInjector()
        if args.inject_fault == "draft":
            if draft_model is None:
                raise SystemExit(
                    "--inject-fault draft needs --draft-layers > 0"
                )
            injector.fail_draft_dispatch(at=2, times=1)
        if args.inject_fault == "page":
            if args.row_cache or not args.kv_page_size:
                raise SystemExit(
                    "--inject-fault page needs the paged layout"
                )
            injector.poison_page(at=2, slot=0)  # page-granular quarantine
        if args.inject_fault == "bitflip":
            if args.row_cache or not args.kv_page_size:
                raise SystemExit(
                    "--inject-fault bitflip needs the paged layout"
                )
            if args.shared_prefix <= 0 or args.no_prefix_cache:
                raise SystemExit(
                    "--inject-fault bitflip needs --shared-prefix > 0 "
                    "with the prefix cache on (a KV reuse to corrupt)"
                )
            injector.flip_bits("kv_pool", at=0)  # first prefix reuse
        if args.inject_fault == "dispatch":
            injector.fail_dispatch(at=2, times=1)  # one mid-run failure
        elif args.inject_fault == "halt":
            injector.fail_dispatch(at=2, times=None)  # fail until HALTED
        elif args.inject_fault == "poison":
            injector.poison_readback(at=2, slot=0, token=-1)
        elif args.inject_fault == "prefill":
            injector.fail_prefill(at=1, times=1)
        elif args.inject_fault == "skew":
            # kick in shortly AFTER the first submissions so their
            # (unskewed) deadlines are already armed when the clock jumps
            import time as _time

            injector.skew_clock(by=3600.0, after=_time.monotonic() + 0.3)

    tp_comms = None
    if args.tp_comms_quantized:
        if args.tp <= 1:
            raise SystemExit("--tp-comms-quantized needs --tp > 1")
        from neuronx_distributed_tpu.parallel.quantized_collectives import (
            QuantizedAllReduceConfig,
        )

        tp_comms = QuantizedAllReduceConfig(enabled=True)
    shared = (
        rng.randint(1, cfg.vocab_size, size=args.shared_prefix).astype(np.int32)
        if args.shared_prefix > 0 else None
    )
    trace_path = args.trace or args.timeline
    timeline = Timeline(trace_path) if trace_path else None
    page, quant = _engine_layout(args)
    engine = ServingEngine(
        model, params,
        num_slots=args.slots,
        max_tokens_in_flight=args.max_tokens_in_flight,
        admission=args.admission,
        decode_chunk_size=args.decode_chunk,
        draft_model=draft_model,
        draft_params=draft_params,
        gamma=args.gamma,
        prefix_cache=None if args.no_prefix_cache else "auto",
        kv_page_size=page,
        kv_num_pages=args.kv_pages,
        kv_host_pages=args.kv_host_pages,
        quantize=quant,
        tp=args.tp if args.tp > 1 else None,
        tp_comms=tp_comms,
        paged_attention=args.paged_attention,
        fault_injector=injector,
        timeline=timeline,
    )
    aot_dir = None
    if args.prewarm or args.aot_cache:
        import time as _time

        from neuronx_distributed_tpu.inference import aot as aot_mod

        aot_dir = args.aot_cache or os.path.join(
            os.path.expanduser("~"), ".cache", "nxd-tpu-aot-demo"
        )
        manifest_there = os.path.exists(
            os.path.join(aot_dir, aot_mod.MANIFEST_NAME)
        )
        if args.prewarm and manifest_there:
            t0 = _time.perf_counter()
            rep = engine.prewarm(cache_dir=aot_dir)
            print(
                f"=== AOT prewarm from {aot_dir}: "
                f"{len(rep['deserialized'])} deserialized, "
                f"{len(rep['replayed'])} replayed "
                f"({len(rep['compiled'])} compiled), "
                f"{len(rep['skew'])} skew fallbacks, "
                f"{len(rep['skipped'])} skipped in "
                f"{_time.perf_counter() - t0:.2f}s ==="
            )
        else:
            aot_mod.enable_persistent_cache(
                os.path.join(aot_dir, aot_mod.XLA_SUBDIR)
            )
            if args.prewarm:
                print(
                    f"=== AOT prewarm: no manifest in {aot_dir} yet — "
                    "serving cold this run; the bundle is written at the "
                    "end, rerun --prewarm to start warm ==="
                )

    frontend = engine
    if args.disaggregate:
        from neuronx_distributed_tpu.serving import DisaggregatedServer

        frontend = DisaggregatedServer(
            engine, n_workers=args.prefill_workers
        )

    from neuronx_distributed_tpu.serving import RejectedError

    # staggered open-loop arrivals: a few upfront, the rest trickle in
    # while the engine is mid-flight (slots churn, decode program reused)
    rejected = 0

    def make_request(i):
        nonlocal rejected
        plen = int(rng.randint(3, 17))
        prompt = rng.randint(1, cfg.vocab_size, size=plen).astype(np.int32)
        if shared is not None:
            prompt = np.concatenate([shared, prompt])
        gcfg = GenerationConfig(
            max_new_tokens=int(rng.randint(4, args.max_new_tokens + 1)),
            temperature=float(rng.choice([0.0, 0.7, 1.0])),
            top_k=int(rng.choice([0, 10, 40])) or None,
            eos_token_id=None,
        )
        try:
            return frontend.submit(
                prompt, gcfg, key=jax.random.PRNGKey(100 + i),
                deadline_s=args.deadline,
                queue_timeout_s=args.queue_timeout,
            )
        except RejectedError as e:
            # backpressure/drain/halt is a demo-visible outcome, not a crash
            rejected += 1
            print(f"r{i} rejected: {e} (queue depth {e.queue_depth})")
            return None

    from neuronx_distributed_tpu.observability import profile_window

    upfront = min(args.slots, args.requests)
    reqs = [r for i in range(upfront) if (r := make_request(i)) is not None]
    i = upfront
    with profile_window(args.profile):
        while frontend.has_work or i < args.requests:
            frontend.step()
            if i < args.requests:
                req = make_request(i)
                if req is not None:
                    reqs.append(req)
                i += 1
            if not frontend.has_work and i >= args.requests:
                break
        frontend.run()

    prefix_desc = (
        "off" if args.no_prefix_cache
        else f"on (shared {args.shared_prefix} tokens)" if shared is not None
        else "on"
    )
    layout_desc = f"paged[{page}]" if page else "row"
    if quant is not None:
        layout_desc += (
            f", quantized weights={quant.weights}"
            + (", kv=int8" if quant.kv else "")
        )
    print(f"\n=== {len(reqs)} requests through {args.slots} slots "
          f"({args.admission} admission, decode chunk "
          f"{args.decode_chunk}, kv {layout_desc}, prefix cache "
          f"{prefix_desc}, fault={args.inject_fault}) ===")
    for req in reqs:
        r = engine.metrics.request_snapshot(req.rid)
        ttft = r.get("ttft")
        wait = r.get("queue_wait")
        ttft_s = f"{ttft * 1e3:7.1f}ms" if ttft is not None else "      - "
        wait_s = f"{wait * 1e3:6.1f}ms" if wait is not None else "     - "
        detail = (
            f"error={req.error!r}" if req.error
            else f"decode={r.get('decode_tokens_per_sec', 0.0):6.1f} tok/s "
                 f"tokens={req.tokens}"
        )
        print(
            f"r{req.rid:<2d} {req.state.value:<9s} "
            f"prompt={r['prompt_len']:>2d} new={len(req.tokens):>2d} "
            f"ttft={ttft_s} wait={wait_s} {detail}"
        )

    snap = engine.metrics.snapshot()
    # the device-efficiency blocks are nested tables — printed in their
    # own sections under --programs instead of the flat k:v dump below
    # (the program table prints from engine.programs.table() directly)
    snap.pop("programs", None)
    hbm_snap = snap.pop("hbm", {})
    snap["decode_compilations"] = engine.decode_compilations
    snap["rejected_submits"] = rejected
    if page:
        snap["kv_pages_usable"] = engine.cache.alloc.capacity
        snap["kv_pages_free"] = engine.cache.alloc.free_pages
        snap["kv_pages_quarantined"] = engine.cache.alloc.pages_quarantined
        snap["prefix_copy_bytes"] = engine.cache.alloc.copy_bytes  # always 0
        engine.cache.check()  # page-leak invariant on the way out
        if engine.tier is not None:
            snap["kv_host_pages_used"] = engine.tier.used_pages
            snap["kv_host_pages_max"] = engine.tier.max_pages
            engine.tier.check()  # host-tier invariant too
    if engine.halt_reason:
        snap["halt_reason"] = engine.halt_reason
    if injector is not None:
        snap["injected_faults"] = dict(injector.counters)
    if args.disaggregate:
        d = frontend.stats
        snap["disagg_handoffs"] = d["handoffs"]
        snap["disagg_prefills"] = d["prefills"]
        snap["disagg_coupled_fallbacks"] = d["coupled_fallbacks"]
        snap["disagg_copy_bytes"] = engine.cache.alloc.copy_bytes
    if args.tp > 1:
        snap["tp"] = args.tp
    if aot_dir is not None:
        save_rep = engine.save_aot(aot_dir)
        snap["aot_programs_saved"] = len(save_rep["saved"])
        print(f"\nAOT bundle written to {aot_dir} "
              f"({len(save_rep['saved'])} executables + manifest)")
    print(f"\n=== engine health: {engine.health().value} ===")
    print("=== metrics snapshot ===")
    for k, v in snap.items():
        print(f"  {k:>28s}: {v:.4f}" if isinstance(v, float) else
              f"  {k:>28s}: {v}")
    if args.programs:
        print("\n=== program ledger (compiler-reported cost) ===")
        print(engine.programs.table())
        print("\n=== hbm ledger ===")
        for name, entry in hbm_snap.get("residents", {}).items():
            unit = (
                f"  ({entry['count']} x {entry['unit_bytes']}B "
                f"{entry['unit']}s)" if "unit_bytes" in entry else ""
            )
            print(f"  {name:>16s}: {entry['bytes']:>12,d} B{unit}")
        print(f"  {'total':>16s}: "
              f"{hbm_snap.get('resident_bytes_total', 0):>12,d} B")
        print(f"  {'bytes_limit':>16s}: {hbm_snap.get('bytes_limit')}")
        print(f"  {'utilization':>16s}: {hbm_snap.get('utilization')}")
        plan = engine.hbm.plan()
        if plan["budget_bytes"] == "unavailable":
            # no device limit on this backend: show the 2x-residents plan
            # so the capacity math is still demonstrated
            plan = engine.hbm.plan(
                budget_bytes=2 * hbm_snap.get("resident_bytes_total", 0)
            )
            print("  plan (no device limit; 2x-residents budget):")
        else:
            print("  plan (device bytes_limit budget):")
        for name, fit in plan["fits"].items():
            print(f"    {name}: +{fit['additional']} {fit['unit']}s fit "
                  f"the remaining {plan['free_bytes']:,d} B")
    if args.prometheus:
        print("\n=== prometheus exposition ===")
        print(engine.metrics.registry.prometheus_text())
    if timeline is not None:
        timeline.save()
        print(f"\ntimeline written to {trace_path} "
              "(open in ui.perfetto.dev; request flows in the 'request' "
              "category)")
    if args.profile:
        print(f"profiler trace dir: {args.profile} (the serving loop: "
              "device ops and nxd.step* spans)")
    return snap


if __name__ == "__main__":
    main()
