"""Serving launcher: continuous-batching engine (paged or slot cache).

CPU demo (reduced config):

  python -m repro.launch.serve --arch granite-8b --smoke \
      --prompts 6 --max-new 12 --paged

Fault-injection demo (the resilience plane, DESIGN.md §14):

  python -m repro.launch.serve --arch granite-8b --smoke --paged \
      --fault-rate 0.05 --watchdog-s 0.5

Telemetry (DESIGN.md §16): the summary JSON always includes per-request
TTFT / inter-token-latency / queue-wait and run-level p50/p99; add
``--trace-out trace.json`` for a Perfetto-viewable lifecycle trace and
``--metrics-out metrics.json`` for the raw registry snapshots.

Workload traces (DESIGN.md §17): replay a frozen JSONL trace on its
stepped arrival clock instead of pre-filling synthetic prompts:

  python -m repro.launch.serve --arch granite-8b --smoke --paged \
      --preempt-policy priority \
      --trace-file benchmarks/traces/bursty_smoke.jsonl

When requests carry priority/traffic classes (a trace, or synthetic
prompts tagged via ``--priority-class``), the summary JSON adds
``latency_by_class``: per-class p50/p99 for every latency metric.
"""
from __future__ import annotations

import argparse
import json
import time

import jax


def main():
    # the engine's tuple is the single source for policy choices (jax
    # is already imported at module scope, so this costs nothing extra)
    from repro.serve import PREEMPT_POLICIES, SPEC_MODES

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--prompts", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=64)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache + paged decode kernel")
    ap.add_argument("--page-size", type=int, default=None,
                    help="KV page size (default: autotuned winner)")
    ap.add_argument("--kv-dtype", default=None,
                    choices=["bf16", "int8", "fp8_e4m3"],
                    help="paged KV pool dtype; int8/fp8 quantize with "
                         "per-page-per-head scales and decode through "
                         "the fused-dequant kernel (requires --paged; "
                         "unsupported dtypes fall back per target)")
    ap.add_argument("--total-pages", type=int, default=None,
                    help="force the KV page pool size (default: "
                         "1 + slots * pages_per_slot, which never "
                         "oversubscribes); smaller values exercise the "
                         "preempt/requeue scheduler")
    ap.add_argument("--preempt-policy", default="lru",
                    choices=list(PREEMPT_POLICIES),
                    help="oversubscribed-pool policy: preempt the "
                         "least-recently-admitted slot, the one with "
                         "the fewest generated tokens, or fail fast "
                         "with the allocator error")
    ap.add_argument("--spec-mode", default="off",
                    choices=list(SPEC_MODES),
                    help="self-speculative decoding: 'ngram' drafts "
                         "--spec-k tokens per step from the sequence's "
                         "own history (prompt lookup, no draft model), "
                         "verifies them in one batched paged-decode "
                         "call, and rolls rejected tokens back by "
                         "truncating the block-table suffix (requires "
                         "--paged and greedy --temperature 0)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens per speculative step (>= 1)")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="inject faults (KV-page corruption, NaN logits, "
                         "allocation failure, stalled step) at this "
                         "per-step probability through serve/faults.py "
                         "(requires --paged); the engine detects and "
                         "recovers them — see the summary's recovery "
                         "counters")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the deterministic fault plan")
    ap.add_argument("--max-retries", type=int, default=3,
                    help="per-request fault-retry budget; past it the "
                         "request finishes with status='failed'")
    ap.add_argument("--watchdog-s", type=float, default=None,
                    help="per-step wall-clock deadline; a step past it "
                         "is discarded and its slots requeued (armed "
                         "after the first, compiling, step)")
    ap.add_argument("--trace-file", default=None, metavar="PATH",
                    help="replay a frozen workload trace (JSONL from "
                         "repro.serve.workload) instead of synthetic "
                         "prompts: each request is submitted when the "
                         "engine's step counter reaches its "
                         "arrival_step, and carries its own priority "
                         "class and per-request max_new decode budget "
                         "(capped by --max-new)")
    ap.add_argument("--priority-class", type=int, default=0,
                    help="priority class stamped on every synthetic "
                         "request (higher = more latency-sensitive; "
                         "pairs with --preempt-policy priority; "
                         "incompatible with --trace-file, which "
                         "carries per-request classes)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the per-request lifecycle trace as "
                         "Chrome trace-event JSON (open in Perfetto: "
                         "ui.perfetto.dev)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the engine + telemetry MetricsRegistry "
                         "snapshots (counters/gauges/histograms) as JSON")
    args = ap.parse_args()
    if args.kv_dtype and not args.paged:
        ap.error("--kv-dtype requires --paged")
    if args.total_pages is not None and not args.paged:
        ap.error("--total-pages requires --paged")
    if args.spec_mode != "off" and not args.paged:
        ap.error("--spec-mode requires --paged")
    if args.fault_rate and not args.paged:
        ap.error("--fault-rate requires --paged")
    if args.trace_file and args.priority_class:
        ap.error("--priority-class only applies to synthetic prompts; "
                 "a trace carries per-request classes")

    from repro.configs import get_config
    from repro.configs.smoke import smoke_config
    from repro.core import tuning
    from repro.core.context import current_context
    from repro.launch.compile_cache import place_compile_cache
    from repro.models.registry import build_model
    from repro.serve import Engine, FaultPlan, Request, ServeConfig, \
        ServeTelemetry

    # Pick up persisted per-arch tuning caches before any kernel traces:
    # block_*=None then resolves to autotuned winners, no re-tuning.
    # (No-op if repro.kernels already auto-loaded them at import.)
    tuning.load_caches()
    place_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    params = model.init_serving(jax.random.PRNGKey(0))
    sc = ServeConfig(slots=args.slots, cache_len=args.cache_len,
                     max_new_tokens=args.max_new,
                     temperature=args.temperature,
                     paged=args.paged, page_size=args.page_size,
                     kv_dtype=args.kv_dtype,
                     total_pages=args.total_pages,
                     preempt_policy=args.preempt_policy,
                     spec_mode=args.spec_mode, spec_k=args.spec_k,
                     max_retries=args.max_retries)
    plan = (FaultPlan(rate=args.fault_rate, seed=args.fault_seed)
            if args.fault_rate > 0 else None)
    # telemetry is always on in the launcher: the per-request latency
    # fields below come from it, and the obs-smoke gate bounds its
    # overhead at < 5% tok/s
    telemetry = ServeTelemetry()
    engine = Engine(model, params, sc, fault_plan=plan,
                    telemetry=telemetry)

    if args.trace_file:
        from repro.serve.workload import load_trace
        trace = load_trace(args.trace_file)
        reqs = trace.requests()
    else:
        trace = None
        import numpy as np
        rng = np.random.default_rng(0)
        reqs = [Request(rid=i, tokens=rng.integers(
            0, cfg.vocab_size, size=args.prompt_len).tolist(),
            priority_class=args.priority_class)
            for i in range(args.prompts)]
    t0 = time.perf_counter()
    submitted = 0
    if trace is None:
        for r in reqs:
            engine.submit(r)
        submitted = len(reqs)
    first = True
    while True:
        # trace replay submits on the engine's own step clock (the
        # workload.replay contract), so queue-wait/TTFT reflect real
        # arrival bursts instead of a pre-filled queue
        while trace is not None and submitted < len(reqs) and \
                trace.entries[submitted].arrival_step <= engine.step_count:
            engine.submit(reqs[submitted])
            submitted += 1
        busy = engine.step()
        if first:
            # arm the watchdog only after the first (compiling) step so
            # jit compile time cannot trip it spuriously
            engine.watchdog_s = args.watchdog_s
            first = False
        if submitted >= len(reqs) and not busy and not engine.queue \
                and not engine.requeue:
            break
    dt = time.perf_counter() - t0
    new_tokens = sum(len(r.out) for r in reqs)
    st = engine.stats()

    def _r(v, nd=5):
        return None if v is None else round(v, nd)

    # per-request latencies derived from the lifecycle trace (the
    # aggregate tok/s alone hid queueing and preemption stalls)
    per_request = [
        {"rid": row["rid"], "status": row["status"],
         "priority_class": row["priority_class"],
         "traffic_class": row["traffic_class"],
         "tokens": row["tokens"], "ttft_s": _r(row["ttft_s"]),
         "itl_p50_s": _r(row["itl_p50_s"]),
         "queue_wait_s": _r(row["queue_wait_s"]),
         "preempt_stall_s": _r(row["preempt_stall_s"]),
         "recovery_s": _r(row["recovery_s"])}
        for row in telemetry.request_metrics()]
    lat = telemetry.summary()
    latency = {m: ({"p50": _r(v["p50"]), "p99": _r(v["p99"]),
                    "count": v["count"]} if v else None)
               for m, v in lat.items() if m != "requests"}
    # run-level percentiles hide per-class SLO behavior: a batch-heavy
    # tail swamps the chat p99.  When requests carry classes (a trace,
    # or --priority-class != 0), group the percentiles by class too.
    by_class = telemetry.summary_by_class()
    latency_by_class = {
        label: {
            "priority_class": blk["priority_class"],
            "requests": blk["requests"],
            "completed": blk["completed"],
            "completion_rate": _r(blk["completion_rate"]),
            "preempts": blk["preempts"],
            **{m: ({"p50": _r(v["p50"]), "p99": _r(v["p99"]),
                    "count": v["count"]} if v else None)
               for m, v in blk.items()
               if m not in ("priority_class", "requests", "completed",
                            "completion_rate", "preempts")},
        }
        for label, blk in by_class.items()}
    classes_present = (len(latency_by_class) > 1
                       or any(label != "0" for label in latency_by_class))

    if args.trace_out:
        telemetry.trace.export(args.trace_out)
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump({"engine": engine.metrics.snapshot(),
                       "telemetry": telemetry.registry.snapshot()},
                      f, indent=1, sort_keys=True)
            f.write("\n")

    dev = jax.devices()[0]
    print(json.dumps({
        "arch": args.arch, "paged": args.paged,
        "device": {"platform": dev.platform, "device_kind": dev.device_kind,
                   "count": len(jax.devices())},
        "target_arch": current_context().arch,
        "kv_dtype": (engine.kv_spec.dtype if getattr(engine, "kv_spec", None)
                     else None),
        "requests": len(reqs),
        "all_done": all(r.done for r in reqs),
        "statuses": {s: sum(r.status == s for r in reqs)
                     for s in ("done", "failed", "pending")},
        "new_tokens": new_tokens, "wall_s": round(dt, 2),
        "tok_per_s": round(new_tokens / dt, 1),
        "preemptions": st["preemptions"],
        "preemptions_by_policy": st["preemptions_by_policy"],
        "requeue_depth": st["requeue_depth"],
        "requeue_peak_depth": st["requeue_peak_depth"],
        "recoveries": st["recoveries"],
        "failed_requests": st["failed_requests"],
        "watchdog_trips": st["watchdog_trips"],
        "last_watchdog_trip": st["last_watchdog_trip"],
        "last_recovery": st["last_recovery"],
        "latency": latency,
        **({"latency_by_class": latency_by_class}
           if classes_present else {}),
        "per_request": per_request,
        **({"quarantined_pages": st["quarantined"],
            "pool_groups": st["pool_groups"]} if args.paged else {}),
        **({"window_prefix_frees": st["window_prefix_frees"]}
           if args.paged and engine.windowed else {}),
        **({"faults_injected": st["faults_injected"]}
           if plan is not None else {}),
        **({"accepted_tokens_per_step":
            round(engine.spec_emitted / max(engine.spec_steps, 1), 2),
            "spec_rejections": engine.spec_rejections}
           if engine.spec else {}),
        "sample_output": reqs[0].out,
    }, indent=1))


if __name__ == "__main__":
    main()
