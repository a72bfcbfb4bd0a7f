"""Run one cell of the chip benchmark and print its result as the last
line of standard output.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell names a configuration (``configs/<config>.json``) and a traffic
mix (``traffic/<traffic>.json``) in ``BENCHMARK.json``.  Set-up makes the
weights from the seed, builds the program's paged ``Engine`` and warms
every program the window will run; the window then serves the mix on
the wall clock for ``--seconds``.  With ``--trace 0`` the result holds
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics,
read from a profiler trace of the window's last few seconds.  Then the
program's state is freed and a sample of what it served is compared
with the plain reference (``check.py``), which decides ``correct``.

Off a TPU, with fewer chips than the cell asks for, or on a chip that is
not in the peaks table, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_IMPORT = time.perf_counter()

import argparse                      # noqa: E402
import dataclasses                   # noqa: E402
import gc                            # noqa: E402
import json                          # noqa: E402
import os                            # noqa: E402
import shutil                        # noqa: E402
import sys                           # noqa: E402
import types                         # noqa: E402
from typing import Any, Dict, List   # noqa: E402

from chipbench import files, stats   # noqa: E402

SRC = files.ROOT / "src"
TRACE_DIR = files.ROOT / ".chipbench" / "trace"


class NoChip(SystemExit):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def process_age() -> float:
    """Seconds since this process started (the kernel's start time)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - T_IMPORT


def place_compile_cache() -> str:
    """The program's persistent cache (``launch/compile_cache``), made to
    keep every program, the short compiles too."""
    import jax
    from repro.launch.compile_cache import place_compile_cache as place
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return place()


def check_device(chips: int, require_tpu: bool):
    import jax
    from chipbench.peaks import UnknownDevice, peaks_for
    devs = jax.devices()
    dev = devs[0]
    log(f"device: platform {dev.platform}, kind {dev.device_kind!r}, "
        f"count {len(devs)}")
    if not require_tpu:
        return dev, len(devs), None
    if dev.platform != "tpu":
        raise NoChip(f"chipbench: no TPU (platform {dev.platform!r}); "
                     f"this benchmark measures the chip only")
    if len(devs) < chips:
        raise NoChip(f"chipbench: the cell asks for {chips} chips, JAX "
                     f"finds {len(devs)}")
    try:
        return dev, len(devs), peaks_for(dev.device_kind)
    except UnknownDevice as e:
        raise NoChip(f"chipbench: {e}") from None


def program_config(spec: Dict[str, Any]):
    """The program's config for this configuration file, checked against
    every number the file states."""
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config(spec["repo_config"]),
                              **spec.get("overrides", {}))
    for k, v in spec["model"].items():
        if hasattr(cfg, k) and getattr(cfg, k) != v:
            raise ValueError(f"{spec['name']}: the program runs {k}="
                             f"{getattr(cfg, k)!r}, the file states {v!r}")
    return cfg


def serve_config(spec: Dict[str, Any], traffic: Dict[str, Any], seed: int):
    from repro.serve import ServeConfig
    s = traffic["serve"]
    if traffic["kind"] == "sessions":
        ses = traffic["sessions"]
        max_new = max(int(n) for n in ses["max_new"] + ses["first_max_new"])
    else:
        max_new = int(traffic["output"]["hi"])
    return ServeConfig(slots=int(s["slots"]), cache_len=int(s["cache_len"]),
                       total_pages=s.get("total_pages"),
                       page_size=s.get("page_size"),
                       max_new_tokens=max_new, paged=True,
                       seed=int(seed) % (2 ** 31))


class Tracer:
    """Traces the last ``seconds`` of the window: the profiler starts
    between two engine calls that long before the window closes, and
    stops once it has closed.  Stopping takes about as long as the trace
    ran (the device's events are collected then); inside the window that
    pause would read as queueing to every host-clock number."""

    def __init__(self, on: bool, window_s: float, seconds: float):
        self.on, self.start_s = on, max(0.0, window_s - seconds)
        self.lo = self.hi = None
        self.pauses_ms: Dict[str, float] = {}

    def tick(self, t: float) -> None:
        import jax
        if not self.on or self.lo is not None or t < self.start_s:
            return
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        TRACE_DIR.mkdir(parents=True)
        t_ask = time.perf_counter()
        jax.profiler.start_trace(str(TRACE_DIR))
        self.lo = time.perf_counter()
        self.pauses_ms["start"] = 1e3 * (self.lo - t_ask)

    def stop(self) -> None:
        import jax
        if self.on and self.lo is not None and self.hi is None:
            self.hi = time.perf_counter()
            jax.profiler.stop_trace()
            self.pauses_ms["stop"] = 1e3 * (time.perf_counter() - self.hi)


def per_layer(cell_name: str, bench, ctx, base=files.HERE
              ) -> Dict[str, Dict[str, Any]]:
    """Each per-layer metric of the cell, read by ``metrics/<name>.py``;
    a reader that finds nothing to read leaves its metric out."""
    out = {}
    for entry in files.cell_metrics(cell_name, "per_layer", bench):
        mod = files.metric_reader(entry["name"], base)
        v = mod.read(ctx)
        if v is not None:
            out[entry["name"]] = {"value": v, "unit": entry["unit"]}
    return out


def run_cell(cell_name: str, cell: Dict[str, Any], spec: Dict[str, Any],
             traffic: Dict[str, Any], seed: int, seconds: float, trace: bool,
             bench: Dict[str, Any], *, require_tpu: bool = True,
             control: bool = False) -> Dict[str, Any]:
    """One run of a cell; returns the result line's object.  With
    ``control`` the fp8 control takes the program's place in the
    comparison, and ``correct`` is the control's (``control.py``); the
    benchmark's own runs never do that."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    import jax
    import jax.numpy as jnp
    from chipbench import check, weights
    from chipbench import traffic as TR
    from chipbench.serve import CompileCounter, Driver, GcClock

    dev, n_dev, peaks = check_device(int(cell["chips"]), require_tpu)
    log(f"compile cache: {place_compile_cache()}")
    from repro.models.registry import build_model
    cfg = program_config(spec)
    m = spec["model"]
    model = build_model(cfg)
    weights.check_layout(m, model.init_abstract())
    counter = CompileCounter()
    gc_clock = GcClock()

    flat = weights.make(m, seed, jnp.dtype(cfg.dtype))
    jax.block_until_ready(flat)
    sc = serve_config(spec, traffic, seed)
    drv = Driver(model, weights.to_program(flat), sc, m, traffic)
    plans = TR.plan(traffic, seed, seconds, m["vocab_size"])
    if traffic["kind"] == "sessions":
        drv.admit_sessions(plans)
    else:
        drv.warm(seed)
    lead_s = float(traffic.get("lead_s", 0.0))

    tr = traffic.get("trace", {})
    tracer = Tracer(trace, seconds, float(tr.get("seconds", 4.0)))

    def tick(t: float) -> None:       # t: seconds since the window opened
        if t >= 0:
            counter.armed = gc_clock.armed = True
        tracer.tick(t)

    if traffic["kind"] == "sessions":
        setup_s = process_age()
        t0 = drv.run_sessions(plans, seconds, seed, tick)
    else:
        # the lead-in traffic fills the batch before the window opens
        setup_s = process_age() + lead_s
        t0 = drv.run_open_loop(plans, seconds, lead_s, tick)
    log(f"set-up: {setup_s!r} s (lead-in {lead_s} s; pool "
        f"{drv.eng.allocator.total_pages} pages of {drv.ps}, {sc.slots} "
        f"slots, cache_len {sc.cache_len})")
    counter.close()
    gc_clock.close()
    tracer.stop()
    t1 = t0 + seconds
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")

    seen = list(drv.seen.values())
    e2e = stats.end_to_end(seen, t0, t1)
    cnt = stats.counts(seen, t0, t1)
    if traffic["kind"] == "sessions":     # every session served counts
        cnt["attempted"] = len(seen)
        cnt["failed"] = sum(r.failed for r in seen)
    host = window_host_numbers(drv, t0, t1, counter.count, gc_clock.pauses)
    log(f"window: {json.dumps(e2e)} {json.dumps(cnt)} "
        f"{json.dumps(host['calls'])} compiles_in_window {counter.count}")
    result_window = {"e2e": e2e, "counts": cnt, "calls": host["calls"]}
    if trace:
        log(f"profiler pauses: {json.dumps(tracer.pauses_ms)} ms")
        result_window["profiler_ms"] = tracer.pauses_ms

    served = sorted(drv.requests.values(), key=lambda r: r.rid)
    finished = [r for r in served if r.status == "done"]
    short = sum(1 for r in finished if len(r.out) != r.max_new)
    to_check = finished if traffic["kind"] == "open_loop" else served
    records = drv.records
    # free the program's state before the reference runs
    drv.eng = None
    del drv
    gc.collect()

    numbers: Dict[str, float] = {"failed_requests": cnt["failed"],
                                 "short_requests": short}
    limits: Dict[str, float] = {"failed_requests": 0, "short_requests": 0,
                                "widest_gap": spec["check"]["widest_gap"]}
    picked = check.sample(to_check, int(traffic["check"]["requests"]), seed)
    numbers.update(check.gaps(flat, m, picked, control=control))
    numbers["requests_compared"] = len(picked)
    correct = check.verdict(numbers, limits) and bool(picked)

    result: Dict[str, Any] = {
        "correct": correct, "attempted": cnt["attempted"],
        "failed": cnt["failed"],
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": n_dev, "memory_peak_bytes": mem}}
    if trace:
        from chipbench import trace as TRC
        events = TRC.read_xplane(str(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx = trace_context(events, records, tracer, m, peaks, host)
        result["metrics"] = per_layer(cell_name, bench, ctx)
        result["device"]["busy_s"] = ctx.busy_s
        result["device"]["window_s"] = ctx.window_s
        result["breakdown"] = {
            "device_ops": TRC.top_ops(ctx.ops),
            "idle_gaps": TRC.idle_gaps(ctx.ops, ctx.spans, ctx.lo_ns,
                                       ctx.hi_ns)}
    else:
        e2e["setup_s"] = setup_s
        result["metrics"] = {
            e["name"]: {"value": e2e[e["name"]], "unit": e["unit"]}
            for e in files.cell_metrics(cell_name, "end_to_end", bench)
            if e["name"] in e2e}
    checks = {k: {"value": numbers.get(k), "limit": v}
              for k, v in limits.items()}
    for k in ("tokens_compared", "program_widest_gap"):
        if k in numbers:
            checks[k] = {"value": numbers[k], "limit": None}
    result["window"] = result_window
    result["checks"] = checks
    log(f"checks: {json.dumps(checks)}")
    log(f"correct: {correct} ({check.describe(numbers, limits)})")
    return result


def window_host_numbers(drv, t0: float, t1: float, compiles: int,
                        gc_pauses: List[float]) -> Dict[str, Any]:
    # a request due in the window and still queued at its close counts
    # at its wait so far, as its TTFT does
    lags = [(t1 if s.submitted is None else s.submitted) - s.due
            for s in drv.seen.values() if t0 <= s.due < t1]
    recs = [r for r in drv.records if t0 <= r.t_start and r.t_end <= t1]
    dec = [r.tokens for r in recs if r.kind == "decode"]
    idle = [b.t_start - a.t_end for a, b in zip(recs, recs[1:])]
    return {"submit_lags_s": lags, "decode_tokens": dec,
            "itl_gaps_s": stats.gaps_in_window(list(drv.seen.values()),
                                               t0, t1),
            "compiles_in_window": compiles,
            "calls": {"decode_steps": len(dec),
                      "admissions": len(recs) - len(dec),
                      "max_decode_ms": 1e3 * max(
                          (r.t_end - r.t_start for r in recs
                           if r.kind == "decode"), default=0.0),
                      "max_between_calls_ms": 1e3 * max(idle, default=0.0),
                      "submit_lag_p95_ms": 1e3 * stats.percentile(lags, 95)
                      if lags else None,
                      "gc_pauses": len(gc_pauses),
                      "max_gc_ms": 1e3 * max(gc_pauses, default=0.0)}}


def trace_context(events, records, tracer, m, peaks, host):
    from chipbench import trace as TRC
    planes = TRC.device_planes(events)
    plane = planes[0] if planes else None
    ops = TRC.ops(events, plane)
    spans = TRC.spans(events)
    evs = ops + spans
    lo = min(e.start_ns for e in evs) if evs else 0.0
    hi = max(e.end_ns for e in evs) if evs else 0.0
    inside = [r for r in records
              if tracer.lo <= r.t_start and r.t_end <= tracer.hi]
    return types.SimpleNamespace(
        events=events, ops=ops, modules=TRC.modules(events, plane),
        spans=spans, lo_ns=lo, hi_ns=hi, window_s=(hi - lo) / 1e9,
        busy_s=TRC.busy_s_mean(events, lo, hi), model=m, peaks=peaks,
        decode_keys=[r.keys for r in inside if r.kind == "decode"],
        prefill_groups=[r.group for r in inside if r.kind == "admit"],
        host=host)


def main(argv: List[str] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = files.benchmark()
    cell = files.cell(args.workload, bench)
    result = run_cell(args.workload, cell, files.config(cell["config"]),
                      files.traffic(cell["traffic"]), args.seed, args.seconds,
                      bool(args.trace), bench)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
