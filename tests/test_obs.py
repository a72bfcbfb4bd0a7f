"""Observability plane (repro.obs + serve/telemetry.py, DESIGN.md §16):
metrics primitives against the numpy reference, trace schema/lifecycle
validation, the zero-extra-sync regression (telemetry must not change
the engine's one-device_get-per-step contract, plain or speculative),
the (step, wall-time) watchdog/recovery records in stats(), the
engine's host spans as a profiler records them, and the engine's
compile counters."""
import json

import jax
import numpy as np
import pytest

from repro.configs.smoke import smoke_config
from repro.models.registry import build_model
from repro.obs.metrics import Histogram, MetricsRegistry
from repro.obs.trace import EVENT_KINDS, Trace
from repro.serve import (Engine, FaultPlan, Request, ServeConfig,
                         ServeTelemetry)
from repro.serve import engine as engine_mod

_STATE = {}


def _model():
    if "model" not in _STATE:
        cfg = smoke_config("granite-8b", num_layers=1)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        _STATE["model"] = (model, params, cfg)
    return _STATE["model"]


def _engine(telemetry=None, plan=None, **kw):
    model, params, cfg = _model()
    base = dict(slots=2, cache_len=32, max_new_tokens=4, paged=True,
                page_size=4)
    base.update(kw)
    return Engine(model, params, ServeConfig(**base), fault_plan=plan,
                  telemetry=telemetry)


def _reqs(n=4):
    return [Request(rid=i, tokens=[3 + i, 5, 7, 11][:3 + (i % 2)])
            for i in range(n)]


def _drive(eng, reqs, watchdog_s=None, max_steps=500):
    for r in reqs:
        eng.submit(r)
    for i in range(max_steps):
        busy = eng.step()
        if i == 0:
            eng.watchdog_s = watchdog_s
        if not busy and not eng.queue and not eng.requeue:
            return reqs
    raise AssertionError(f"engine did not drain: {eng.stats()}")


# ------------------------------------------------------- histograms ----

def test_histogram_percentiles_within_bucket_factor():
    """Bucketed percentile estimates land within one geometric bucket
    factor of the exact numpy sample percentile (metrics.py's
    documented accuracy contract)."""
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=-4.0, sigma=1.5, size=2000)
    h = Histogram("t", lo=1e-5, hi=1e3, factor=1.25)
    for v in samples:
        h.observe(float(v))
    for q in (50, 90, 99):
        exact = float(np.percentile(samples, q))
        est = h.percentile(q)
        assert exact / h.factor <= est <= exact * h.factor, \
            (q, est, exact)


def test_histogram_exact_moments_ride_alongside():
    h = Histogram("t", lo=1e-3, hi=1e2)
    vals = [0.5, 0.002, 7.0, 0.1]
    for v in vals:
        h.observe(v)
    assert h.count == len(vals)
    assert h.sum == pytest.approx(sum(vals))
    assert h.min == min(vals) and h.max == max(vals)
    assert h.mean == pytest.approx(sum(vals) / len(vals))


def test_histogram_underflow_overflow_return_tracked_extremes():
    h = Histogram("t", lo=1e-2, hi=1.0)
    h.observe(1e-6)   # underflow bucket
    h.observe(50.0)   # overflow bucket
    assert h.percentile(1) == 1e-6
    assert h.percentile(100) == 50.0
    assert sum(h.counts) == h.count == 2
    assert h.percentile(50) is not None
    assert Histogram("empty").percentile(50) is None


def test_registry_get_or_create_and_type_conflicts():
    reg = MetricsRegistry()
    c = reg.counter("serve.steps")
    assert reg.counter("serve.steps") is c
    c.inc(3)
    with pytest.raises(ValueError, match="monotonic"):
        c.inc(-1)
    g = reg.gauge("pool.pages")
    g.set_max(4.0)
    g.set_max(2.0)
    assert g.value == 4.0
    with pytest.raises(TypeError, match="already registered"):
        reg.histogram("serve.steps")
    # snapshot is JSON-serializable as-is (launch --metrics-out path)
    json.dumps(reg.snapshot())


# ------------------------------------------------------------ trace ----

def _fake_clock():
    t = [0.0]

    def clock():
        t[0] += 0.001
        return t[0]

    return clock


def _record_lifecycle(tr, rid, slot=0):
    tr.record("submitted", rid=rid)
    tr.record("admitted", rid=rid, slot=slot, step=1)
    tr.record("first_token", rid=rid, slot=slot, step=1)
    tr.record("tokens", rid=rid, slot=slot, step=2, n=1)
    tr.record("finished", rid=rid, slot=slot, step=3)


def test_trace_valid_lifecycle_passes_validation():
    tr = Trace(capacity=64, clock=_fake_clock())
    _record_lifecycle(tr, rid=0)
    tr.record("step", step=3, emitted=1)
    assert tr.validate() == []
    assert [e.kind for e in tr.lifecycle(0)] == \
        ["submitted", "admitted", "first_token", "tokens", "finished"]


def test_trace_rejects_unknown_kind():
    tr = Trace(capacity=4)
    with pytest.raises(ValueError, match="unknown trace event kind"):
        tr.record("teleported", rid=0)


def test_trace_validation_catches_lifecycle_violations():
    tr = Trace(capacity=64, clock=_fake_clock())
    tr.record("submitted", rid=0)
    tr.record("admitted", rid=0, slot=0, step=1)
    tr.record("finished", rid=0, slot=0, step=2)  # no first_token
    problems = tr.validate()
    assert any("without 'first_token'" in p for p in problems), problems

    tr2 = Trace(capacity=64, clock=_fake_clock())
    _record_lifecycle(tr2, rid=1)
    tr2.record("tokens", rid=1, slot=0, step=4, n=1)  # after terminal
    assert any("after terminal" in p for p in tr2.validate())


def test_trace_ring_is_bounded_and_counts_drops():
    tr = Trace(capacity=4, clock=_fake_clock())
    _record_lifecycle(tr, rid=0)  # 5 events into a 4-ring
    assert len(tr) == 4
    assert tr.dropped == 1
    # head fell off the ring: validate() must not flag the truncated
    # lifecycle as malformed
    assert tr.validate() == []


def test_trace_export_schema(tmp_path):
    tr = Trace(capacity=64, clock=_fake_clock())
    _record_lifecycle(tr, rid=0)
    tr.record("step", step=3, emitted=1,
              pools={"global": {"in_use": 2, "quarantined": 0}})
    p = tmp_path / "trace.json"
    doc = tr.export(str(p))
    with open(p) as f:
        assert json.load(f) == doc
    evs = doc["traceEvents"]
    phases = {e["ph"] for e in evs}
    assert {"M", "i", "X", "C"} <= phases  # metadata, instants,
    # residency spans, counter series
    for e in evs:
        assert {"ph", "pid", "tid"} <= set(e)
        if e["ph"] != "M":
            assert "ts" in e
    spans = [e for e in evs if e["ph"] == "X"]
    assert spans and all(e["dur"] > 0 for e in spans)
    assert doc["otherData"]["recorded_events"] == len(tr)


# ---------------------------------------- zero-extra-sync regression ----

@pytest.mark.parametrize("spec_mode", ["off", "ngram"])
def test_telemetry_adds_no_device_syncs(monkeypatch, spec_mode):
    """The one-device_get-per-step contract with telemetry attached:
    same call count AND token-identical outputs as a bare engine, on
    both the plain and the batched-speculative step paths."""
    results = {}
    for with_tel in (False, True):
        calls = [0]
        real = engine_mod._device_get

        def counting(x, _real=real, _calls=calls):
            _calls[0] += 1
            return _real(x)

        monkeypatch.setattr(engine_mod, "_device_get", counting)
        tel = ServeTelemetry() if with_tel else None
        eng = _engine(telemetry=tel, spec_mode=spec_mode, spec_k=3)
        reqs = _drive(eng, _reqs())
        monkeypatch.setattr(engine_mod, "_device_get", real)
        assert all(r.done for r in reqs)
        results[with_tel] = (calls[0], [r.out for r in reqs])
    assert results[True][0] == results[False][0], \
        f"telemetry changed device_get count: {results}"
    assert results[True][1] == results[False][1]


# ------------------------------------------- derived latency metrics ----

def test_telemetry_derives_request_latencies_and_summary():
    tel = ServeTelemetry()
    reqs = _drive(_engine(telemetry=tel), _reqs(5))  # 5 reqs, 2 slots:
    assert all(r.done for r in reqs)                 # some must queue
    rows = tel.request_metrics()
    assert len(rows) == 5
    for r in rows:
        assert r["status"] == "finished"
        assert r["ttft_s"] > 0 and r["queue_wait_s"] >= 0
        assert r["e2e_s"] >= r["ttft_s"]
        assert r["itl_p50_s"] is not None and r["tokens"] == 4
    # summary percentiles are numpy-exact over the per-request samples
    s = tel.summary(qs=(50, 99))
    assert s["requests"] == 5
    ttft = tel.samples("ttft_s")
    assert s["ttft_s"]["p50"] == pytest.approx(
        float(np.percentile(ttft, 50)))
    assert s["ttft_s"]["p99"] == pytest.approx(
        float(np.percentile(ttft, 99)))
    assert s["ttft_s"]["count"] == 5
    with pytest.raises(ValueError, match="unknown latency metric"):
        tel.samples("nope")
    # the registry's bucketed twin saw the same observations
    assert tel.registry.histogram("serve.ttft_s").count == 5
    assert tel.trace.validate() == []


# ----------------------------- watchdog / recovery (step, wall-time) ----

def test_stats_exposes_last_watchdog_trip_and_recovery_records():
    """Satellite regression: trips and recoveries carry (step,
    wall-time) records in stats(), not just counts."""
    eng = _engine()
    st = eng.stats()
    assert st["last_watchdog_trip"] is None
    assert st["last_recovery"] is None

    tel = ServeTelemetry()
    eng = _engine(telemetry=tel, max_new_tokens=8, max_retries=6,
                  retry_backoff=1,
                  plan=FaultPlan(stall_s=0.5).at(4, "stall"))
    reqs = _drive(eng, _reqs(), watchdog_s=0.25)
    assert all(r.done for r in reqs)
    st = eng.stats()
    assert st["watchdog_trips"] == 1
    trip = st["last_watchdog_trip"]
    assert set(trip) == {"step", "wall_time_s"}
    assert trip["step"] >= 1 and trip["wall_time_s"] > 0
    rec = st["last_recovery"]
    assert set(rec) == {"step", "kind", "wall_time_s"}
    assert rec["kind"] == "stall"
    assert rec["wall_time_s"] >= trip["wall_time_s"]
    # and the lifecycle trace saw the same events
    kinds = {e.kind for e in tel.trace.events}
    assert {"watchdog_trip", "requeued"} <= kinds
    assert tel.registry.counter("serve.watchdog_trips").value == 1


def test_fault_plan_keeps_injection_log():
    plan = FaultPlan().at(2, "kv_corrupt")
    eng = _engine(plan=plan, max_new_tokens=8, max_retries=6,
                  retry_backoff=1)
    reqs = _drive(eng, _reqs())
    assert all(r.done for r in reqs)
    assert any(kind == "kv_corrupt" and step == 2
               for step, kind, _slot in plan.injection_log)


# ------------------------------------------------ engine host spans ----

_STEP_SPANS = {"repro.engine.step", "repro.engine.step.pages",
               "repro.engine.step.dispatch", "repro.engine.step.sync",
               "repro.engine.step.commit"}
_ADMIT_SPANS = {"repro.engine.admit", "repro.engine.admit_group",
                "repro.engine.admit.prefill", "repro.engine.admit.sync",
                "repro.engine.admit.scatter"}


def _traced_spans(tmp_path, run):
    """Run ``run()`` under the profiler and return the ``repro.*`` host
    spans of its trace as (name, start_ns, end_ns, line, args)."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    out = []
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, line.name,
                                dict(e.stats)))
    return out


def _inside(child, parents):
    return any(p[3] == child[3] and p[1] <= child[1] and child[2] <= p[2]
               for p in parents)


@pytest.mark.parametrize("case", ["plain", "spec", "preempt"])
def test_engine_spans_nest_and_count(tmp_path, monkeypatch, case):
    """Under the profiler every engine phase the run reaches shows as a
    ``repro.*`` host span: one ``engine.step`` per ``step()`` call, one
    ``engine.admit_group`` per prompt-length group (with its k and plen),
    each ``*.sync`` inside its parent, every step phase inside a step."""
    kw = {"plain": dict(slots=4),
          "spec": dict(slots=4, spec_mode="ngram", spec_k=2),
          "preempt": dict(slots=2, page_size=8, total_pages=5,
                          max_new_tokens=24)}[case]
    eng = _engine(**kw)
    reqs = ([Request(rid=i, tokens=[1 + i] * 6) for i in range(4)]
            if case == "preempt" else
            [Request(rid=0, tokens=[3, 5, 7, 11, 13]),
             Request(rid=1, tokens=[2, 4, 6, 8, 10]),
             Request(rid=2, tokens=[9, 8, 7, 6, 5, 4, 3])])
    groups = []
    admit_group = Engine._admit_group

    def spy(self, reqs, plen):
        groups.append({"k": len(reqs), "plen": plen})
        return admit_group(self, reqs, plen)
    monkeypatch.setattr(Engine, "_admit_group", spy)
    calls = []

    def run():
        for r in reqs:
            eng.submit(r)
        while True:
            calls.append(eng.step())
            if not calls[-1] and not eng.queue and not eng.requeue:
                break
    spans = _traced_spans(tmp_path, run)
    assert all(r.done for r in reqs)
    names = {s[0] for s in spans}
    want = _STEP_SPANS | _ADMIT_SPANS
    if case == "preempt":
        assert eng.preemptions > 0
        want = want | {"repro.engine.preempt"}
    assert names == want

    steps = [s for s in spans if s[0] == "repro.engine.step"]
    assert len(steps) == len(calls)
    assert [s[4].get("batch", 0) > 0 for s in
            sorted(steps, key=lambda s: s[1])] == calls
    grp = [s for s in spans if s[0] == "repro.engine.admit_group"]
    assert [s[4] for s in sorted(grp, key=lambda s: s[1])] == groups
    if case == "plain":
        assert groups == [{"k": 2, "plen": 5}, {"k": 1, "plen": 7}]
    for s in spans:
        if s[0] == "repro.engine.step.sync":
            assert _inside(s, steps)
        elif s[0] == "repro.engine.admit.sync":
            assert _inside(s, grp)
        if s[0] in _STEP_SPANS - {"repro.engine.step"}:
            assert _inside(s, steps)
    # the engine's programs compile on their first call only
    disp = sorted((s for s in spans if s[0] == "repro.engine.step.dispatch"),
                  key=lambda s: s[1])
    assert disp[0][4] == {"compiled": 1}
    assert all(s[4] == {} for s in disp[1:])


def test_compile_counter_counts_new_prompt_lengths_once():
    """``serve.compiles.prefill`` rises by one for a new prompt length
    and not for a repeated one; the step compiles once."""
    eng = _engine(slots=2)
    c = eng.metrics.counter

    def serve(plen, rid):
        _drive(eng, [Request(rid=rid, tokens=[5] * plen)])
        return {k: c(f"serve.compiles.{k}").value
                for k in ("prefill", "admit", "step", "spec")}

    assert serve(5, 0) == {"prefill": 1, "admit": 1, "step": 1, "spec": 0}
    assert serve(5, 1) == {"prefill": 1, "admit": 1, "step": 1, "spec": 0}
    assert serve(7, 2) == {"prefill": 2, "admit": 1, "step": 1, "spec": 0}
    snap = eng.metrics.snapshot()["counters"]
    assert snap["serve.compiles.prefill"] == 2
