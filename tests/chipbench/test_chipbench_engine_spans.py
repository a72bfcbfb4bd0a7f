"""The program's own host spans (``repro.engine.*``) leave the harness as
it was: on the recorded v5e trace with engine spans added, the context,
the breakdown and every per-layer reading stay the same; on a real
profiler trace of an engine, ``read_xplane`` keeps the harness's
``chipbench.*`` spans and drops the program's."""
from __future__ import annotations

import json
import pathlib
import types

import jax
import pytest

from chipbench import files, run
from chipbench import trace as TRC
from chipbench.peaks import peaks_for
from chipbench.serve import StepRecord

DATA = pathlib.Path(__file__).resolve().parent / "data"
HOST, PY = "/host:CPU", "python3"
MS = 1e6                                  # ns
READERS = [m["name"] for m in files.benchmark()["per_layer"]]


def host(name, start, end):
    return TRC.Event(HOST, PY, name, start * MS, (end - start) * MS)


def recorded():
    return [TRC.Event(**e) for e in
            json.loads((DATA / "trace_small.json").read_text())["events"]]


def with_engine_spans(events):
    """The recorded trace with a decode step's engine spans laid inside
    the harness's ``chipbench.step`` span, on its thread."""
    (h,) = TRC.spans(events)
    t = h.start_ns / MS
    return events + [
        host("repro.engine.step", t + 0.02, t + 87.42),
        host("repro.engine.step.pages", t + 0.03, t + 0.5),
        host("repro.engine.step.dispatch", t + 0.5, t + 4.1),
        host("repro.engine.step.sync", t + 4.1, t + 85.3),
        host("repro.engine.step.commit", t + 85.3, t + 87.4)]


def context(events):
    tracer = types.SimpleNamespace(lo=0.0, hi=float("inf"))
    recs = [StepRecord(0.0, 1.0, "decode", [2048 + 64 * i for i in range(12)],
                       None, 12)]
    numbers = {"submit_lags_s": [0.1, 0.2], "decode_tokens": [12],
               "itl_gaps_s": [0.08, 0.09], "compiles_in_window": 0}
    return run.trace_context(events, recs, tracer,
                             files.config("granite-8b")["model"],
                             peaks_for("TPU v5 lite"), numbers)


def test_engine_spans_leave_the_context_and_breakdown_as_they_were():
    old = recorded()
    new = with_engine_spans(old)
    assert TRC.spans(new) == TRC.spans(old)
    a, b = context(old), context(new)
    for k in ("ops", "modules", "spans", "lo_ns", "hi_ns", "window_s",
              "busy_s", "decode_keys", "prefill_groups"):
        assert getattr(a, k) == getattr(b, k), k
    assert TRC.top_ops(a.ops) == TRC.top_ops(b.ops)
    assert TRC.idle_gaps(a.ops, a.spans, a.lo_ns, a.hi_ns) == \
        TRC.idle_gaps(b.ops, b.spans, b.lo_ns, b.hi_ns)


@pytest.mark.parametrize("name", READERS)
def test_engine_spans_leave_each_reading_as_it_was(name):
    reader = files.metric_reader(name)
    old = recorded()
    assert reader.read(context(old)) == \
        reader.read(context(with_engine_spans(old)))


def test_recorded_trace_gives_the_device_readings():
    """The identity above is no comparison of two Nones."""
    ctx = context(recorded())
    for name in ("decode_step_ms", "paged_attention_roofline",
                 "device_idle_share"):
        assert files.metric_reader(name).read(ctx) is not None, name


def test_read_xplane_keeps_harness_spans_and_drops_the_engines(tmp_path):
    """A tiny engine stepped inside ``chipbench.step`` annotations, as the
    harness does, under the profiler: the raw trace holds the engine's
    ``repro.*`` spans, and ``read_xplane`` returns one ``chipbench.step``
    span per call and no ``repro.*`` event."""
    from repro.configs.smoke import smoke_config
    from repro.models.registry import build_model
    from repro.serve import Engine, Request, ServeConfig

    cfg = smoke_config("granite-8b", num_layers=1)
    model = build_model(cfg)
    eng = Engine(model, model.init(jax.random.PRNGKey(0)),
                 ServeConfig(slots=2, cache_len=32, max_new_tokens=4,
                             paged=True, page_size=4))
    eng.submit(Request(rid=0, tokens=[3, 5, 7, 11]))
    calls, busy = 0, True
    jax.profiler.start_trace(str(tmp_path))
    try:
        while busy or eng.queue or eng.requeue:
            with jax.profiler.TraceAnnotation("chipbench.step"):
                busy = eng.step()
            calls += 1
    finally:
        jax.profiler.stop_trace()
    raw = [e.name for path in tmp_path.glob("**/*.xplane.pb")
           for plane in jax.profiler.ProfileData.from_file(str(path)).planes
           for line in plane.lines for e in line.events]
    assert "repro.engine.step" in raw and "repro.engine.admit_group" in raw
    events = TRC.read_xplane(str(tmp_path))
    assert not [e for e in events if e.name.startswith("repro.")]
    assert [e.name for e in TRC.spans(events)] == ["chipbench.step"] * calls
