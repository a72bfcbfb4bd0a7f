"""The reduction from profiler events to busy and idle time, per-kernel
sums, self time and idle gaps named by the harness's host spans: on
hand-made events whose answers are known, and on a small trace recorded
on a TPU v5e (``data/trace_small.json``), checked against a brute-force
count of busy microseconds."""
from __future__ import annotations

import json
import pathlib

import numpy as np
import pytest

from chipbench import trace as TRC

DATA = pathlib.Path(__file__).resolve().parent / "data"
DEV = "/device:TPU:0"
PAGED = "portable_paged_decode_attention"


def ev(line, name, start, dur, plane=DEV):
    return TRC.Event(plane, line, name, float(start), float(dur))


def test_union_busy_and_gaps_by_hand():
    ops = [ev(TRC.OPS_LINE, "%fusion.1 = bf16[8] fusion(%x)", 0, 10),
           ev(TRC.OPS_LINE, "%fusion.2 = bf16[8] fusion(%fusion.1)", 5, 10),
           ev(TRC.OPS_LINE, f"%{PAGED}.9 = (f32[8]) custom-call()", 30, 5),
           ev(TRC.OPS_LINE, f"%copy.3 = bf16[8] copy(%{PAGED}.9)", 40, 2),
           ev(TRC.OPS_LINE, f"%{PAGED}.9 = (f32[8]) custom-call()", 60, 5)]
    spans = [ev("python", "chipbench.step", 14, 18, plane="/host:CPU"),
             ev("python", "chipbench.admit", 36, 30, plane="/host:CPU")]
    assert TRC.union([(0, 10), (5, 15), (30, 35)]) == [(0, 15), (30, 35)]
    assert TRC.busy_ns(ops, 0, 100) == 27
    assert TRC.busy_ns(ops, 8, 32) == 9                 # clipped
    assert TRC.busy_s_mean(ops, 0, 100) == pytest.approx(27e-9)
    paged = TRC.named(ops, PAGED)                       # not the copy of it
    assert len(paged) == 2 and TRC.seconds(paged) == pytest.approx(10e-9)
    gaps = TRC.idle_gaps(ops, spans, 0, 100)
    assert gaps[0] == ["host idle", pytest.approx(35e-9)]        # 65..100
    assert gaps[1] == ["chipbench.admit", pytest.approx(18e-9)]  # 42..60
    assert gaps[2] == ["chipbench.step", pytest.approx(15e-9)]   # 15..30


def test_self_time_takes_nested_ops_out_of_their_loop():
    loop = ev(TRC.OPS_LINE, "%while.5 = (s32[]) while()", 0, 100)
    inner = [ev(TRC.OPS_LINE, f"%{PAGED}.9 = (f32[8]) custom-call()", 10, 30),
             ev(TRC.OPS_LINE, "%fusion.7 = bf16[8] fusion()", 50, 20)]
    got = {e.op: t for e, t in TRC.self_ns([loop] + inner)}
    assert got == {"while.5": 50, f"{PAGED}.9": 30, "fusion.7": 20}
    top = TRC.top_ops([loop] + inner)
    assert top[0] == ["while.5", pytest.approx(50e-9)]
    assert top[1] == [f"{PAGED}.9", pytest.approx(30e-9)]


def test_recorded_trace_against_a_brute_force_count():
    events = [TRC.Event(**e) for e in
              json.loads((DATA / "trace_small.json").read_text())["events"]]
    assert TRC.device_planes(events) == [DEV]
    ops = TRC.ops(events, DEV)
    spans = TRC.spans(events)
    assert {s.name for s in spans} <= {"chipbench.submit", "chipbench.admit",
                                      "chipbench.step"}
    lo = min(e.start_ns for e in ops + spans)
    hi = max(e.end_ns for e in ops + spans)
    # busy microseconds, counted one by one
    grid = np.zeros(int((hi - lo) // 1000) + 2, bool)
    for o in ops:
        grid[int((o.start_ns - lo) // 1000):
             int(np.ceil((o.end_ns - lo) / 1000))] = True
    busy = TRC.busy_ns(ops, lo, hi)
    assert busy / 1000 == pytest.approx(grid.sum(), abs=2 * len(ops) ** 0.5
                                        + 0.002 * grid.sum())
    assert 0 < busy < hi - lo
    paged = TRC.named(ops, PAGED)
    assert paged and all(o.name.startswith(f"%{PAGED}") for o in paged)
    assert TRC.seconds(paged) == pytest.approx(
        sum(o.dur_ns for o in ops if o.op.startswith(PAGED)) / 1e9)
    assert sum(t for _, t in TRC.self_ns(ops)) == pytest.approx(
        sum(o.dur_ns for o in ops), rel=0.5)
    assert sum(t for _, t in TRC.self_ns(ops)) <= sum(o.dur_ns for o in ops)
    gaps = TRC.idle_gaps(ops, spans, lo, hi, n=10 ** 6)
    assert sum(g[1] for g in gaps) == pytest.approx((hi - lo - busy) / 1e9)
    assert {g[0] for g in gaps} <= {s.name for s in spans} | {"host idle"}
