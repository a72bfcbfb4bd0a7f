"""Reduce a profiler trace to what the per-layer metrics read.

A trace is read once into plain events (``Event``): device operations
from the TPU planes, and the harness's own host spans (``chipbench.*``,
written by ``jax.profiler.TraceAnnotation``) from the host plane.  The
reductions below take those lists, so they can be checked on a small
recorded trace without a chip.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SPAN_PREFIX = "chipbench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def op(self) -> str:
        """The operation's own name: ``%fusion.3 = bf16[...] fusion(...)``
        on the TPU's ops line reads ``fusion.3``."""
        return self.name.split(" = ", 1)[0].lstrip("%")


def read_xplane(trace_dir: str) -> List[Event]:
    """Device events of every TPU plane and the harness's host spans."""
    import jax
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out: List[Event] = []
    for path in paths:
        pd = jax.profiler.ProfileData.from_file(path)
        for plane in pd.planes:
            device = plane.name.startswith("/device:TPU:")
            host = plane.name.startswith("/host:")
            if not (device or host):
                continue
            for line in plane.lines:
                if device and line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                for ev in line.events:
                    if host and not ev.name.startswith(SPAN_PREFIX):
                        continue
                    out.append(Event(plane.name, line.name, ev.name,
                                     float(ev.start_ns),
                                     float(ev.duration_ns)))
    return out


def device_planes(events: Iterable[Event]) -> List[str]:
    return sorted({e.plane for e in events if e.plane.startswith("/device:")})


def ops(events: Iterable[Event], plane: Optional[str] = None) -> List[Event]:
    return [e for e in events if e.line == OPS_LINE
            and (plane is None or e.plane == plane)]


def modules(events: Iterable[Event], plane: Optional[str] = None
            ) -> List[Event]:
    return [e for e in events if e.line == MODULES_LINE
            and (plane is None or e.plane == plane)]


def spans(events: Iterable[Event]) -> List[Event]:
    return [e for e in events if e.name.startswith(SPAN_PREFIX)]


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint ones, in order."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_ns(op_events: Sequence[Event], lo: float, hi: float) -> float:
    """Length of the union of the operations' intervals within [lo, hi]."""
    return sum(e - s for s, e in clip(
        union((o.start_ns, o.end_ns) for o in op_events), lo, hi))


def busy_s_mean(events: Sequence[Event], lo: float, hi: float) -> float:
    """Busy seconds in [lo, hi], averaged over the TPU planes."""
    planes = device_planes(events)
    if not planes:
        return 0.0
    return sum(busy_ns(ops(events, p), lo, hi) for p in planes) \
        / len(planes) / 1e9


def named(op_events: Iterable[Event], prefix: str) -> List[Event]:
    """Operations whose own name starts with ``prefix`` (a kernel's name
    gets a ``.N`` suffix in the compiled program)."""
    return [o for o in op_events if o.op.startswith(prefix)]


def self_ns(op_events: Sequence[Event]) -> List[Tuple[Event, float]]:
    """Each operation with its time less that of the operations nested
    inside it on the same line (a layer loop holds its layers' ops)."""
    out: List[Tuple[Event, float]] = []
    by_line: Dict[tuple, List[Event]] = collections.defaultdict(list)
    for o in op_events:
        by_line[(o.plane, o.line)].append(o)
    for evs in by_line.values():
        evs = sorted(evs, key=lambda e: (e.start_ns, -e.dur_ns))
        stack: List[List] = []          # [event, self time]
        for e in evs:
            while stack and e.start_ns >= stack[-1][0].end_ns:
                out.append(tuple(stack.pop()))
            if stack:
                stack[-1][1] -= min(e.end_ns, stack[-1][0].end_ns) \
                    - e.start_ns
            stack.append([e, e.dur_ns])
        out.extend(tuple(x) for x in stack)
    return out


def seconds(evs: Iterable[Event]) -> float:
    return sum(e.dur_ns for e in evs) / 1e9


def top_ops(op_events: Sequence[Event], n: int = 10) -> List[list]:
    """The operations that took most device time of their own, summed by
    name with the ``.N`` suffix kept (one entry per compiled op)."""
    tot: Dict[str, float] = collections.defaultdict(float)
    for o, t in self_ns(op_events):
        tot[o.op] += t / 1e9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(op_events: Sequence[Event], host_spans: Sequence[Event],
              lo: float, hi: float, n: int = 10) -> List[list]:
    """The ``n`` longest stretches of [lo, hi] with no device operation,
    each named by what covers most of it: a host span, or ``host idle``
    for the part no span covers."""
    busy = clip(union((o.start_ns, o.end_ns) for o in op_events), lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    named = []
    for s, e in gaps:
        cover: Dict[str, float] = collections.defaultdict(float)
        for sp in host_spans:
            ov = min(e, sp.end_ns) - max(s, sp.start_ns)
            if ov > 0:
                cover[sp.name] += ov
        covered = sum(b - a for a, b in clip(
            union((sp.start_ns, sp.end_ns) for sp in host_spans), s, e))
        cover["host idle"] = (e - s) - covered
        name = max(cover, key=cover.get)
        named.append([name, (e - s) / 1e9])
    named.sort(key=lambda x: -x[1])
    return named[:n]
