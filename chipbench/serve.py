"""Set-up, warm-up and the measured window of one cell, driving the
program's paged ``Engine`` through ``submit``/``_admit``/``step``.

Admission is the harness's own front end.  A request is handed to the
engine only when it can be admitted at once: a slot is free, and the
page pool holds what every admitted request needs to reach its full
length, so the engine never preempts.  Requests of one prompt length
go in groups whose sizes the traffic file lists, one group per
``_admit`` call, so the window only runs programs that set-up warmed.
Each request is timed from when it was due.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from chipbench import traffic as T
from chipbench.stats import Seen

WARM_MAX_NEW = 2


def now() -> float:
    return time.perf_counter()


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(f"chipbench.{name}")


@dataclasses.dataclass
class StepRecord:
    t_start: float
    t_end: float
    kind: str                         # "decode" | "admit"
    keys: List[int]                   # decode: keys each active slot reads
    group: Optional[tuple] = None     # admit: (group size, prompt length)
    tokens: int = 0                   # tokens committed by this call


class CompileCounter:
    """Executables compiled or loaded from the persistent cache while
    armed, from ``jax.monitoring``'s backend-compile event."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.event = dispatch.BACKEND_COMPILE_EVENT
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration_secs, **kw):
        if self.armed and event == self.event:
            self.count += 1

    def close(self) -> None:
        from jax._src import monitoring
        self.armed = False
        monitoring.unregister_event_duration_listener(self._on)


class GcClock:
    """Pauses of Python's garbage collector while armed: how many, and
    the longest, so that a long engine call can be told apart from one
    the collector stopped."""

    def __init__(self):
        self.armed = False
        self.pauses: List[float] = []
        self._t = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = now()
        elif self.armed and self._t is not None:
            self.pauses.append(now() - self._t)

    def close(self) -> None:
        self.armed = False
        gc.callbacks.remove(self._on)


class Driver:
    def __init__(self, model, params, sc, m: Dict[str, Any],
                 traffic: Dict[str, Any]):
        from repro.serve import Engine
        self.eng = Engine(model, params, sc)
        self.sc = sc
        self.m = m
        self.traffic = traffic
        self.ps = self.eng.page_size
        self.group_sizes = sorted(traffic.get("group_sizes", [1]),
                                  reverse=True)
        self.inflight: Dict[int, Any] = {}   # rid -> Request, unfinished
        self.requests: Dict[int, Any] = {}   # rid -> Request, all served
        self.seen: Dict[int, Seen] = {}
        self.final_pages: Dict[int, int] = {}
        self.records: List[StepRecord] = []

    # -- page budget ---------------------------------------------------
    def pages_for(self, plen: int, max_new: int) -> int:
        n = min(plen + max_new, self.sc.cache_len)
        return -(-n // self.ps)

    def page_budget(self) -> int:
        """Free pages once every admitted request has reached its end."""
        eng = self.eng
        owed = 0
        for slot, r in enumerate(eng.active):
            if r is not None and r.rid in self.final_pages:
                held = int(np.count_nonzero(eng.block_tables[slot]))
                owed += max(self.final_pages[r.rid] - held, 0)
        return eng.allocator.available - owed

    def free_slots(self) -> int:
        return sum(1 for r in self.eng.active if r is None)

    # -- one call into the engine --------------------------------------
    def _stamp(self, t: float) -> int:
        new = 0
        for rid, r in list(self.inflight.items()):
            s = self.seen[rid]
            k = len(r.out) - len(s.token_times)
            if k > 0:
                s.token_times.extend([t] * k)
                new += k
            if r.status != "pending":
                s.failed = r.status == "failed"
                del self.inflight[rid]
        return new

    def admit_group(self, reqs, due: Dict[int, float]) -> None:
        from repro.serve import Request
        t = now()
        for p in reqs:
            r = Request(rid=p.rid, tokens=p.tokens, max_new=p.max_new)
            self.seen[p.rid] = Seen(p.rid, due.get(p.rid, t), submitted=t)
            self.final_pages[p.rid] = self.pages_for(p.prompt_len, p.max_new)
            with annotate("submit"):
                self.eng.submit(r)
            self.inflight[p.rid] = r
            self.requests[p.rid] = r
        with annotate("admit"):
            self.eng._admit()
        t1 = now()
        n = self._stamp(t1)
        self.records.append(StepRecord(t, t1, "admit", [],
                                       (len(reqs), reqs[0].prompt_len), n))

    def decode(self) -> bool:
        if not any(r is not None for r in self.eng.active):
            return False
        keys = [len(r.tokens) + len(r.out) for r in self.eng.active
                if r is not None]
        t = now()
        with annotate("step"):
            self.eng.step()
        t1 = now()
        n = self._stamp(t1)
        self.records.append(StepRecord(t, t1, "decode", keys, None, n))
        return True

    # -- set-up --------------------------------------------------------
    def warm(self, seed: int) -> None:
        """Run every (prompt length, group size) the window can admit, and
        the decode step, with throw-away requests."""
        rid = -1
        for plen, k in T.warm_shapes(self.traffic):
            reqs = []
            for _ in range(k):
                reqs.append(T.Planned(rid, 0.0, plen, WARM_MAX_NEW,
                                      T.prompt_tokens(seed, -rid, plen,
                                                      self.m["vocab_size"],
                                                      salt=5)))
                rid -= 1
            self.admit_group(reqs, {})
            while self.inflight:
                self.decode()
        self.records.clear()
        self.seen.clear()
        self.requests.clear()

    def admit_sessions(self, plans: List[T.Planned]) -> None:
        """Admit every client's first session, one per prefill call, then
        take one decode step so the step program is compiled and loaded."""
        for p in sorted(plans, key=lambda p: p.prompt_len):
            self.admit_group([p], {})
        self.decode()
        self.records.clear()

    # -- the window ----------------------------------------------------
    def _admit_due(self, pending: collections.deque,
                   due: Dict[int, float]) -> None:
        while pending:
            free = self.free_slots()
            if free == 0:
                return
            plen = pending[0].prompt_len
            same = [p for p in pending if p.prompt_len == plen]
            budget = self.page_budget()
            need = self.pages_for(plen, max(p.max_new for p in same))
            fit = min(free, len(same), budget // max(need, 1))
            k = next((g for g in self.group_sizes if g <= fit), 0)
            if k == 0:
                return
            grp = same[:k]
            for p in grp:
                pending.remove(p)
            self.admit_group(grp, due)

    def run_open_loop(self, plans: List[T.Planned], seconds: float,
                      lead_s: float = 0.0,
                      on_tick: Callable[[float], None] = lambda t: None
                      ) -> float:
        """Serve ``plans`` as they fall due, from ``lead_s`` before the
        window opens until ``seconds`` after, so the window sees the
        batch in its steady state.  Returns the clock at which the window
        opened; every time kept is on that clock (``time.perf_counter``)."""
        start = now()
        t0 = start + lead_s
        due = {p.rid: start + p.due_s for p in plans}
        upcoming = collections.deque(sorted(plans, key=lambda p: p.due_s))
        pending: collections.deque = collections.deque()
        while True:
            t = now()
            if t - t0 >= seconds:
                break
            on_tick(t - t0)
            while upcoming and due[upcoming[0].rid] <= t:
                pending.append(upcoming.popleft())
            self._admit_due(pending, due)
            if not self.decode():
                nxt = due[upcoming[0].rid] if upcoming else t0 + seconds
                time.sleep(max(0.0, min(nxt, t0 + seconds) - now()))
        for p in pending:                  # due, never handed to the engine
            self.seen[p.rid] = Seen(p.rid, due[p.rid])
        return t0

    def run_sessions(self, plans: List[T.Planned], seconds: float, seed: int,
                     on_tick: Callable[[float], None] = lambda t: None
                     ) -> float:
        """Decode the clients' sessions for ``seconds``; a client whose
        session ends starts its next one, due when the last ended."""
        t0 = now()
        client_of = {p.rid: p.rid for p in plans}    # live rid -> client
        next_rid = len(plans)
        while now() - t0 < seconds:
            on_tick(now() - t0)
            for rid in [r for r in client_of if r not in self.inflight]:
                client = client_of.pop(rid)
                nxt = T.next_session(self.traffic, client, next_rid, seed,
                                     self.m["vocab_size"])
                next_rid += 1
                client_of[nxt.rid] = client
                self.admit_group([nxt], {nxt.rid: now()})
            if not self.decode():
                break
        return t0
