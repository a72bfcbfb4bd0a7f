"""Continuous-batching serving engine: batched prefill admission + a
fully device-resident decode loop, over a paged or slot-dense KV cache.

Scheduler state (active mask, lengths, current tokens, emitted-token
counts) lives **on device**: ``step()`` runs one jitted decode —
model step, sampling, length/active/finish updates — and performs a
single ``jax.device_get`` of the small (next_token, done, bad,
emitted) tuple.  The host keeps numpy mirrors (updated from that one
transfer) purely for admission control and page allocation; no
per-slot syncs, no per-step host-built arrays (the bugs the slot
engine had: see the regression tests in tests/test_serve.py).

Observability (DESIGN.md §16): scheduler/resilience counters are
backed by a per-engine ``MetricsRegistry`` (``stats()`` is the
compatible façade; the old attribute names remain as read-only
properties).  Per-step telemetry counters — emitted tokens, accepted
spec length, the bad-slot lane — are *piggybacked onto the existing
step-result tuple*, so attaching a ``ServeTelemetry``
(serve/telemetry.py) records the full per-request lifecycle trace and
latency histograms without adding a single device sync; a regression
test counts ``_device_get`` calls with telemetry on vs off.  Host
spans (``repro.engine.step``, ``.admit_group`` and their phases, see
``obs.trace.span``) name each stretch of host work for a running
profiler, and ``serve.compiles.<program>`` counts the calls that grew
a jitted program's executable cache.

Admission is batched: queued requests are grouped by prompt length and
each group is prefilled in ONE compiled call (grouping by exact length
keeps right-padding out of recurrent/ring caches, and makes the
last-position logits correct for every row), then scattered into slots
(dense) or freshly allocated pages (paged) in one more compiled call.

Paged mode (``ServeConfig(paged=True)``) stores global-attention KV in
fixed-size pages from a shared pool (serve/paging.py) and decodes
through the paged flash-decode kernel; the page size defaults to the
autotuner's per-target winner for ``paged_decode_attention``.  With
``kv_dtype`` the pools quantize (int8 everywhere, fp8-e4m3 where the
target's ISA supports it — repro.quant resolves with clean fallback)
and decode runs the fused-dequant kernel; ``"bf16"`` is passthrough.

Termination: a slot finishes when it has emitted ``max_new_tokens``,
sampled ``eos_id``, or its cache is truly full — ``lengths ==
cache_len`` *after* the final row is written, so the last cache row is
usable (the slot engine freed one token early).

Oversubscription (paged mode): when an explicit ``total_pages`` makes
the pool smaller than the working set, a slot crossing a page boundary
mid-decode can find the pool dry.  ``ServeConfig.preempt_policy``
decides what happens: ``"lru"`` (default) preempts the
least-recently-admitted slot, ``"shortest"`` the one with the fewest
generated tokens, ``"priority"`` the lowest ``Request.priority_class``
(ties by admission stamp — the SLO-aware policy, which additionally
lets a strictly-higher-class waiting request evict at admission time),
and ``"fail"`` keeps the pre-preemption behavior of raising the
allocator's actionable error.  Admission itself is latency-class-aware:
within the requeue deque and the fresh queue, higher ``priority_class``
admits first, FIFO within a class (DESIGN.md §17).  A preempted slot is
checkpointed as prompt + tokens generated so far onto a requeue deque,
its pages are bulk-reclaimed through the strict allocator, and it is
re-admitted later through the ordinary batched-prefill path with the
generated tokens appended to the prompt — under greedy decoding the
final outputs are token-identical to an un-preempted run (re-prefill
recomputes exactly the KV the decode steps wrote, including the dense
recurrent/ring leaves, which is why re-prefill was chosen over paging
state out to host memory — DESIGN.md §12).  Requeued requests are
re-admitted ahead of never-admitted ones (the starvation guard), and
``lru`` never victimizes the slot it is allocating for, so the growing
slot always makes progress.

Resilience (serve/faults.py, DESIGN.md §14): the step is guarded by a
NaN/Inf logits sentinel folded into its return tuple (no extra
transfer), a host-side watchdog around dispatch + device_get, and the
``paging.audit()`` invariant auditor.  A detected fault checkpoints
the slot through the same requeue path preemption uses — with a
per-request retry budget and exponential backoff; corrupted pool
pages are quarantined (capacity shrinks, never recycled), repeated
speculation-step faults disable drafting for the offending request,
and an exhausted budget finishes the request with an explicit
``failed`` status instead of raising.  Recovery is re-prefill of the
committed checkpoint, so under greedy decoding every recovered
request is token-identical to an un-faulted run.  Step results commit
only *after* the device_get returns inside the watchdog deadline; a
tripped watchdog discards the step wholesale and requeues every
active slot.
"""
from __future__ import annotations

import collections
import dataclasses
import time
import warnings
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.registry import Model
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import span
from repro.serve import paging
from repro.serve.faults import FAULT_KINDS, FaultPlan, corrupt_page, \
    nonfinite_pages

# Indirection for tests that count host syncs per step.
_device_get = jax.device_get


@dataclasses.dataclass
class ServeConfig:
    slots: int = 4
    cache_len: int = 128
    max_new_tokens: int = 16
    temperature: float = 0.0
    eos_id: Optional[int] = None
    seed: int = 0
    paged: bool = False
    page_size: Optional[int] = None    # None -> per-target tuning table
    total_pages: Optional[int] = None  # None -> 1 + slots*pages_per_slot
    # Window-group pool size (paged hybrid models only): pages backing
    # the kw/vw pools of sliding-window layers.  None -> 1 + slots *
    # window_table_width, which never exhausts because eager prefix
    # free keeps every slot's window footprint <= T_w pages.
    total_pages_window: Optional[int] = None
    on_overflow: str = "reject"        # "reject" | "truncate"
    # KV pool dtype (paged only): None = model-dtype passthrough;
    # "bf16" | "int8" | "fp8_e4m3" resolve through the arch-aware
    # capability query (repro.quant) with clean per-target fallback.
    kv_dtype: Optional[str] = None
    # Oversubscribed-pool policy (paged only): what to do when the page
    # pool runs dry while a decoding slot needs its next page.
    #   "lru"      preempt the least-recently-admitted slot (default)
    #   "shortest" preempt the slot with the fewest generated tokens
    #   "priority" preempt the lowest Request.priority_class first
    #              (ties by admission stamp) — the SLO-aware policy;
    #              it also lets a waiting higher-class request evict a
    #              strictly-lower-class slot at admission time
    #   "fail"     raise the allocator's actionable error (pre-PR-5)
    preempt_policy: str = "lru"
    # Self-speculative decoding (paged + greedy only): "ngram" drafts
    # spec_k tokens per step from the slot's own token history (prompt
    # lookup — no draft model) and verifies all of them in ONE batched
    # paged-decode call; rejected tokens roll back by truncating the
    # block-table suffix.  "off" is the plain one-token step.
    spec_mode: str = "off"
    spec_k: int = 4
    # Resilience knobs (DESIGN.md §14).  A faulted slot is requeued and
    # re-prefilled at most max_retries times, with an exponential
    # backoff of retry_backoff * 2**(retries-1) engine steps between
    # attempts; past the budget the request finishes with an explicit
    # ``failed`` status.  watchdog_s bounds the wall-clock of one step
    # dispatch + device_get; a step past the deadline is discarded
    # un-committed and every active slot requeues (None disables).
    # spec_disable_after: speculation-step faults on one request before
    # its drafting is disabled (it decodes 1 token/step from then on).
    max_retries: int = 3
    retry_backoff: int = 2
    watchdog_s: Optional[float] = None
    spec_disable_after: int = 2


#: Valid ServeConfig.preempt_policy values (launch/serve.py choices).
PREEMPT_POLICIES = ("lru", "shortest", "priority", "fail")

#: Valid ServeConfig.spec_mode values (launch/serve.py choices).
SPEC_MODES = ("off", "ngram")


@dataclasses.dataclass
class Request:
    rid: int
    tokens: List[int]
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    truncated: bool = False
    preempts: int = 0       # times this request was preempted/requeued
    # SLO class (DESIGN.md §17): higher = more latency-sensitive.  Read
    # by priority-aware admission ordering, the "priority" victim
    # policy, and per-class telemetry percentiles.  traffic_class is
    # the human-readable workload label ("chat"/"longdoc"/"batch") the
    # trace generator stamps; reporting groups by it when present.
    priority_class: int = 0
    traffic_class: Optional[str] = None
    # per-request decode budget: caps this request's generated tokens
    # at min(max_new, ServeConfig.max_new_tokens).  None = the engine
    # default.  Trace entries carry their sampled output lengths here.
    max_new: Optional[int] = None
    # resilience state (engine-managed): fault-retry count, earliest
    # engine step for re-admission (exponential backoff stamp), and the
    # explicit terminal failure flag for an exhausted retry budget
    retries: int = 0
    not_before: int = 0
    failed: bool = False
    # speculation-step faults observed for this request; at
    # ServeConfig.spec_disable_after the engine pins the slot to plain
    # 1-token decoding (the degrade rung of the recovery ladder)
    spec_faults: int = 0
    spec_disabled: bool = False

    @property
    def status(self) -> str:
        """'done' | 'failed' | 'pending' — failed is terminal and
        explicit, never an exception out of the serve loop."""
        if self.failed:
            return "failed"
        return "done" if self.done else "pending"


class Engine:
    def __init__(self, model: Model, params, sc: ServeConfig,
                 fault_plan: Optional[FaultPlan] = None,
                 telemetry=None):
        self.model = model
        self.params = params
        self.sc = sc
        self.cfg = model.cfg
        slots = sc.slots
        # Observability (DESIGN.md §16): every engine carries a
        # MetricsRegistry — the backing store for the scheduler/
        # resilience counters stats() reads (the legacy attribute names
        # remain as read-only properties below).  ``telemetry`` is an
        # optional, attachable serve.telemetry.ServeTelemetry recording
        # the per-request lifecycle trace + latency histograms; every
        # hook site below costs one ``is None`` check when detached,
        # runs on the host commit path after the step's single
        # device_get, and never adds a device sync.
        self.metrics = MetricsRegistry()
        self.telemetry = telemetry
        # (step, wall-time) records for the most recent watchdog trip /
        # fault recovery — stats() exposes them so an operator can
        # correlate with external logs (previously counted, never
        # timestamped)
        self.last_watchdog_trip: Optional[Dict[str, Any]] = None
        self.last_recovery: Optional[Dict[str, Any]] = None
        if sc.on_overflow not in ("reject", "truncate"):
            raise ValueError(f"on_overflow must be 'reject' or 'truncate', "
                             f"got {sc.on_overflow!r}")
        if sc.max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got "
                             f"{sc.max_retries}")
        if sc.retry_backoff < 0:
            raise ValueError(f"retry_backoff must be >= 0, got "
                             f"{sc.retry_backoff}")
        if fault_plan is not None and not sc.paged:
            raise ValueError("fault injection requires paged=True "
                             "(kv_corrupt/alloc_fail target the page pool)")
        if sc.preempt_policy not in PREEMPT_POLICIES:
            raise ValueError(f"preempt_policy must be one of "
                             f"{PREEMPT_POLICIES}, got {sc.preempt_policy!r}")
        if sc.spec_mode not in SPEC_MODES:
            raise ValueError(f"spec_mode must be one of {SPEC_MODES}, "
                             f"got {sc.spec_mode!r}")
        self.spec = sc.spec_mode != "off"
        if self.spec:
            if not sc.paged:
                raise ValueError("spec_mode requires paged=True (rollback "
                                 "is block-table suffix truncation)")
            if sc.temperature > 0.0:
                raise ValueError(
                    f"spec_mode={sc.spec_mode!r} requires greedy decoding: "
                    f"verification accepts drafts by token identity with "
                    f"the argmax chain, which sampling at temperature="
                    f"{sc.temperature} breaks; set temperature=0.0")
            if sc.spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {sc.spec_k}")
            kinds = set(model.cfg.layer_kinds())
            if kinds - {"global"} or model.cfg.is_encoder_decoder:
                raise ValueError(
                    f"spec_mode supports attention-only decoder models "
                    f"(global attention / MLA); layer kinds "
                    f"{sorted(kinds)} include sequential state that a "
                    f"batched verify cannot roll back")

        self.paged = sc.paged
        if sc.kv_dtype is not None and not sc.paged:
            raise ValueError("kv_dtype requires paged=True (only paged "
                             "pools are dtype-parametric)")
        if self.paged:
            from repro.quant import resolve_kv_spec
            self.kv_spec = resolve_kv_spec(sc.kv_dtype)
            self.page_size = self._resolve_page_size()
            self.pages_per_slot = paging.pages_per_slot(sc.cache_len,
                                                        self.page_size)
            total = sc.total_pages or (1 + slots * self.pages_per_slot)
            self.allocator = paging.PageAllocator(total)
            self.block_tables = np.full((slots, self.pages_per_slot),
                                        paging.NULL_PAGE, np.int32)
            self._bt_dev = jnp.asarray(self.block_tables)
            self._bt_dirty = False
            # pages ensured for each slot this step (page-count horizon
            # the spec-step rollback truncates back from)
            self._ensured = np.zeros((slots,), np.int64)
            # window group: sliding-window ("local") layers page through
            # ring block tables over their own pool, O(window) per slot.
            # MLA models cache full per-head K/V even for local kinds,
            # so they stay in the global group (mirrors the routing in
            # paging._is_window_leaf_dict).
            self.window = getattr(self.cfg, "window", None)
            self.windowed = bool(
                "local" in set(self.cfg.layer_kinds())
                and self.window and self.window < sc.cache_len
                and not self.cfg.mla)
            total_w = None
            if self.windowed:
                self.tw = paging.window_table_width(self.window,
                                                    self.page_size)
                total_w = sc.total_pages_window or (1 + slots * self.tw)
                self.allocator_w = paging.PageAllocator(total_w)
                self.block_tables_w = np.full((slots, self.tw),
                                              paging.NULL_PAGE, np.int32)
                self._btw_dev = jnp.asarray(self.block_tables_w)
                self._btw_dirty = False
                # first live global page per slot (the sliding lease's
                # low-water mark free_prefix advances from)
                self.win_first = np.zeros((slots,), np.int64)
            self.caches = paging.init_paged_caches(
                model, slots, sc.cache_len, self.page_size, total,
                kv_spec=self.kv_spec, total_pages_window=total_w)
            has_kw = any("kw" in c for seg in self.caches for c in seg)
            assert has_kw == self.windowed, \
                "engine/paging window-group routing disagree"
        else:
            self.kv_spec = None
            self.windowed = False
            self.window = None
            self.caches = model.init_decode_caches(slots, sc.cache_len)

        # device-resident scheduler state
        self.lengths = jnp.zeros((slots,), jnp.int32)
        self.cur_tok = jnp.zeros((slots,), jnp.int32)
        self.n_out = jnp.zeros((slots,), jnp.int32)
        self.active_mask = jnp.zeros((slots,), jnp.bool_)
        # per-slot decode budget (device): admission writes each
        # request's effective max_new here, so the jitted finish check
        # is elementwise — a trace request with a 3-token budget ends
        # at 3 even when the engine default is 16
        self.max_new_dev = jnp.full((slots,), sc.max_new_tokens,
                                    jnp.int32)
        # per-slot committed token history (device): position p holds
        # the token whose KV sits in cache row p.  Column cache_len is a
        # dump row absorbing clipped writes at the cache edge.  Fed by
        # admission and the spec step; only the n-gram proposer reads it.
        self.tok_hist = jnp.zeros((slots, sc.cache_len + 1), jnp.int32)
        # host mirrors (admission control / page allocation only)
        self._len_h = np.zeros((slots,), np.int64)
        self._active_h = np.zeros((slots,), bool)

        self.active: List[Optional[Request]] = [None] * slots
        self.queue: List[Request] = []
        # preempt/requeue scheduler state: checkpointed (preempted)
        # requests wait here and are re-admitted ahead of fresh queue
        # entries (the starvation guard); _admit_seq[slot] is a
        # monotonic admission stamp the "lru" victim policy reads.
        self.requeue: collections.deque[Request] = collections.deque()
        # pre-create the registry-backed scheduler/resilience counters
        # so snapshot()/stats() show explicit zeros from step one
        m = self.metrics
        m.counter("serve.preemptions")
        for p in PREEMPT_POLICIES:
            m.counter(f"serve.preemptions.{p}")
        for k in FAULT_KINDS:
            m.counter(f"serve.recoveries.{k}")
        m.counter("serve.failed_requests")
        m.counter("serve.watchdog_trips")
        m.counter("serve.spec_steps")
        m.counter("serve.spec_emitted")
        m.counter("serve.spec_rejections")
        m.counter("serve.window_prefix_frees")
        m.gauge("serve.requeue_peak_depth")
        for prog in ("prefill", "admit", "step", "spec"):
            m.counter(f"serve.compiles.{prog}")
        m.counter("serve.decode.kv_pages_live")
        m.counter("serve.decode.kv_pages_table")
        self._admit_seq = np.zeros((slots,), np.int64)
        self._seq = 0
        self._key = jax.random.PRNGKey(sc.seed)
        # resilience state: the injectable fault plan (None in
        # production paths); the step counter backoff stamps are quoted
        # in (it ticks even on idle steps, so a backing-off requeue
        # always drains); the sticky alloc-failure deny; and the
        # recovery-ladder counters
        self.fault_plan = fault_plan
        self.watchdog_s = sc.watchdog_s
        self.step_count = 0
        self._alloc_deny = False
        # per-slot drafting enable for the spec step (a request whose
        # spec_faults crossed spec_disable_after decodes 1 token/step)
        self._spec_ok_h = np.ones((slots,), bool)
        self._spec_ok_dev = jnp.asarray(self._spec_ok_h)
        self._spec_ok_dirty = False

        self._prefill = jax.jit(
            lambda p, t: model.prefill(p, t, sc.cache_len, {}))
        self._step_fn = jax.jit(self._build_step())
        self._admit_fn = jax.jit(self._build_admit())
        self._spec_fn = jax.jit(self._build_spec_step()) if self.spec \
            else None

    # -- registry-backed counters (legacy attribute names) -----------------
    # The scheduler/resilience counters live in self.metrics; these
    # read-only properties keep every existing caller of the old plain
    # attributes working (benchmarks, launchers, tests) while making a
    # stray `eng.preemptions += 1` an AttributeError instead of a
    # silently-forked count.
    @property
    def preemptions(self) -> int:
        return self.metrics.counter("serve.preemptions").value

    @property
    def preemptions_by_policy(self) -> Dict[str, int]:
        return {p: self.metrics.counter(f"serve.preemptions.{p}").value
                for p in PREEMPT_POLICIES}

    @property
    def requeue_peak_depth(self) -> int:
        return int(self.metrics.gauge("serve.requeue_peak_depth").value)

    @property
    def recoveries(self) -> Dict[str, int]:
        return {k: self.metrics.counter(f"serve.recoveries.{k}").value
                for k in FAULT_KINDS}

    @property
    def failed_requests(self) -> int:
        return self.metrics.counter("serve.failed_requests").value

    @property
    def watchdog_trips(self) -> int:
        return self.metrics.counter("serve.watchdog_trips").value

    @property
    def spec_steps(self) -> int:
        return self.metrics.counter("serve.spec_steps").value

    @property
    def spec_emitted(self) -> int:
        return self.metrics.counter("serve.spec_emitted").value

    @property
    def spec_rejections(self) -> int:
        return self.metrics.counter("serve.spec_rejections").value

    @property
    def window_prefix_frees(self) -> int:
        return self.metrics.counter("serve.window_prefix_frees").value

    def _pool_pressure_brief(self) -> Dict[str, Dict[str, int]]:
        """Host-side live/quarantined page counts per pool group (no
        device reads) — the per-step allocator sample on_step records."""
        groups = {"global": self.allocator.brief()}
        if self.windowed:
            groups["window"] = self.allocator_w.brief()
        return groups

    def _jit_call(self, prog: str, fn, sp, *args):
        """Call the jitted ``fn``.  A call that grew its executable
        cache compiled (or loaded) a program: it counts under
        ``serve.compiles.<prog>`` and marks the span ``sp`` with
        ``compiled=1``.  Reading the cache size syncs nothing."""
        n = fn._cache_size()
        out = fn(*args)
        if fn._cache_size() > n:
            self.metrics.counter(f"serve.compiles.{prog}").inc()
            sp.set_metadata(compiled=1)
        return out

    # -- jitted bodies ----------------------------------------------------
    def _resolve_page_size(self) -> int:
        if self.sc.page_size is not None:
            ps = int(self.sc.page_size)
        else:
            from repro.core import tuning
            op = ("quant_paged_decode_attention"
                  if self.kv_spec is not None and self.kv_spec.quantized
                  else "paged_decode_attention")
            ps = int(tuning.block_size(op, "page_size"))
        return max(1, min(ps, self.sc.cache_len))

    def _sample(self, logits, key):
        if self.sc.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits / self.sc.temperature, axis=-1).astype(jnp.int32)

    def _build_step(self):
        model, cache_len = self.model, self.sc.cache_len

        def step_fn(params, caches, cur_tok, lengths, active, n_out, key,
                    eos_id, max_new, block_tables, nan_mask):
            logits, new_caches = model.decode_step(
                params, caches, cur_tok, lengths, block_tables=block_tables)
            # nan_logits fault injection: flip the target rows before
            # the sentinel so detection sees what a real compute fault
            # would produce (all-zeros mask on the un-faulted path)
            logits = jnp.where(nan_mask[:, None], jnp.nan, logits)
            # NaN/Inf sentinel, folded into the step's return tuple —
            # detection costs no extra transfer.  A flagged slot's
            # sampled token is garbage; the host discards it and routes
            # the slot down the recovery ladder instead of committing.
            bad = active & ~jnp.all(jnp.isfinite(logits), axis=-1)
            next_tok = self._sample(logits, key)
            adv = active.astype(jnp.int32)
            new_lengths = lengths + adv
            new_n_out = n_out + adv
            # finish: budget spent, EOS sampled, or no cache row left for
            # the *next* token (the final row at cache_len-1 is usable).
            # A sentinel-flagged slot never finishes here — its fate is
            # the host-side recovery ladder, not the EOS of a NaN argmax.
            done = active & ~bad & ((new_n_out >= max_new)
                                    | (next_tok == eos_id)
                                    | (new_lengths + 1 > cache_len))
            new_active = active & ~done
            # per-step device counter, piggybacked onto the step-result
            # tuple so telemetry rides the existing single device_get
            # (zero extra syncs — the obs regression test counts calls)
            emitted = jnp.sum((active & ~bad).astype(jnp.int32))
            return (next_tok, new_lengths, new_active, new_n_out, done,
                    bad, emitted, new_caches)

        return step_fn

    def _build_spec_step(self):
        model, cache_len = self.model, self.sc.cache_len
        slots, k = self.sc.slots, self.sc.spec_k
        k1 = k + 1
        w = cache_len + 1                      # tok_hist width (+dump col)

        def propose(hist, cur_tok, lengths):
            """N-gram prompt lookup: draft the k tokens that followed the
            most recent prior occurrence of ``cur_tok`` in the slot's own
            history, preferring occurrences whose *predecessor* also
            matches (bigram beats unigram; latest occurrence breaks
            ties).  No occurrence -> repeat ``cur_tok`` k times, which
            captures the fixed-point attractors greedy decode falls
            into.  ``hist`` already holds ``cur_tok`` at ``lengths``."""
            idx = jnp.arange(w, dtype=jnp.int32)[None, :]
            big = lengths[:, None]             # (B,1) match below L only
            match = (idx < big) & (hist == cur_tok[:, None])
            prev = jnp.concatenate(
                [jnp.zeros_like(hist[:, :1]), hist[:, :-1]], axis=1)
            ctx = jnp.take_along_axis(hist, jnp.maximum(big - 1, 0), axis=1)
            bigram = (idx >= 1) & (big >= 1) & (prev == ctx)
            score = jnp.where(match, 1 + bigram.astype(jnp.int32), 0)
            rank = jnp.where(score > 0, score * w + idx, -1)
            j = jnp.argmax(rank, axis=1).astype(jnp.int32)
            found = jnp.max(rank, axis=1) >= 0
            di = j[:, None] + 1 + jnp.arange(k, dtype=jnp.int32)[None, :]
            d = jnp.take_along_axis(hist, jnp.minimum(di, w - 1), axis=1)
            return jnp.where(found[:, None] & (di <= big), d,
                             cur_tok[:, None])

        def spec_step_fn(params, caches, tok_hist, cur_tok, lengths,
                         active, n_out, eos_id, max_new, block_tables,
                         nan_mask, spec_ok):
            rows = jnp.arange(slots)
            # commit cur_tok into the history at its cache position L
            # *before* proposing, so drafts reading up to L are real
            p0 = jnp.minimum(lengths, cache_len)
            tok_hist = tok_hist.at[rows, p0].set(
                jnp.where(active, cur_tok, tok_hist[rows, p0]))
            drafts = propose(tok_hist, cur_tok, lengths)
            window = jnp.concatenate([cur_tok[:, None], drafts], axis=1)
            # draft positions L+1..L+k: accepted ones hold committed
            # tokens (acceptance == identity with the argmax chain);
            # rejected ones are stale but sit past the new length, and
            # the proposer masks on idx < L, so they are never read
            for t in range(1, k1):
                pt = jnp.minimum(lengths + t, cache_len)
                tok_hist = tok_hist.at[rows, pt].set(
                    jnp.where(active, window[:, t], tok_hist[rows, pt]))

            logits, new_caches = model.spec_decode_step(
                params, caches, window, lengths, block_tables)
            # nan_logits injection + NaN/Inf sentinel over the whole
            # verify window (any poisoned position taints the slot) —
            # same contract as the plain step, still one device_get
            logits = jnp.where(nan_mask[:, None, None], jnp.nan, logits)
            bad = active & ~jnp.all(jnp.isfinite(logits), axis=(1, 2))
            y = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # (B,K1)

            # accept-longest-prefix: row t's output is emitted iff every
            # earlier row was emitted, did not finish, and its draft
            # matched the argmax chain (token identity == greedy parity).
            # spec_ok gates drafting per slot: a request degraded by
            # repeated speculation faults accepts only row 0, which is
            # bit-identical to the plain decode step's token.
            t_idx = jnp.arange(k1, dtype=jnp.int32)[None, :]
            done_t = (active[:, None]
                      & ((n_out[:, None] + t_idx + 1 >= max_new[:, None])
                         | (y == eos_id)
                         | (lengths[:, None] + t_idx + 2 > cache_len)))
            cont = ((window[:, 1:] == y[:, :-1]) & ~done_t[:, :-1]
                    & spec_ok[:, None])
            prefix = jnp.concatenate(
                [active[:, None],
                 active[:, None] & jnp.cumprod(
                     cont.astype(jnp.int32), axis=1).astype(bool)], axis=1)
            n_emit = prefix.sum(axis=1).astype(jnp.int32)
            done = (prefix & done_t).any(axis=1) & ~bad
            new_active = active & ~done
            new_lengths = lengths + n_emit
            new_n_out = n_out + n_emit
            last = jnp.take_along_axis(
                y, jnp.maximum(n_emit - 1, 0)[:, None], axis=1)[:, 0]
            new_cur = jnp.where(active, last, cur_tok)
            return (y, n_emit, new_lengths, new_active, new_n_out, done,
                    bad, new_caches, tok_hist, new_cur)

        return spec_step_fn

    def _build_admit(self):
        window = self.window if self.windowed else None

        def admit_fn(caches, lengths, cur_tok, active, n_out, tok_hist,
                     max_new, cache1, first_tok, slot_idx, plens,
                     admit_active, n_out_vals, max_new_vals, page_rows,
                     hist_rows, page_rows_w):
            caches = paging.scatter_prefill(caches, cache1, slot_idx,
                                            page_rows,
                                            page_rows_w=page_rows_w,
                                            plens=plens, window=window)
            lengths = lengths.at[slot_idx].set(plens)
            cur_tok = cur_tok.at[slot_idx].set(first_tok)
            active = active.at[slot_idx].set(admit_active)
            # fresh admissions enter with n_out=1 (the prefill sample);
            # re-admitted preempted requests resume their real count so
            # the jitted max_new check stays in lockstep with req.out
            n_out = n_out.at[slot_idx].set(n_out_vals)
            # per-slot decode budget: the elementwise finish check reads
            # this instead of the scalar engine default
            max_new = max_new.at[slot_idx].set(max_new_vals)
            tok_hist = tok_hist.at[slot_idx].set(hist_rows)
            return (caches, lengths, cur_tok, active, n_out, tok_hist,
                    max_new)

        return admit_fn

    # -- request lifecycle ------------------------------------------------
    def submit(self, req: Request):
        """Queue a request; prompts that cannot leave room for a single
        decoded token are rejected (or tail-truncated) *here*, before
        they can clamp-corrupt a cache slot."""
        limit = self.sc.cache_len - 1
        if self.paged:
            # an undersized pool (explicit total_pages, or one shrunk by
            # fault quarantine) that can never hold the prompt would
            # requeue forever — fail here instead
            usable = self.allocator.usable
            fits = usable * self.page_size - 1
            limit = min(limit, fits) if self.sc.on_overflow == "truncate" \
                else limit
            if (self.sc.on_overflow != "truncate" and self.windowed
                    and len(paging.live_window_pages(
                        len(req.tokens) + 1, self.window,
                        self.page_size)) > self.allocator_w.usable):
                raise ValueError(
                    f"request {req.rid}: prompt of {len(req.tokens)} tokens "
                    f"needs more window KV pages than the window pool "
                    f"holds ({self.allocator_w.usable} x {self.page_size}); "
                    f"raise total_pages_window")
            if (self.sc.on_overflow != "truncate"
                    and paging.pages_per_slot(len(req.tokens) + 1,
                                              self.page_size) > usable):
                # +1: every admitted request writes at least one decoded
                # token, so its first step needs that page too
                raise ValueError(
                    f"request {req.rid}: prompt of {len(req.tokens)} tokens "
                    f"(+1 decode) needs more KV pages than the whole pool "
                    f"holds ({usable} x {self.page_size}); raise total_pages")
        if len(req.tokens) > limit:
            # limit == 0 (cache_len=1, or a one-page pool) can never be
            # truncated into: tokens[-0:] would keep the whole prompt
            if self.sc.on_overflow == "truncate" and limit > 0:
                warnings.warn(
                    f"request {req.rid}: prompt of {len(req.tokens)} tokens "
                    f"exceeds the cache capacity of {limit}; keeping the "
                    f"last {limit}", stacklevel=2)
                req.tokens = list(req.tokens[-limit:])
                req.truncated = True
            else:
                raise ValueError(
                    f"request {req.rid}: prompt of {len(req.tokens)} tokens "
                    f"does not fit cache_len={self.sc.cache_len} (need <= "
                    f"cache_len-1; set ServeConfig.on_overflow='truncate' "
                    f"to clip instead)")
        if not req.tokens:
            raise ValueError(f"request {req.rid}: empty prompt")
        if req.max_new is not None and req.max_new < 1:
            raise ValueError(f"request {req.rid}: max_new must be >= 1, "
                             f"got {req.max_new}")
        self.queue.append(req)
        if self.telemetry is not None:
            self.telemetry.on_submit(req, self.step_count)

    def _req_max_new(self, req: Request) -> int:
        """Effective decode budget: the request's own cap, bounded by
        the engine-wide ceiling (slot state is sized for the latter)."""
        if req.max_new is None:
            return self.sc.max_new_tokens
        return min(req.max_new, self.sc.max_new_tokens)

    def _free_slots(self) -> List[int]:
        return [s for s in range(self.sc.slots) if self.active[s] is None]

    def _take_waiting(self, n: int) -> List[Request]:
        """Remove up to ``n`` backoff-eligible requests across the
        requeue deque and the fresh queue, in latency-class-aware
        order: highest priority_class first; *within* a class,
        preempted checkpoints ahead of fresh traffic (the PR 5
        starvation guard, now scoped per class so a high-class arrival
        is never stuck behind a lower class's checkpoint), FIFO within
        each pool.  Ineligible (backing-off) / unchosen entries keep
        their relative order.  With uniform priorities this reduces to
        exactly the old requeue-then-queue FIFO, so non-SLO workloads
        see the PR 5 admission order unchanged."""
        if n <= 0:
            return []
        cand = [(-r.priority_class, 0, i) for i, r in
                enumerate(self.requeue)
                if r.not_before <= self.step_count]
        cand += [(-r.priority_class, 1, i) for i, r in
                 enumerate(self.queue)
                 if r.not_before <= self.step_count]
        cand.sort()
        take = cand[:n]
        picked = [(self.requeue if pool == 0 else self.queue)[i]
                  for _, pool, i in take]
        for _, pool, i in sorted(take, key=lambda t: t[2], reverse=True):
            del (self.requeue if pool == 0 else self.queue)[i]
        return picked

    def _admit(self):
        """Admit waiting requests into free slots, one batched prefill +
        one batched cache scatter per prompt-length group.  Admission
        is latency-class-aware (see _take_waiting): higher
        priority_class first; within a class, preempted checkpoints on
        the requeue deque ahead of never-admitted queue entries (the
        starvation guard: a checkpoint is never stuck behind fresh
        traffic of its own class), FIFO within each pool; requests
        backing off after a fault requeue are skipped with order
        preserved, so a flapping request cannot hot-loop re-prefill.
        Under the "priority" policy a waiting request whose class
        strictly exceeds an active slot's also evicts at admission
        time (see _priority_admission_preempt)."""
        if not (self.requeue or self.queue):
            return
        with span("engine.admit"):
            if self.paged and self.sc.preempt_policy == "priority":
                self._priority_admission_preempt()
            while self._free_slots() and (self.requeue or self.queue):
                free = len(self._free_slots())
                batch: List[Request] = self._take_waiting(free)
                if not batch:
                    # everything waiting is backing off; idle steps keep
                    # ticking step_count, so the stamps always expire
                    return
                groups: Dict[int, List[Request]] = {}
                for r in batch:
                    # effective prompt: original tokens plus everything
                    # already generated (empty for fresh requests, the
                    # checkpoint for requeued ones)
                    groups.setdefault(len(r.tokens) + len(r.out),
                                      []).append(r)
                admitted = 0
                for plen, reqs in groups.items():
                    admitted += self._admit_group(reqs, plen)
                # a request finishing *at* admission (EOS on the prefill
                # sample, max_new=1) frees its slot immediately; loop so
                # the queue can backfill it this same scheduling round.
                # Zero admissions means the page pool is out of capacity
                # for everything queued — stop; frees will unblock it.
                if admitted == 0:
                    return

    def _requeue_front(self, reqs: List[Request]) -> None:
        """Push un-admittable requests back where they came from,
        preserving order: preempted checkpoints to the requeue deque,
        fresh requests to the queue head."""
        for r in reversed(reqs):
            if r.preempts:
                self.requeue.appendleft(r)
            else:
                self.queue.insert(0, r)

    def _admit_group(self, reqs: List[Request], plen: int) -> int:
        """Admit one same-effective-prompt-length group; returns
        #admitted.  Requests the page pool cannot hold right now go
        back to their deque head (admission is the capacity check —
        allocation below can then never fail, so failure can't leak
        half a group)."""
        with span("engine.admit_group", k=len(reqs), plen=plen):
            if self.paged:
                # +1: the first decode step writes at position plen,
                # which may sit on the page after the prompt's last.  A
                # requeued checkpoint at plen == cache_len finishes at
                # admission and never decodes, so its need is capped at
                # the cache.
                need = paging.pages_per_slot(
                    min(plen + 1, self.sc.cache_len), self.page_size)
                fit = self.allocator.available // max(need, 1)
                if self.windowed:
                    need_w = len(paging.live_window_pages(
                        min(plen + 1, self.sc.cache_len), self.window,
                        self.page_size))
                    fit = min(fit,
                              self.allocator_w.available // max(need_w, 1))
                if fit < len(reqs):
                    self._requeue_front(reqs[fit:])
                    reqs = reqs[:fit]
                if not reqs:
                    return 0
            slots = self._free_slots()[:len(reqs)]
            with span("engine.admit.prefill") as sp:
                toks = jnp.asarray([r.tokens + r.out for r in reqs],
                                   jnp.int32)
                logits, cache1 = self._jit_call("prefill", self._prefill,
                                                sp, self.params, toks)
                self._key, sub = jax.random.split(self._key)
                first = self._sample(logits, sub)
            with span("engine.admit.sync"):
                first_h = np.asarray(_device_get(first))  # one sync/group
            with span("engine.admit.scatter") as sp:
                self._scatter_group(reqs, plen, slots, first_h, cache1, sp)
        return len(reqs)

    def _scatter_group(self, reqs: List[Request], plen: int,
                       slots: List[int], first_h, cache1, sp) -> None:
        """Give an admitted group its pages and history rows, write its
        prefill cache and first tokens into the slots (one ``_admit_fn``
        call), and take it into the host's slot bookkeeping."""
        k = len(reqs)
        # token-history rows for the spec proposer: position p holds the
        # token cached at row p.  Host-built at the fixed width W so the
        # admit retrace stays keyed on group size only; the prefill
        # sample is NOT included — it is cur_tok, and the spec step
        # writes it at position plen itself.
        hist_rows = np.zeros((k, self.sc.cache_len + 1), np.int32)
        for i, r in enumerate(reqs):
            hist_rows[i, :plen] = r.tokens + r.out

        page_rows = None
        page_rows_w = None
        if self.paged:
            rows = np.full((k, self.pages_per_slot), paging.NULL_PAGE,
                           np.int32)
            n_pages = paging.pages_per_slot(plen, self.page_size)
            for i, slot in enumerate(slots):
                rows[i, :n_pages] = self.allocator.alloc_many(n_pages)
                self.block_tables[slot] = rows[i]
            page_rows = jnp.asarray(rows)
            self._bt_dirty = True
            if self.windowed:
                # window group: allocate only the prompt's live window
                # pages.  rows_w is global-page-indexed (full timeline
                # width) for the prefill scatter; the persistent ring
                # table keeps the same pages at column g % T_w.
                rows_w = np.full((k, self.pages_per_slot),
                                 paging.NULL_PAGE, np.int32)
                for i, slot in enumerate(slots):
                    for g in paging.live_window_pages(
                            plen, self.window, self.page_size):
                        rows_w[i, g] = self.allocator_w.alloc()
                        self.block_tables_w[slot, g % self.tw] = rows_w[i, g]
                    self.win_first[slot] = paging.first_live_page(
                        plen, self.window, self.page_size)
                page_rows_w = jnp.asarray(rows_w)
                self._btw_dirty = True

        admit_active = np.ones((k,), bool)
        for i, (req, slot) in enumerate(zip(reqs, slots)):
            req.out.append(int(first_h[i]))
            hit_eos = (self.sc.eos_id is not None
                       and first_h[i] == self.sc.eos_id)
            # plen + 1 > cache_len: a requeued checkpoint whose cache is
            # full after re-prefill — its re-prefill sample IS the final
            # token the un-preempted run would have emitted
            if (hit_eos or len(req.out) >= self._req_max_new(req)
                    or plen + 1 > self.sc.cache_len):
                admit_active[i] = False
        n_out_vals = np.asarray([len(r.out) for r in reqs], np.int32)
        max_new_vals = np.asarray([self._req_max_new(r) for r in reqs],
                                  np.int32)

        (self.caches, self.lengths, self.cur_tok, self.active_mask,
         self.n_out, self.tok_hist, self.max_new_dev) = self._jit_call(
            "admit", self._admit_fn, sp,
            self.caches, self.lengths, self.cur_tok, self.active_mask,
            self.n_out, self.tok_hist, self.max_new_dev, cache1,
            jnp.asarray(first_h), jnp.asarray(slots, jnp.int32),
            jnp.full((k,), plen, jnp.int32), jnp.asarray(admit_active),
            jnp.asarray(n_out_vals), jnp.asarray(max_new_vals),
            page_rows, jnp.asarray(hist_rows), page_rows_w)

        tel = self.telemetry
        for i, (req, slot) in enumerate(zip(reqs, slots)):
            self._seq += 1
            self._admit_seq[slot] = self._seq
            if self.spec and self._spec_ok_h[slot] == req.spec_disabled:
                # degrade rung: a request that repeatedly faulted inside
                # speculative steps decodes 1 token/step from now on
                self._spec_ok_h[slot] = not req.spec_disabled
                self._spec_ok_dirty = True
            if tel is not None:
                tel.on_admit(req, slot, self.step_count)
                # admission always commits one sampled token (the
                # prefill logits); it is the request's FIRST generated
                # token only on fresh admission — re-prefills resume an
                # out that already has history
                if len(req.out) == 1:
                    tel.on_first_token(req, slot, self.step_count)
                tel.on_tokens(req, slot, self.step_count, 1)
            if admit_active[i]:
                self.active[slot] = req
                self._active_h[slot] = True
                self._len_h[slot] = plen
            else:
                req.done = True            # finished at prefill
                if tel is not None:
                    tel.on_finish(req, slot, self.step_count)
                self._release(slot)

    def _release(self, slot: int):
        """Return a slot (and its pages) to the pool."""
        self.active[slot] = None
        self._active_h[slot] = False
        self._len_h[slot] = 0
        if self.paged:
            # reclaim filters the row's NULL_PAGE entries; the allocator
            # itself stays strict (double-free / null-page freeing raise)
            self.allocator.reclaim(self.block_tables[slot])
            self.block_tables[slot] = paging.NULL_PAGE
            self._bt_dirty = True
            if self.windowed:
                self.allocator_w.reclaim(self.block_tables_w[slot])
                self.block_tables_w[slot] = paging.NULL_PAGE
                self.win_first[slot] = 0
                self._btw_dirty = True

    # -- preempt/requeue scheduler ----------------------------------------
    def _select_victim(self, needy: int) -> Optional[int]:
        """Pick the slot to preempt so ``needy`` can take a page.

        Never the needy slot itself: preempting the slot that is asking
        for a page cannot help it (its checkpoint needs at least the
        pages it already holds), and excluding it guarantees the grower
        makes progress, which bounds the preempt/re-admit churn.
        Returns None when no other slot is active.
        """
        cands = [int(s) for s in np.nonzero(self._active_h)[0]
                 if int(s) != needy]
        if not cands:
            return None
        if self.sc.preempt_policy == "lru":
            # least-recent admit; a just-re-admitted checkpoint carries
            # the newest stamp, so lru never thrashes it
            return min(cands, key=lambda s: self._admit_seq[s])
        if self.sc.preempt_policy == "priority":
            # SLO-aware: lowest priority_class absorbs the preemption;
            # within a class the oldest admission stamp goes first (the
            # lru rule), so equal-priority traffic degrades exactly like
            # "lru" and a re-admitted checkpoint is never thrashed
            return min(cands,
                       key=lambda s: (self.active[s].priority_class,
                                      self._admit_seq[s]))
        # "shortest": fewest generated tokens = least work thrown away;
        # admission stamp breaks ties deterministically (oldest first)
        return min(cands, key=lambda s: (len(self.active[s].out),
                                         self._admit_seq[s]))

    def _priority_admission_preempt(self) -> None:
        """Admission-time eviction for the "priority" policy: while no
        slot is free and the best backoff-eligible waiting request's
        class *strictly* exceeds the lowest active slot's, checkpoint
        that slot so the high-class request admits this step instead of
        queueing behind a full batch of low-class decodes.  Strict
        inequality means equal-priority traffic never churns, and the
        evicted checkpoint re-enters via the requeue deque ahead of
        fresh traffic (the PR 5 starvation guard), so every class keeps
        draining — the liveness argument in DESIGN.md §17."""
        while not self._free_slots():
            waiting = [r.priority_class
                       for pool in (self.requeue, self.queue)
                       for r in pool if r.not_before <= self.step_count]
            if not waiting:
                return
            slots = [int(s) for s in np.nonzero(self._active_h)[0]]
            if not slots:
                return
            victim = min(slots, key=lambda s: (
                self.active[s].priority_class, self._admit_seq[s]))
            if max(waiting) <= self.active[victim].priority_class:
                return
            self._preempt(victim)

    def _preempt(self, slot: int) -> None:
        """Checkpoint ``slot`` onto the requeue deque and reclaim its
        pages.  The checkpoint is pure host state (prompt + tokens
        generated so far, already in ``req.out``); the device rows are
        parked exactly like a released slot's — active mask off, block
        table reset to the null page so the stale ``cur_tok`` keeps
        scattering its KV into trash until the slot is reused."""
        with span("engine.preempt", slot=slot):
            req = self.active[slot]
            eff = len(req.tokens) + len(req.out)
            usable = self.allocator.usable
            if paging.pages_per_slot(min(eff + 1, self.sc.cache_len),
                                     self.page_size) > usable:
                # the checkpoint could never be re-admitted: requeueing it
                # would spin forever, so surface the sizing problem now
                raise RuntimeError(
                    f"request {req.rid}: checkpoint of {eff} tokens needs "
                    f"more KV pages than the pool's usable capacity ({usable} "
                    f"x {self.page_size}); raise ServeConfig.total_pages")
            req.preempts += 1
            self.metrics.counter("serve.preemptions").inc()
            self.metrics.counter(
                f"serve.preemptions.{self.sc.preempt_policy}").inc()
            self.requeue.append(req)
            self.metrics.gauge("serve.requeue_peak_depth").set_max(
                len(self.requeue))
            if self.telemetry is not None:
                self.telemetry.on_preempt(req, slot, self.step_count)
            # park the device rows: the jitted step must stop advancing this
            # slot *before* the next decode, not at its end like finish does
            self.active_mask = self.active_mask.at[slot].set(False)
            self._release(slot)

    def _ensure_pages(self, horizon: int = 1):
        """Allocate the pages the next ``horizon`` tokens of each active
        slot write into, when the slot is about to cross a page
        boundary.  Plain decode ensures one token ahead; the spec step
        ensures its whole ``spec_k + 1`` verify window (capped at the
        cache) and rolls unused pages back afterwards.  An
        oversubscribed pool (explicit total_pages) can run dry here
        mid-decode: with ``preempt_policy="fail"`` that raises the
        allocator's actionable error; under ``"lru"``/``"shortest"`` a
        victim slot is checkpointed onto the requeue deque (freeing its
        pages) until the needy slot can allocate."""
        for slot in np.nonzero(self._active_h)[0]:
            slot = int(slot)
            if not self._active_h[slot]:       # preempted earlier in loop
                continue
            target = min(int(self._len_h[slot]) + horizon,
                         self.sc.cache_len)
            if self.windowed:
                # eager reclaim first: pages the advancing window left
                # behind go back to the pool *before* anything allocates
                # this step, so window-pool pressure stays O(window)
                new_first = paging.first_live_page(
                    target, self.window, self.page_size)
                freed = paging.free_prefix(
                    self.allocator_w, self.block_tables_w[slot],
                    int(self.win_first[slot]), new_first)
                if freed:
                    self.metrics.counter(
                        "serve.window_prefix_frees").inc(freed)
                    self._btw_dirty = True
                self.win_first[slot] = new_first
            needed = paging.pages_per_slot(target, self.page_size)
            faulted = False
            for j in range(needed):
                if self.block_tables[slot, j] != paging.NULL_PAGE:
                    continue
                if self._alloc_deny:
                    # injected allocator failure, beyond what preemption
                    # can absorb: the needy slot itself goes down the
                    # recovery ladder.  The deny is sticky until it
                    # bites (a scheduled injection always manifests)
                    # and one-shot once it has.
                    self._alloc_deny = False
                    self._fault_requeue(slot, "alloc_fail")
                    faulted = True
                    break
                if self.sc.preempt_policy != "fail":
                    while self.allocator.available == 0:
                        victim = self._select_victim(slot)
                        if victim is None:
                            # sole active sequence holding every usable
                            # page: nothing to preempt, cannot continue
                            raise RuntimeError(
                                f"KV page pool exhausted: slot {slot} is "
                                f"the only active sequence and already "
                                f"holds all {self.allocator.usable} "
                                f"usable pages; raise "
                                f"ServeConfig.total_pages "
                                f"(or lower cache_len)")
                        self._preempt(victim)
                self.block_tables[slot, j] = self.allocator.alloc()
                self._bt_dirty = True
            if not faulted:
                self._ensured[slot] = needed
                if self.windowed:
                    # the ring column a fresh page lands in was vacated
                    # by free_prefix (its old occupant is exactly T_w
                    # pages behind, always outside the live window), so
                    # with default pool sizing this alloc cannot run
                    # dry; an explicit undersized total_pages_window
                    # falls back on preemption like the global pool
                    for g in paging.live_window_pages(
                            target, self.window, self.page_size):
                        col = g % self.tw
                        if self.block_tables_w[slot, col] != \
                                paging.NULL_PAGE:
                            continue
                        if self.sc.preempt_policy != "fail":
                            while self.allocator_w.available == 0:
                                victim = self._select_victim(slot)
                                if victim is None:
                                    raise RuntimeError(
                                        f"window KV page pool exhausted: "
                                        f"slot {slot} is the only active "
                                        f"sequence; raise "
                                        f"ServeConfig.total_pages_window")
                                self._preempt(victim)
                        self.block_tables_w[slot, col] = \
                            self.allocator_w.alloc()
                        self._btw_dirty = True

    # -- fault injection + recovery ladder --------------------------------
    def _draw_faults(self):
        """Query the fault plan exactly once for this step.  kv_corrupt
        is applied immediately (a pool-page NaN write); alloc_fail arms
        the sticky allocator deny; nan_logits slots and the stall sleep
        are returned for the jitted step / watchdog window."""
        nan_slots: List[int] = []
        stall = 0.0
        if self.fault_plan is None:
            return nan_slots, stall
        active = [int(s) for s in np.nonzero(self._active_h)[0]]
        for kind, slot in self.fault_plan.faults_for(self.step_count,
                                                     active):
            if self.telemetry is not None:
                self.telemetry.on_fault_injected(
                    self.step_count, kind,
                    int(slot) if slot is not None else None)
            if kind == "alloc_fail":
                self._alloc_deny = True
            elif kind == "stall":
                stall = max(stall, self.fault_plan.stall_s)
            elif kind == "nan_logits":
                nan_slots.append(int(slot))
            elif kind == "kv_corrupt":
                self._corrupt_slot(int(slot))
        return nan_slots, stall

    def _corrupt_slot(self, slot: int) -> None:
        """Poison the slot's first live page (always inside the read
        prefix: position 0 lives there and active implies length >= 1)."""
        page = int(self.block_tables[slot, 0])
        if page != paging.NULL_PAGE:
            self.caches = corrupt_page(self.caches, page)

    def _nan_mask(self, nan_slots: List[int]):
        mask = np.zeros((self.sc.slots,), bool)
        for s in nan_slots:
            if self._active_h[s]:     # target may have been preempted
                mask[s] = True
        return jnp.asarray(mask)

    def _watchdog_tripped(self, t0: float) -> bool:
        """Deadline check around one dispatch + device_get.  On a trip
        the caller discards the step's un-committed results (device
        state holds the *previous* step) and every active slot goes
        down the recovery ladder — re-prefill of the committed
        checkpoint keeps greedy outputs token-identical.  Detection
        happens once the transfer returns: a device wedged hard enough
        to never return needs an external supervisor, but a stalled
        step (the injectable class) is caught and recovered here."""
        if self.watchdog_s is None:
            return False
        if time.perf_counter() - t0 <= self.watchdog_s:
            return False
        self.metrics.counter("serve.watchdog_trips").inc()
        self.last_watchdog_trip = {"step": self.step_count,
                                   "wall_time_s": time.time()}
        if self.telemetry is not None:
            self.telemetry.on_watchdog_trip(self.step_count)
        for slot in np.nonzero(self._active_h)[0]:
            self._fault_requeue(int(slot), "stall")
        return True

    def _handle_bad_slot(self, slot: int) -> None:
        """The NaN/Inf sentinel flagged ``slot``: discriminate KV-pool
        corruption from a transient compute fault by scanning the
        slot's live pages (device reductions on the fault path only),
        quarantine whatever is corrupted, then requeue the request."""
        kind = "nan_logits"
        if self.paged:
            live = [int(p) for p in self.block_tables[slot]
                    if int(p) != paging.NULL_PAGE]
            corrupt = nonfinite_pages(self.caches, live)
            if corrupt:
                kind = "kv_corrupt"
                # quarantine first (pages leave the allocated set), and
                # null the table entries so _release's reclaim does not
                # try to free what is no longer leased
                self.allocator.quarantine(corrupt)
                cset = set(corrupt)
                row = self.block_tables[slot]
                for j in range(len(row)):
                    if int(row[j]) in cset:
                        row[j] = paging.NULL_PAGE
                self._bt_dirty = True
        self._fault_requeue(slot, kind)

    def _fault_requeue(self, slot: int, kind: str) -> None:
        """One rung down the recovery ladder for a faulted slot: park
        the device rows exactly like a preemption, spend one unit of
        the request's retry budget, stamp the exponential backoff, and
        checkpoint it onto the same requeue deque preemption uses —
        re-prefill reproduces the committed tokens exactly under
        greedy decoding.  An exhausted budget, or a pool quarantined
        below what the checkpoint needs, finishes the request with the
        explicit ``failed`` status instead of raising."""
        req = self.active[slot]
        self.active_mask = self.active_mask.at[slot].set(False)
        req.retries += 1
        if self.spec:
            req.spec_faults += 1
            if (req.spec_faults >= self.sc.spec_disable_after
                    and not req.spec_disabled):
                req.spec_disabled = True
                if self.telemetry is not None:
                    self.telemetry.on_spec_degraded(req, slot,
                                                    self.step_count)
        eff = len(req.tokens) + len(req.out)
        need = (paging.pages_per_slot(min(eff + 1, self.sc.cache_len),
                                      self.page_size)
                if self.paged else 0)
        if req.retries > self.sc.max_retries \
                or (self.paged and need > self.allocator.usable):
            req.failed = True
            self.metrics.counter("serve.failed_requests").inc()
            if self.telemetry is not None:
                self.telemetry.on_fail(req, slot, self.step_count, kind)
            self._release(slot)
            return
        self.metrics.counter(f"serve.recoveries.{kind}").inc()
        self.last_recovery = {"step": self.step_count, "kind": kind,
                              "wall_time_s": time.time()}
        if self.telemetry is not None:
            self.telemetry.on_fault_requeue(req, slot, self.step_count,
                                            kind)
        req.not_before = (self.step_count + self.sc.retry_backoff
                          * (2 ** (req.retries - 1)))
        self.requeue.append(req)
        self.metrics.gauge("serve.requeue_peak_depth").set_max(
            len(self.requeue))
        self._release(slot)

    def audit(self) -> List[str]:
        """paging.audit over the live scheduler state: allocator
        conservation, live-prefix integrity, no double leases, in_use
        == sum of per-slot page needs.  Empty list = consistent (dense
        engines have no pool to audit).  The chaos/serve/oversub/spec
        smoke gates call this after every step."""
        if not self.paged:
            return []
        probs = paging.audit(self.allocator, self.block_tables,
                             self._len_h, self._active_h, self.page_size)
        if self.windowed:
            probs += ["window: " + p for p in paging.audit(
                self.allocator_w, self.block_tables_w, self._len_h,
                self._active_h, self.page_size, window=self.window)]
        return probs

    # -- main loop ---------------------------------------------------------
    def step(self) -> bool:
        """One decode step for all active slots.  Returns busy-ness.

        Results are held in locals and committed only after the step's
        single device_get lands inside the watchdog deadline; sentinel-
        flagged slots commit nothing and route through the recovery
        ladder instead."""
        with span("engine.step") as sp:
            self.step_count += 1
            self._admit()
            if not self._active_h.any():
                return False
            sp.set_metadata(batch=int(self._active_h.sum()))
            if self.paged:
                self._count_kv_pages()
            nan_slots, stall = self._draw_faults()
            if self.spec:
                return self._spec_step(nan_slots, stall)
            return self._plain_step(nan_slots, stall)

    def _count_kv_pages(self):
        """Table entries the paged kernel reads this step against those
        its table holds: pages of each active slot's post-write length
        (``serve.decode.kv_pages_live``) and slots x table width
        (``serve.decode.kv_pages_table``), from host state alone."""
        live = -(-(self._len_h[self._active_h] + 1) // self.page_size)
        self.metrics.counter("serve.decode.kv_pages_live").inc(
            int(live.sum()))
        self.metrics.counter("serve.decode.kv_pages_table").inc(
            self.block_tables.size)

    def _plain_step(self, nan_slots: List[int], stall: float) -> bool:
        """One plain decode step: ensure pages, run the jitted step, take
        its one device_get, then commit each slot's token."""
        with span("engine.step.pages"):
            if self.paged:
                self._ensure_pages()
                if not self._active_h.any():   # alloc_fail took the last
                    return True
                if self._bt_dirty:   # re-upload only when tables changed
                    self._bt_dev = jnp.asarray(self.block_tables)
                    self._bt_dirty = False
                bt = self._bt_dev
                if self.windowed:
                    if self._btw_dirty:
                        self._btw_dev = jnp.asarray(self.block_tables_w)
                        self._btw_dirty = False
                    bt = {"global": self._bt_dev, "window": self._btw_dev}
            else:
                bt = None
        with span("engine.step.dispatch") as sp:
            self._key, sub = jax.random.split(self._key)
            eos = jnp.int32(self.sc.eos_id if self.sc.eos_id is not None
                            else -1)
            t0 = time.perf_counter()
            (next_tok, new_lengths, new_active, new_n_out, done, bad,
             emitted, new_caches) = self._jit_call(
                "step", self._step_fn, sp,
                self.params, self.caches, self.cur_tok, self.lengths,
                self.active_mask, self.n_out, sub, eos, self.max_new_dev,
                bt, self._nan_mask(nan_slots))
        with span("engine.step.sync"):
            if stall:
                time.sleep(stall)                   # injected device stall
            # THE one sync/step — the emitted-token counter piggybacks here
            nt, dn, bh, em = _device_get((next_tok, done, bad, emitted))
        with span("engine.step.commit"):
            if self._watchdog_tripped(t0):
                return True         # step discarded; active slots requeued
            self.lengths, self.active_mask, self.n_out = \
                new_lengths, new_active, new_n_out
            self.caches = new_caches
            self.cur_tok = next_tok
            nt, dn, bh = np.asarray(nt), np.asarray(dn), np.asarray(bh)
            tel = self.telemetry
            n_bad = 0
            for slot in np.nonzero(self._active_h)[0]:
                slot = int(slot)
                if bh[slot]:
                    n_bad += 1
                    self._handle_bad_slot(slot)
                    continue
                req = self.active[slot]
                req.out.append(int(nt[slot]))
                self._len_h[slot] += 1
                if tel is not None:
                    tel.on_tokens(req, slot, self.step_count, 1)
                if dn[slot]:
                    req.done = True
                    if tel is not None:
                        tel.on_finish(req, slot, self.step_count)
                    self._release(slot)
            if tel is not None:
                tel.on_step(self.step_count, emitted=int(em),
                            bad_slots=n_bad,
                            pools=(self._pool_pressure_brief()
                                   if self.paged else None))
        return True

    def _spec_step(self, nan_slots: List[int], stall: float) -> bool:
        """One speculative verify step for all active slots: ensure the
        whole window's pages, run the jitted draft+verify+accept step,
        then commit accepted tokens and roll rejected pages back by
        truncating each block-table suffix (still exactly ONE device_get
        per step).  Invariant restored at every step boundary: in_use ==
        sum over active slots of pages_per_slot(length).  The same
        sentinel/watchdog/recovery contract as the plain step applies;
        a sentinel-flagged slot skips commit *and* rollback — release
        reclaims its whole ensured row."""
        k1 = self.sc.spec_k + 1
        with span("engine.step.pages"):
            self._ensure_pages(horizon=k1)
            if not self._active_h.any():   # alloc_fail took the last slot
                return True
            if self._bt_dirty:
                self._bt_dev = jnp.asarray(self.block_tables)
                self._bt_dirty = False
            if self._spec_ok_dirty:
                self._spec_ok_dev = jnp.asarray(self._spec_ok_h)
                self._spec_ok_dirty = False
        with span("engine.step.dispatch") as sp:
            eos = jnp.int32(self.sc.eos_id if self.sc.eos_id is not None
                            else -1)
            t0 = time.perf_counter()
            (y, n_emit, new_lengths, new_active, new_n_out, done, bad,
             new_caches, new_hist, new_cur) = self._jit_call(
                "spec", self._spec_fn, sp,
                self.params, self.caches, self.tok_hist, self.cur_tok,
                self.lengths, self.active_mask, self.n_out, eos,
                self.max_new_dev, self._bt_dev, self._nan_mask(nan_slots),
                self._spec_ok_dev)
        with span("engine.step.sync"):
            if stall:
                time.sleep(stall)                   # injected device stall
            yh, ne, dn, bh = _device_get((y, n_emit, done, bad))  # THE sync
        with span("engine.step.commit"):
            if self._watchdog_tripped(t0):
                return True         # step discarded; active slots requeued
            self.lengths, self.active_mask, self.n_out = \
                new_lengths, new_active, new_n_out
            self.caches, self.tok_hist, self.cur_tok = \
                new_caches, new_hist, new_cur
            yh, ne, dn, bh = (np.asarray(yh), np.asarray(ne), np.asarray(dn),
                              np.asarray(bh))
            self.metrics.counter("serve.spec_steps").inc()
            tel = self.telemetry
            n_bad = 0
            accepted = 0
            for slot in np.nonzero(self._active_h)[0]:
                slot = int(slot)
                if bh[slot]:
                    n_bad += 1
                    self._handle_bad_slot(slot)   # release reclaims the row
                    continue
                req = self.active[slot]
                m = int(ne[slot])
                req.out.extend(int(t) for t in yh[slot, :m])
                self._len_h[slot] += m
                self.metrics.counter("serve.spec_emitted").inc(m)
                accepted += m
                if tel is not None and m > 0:
                    tel.on_tokens(req, slot, self.step_count, m)
                if dn[slot]:
                    req.done = True
                    if tel is not None:
                        tel.on_finish(req, slot, self.step_count)
                    self._release(slot)   # reclaims the whole row, tail incl.
                else:
                    if m < k1:
                        self.metrics.counter("serve.spec_rejections").inc()
                    # rollback: drop the rejected tail's pages; rejected rows
                    # inside kept pages sit past the new length and are
                    # masked by every later read
                    keep = paging.pages_per_slot(int(self._len_h[slot]),
                                                 self.page_size)
                    if paging.truncate_suffix(self.allocator,
                                              self.block_tables[slot], keep,
                                              int(self._ensured[slot])):
                        self._bt_dirty = True
            if tel is not None:
                # ne rode the step's existing single device_get: the
                # accepted spec length per slot IS the emitted count
                tel.on_step(self.step_count, emitted=accepted,
                            bad_slots=n_bad, accepted=accepted,
                            pools=self._pool_pressure_brief())
        return True

    def run_to_completion(self, requests: List[Request],
                          max_steps: int = 10_000) -> List[Request]:
        for r in requests:
            self.submit(r)
        for _ in range(max_steps):
            if not self.step() and not self.queue and not self.requeue:
                break
        return requests

    def stats(self) -> Dict[str, Any]:
        """Scheduler + allocator pressure + resilience counters (all
        host-side; no device sync).

        A compatible façade over ``self.metrics`` — the counters
        themselves live in the MetricsRegistry (see the properties
        above); callers wanting histograms or raw counter objects read
        ``eng.metrics.snapshot()`` instead."""
        d = {"preemptions": self.preemptions,
             "preemptions_by_policy": self.preemptions_by_policy,
             "requeued_waiting": len(self.requeue),
             "requeue_depth": len(self.requeue),
             "requeue_peak_depth": self.requeue_peak_depth,
             "queued_waiting": len(self.queue),
             "steps": self.step_count,
             "recoveries": self.recoveries,
             "recoveries_total": sum(self.recoveries.values()),
             "failed_requests": self.failed_requests,
             "watchdog_trips": self.watchdog_trips,
             # (step, wall-time) records for operator log correlation;
             # None until the first trip/recovery
             "last_watchdog_trip": self.last_watchdog_trip,
             "last_recovery": self.last_recovery}
        if self.fault_plan is not None:
            d["faults_injected"] = dict(self.fault_plan.injected)
        if self.paged:
            # top-level pressure keys stay the global group's (the keys
            # every existing gate reads); pool_groups breaks pressure
            # out per layer-group for hybrid models
            d.update(self.allocator.pressure())
            groups = {"global": self.allocator.pressure()}
            if self.windowed:
                groups["window"] = self.allocator_w.pressure()
                d["window_prefix_frees"] = self.window_prefix_frees
            d["pool_groups"] = groups
        if self.spec:
            d.update({"spec_steps": self.spec_steps,
                      "spec_emitted": self.spec_emitted,
                      "spec_rejections": self.spec_rejections})
        return d


def run_recording_finish_order(engine, requests: List[Request],
                               max_steps: int = 10_000) -> List[int]:
    """Run ``requests`` to completion, returning rids in finish order
    (same-step ties break deterministically in ``requests`` order).

    The scheduling-contract observer shared by the kv_quant benchmark
    gate and examples/serve_continuous.py: quantization may perturb
    logits within tolerance, so the cross-dtype invariant those assert
    is *when* each request finishes, not which tokens it sampled.
    """
    for r in requests:
        engine.submit(r)
    order: List[int] = []
    seen = set()
    for _ in range(max_steps):
        busy = engine.step()
        for r in requests:
            if r.done and r.rid not in seen:
                seen.add(r.rid)
                order.append(r.rid)
        if not busy and not engine.queue and not getattr(engine, "requeue",
                                                         ()):
            break
    return order
