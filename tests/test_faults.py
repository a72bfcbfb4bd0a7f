"""Fault-injection plane + self-healing serve loop (serve/faults.py,
DESIGN.md §14): the FaultPlan schedule, the NaN-propagation physics the
kv_corrupt injector relies on, and the engine's detect/retry/degrade/
quarantine recovery ladder with its token-identity contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.smoke import smoke_config
from repro.models.registry import build_model
from repro.serve import (FAULT_KINDS, Engine, FaultPlan, Request,
                         ServeConfig)
from repro.serve.faults import corrupt_page, nonfinite_pages

_STATE = {}


def _model():
    if "model" not in _STATE:
        cfg = smoke_config("granite-8b", num_layers=1)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        _STATE["model"] = (model, params, cfg)
    return _STATE["model"]


def _engine(plan=None, **kw):
    model, params, cfg = _model()
    base = dict(slots=2, cache_len=32, max_new_tokens=8, paged=True,
                page_size=4, max_retries=6, retry_backoff=1)
    base.update(kw)
    return Engine(model, params, ServeConfig(**base), fault_plan=plan)


def _reqs(n=4):
    return [Request(rid=i, tokens=[3 + i, 5, 7, 11][:3 + (i % 2)])
            for i in range(n)]


def _drive(eng, reqs, watchdog_s=None, max_steps=500):
    """Submit + step to drain, auditing every step; arms the watchdog
    after the first (compiling) step."""
    for r in reqs:
        eng.submit(r)
    for i in range(max_steps):
        busy = eng.step()
        if i == 0:
            eng.watchdog_s = watchdog_s
        assert eng.audit() == [], eng.audit()
        if not busy and not eng.queue and not eng.requeue:
            return reqs
    raise AssertionError(f"engine did not drain: {eng.stats()}")


def _reference_outputs():
    if "want" not in _STATE:
        reqs = _drive(_engine(), _reqs())
        assert all(r.done for r in reqs)
        _STATE["want"] = {r.rid: list(r.out) for r in reqs}
    return _STATE["want"]


# ------------------------------------------------------------ FaultPlan ----

def test_fault_plan_validates_inputs():
    with pytest.raises(ValueError, match="rate"):
        FaultPlan(rate=1.5)
    with pytest.raises(ValueError, match="unknown fault kinds"):
        FaultPlan(kinds=("kv_corrupt", "bogus"))
    with pytest.raises(ValueError, match="at least one"):
        FaultPlan(kinds=())
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultPlan().at(3, "bogus")


def test_fault_plan_seeded_draws_replay_exactly():
    """Two plans with the same seed make identical random draws — the
    property the chaos gate's token-identity assertion rests on."""
    draws = []
    for _ in range(2):
        plan = FaultPlan(rate=0.5, seed=42)
        draws.append([plan.faults_for(s, [0, 1, 2]) for s in range(40)])
    assert draws[0] == draws[1]
    fired = [f for fs in draws[0] for f in fs]
    assert fired, "rate=0.5 over 40 steps never fired"
    # memoization: re-querying a past step is stable, out of order too
    plan = FaultPlan(rate=0.5, seed=42)
    first = [plan.faults_for(s, [0, 1, 2]) for s in range(40)]
    again = [plan.faults_for(s, [0, 1, 2]) for s in reversed(range(40))]
    assert first == list(reversed(again))


def test_fault_plan_scheduled_entries_resolve_slots():
    plan = (FaultPlan().at(3, "kv_corrupt")
            .at(3, "nan_logits", slot=5).at(4, "alloc_fail"))
    # slot=None -> first active; explicit slot kept when active
    assert plan.faults_for(3, [2, 5]) == [("kv_corrupt", 2),
                                          ("nan_logits", 5)]
    # slot-targeted kinds are dropped with no active slots; alloc_fail
    # is not slot-targeted and survives
    assert plan.faults_for(5, []) == []
    plan2 = FaultPlan().at(7, "kv_corrupt").at(7, "alloc_fail")
    assert plan2.faults_for(7, []) == [("alloc_fail", None)]
    assert plan2.injected["alloc_fail"] == 1
    assert plan2.injected["kv_corrupt"] == 0      # dropped != injected


# -------------------------------------------------- NaN-propagation law ----

def test_v_pool_nan_propagates_k_pool_does_not():
    """The physics the injector is built on: NaN in a K page is
    swallowed by the paged kernel's NEG_INF guards + the caller's
    ``l == 0`` normalizer (silent zeros — undetectable), while NaN in a
    V page flows through ``p @ v`` into exactly the owning slot's
    output.  This is why corrupt_page poisons the value pool."""
    from repro.kernels.decode_attention.ops import paged_decode_attention
    b, hkv, d, ps, t = 3, 2, 16, 4, 2
    n_pages = 1 + b * t
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, 4, d), jnp.float32)
    kpg = jax.random.normal(ks[1], (hkv, n_pages, ps, d), jnp.float32)
    vpg = jax.random.normal(ks[2], (hkv, n_pages, ps, d), jnp.float32)
    bt = jnp.arange(1, n_pages, dtype=jnp.int32).reshape(b, t)
    lengths = jnp.full((b,), 6, jnp.int32)
    poison = int(bt[1, 0])                        # a page slot 1 reads
    out_k = paged_decode_attention(q, kpg.at[:, poison].set(jnp.nan), vpg,
                                   bt, lengths, page_size=ps, block_kv=ps)
    out_v = paged_decode_attention(q, kpg, vpg.at[:, poison].set(jnp.nan),
                                   bt, lengths, page_size=ps, block_kv=ps)
    fin = lambda o: [bool(jnp.all(jnp.isfinite(o[i]))) for i in range(b)]
    assert fin(out_k) == [True, True, True]       # K NaN vanishes silently
    assert fin(out_v) == [True, False, True]      # V NaN hits slot 1 only


def test_corrupt_page_targets_value_leaf_and_scan_finds_it():
    f = jnp.zeros((1, 2, 5, 4, 8), jnp.float32)   # (reps,H,pages,ps,D)
    caches = [({"kp": f, "vp": f}, {"k": f})]
    got = corrupt_page(caches, page=3)
    assert bool(jnp.all(jnp.isfinite(got[0][0]["kp"])))     # K untouched
    assert not bool(jnp.all(jnp.isfinite(got[0][0]["vp"][:, :, 3])))
    assert nonfinite_pages(got, [1, 2, 3, 4]) == [3]
    # quantized pools: the int8 value pool cannot hold NaN; the V scale
    # pool is the poisonable float leaf
    qcaches = [({"kp": f.astype(jnp.int8), "vp": f.astype(jnp.int8),
                 "ks": f[..., 0], "vs": f[..., 0]},)]
    got_q = corrupt_page(qcaches, page=2)
    assert not bool(jnp.all(jnp.isfinite(got_q[0][0]["vs"][:, :, 2])))
    assert nonfinite_pages(got_q, [2, 3]) == [2]
    with pytest.raises(ValueError, match="no paged float pool"):
        corrupt_page([({"k": f, "v": f},)], page=1)


# ---------------------------------------------------- recovery ladder ----

def test_fault_plan_requires_paged_engine():
    model, params, _ = _model()
    with pytest.raises(ValueError, match="requires paged"):
        Engine(model, params, ServeConfig(paged=False),
               fault_plan=FaultPlan())


@pytest.mark.parametrize("kind", ["nan_logits", "kv_corrupt", "alloc_fail"])
def test_single_fault_recovers_token_identical(kind):
    """One scheduled fault of each non-stall class: the engine detects
    it, requeues the slot, and the drained outputs are token-identical
    to the un-faulted greedy run."""
    want = _reference_outputs()
    eng = _engine(plan=FaultPlan().at(3, kind))
    reqs = _drive(eng, _reqs())
    assert all(r.done for r in reqs)
    assert {r.rid: list(r.out) for r in reqs} == want
    st = eng.stats()
    assert st["recoveries"][kind] >= 1, st
    assert any(r.retries > 0 for r in reqs)
    if kind == "kv_corrupt":
        assert st["quarantined"] >= 1
        # quarantined capacity never returns: the pool drains to
        # total - null - quarantined, and usable shrinks to match
        assert st["available"] == st["total_pages"] - 1 - st["quarantined"]
        assert eng.allocator.usable == st["total_pages"] - 1 \
            - st["quarantined"]
    else:
        assert st["available"] == st["total_pages"] - 1


def test_stall_watchdog_discards_step_and_recovers():
    want = _reference_outputs()
    eng = _engine(plan=FaultPlan(stall_s=0.5).at(4, "stall"))
    reqs = _drive(eng, _reqs(), watchdog_s=0.25)
    assert all(r.done for r in reqs)
    assert {r.rid: list(r.out) for r in reqs} == want
    st = eng.stats()
    assert st["watchdog_trips"] == 1
    assert st["recoveries"]["stall"] >= 1


def test_donated_caches_are_adopted_through_faults():
    """The step and admission consume (donate) the caches they are
    given: after each engine call that ran one, the previous tree is
    deleted and the engine holds the returned one, on the watchdog
    trip's path and the NaN sentinel's too, and the drained outputs
    stay token-identical to the un-faulted run."""
    want = _reference_outputs()
    eng = _engine(plan=FaultPlan(stall_s=0.5).at(4, "stall")
                  .at(7, "nan_logits"))
    reqs = _reqs()
    for r in reqs:
        eng.submit(r)
    consumed, tripped = 0, False
    for i in range(500):
        before = jax.tree.leaves(eng.caches)
        trips = eng.watchdog_trips
        busy = eng.step()
        if i == 0:
            eng.watchdog_s = 0.25
        gone = [x.is_deleted() for x in before]
        assert all(gone) or not any(gone)
        consumed += all(gone)
        if eng.watchdog_trips > trips:
            tripped = True
            assert all(gone)            # the discarded step's pool too
        assert not any(x.is_deleted() for x in jax.tree.leaves(eng.caches))
        assert eng.audit() == [], eng.audit()
        if not busy and not eng.queue and not eng.requeue:
            break
    assert tripped and consumed > len(reqs)
    assert eng.stats()["recoveries"]["nan_logits"] >= 1
    assert {r.rid: list(r.out) for r in reqs} == want


def test_retry_budget_exhaustion_fails_explicitly():
    """Past max_retries the request finishes with status='failed' —
    never an exception out of the serve loop — and the other requests
    still complete token-identically."""
    want = _reference_outputs()
    plan = FaultPlan()
    for s in range(2, 14):                        # hammer one slot
        plan.at(s, "nan_logits", slot=0)
    eng = _engine(plan=plan, max_retries=2)
    reqs = _drive(eng, _reqs())
    assert all(r.status in ("done", "failed") for r in reqs)
    failed = [r for r in reqs if r.failed]
    assert failed, "retry budget never exhausted"
    assert eng.stats()["failed_requests"] == len(failed)
    for r in reqs:
        if r.done:
            assert list(r.out) == want[r.rid]


def test_repeated_spec_faults_degrade_to_plain_decode():
    """The degrade rung: spec_disable_after spec-step faults pin the
    request to 1-token steps (row 0 of the verify window is
    bit-identical to plain decode), outputs still token-identical."""
    ref = _drive(_engine(spec_mode="ngram", spec_k=3), _reqs(2))
    plan = FaultPlan().at(2, "nan_logits", slot=0).at(3, "nan_logits",
                                                      slot=0)
    eng = _engine(plan=plan, spec_mode="ngram", spec_k=3,
                  spec_disable_after=2)
    reqs = _drive(eng, _reqs(2))
    assert all(r.done for r in reqs)
    assert any(r.spec_disabled for r in reqs)
    assert [r.out for r in reqs] == [r.out for r in ref]


def test_backoff_stamp_delays_readmission():
    """A faulted request is not re-admitted before its exponential
    backoff stamp expires (not_before quotes engine steps)."""
    eng = _engine(plan=FaultPlan().at(3, "nan_logits", slot=0),
                  retry_backoff=4)
    reqs = _reqs(1)
    for r in reqs:
        eng.submit(r)
    readmitted_at = None
    for i in range(200):
        busy = eng.step()
        if readmitted_at is None and reqs[0].retries and eng._active_h[0]:
            readmitted_at = eng.step_count
            assert eng.step_count >= reqs[0].not_before
        if not busy and not eng.queue and not eng.requeue:
            break
    assert reqs[0].done and readmitted_at is not None
    assert reqs[0].not_before > 3 + 1             # a real delay was stamped


# ----------------------------------------------------- stats / counters ----

def test_stats_exposes_scheduler_and_resilience_counters():
    """Satellite: requeue depth + per-policy preemption counts leave
    host-private state and land in stats(), alongside the fault/retry
    counters the launcher summary quotes."""
    eng = _engine()
    st = eng.stats()
    for key in ("requeue_depth", "requeue_peak_depth",
                "preemptions_by_policy", "recoveries", "recoveries_total",
                "failed_requests", "watchdog_trips", "steps"):
        assert key in st, key
    assert set(st["recoveries"]) == set(FAULT_KINDS)
    assert set(st["preemptions_by_policy"]) >= {"lru", "shortest", "fail"}

    # an oversubscribed run attributes its preemptions to the policy:
    # 4 usable pages cannot hold two slots that each grow to 3 pages
    model, params, _ = _model()
    sc = ServeConfig(slots=2, cache_len=32, max_new_tokens=8, paged=True,
                     page_size=4, total_pages=5,
                     preempt_policy="shortest")
    eng2 = Engine(model, params, sc)
    _drive(eng2, _reqs())
    st2 = eng2.stats()
    assert st2["preemptions"] > 0
    assert st2["preemptions_by_policy"]["shortest"] == st2["preemptions"]
    assert st2["requeue_peak_depth"] >= 1
    assert st2["requeue_depth"] == 0              # drained

    # with a plan attached, the injection-side counters appear too
    eng3 = _engine(plan=FaultPlan().at(2, "nan_logits"))
    _drive(eng3, _reqs(1))
    assert eng3.stats()["faults_injected"]["nan_logits"] == 1
