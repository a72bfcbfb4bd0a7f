"""Serving engine: continuous batching, slot reuse, greedy consistency,
plus regression tests for the three slot-engine bugs (prompt overflow,
early cache-full finish, stale freed slots) and paged/dense parity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.smoke import smoke_config
from repro.models.registry import build_model
from repro.serve import Engine, Request, ServeConfig
from repro.serve import engine as engine_mod

_STATE = {}


def _model():
    if "model" not in _STATE:
        cfg = smoke_config("granite-8b", num_layers=2)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        _STATE["model"] = (model, params, cfg)
    return _STATE["model"]


def _engine(slots=2, cache_len=32, max_new=4, temperature=0.0, **kw):
    model, params, cfg = _model()
    sc = ServeConfig(slots=slots, cache_len=cache_len,
                     max_new_tokens=max_new, temperature=temperature, **kw)
    return Engine(model, params, sc), model, params, cfg


def test_all_requests_complete_with_queueing():
    engine, *_ = _engine(slots=2, max_new=3)
    reqs = [Request(rid=i, tokens=[1 + i, 2, 3, 4]) for i in range(5)]
    engine.run_to_completion(reqs)
    assert all(r.done for r in reqs)
    assert all(len(r.out) == 3 for r in reqs)


def test_greedy_decode_matches_teacher_forcing():
    """Engine's greedy continuation == argmax chain via full forwards."""
    engine, model, params, cfg = _engine(slots=1, cache_len=32, max_new=3)
    prompt = [5, 9, 2, 7]
    req = Request(rid=0, tokens=list(prompt))
    engine.run_to_completion([req])

    toks = list(prompt)
    want = []
    for _ in range(3):
        logits, _ = model.prefill(params, jnp.asarray([toks], jnp.int32),
                                  32, {})
        nxt = int(jnp.argmax(logits[0]))
        want.append(nxt)
        toks.append(nxt)
    assert req.out == want, (req.out, want)


def test_slots_are_reused():
    engine, *_ = _engine(slots=1, max_new=2)
    reqs = [Request(rid=i, tokens=[3, 1, 4]) for i in range(3)]
    engine.run_to_completion(reqs)
    assert all(r.done for r in reqs)
    # after completion the pool is fully free
    assert all(s is None for s in engine.active)


def test_eos_stops_early():
    engine, model, params, cfg = _engine(slots=1, cache_len=32, max_new=8)
    # discover the greedy first token, then make it the EOS
    logits, _ = model.prefill(params, jnp.asarray([[5, 9, 2]], jnp.int32),
                              32, {})
    eos = int(jnp.argmax(logits[0]))
    engine.sc.eos_id = eos
    req = Request(rid=0, tokens=[5, 9, 2])
    engine.run_to_completion([req])
    assert req.out[-1] == eos
    assert len(req.out) < 8


# ------------------------------------------------------ bug regressions ----

def test_submit_rejects_prompt_overflowing_cache():
    """Regression: the slot engine silently admitted prompts with
    len(tokens) >= cache_len; the clamped cache write corrupted the
    slot.  submit() must reject them up front."""
    engine, *_ = _engine(slots=1, cache_len=8)
    with pytest.raises(ValueError, match="does not fit"):
        engine.submit(Request(rid=0, tokens=list(range(8))))
    with pytest.raises(ValueError, match="does not fit"):
        engine.submit(Request(rid=1, tokens=list(range(20))))
    engine.submit(Request(rid=2, tokens=list(range(7))))   # fits
    assert len(engine.queue) == 1


def test_submit_truncate_mode_keeps_prompt_tail():
    engine, *_ = _engine(slots=1, cache_len=8, on_overflow="truncate")
    req = Request(rid=0, tokens=list(range(20)))
    with pytest.warns(UserWarning, match="exceeds"):
        engine.submit(req)
    assert req.tokens == list(range(13, 20)) and req.truncated
    engine.run_to_completion([])
    assert req.done


def test_cache_full_uses_final_row():
    """Regression: the slot engine finished at lengths+1 >= cache_len,
    wasting the final cache row.  A prompt of P tokens in a cache of C
    rows must yield exactly C - P + 1 output tokens (every row written
    once) when nothing else stops decode."""
    cache_len, plen = 12, 4
    engine, *_ = _engine(slots=1, cache_len=cache_len, max_new=100)
    req = Request(rid=0, tokens=list(range(1, plen + 1)))
    engine.run_to_completion([req])
    assert req.done
    assert len(req.out) == cache_len - plen + 1, req.out


def test_freed_slot_does_not_corrupt_successor():
    """Regression: freed slots keep flowing through the batched decode
    with stale cur_tok; their writes must never corrupt a later request
    admitted into the same slot (or any other slot's stream)."""
    engine, *_ = _engine(slots=1, cache_len=32, max_new=3)
    reqs = [Request(rid=i, tokens=[7 + i, 3, 5]) for i in range(3)]
    engine.run_to_completion(reqs)

    # each request, served alone on a fresh engine, must match
    for i in range(3):
        solo_engine, *_ = _engine(slots=1, cache_len=32, max_new=3)
        solo = Request(rid=10 + i, tokens=[7 + i, 3, 5])
        solo_engine.run_to_completion([solo])
        assert solo.out == reqs[i].out, (i, solo.out, reqs[i].out)


def test_single_device_get_per_step():
    """Regression: the slot engine synced once per slot per step (plus a
    host-rebuilt active mask); the rewrite must do exactly one
    device_get per decode step."""
    engine, *_ = _engine(slots=4, cache_len=32, max_new=4)
    for i in range(4):
        engine.submit(Request(rid=i, tokens=[1 + i, 2, 3]))
    engine._admit()

    calls = []
    real = engine_mod._device_get
    engine_mod._device_get = lambda x: (calls.append(1) or real(x))
    try:
        assert engine.step()
    finally:
        engine_mod._device_get = real
    assert len(calls) == 1, f"{len(calls)} host syncs in one step"


# ------------------------------------------------------------ edge cases ----

def test_eos_sampled_at_prefill_finishes_immediately():
    """EOS as the very first sampled token: the request completes at
    admission, the slot frees, and the queue backfills the same round."""
    engine, model, params, cfg = _engine(slots=1, cache_len=32, max_new=8)
    logits, _ = model.prefill(params, jnp.asarray([[5, 9, 2]], jnp.int32),
                              32, {})
    eos = int(jnp.argmax(logits[0]))
    engine.sc.eos_id = eos
    first = Request(rid=0, tokens=[5, 9, 2])
    other = Request(rid=1, tokens=[4, 4, 4, 4])
    engine.run_to_completion([first, other])
    assert first.done and len(first.out) == 1 and first.out[0] == eos
    assert other.done and len(other.out) >= 1


def test_queue_drain_many_more_requests_than_slots():
    engine, *_ = _engine(slots=2, cache_len=32, max_new=2)
    reqs = [Request(rid=i, tokens=[1 + (i % 5), 2]) for i in range(11)]
    engine.run_to_completion(reqs)
    assert all(r.done for r in reqs)
    assert all(len(r.out) == 2 for r in reqs)
    assert all(s is None for s in engine.active)


def test_cache_full_termination_under_queue_pressure():
    """Slots that hit cache-full must free and let the queue drain."""
    engine, *_ = _engine(slots=2, cache_len=8, max_new=100)
    reqs = [Request(rid=i, tokens=[1 + i, 2, 3]) for i in range(5)]
    engine.run_to_completion(reqs)
    assert all(r.done for r in reqs)
    assert all(len(r.out) == 8 - 3 + 1 for r in reqs)


def test_temperature_sampling_deterministic_under_seed():
    def run(seed):
        engine, *_ = _engine(slots=2, cache_len=32, max_new=6,
                             temperature=0.8, seed=seed)
        reqs = [Request(rid=i, tokens=[2 + i, 9, 4]) for i in range(4)]
        engine.run_to_completion(reqs)
        return [r.out for r in reqs]

    assert run(7) == run(7)                 # same seed -> same stream
    assert run(7) != run(123)               # different seed -> diverges

    def greedy(seed):                       # greedy ignores the seed
        engine, *_ = _engine(slots=2, cache_len=32, max_new=6, seed=seed)
        req = Request(rid=0, tokens=[2, 9, 4])
        engine.run_to_completion([req])
        return req.out

    assert greedy(7) == greedy(123)


# ---------------------------------------------------------------- paged ----

def test_paged_engine_matches_dense_engine():
    """Paged and slot cache layouts must produce identical greedy
    streams over a mixed-length queued workload."""
    outs = {}
    for paged in (False, True):
        engine, _, _, cfg = _engine(slots=2, cache_len=32, max_new=4,
                                    paged=paged, page_size=8)
        reqs = [Request(rid=i, tokens=[1 + i] * (3 + i)) for i in range(5)]
        engine.run_to_completion(reqs)
        assert all(r.done for r in reqs)
        outs[paged] = [r.out for r in reqs]
    assert outs[True] == outs[False]


def test_in_place_pools_match_dense_greedy():
    """The paged engine's decode scan carries its plain pools in place
    and its step and admission consume them (donated).  Over decodes
    that cross pages in both layers, its greedy tokens equal the
    dense-cache engine's at the same seed."""
    outs = {}
    for paged in (False, True):
        engine, *_ = _engine(slots=3, cache_len=48, max_new=20,
                             paged=paged, page_size=4)
        assert engine.metrics.gauge(
            "serve.decode.pool_in_place").value == int(paged)
        reqs = [Request(rid=i, tokens=[2 + i] * (5 + 3 * i))
                for i in range(4)]
        engine.run_to_completion(reqs)
        assert all(r.done and len(r.out) == 20 for r in reqs)
        outs[paged] = [r.out for r in reqs]
    assert outs[True] == outs[False]


@pytest.mark.parametrize("arch,kv_dtype,want", [
    ("granite-8b", None, 1),          # one plain paged entry
    ("granite-8b", "int8", 0),        # scale pools: per-layer slices
    ("gemma2-2b", None, 1),           # its global layer; the window
                                      # group's kw/vw entry falls back
    ("gemma2-2b", "int8", 0),
])
def test_pool_in_place_gauge_counts_plain_paged_entries(arch, kv_dtype,
                                                        want):
    cfg = smoke_config(arch, num_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = Engine(model, params, ServeConfig(
        slots=2, cache_len=32, paged=True, page_size=8, kv_dtype=kv_dtype))
    assert engine.metrics.gauge(
        "serve.decode.pool_in_place").value == want
    assert engine.metrics.snapshot()["gauges"][
        "serve.decode.pool_in_place"] == want


def test_paged_pages_allocated_on_demand_and_freed():
    engine, *_ = _engine(slots=2, cache_len=32, max_new=8, paged=True,
                         page_size=8)
    total = engine.allocator.total_pages
    assert total == 1 + 2 * 4               # null + slots * pages_per_slot
    reqs = [Request(rid=i, tokens=[1 + i, 2, 3]) for i in range(3)]
    engine.run_to_completion(reqs)
    assert all(r.done for r in reqs)
    # all pages returned, all block-table rows reset to the null page
    assert engine.allocator.available == total - 1
    assert (engine.block_tables == 0).all()


def test_kv_page_counters_count_live_and_table_entries():
    """Each paged step adds the pages of every active slot's post-write
    length and the table's slots x width, from host state alone."""
    engine, *_ = _engine(slots=2, cache_len=32, max_new=8, paged=True,
                         page_size=8)
    seen = []
    plain = engine._plain_step

    def record(*a):
        seen.append(engine._len_h[engine._active_h] + 1)
        return plain(*a)
    engine._plain_step = record
    reqs = [Request(rid=i, tokens=[1 + i] * (3 + 6 * i)) for i in range(3)]
    engine.run_to_completion(reqs)
    assert all(r.done for r in reqs) and seen
    snap = engine.metrics.snapshot()["counters"]     # as --metrics-out has it
    assert snap["serve.decode.kv_pages_table"] == len(seen) * 2 * 4
    assert snap["serve.decode.kv_pages_live"] == sum(
        int(np.sum(-(-lens // 8))) for lens in seen)


def test_paged_pool_exhaustion_requeues_instead_of_losing_requests():
    """Regression: with an undersized (oversubscribed) pool, a group
    admission that cannot get pages must requeue — not leak pages, not
    drop requests, not wedge the engine."""
    # 3 usable pages of 4 tokens; each 6-token prompt needs 2 pages, so
    # only one of the two requests can hold pages at a time.
    engine, *_ = _engine(slots=2, cache_len=16, max_new=2, paged=True,
                         page_size=4, total_pages=4)
    reqs = [Request(rid=i, tokens=[1 + i] * 6) for i in range(2)]
    engine.run_to_completion(reqs)
    assert all(r.done for r in reqs)
    assert all(len(r.out) == 2 for r in reqs)
    assert engine.allocator.available == 3      # nothing leaked
    # and a prompt no empty pool could ever hold is rejected up front
    with pytest.raises(ValueError, match="whole pool"):
        engine.submit(Request(rid=9, tokens=[1] * 14))


def _oversub_engine(policy="lru", total_pages=5, **kw):
    """2 slots x 4 pages of 8 tokens needed, pool holds 4 usable: decode
    past length 8 crosses page boundaries and runs the pool dry."""
    return _engine(slots=2, cache_len=32, max_new=24, paged=True,
                   page_size=8, total_pages=total_pages,
                   preempt_policy=policy, **kw)


def _oversub_requests(n=4):
    return [Request(rid=i, tokens=[1 + i] * 6) for i in range(n)]


def test_fail_policy_raises_actionable_error_mid_decode():
    """Regression: preempt_policy="fail" preserves the pre-scheduler
    behavior — the allocator running dry mid-decode raises its
    actionable message instead of preempting."""
    engine, *_ = _oversub_engine(policy="fail")
    with pytest.raises(RuntimeError, match="exhausted"):
        engine.run_to_completion(_oversub_requests(2))
    assert engine.preemptions == 0


def test_victim_selection_per_policy():
    """lru picks the least-recently-admitted slot, shortest the one
    with the fewest generated tokens (admit stamp breaks ties); the
    needy slot itself is never a victim."""
    engine, *_ = _engine(slots=3, cache_len=32, max_new=4, paged=True,
                         page_size=8)
    for s, (seq, n_gen) in enumerate([(5, 1), (2, 7), (9, 3)]):
        engine.active[s] = Request(rid=s, tokens=[1], out=[0] * n_gen)
        engine._active_h[s] = True
        engine._admit_seq[s] = seq

    engine.sc.preempt_policy = "lru"
    assert engine._select_victim(0) == 1        # oldest admit stamp
    assert engine._select_victim(1) == 0        # never the needy slot
    engine.sc.preempt_policy = "shortest"
    assert engine._select_victim(1) == 0        # fewest generated
    assert engine._select_victim(0) == 2
    # sole active sequence -> no victim
    engine._active_h[:] = False
    engine._active_h[0] = True
    assert engine._select_victim(0) is None


def test_priority_victim_selection():
    """The "priority" policy evicts the lowest priority_class first,
    oldest admit stamp breaking ties within a class; the needy slot is
    never a victim (ISSUE 10's SLO-aware victim ordering)."""
    engine, *_ = _engine(slots=3, cache_len=32, max_new=4, paged=True,
                         page_size=8, preempt_policy="priority")
    for s, (seq, pc) in enumerate([(5, 2), (2, 0), (9, 0)]):
        engine.active[s] = Request(rid=s, tokens=[1], priority_class=pc)
        engine._active_h[s] = True
        engine._admit_seq[s] = seq
    assert engine._select_victim(0) == 1   # lowest class, oldest stamp
    assert engine._select_victim(1) == 2   # never the needy slot
    engine.active[2].priority_class = 1
    assert engine._select_victim(0) == 1   # class outranks admit stamp
    engine._active_h[1] = False
    assert engine._select_victim(0) == 2


def test_priority_admission_ordering():
    """_take_waiting admits by class first (requeued checkpoints still
    beat fresh arrivals *within* a class — the per-class starvation
    guard), and reduces to exact legacy FIFO when priorities are
    uniform."""
    engine, *_ = _engine(slots=2, cache_len=32, max_new=4, paged=True,
                         page_size=8, preempt_policy="priority")
    engine.queue.extend([
        Request(rid=0, tokens=[1], priority_class=0),
        Request(rid=1, tokens=[1], priority_class=2),
        Request(rid=2, tokens=[1], priority_class=1),
    ])
    engine.requeue.append(Request(rid=3, tokens=[1], priority_class=1))
    got = [r.rid for r in engine._take_waiting(4)]
    # class 2 first, then class 1 with the requeued checkpoint (rid 3)
    # ahead of the fresh arrival (rid 2), then class 0
    assert got == [1, 3, 2, 0]
    assert not engine.queue and not engine.requeue

    # uniform priorities: requeue pool strictly first, then queue FIFO
    engine.requeue.extend([Request(rid=10, tokens=[1]),
                           Request(rid=11, tokens=[1])])
    engine.queue.extend([Request(rid=12, tokens=[1]),
                         Request(rid=13, tokens=[1])])
    assert [r.rid for r in engine._take_waiting(3)] == [10, 11, 12]
    assert [r.rid for r in engine._take_waiting(3)] == [13]

    # a retry backoff (not_before in the future) is skipped either way
    held = Request(rid=20, tokens=[1], priority_class=5)
    held.not_before = engine.step_count + 10
    engine.queue.append(held)
    engine.queue.append(Request(rid=21, tokens=[1]))
    assert [r.rid for r in engine._take_waiting(2)] == [21]
    assert [r.rid for r in engine.queue] == [20]


def test_per_request_max_new_budget():
    """Request.max_new caps that request's decode independently of the
    batch (the jitted finish check reads the per-slot vector), and is
    itself capped by ServeConfig.max_new_tokens."""
    engine, *_ = _engine(slots=2, cache_len=32, max_new=6, paged=True,
                         page_size=8)
    reqs = [Request(rid=0, tokens=[3, 1, 4], max_new=2),
            Request(rid=1, tokens=[3, 1, 4]),            # engine default
            Request(rid=2, tokens=[3, 1, 4], max_new=50)]  # capped
    engine.run_to_completion(reqs)
    assert all(r.done for r in reqs)
    assert [len(r.out) for r in reqs] == [2, 6, 6]
    # budgets are per-request, not per-slot residue: the short request's
    # slot is reused at full budget
    with pytest.raises(ValueError, match="max_new"):
        engine.submit(Request(rid=9, tokens=[1], max_new=0))


def test_preempted_requests_resume_token_identical():
    """The acceptance gate at test scale: a 0.5x page pool must yield
    greedy outputs token-identical to the unconstrained run under both
    preempting policies, with real preemptions and no leaked pages."""
    ref_engine, *_ = _engine(slots=2, cache_len=32, max_new=24,
                             paged=True, page_size=8)
    ref = _oversub_requests()
    ref_engine.run_to_completion(ref)
    assert ref_engine.preemptions == 0
    want = [r.out for r in ref]

    for policy in ("lru", "shortest"):
        engine, *_ = _oversub_engine(policy=policy)
        reqs = _oversub_requests()
        engine.run_to_completion(reqs)
        assert all(r.done for r in reqs)
        assert [r.out for r in reqs] == want, policy
        assert engine.preemptions > 0, f"{policy} never preempted"
        assert sum(r.preempts for r in reqs) == engine.preemptions
        st = engine.stats()
        assert st["available"] == st["total_pages"] - 1   # no leaks
        assert not engine.requeue and not engine.queue


def test_starvation_guard_requeued_admitted_before_fresh():
    """A preempted checkpoint must be re-admitted ahead of fresh queue
    entries, and under sustained pressure every request (preempted or
    not) eventually completes."""
    engine, *_ = _engine(slots=1, cache_len=32, max_new=4, paged=True,
                         page_size=8)
    resumed = Request(rid=0, tokens=[3, 1, 4], preempts=1)
    resumed.out = [7]                       # checkpoint: one generated
    fresh = Request(rid=1, tokens=[2, 2, 2])
    engine.queue.append(fresh)
    engine.requeue.append(resumed)
    engine._admit()
    assert engine.active[0] is resumed      # checkpoint won the slot
    assert fresh in engine.queue

    # sustained pressure: more requests than slots on a 0.5x pool
    engine, *_ = _oversub_engine(policy="shortest")
    reqs = _oversub_requests(6)
    engine.run_to_completion(reqs)
    assert all(r.done for r in reqs)
    assert engine.preemptions > 0
    assert not engine.requeue


def test_preempt_and_readmit_under_int8_pool():
    """Preemption must compose with the quantized scatter-prefill
    re-admission path: int8 pools at 0.5x pages complete every request
    with the full token budget and drain the pool clean.  (Token-level
    parity is a bf16 contract only — requantization error differs
    between incremental decode writes and whole-page re-prefill.)"""
    engine, *_ = _oversub_engine(policy="lru", kv_dtype="int8")
    reqs = _oversub_requests()
    engine.run_to_completion(reqs)
    assert all(r.done for r in reqs)
    assert all(len(r.out) == 24 for r in reqs)
    assert engine.preemptions > 0
    st = engine.stats()
    assert st["available"] == st["total_pages"] - 1


def test_preemption_survives_ring_cache_model():
    """Preempt/re-admit must survive mixed cache modes: gemma2's local
    ring layers stay slot-dense and wrap past the window mid-decode,
    and re-prefill must rebuild that ring state (scatter_prefill
    overwrites the whole dense slot row) — outputs token-identical to
    the unconstrained paged run."""
    cfg = smoke_config("gemma2-2b", num_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def run(**kw):
        engine = Engine(model, params, ServeConfig(
            slots=2, cache_len=32, max_new_tokens=24, paged=True,
            page_size=8, **kw))
        reqs = [Request(rid=i, tokens=[1 + i] * 6) for i in range(3)]
        engine.run_to_completion(reqs)
        assert all(r.done for r in reqs)
        return engine, [r.out for r in reqs]

    _, want = run()
    engine, got = run(total_pages=5, preempt_policy="lru")
    assert got == want, "ring-cache model diverged under preemption"
    assert engine.preemptions > 0


def test_checkpoint_readmitted_at_full_cache_emits_final_token():
    """A checkpoint preempted with one cache row left re-prefills to a
    completely full cache: it must finish at admission, and its
    re-prefill sample must be exactly the final token the un-preempted
    run emits (the cache-full edge of the resume path)."""
    cache_len, plen = 12, 4
    ref_engine, *_ = _engine(slots=1, cache_len=cache_len, max_new=100,
                             paged=True, page_size=4)
    ref = Request(rid=0, tokens=list(range(1, plen + 1)))
    ref_engine.run_to_completion([ref])
    assert len(ref.out) == cache_len - plen + 1      # every row written

    engine, *_ = _engine(slots=1, cache_len=cache_len, max_new=100,
                         paged=True, page_size=4)
    resumed = Request(rid=1, tokens=list(range(1, plen + 1)), preempts=1)
    resumed.out = list(ref.out[:-1])    # checkpoint: eff_plen == cache_len
    engine.requeue.append(resumed)
    engine.run_to_completion([])
    assert resumed.done
    assert resumed.out == ref.out
    st = engine.stats()
    assert st["available"] == st["total_pages"] - 1


def test_sole_active_sequence_overflowing_pool_raises():
    """When the only active sequence already holds every usable page,
    there is nothing to preempt and requeueing it would spin forever —
    the engine must surface the sizing problem."""
    engine, *_ = _engine(slots=1, cache_len=32, max_new=24, paged=True,
                         page_size=8, total_pages=3)
    with pytest.raises(RuntimeError, match="only active"):
        engine.run_to_completion([Request(rid=0, tokens=[2] * 6)])


def test_preempt_policy_validated():
    with pytest.raises(ValueError, match="preempt_policy"):
        _engine(paged=True, preempt_policy="round-robin")


def test_paged_long_decode_crosses_page_boundaries():
    """A request decoding across several page boundaries (on-demand
    page allocation mid-stream) must match the dense engine exactly."""
    outs = {}
    for paged in (False, True):
        engine, *_ = _engine(slots=1, cache_len=32, max_new=24,
                             paged=paged, page_size=4)
        req = Request(rid=0, tokens=[11, 3])
        engine.run_to_completion([req])
        assert req.done
        outs[paged] = req.out
    assert len(outs[True]) == 24
    assert outs[True] == outs[False]


# ----------------------------------------------------------- speculative ----

def _spec_engine(spec_k=4, **kw):
    return _engine(slots=2, cache_len=32, max_new=12, paged=True,
                   page_size=8, spec_mode="ngram", spec_k=spec_k, **kw)


def _spec_requests(n=4):
    # mixed lengths so admission groups differ and drafts cross pages
    return [Request(rid=i, tokens=[1 + i] * (3 + i)) for i in range(n)]


def test_spec_matches_plain_paged_greedy():
    """The speculative contract: accepted drafts equal the tokens the
    plain argmax chain would emit, so outputs are token-identical for
    any k — with real rejections exercised, not just lucky accepts."""
    ref_engine, *_ = _engine(slots=2, cache_len=32, max_new=12,
                             paged=True, page_size=8)
    ref = _spec_requests()
    ref_engine.run_to_completion(ref)
    want = [r.out for r in ref]

    for k in (1, 2, 4):
        engine, *_ = _spec_engine(spec_k=k)
        reqs = _spec_requests()
        engine.run_to_completion(reqs)
        assert all(r.done for r in reqs)
        assert [r.out for r in reqs] == want, k
        assert engine.spec_rejections > 0, f"k={k} never rejected a draft"
        st = engine.stats()
        assert st["available"] == st["total_pages"] - 1   # no leaks


def test_spec_rollback_restores_page_watermark():
    """After every speculative step the pool must hold exactly the
    pages the accepted lengths need: rejected drafts' pages are rolled
    back by block-table suffix truncation, never leaked."""
    from repro.serve import paging
    engine, *_ = _spec_engine(spec_k=4)
    for r in _spec_requests():
        engine.submit(r)
    engine._admit()
    steps = 0
    while engine.step():
        steps += 1
        want = sum(paging.pages_per_slot(int(engine._len_h[s]),
                                         engine.page_size)
                   for s in range(engine.sc.slots)
                   if engine.active[s] is not None)
        assert engine.allocator.pressure()["in_use"] == want, steps
        engine._admit()
    assert engine.spec_rejections > 0
    assert engine.allocator.pressure()["in_use"] == 0


def test_spec_single_device_get_per_step():
    """The k+1-token verification step must keep the engine's one-sync
    contract: draft, verify, accept, and rollback planning all ride a
    single device_get."""
    engine, *_ = _spec_engine(spec_k=4)
    for r in _spec_requests():
        engine.submit(r)
    engine._admit()

    calls = []
    real = engine_mod._device_get
    engine_mod._device_get = lambda x: (calls.append(1) or real(x))
    try:
        assert engine.step()
    finally:
        engine_mod._device_get = real
    assert len(calls) == 1, f"{len(calls)} host syncs in one spec step"


def test_spec_config_validation():
    with pytest.raises(ValueError, match="temperature"):
        _spec_engine(temperature=0.8)
    with pytest.raises(ValueError, match="paged"):
        _engine(spec_mode="ngram")
    with pytest.raises(ValueError, match="spec_mode"):
        _engine(paged=True, spec_mode="draft-model")
    with pytest.raises(ValueError, match="spec_k"):
        _spec_engine(spec_k=0)


def test_preempt_mid_speculation_checkpoints_accepted_prefix():
    """Preemption composing with speculation: a victim checkpointed
    between speculative steps must resume from its *accepted* prefix
    only (rejected drafts were already rolled back), so an
    oversubscribed spec run stays token-identical to the unconstrained
    plain paged run."""
    ref_engine, *_ = _engine(slots=2, cache_len=32, max_new=24,
                             paged=True, page_size=8)
    ref = _oversub_requests()
    ref_engine.run_to_completion(ref)
    want = [r.out for r in ref]

    engine, *_ = _engine(slots=2, cache_len=32, max_new=24, paged=True,
                         page_size=8, total_pages=5, preempt_policy="lru",
                         spec_mode="ngram", spec_k=4)
    reqs = _oversub_requests()
    engine.run_to_completion(reqs)
    assert all(r.done for r in reqs)
    assert [r.out for r in reqs] == want
    assert engine.preemptions > 0, "spec oversub run never preempted"
    assert engine.spec_rejections > 0
    st = engine.stats()
    assert st["available"] == st["total_pages"] - 1
