"""Paged KV subsystem: allocator, paged kernel, repaging, pool writes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import context as ctx
from repro.kernels.decode_attention.ops import (decode_attention,
                                                paged_decode_attention,
                                                paged_decode_attention_op)
from repro.kernels.decode_attention import paged as paged_kernel
from repro.kernels.decode_attention.paged import repage
from repro.kernels.decode_attention.ref import (
    decode_attention_ref, gather_pages, paged_decode_attention_ref,
    quant_paged_decode_attention_ref)
from repro.serve import paging
from repro.sharding.kernel_sharding import sharded_paged_decode_update_attend


def _rand(shape, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


# ------------------------------------------------------------ allocator ----

def test_allocator_alloc_free_reuse():
    a = paging.PageAllocator(6)               # pages 1..5 usable
    assert a.available == 5
    got = a.alloc_many(3)
    assert len(set(got)) == 3 and paging.NULL_PAGE not in got
    a.free(got)
    assert a.available == 5
    # LIFO: the just-freed pages come back first
    assert a.alloc() == got[-1]


def test_allocator_never_hands_out_null_page():
    a = paging.PageAllocator(4)
    pages = a.alloc_many(3)
    assert paging.NULL_PAGE not in pages
    # freeing the reserved null page is a caller bug, not a no-op:
    # the engine filters NULL_PAGE table entries before freeing
    with pytest.raises(ValueError, match="null page"):
        a.free([paging.NULL_PAGE])
    assert a.available == 0


def test_allocator_exhaustion_raises():
    a = paging.PageAllocator(3)
    a.alloc_many(2)
    with pytest.raises(RuntimeError, match="exhausted"):
        a.alloc()
    with pytest.raises(RuntimeError, match="exhausted"):
        a.alloc_many(1)


def test_allocator_rejects_double_free():
    """A page freed twice would be handed to two live sequences — the
    allocator must catch the caller bug, and must reject the whole
    batch before mutating anything."""
    a = paging.PageAllocator(6)
    pages = a.alloc_many(3)
    a.free(pages[:1])
    with pytest.raises(ValueError, match="double free"):
        a.free(pages[:1])
    # a batch mixing one valid and one already-free page must not
    # partially apply: the valid page stays allocated
    with pytest.raises(ValueError, match="double free"):
        a.free([pages[1], pages[0]])
    assert a.available == 3                     # only pages[0] came back
    a.free(pages[1:])                           # still freeable once
    assert a.available == 5


def test_allocator_rejects_duplicate_within_one_batch():
    """free([p, p]) must fail atomically: a duplicate inside a single
    batch would otherwise pass the allocated check twice and land the
    page on the free list twice — the double-lease in one call."""
    a = paging.PageAllocator(6)
    p = a.alloc_many(3)[0]
    before = a.available
    with pytest.raises(ValueError, match="double free"):
        a.free([p, p])
    assert a.available == before                # nothing mutated
    a.free([p])                                 # still freeable once
    assert a.alloc() == p                       # and handed out once
    with pytest.raises(RuntimeError):
        a.alloc_many(3)                         # only 2 others remain free


def test_allocator_never_allocated_free_rejected():
    a = paging.PageAllocator(8)
    a.alloc()
    with pytest.raises(ValueError, match="double free"):
        a.free([5])                             # in the free list, not out


def test_alloc_many_partial_exhaustion_rolls_back():
    """A failed alloc_many must leave the allocator exactly as it was:
    no pages leak out of the free list mid-batch."""
    a = paging.PageAllocator(5)                 # 4 usable pages
    got = a.alloc_many(2)
    before = a.available
    with pytest.raises(RuntimeError, match="exhausted"):
        a.alloc_many(3)                         # only 2 free
    assert a.available == before
    # the survivors are still allocatable and the earlier allocation
    # is still tracked (freeing it back works once)
    more = a.alloc_many(2)
    assert len(set(got + more)) == 4
    a.free(got + more)
    assert a.available == 4


def test_allocator_pressure_stats():
    """The preempt scheduler and oversub bench read these counters."""
    a = paging.PageAllocator(6)
    got = a.alloc_many(3)
    assert a.pressure() == {"total_pages": 6, "available": 2, "in_use": 3,
                            "peak_in_use": 3, "allocs": 3, "frees": 0,
                            "quarantined": 0}
    a.free(got[:2])
    st = a.pressure()
    assert st["in_use"] == 1 and st["frees"] == 2
    assert st["peak_in_use"] == 3                 # high-water mark sticks
    a.alloc_many(2)
    assert a.pressure()["peak_in_use"] == 3
    a.alloc()
    assert a.pressure()["peak_in_use"] == 4


def test_allocator_reclaim_filters_null_strict_otherwise():
    """reclaim() frees a whole block-table row, filtering only the
    NULL_PAGE placeholders; the underlying free stays strict, so
    reclaiming the same row twice still raises."""
    a = paging.PageAllocator(8)
    pages = a.alloc_many(3)
    row = np.array(pages + [paging.NULL_PAGE] * 3, np.int32)
    assert a.reclaim(row) == 3
    assert a.available == 7
    with pytest.raises(ValueError, match="double free"):
        a.reclaim(row)
    assert a.reclaim([paging.NULL_PAGE] * 4) == 0   # all-null row is a no-op


def test_truncate_suffix_frees_exact_tail():
    """Speculative rollback: truncating a block-table suffix frees
    exactly the tail pages and returns the pool to the pre-speculation
    watermark."""
    a = paging.PageAllocator(10)
    pages = a.alloc_many(5)
    row = np.array(pages + [paging.NULL_PAGE], np.int32)
    before = a.pressure()["in_use"]
    assert paging.truncate_suffix(a, row, keep=2, upto=5) == 3
    assert a.pressure()["in_use"] == before - 3
    # kept prefix untouched, freed tail nulled out
    assert list(row[:2]) == pages[:2]
    assert all(int(p) == paging.NULL_PAGE for p in row[2:])
    # the freed pages are allocatable again
    assert set(a.alloc_many(3)) == set(pages[2:])


def test_truncate_suffix_empty_tail_is_noop():
    a = paging.PageAllocator(8)
    pages = a.alloc_many(3)
    row = np.array(pages, np.int32)
    assert paging.truncate_suffix(a, row, keep=3, upto=3) == 0
    assert paging.truncate_suffix(a, row, keep=3) == 0
    assert a.pressure()["in_use"] == 3


def test_truncate_suffix_double_truncation_raises():
    """Truncating the same suffix twice means the engine lost track of
    the ensured-page watermark — the NULL entries must be rejected, not
    silently skipped (that would mask a double free elsewhere)."""
    a = paging.PageAllocator(8)
    pages = a.alloc_many(4)
    row = np.array(pages, np.int32)
    paging.truncate_suffix(a, row, keep=1, upto=4)
    with pytest.raises(ValueError, match="truncate_suffix"):
        paging.truncate_suffix(a, row, keep=1, upto=4)
    # pool state untouched by the failed call
    assert a.pressure()["in_use"] == 1


# --------------------------------------------------------- paged kernel ----

def _paged_fixture(b=2, hq=4, hkv=2, d=32, pages_per_slot=3, ps=32, seed=0):
    n_pages = 1 + b * pages_per_slot
    kpg = _rand((hkv, n_pages, ps, d), seed + 1)
    vpg = _rand((hkv, n_pages, ps, d), seed + 2)
    q = _rand((b, hq, d), seed)
    perm = np.random.default_rng(seed).permutation(np.arange(1, n_pages))
    bt = jnp.asarray(perm.reshape(b, pages_per_slot), jnp.int32)
    lengths = jnp.array([ps * pages_per_slot - 5, ps + 3][:b], jnp.int32)
    return q, kpg, vpg, bt, lengths


def test_paged_matches_dense_on_gathered_cache():
    """Paging must be semantically invisible: the paged kernel on a
    scrambled pool == the dense kernel on the gathered dense cache."""
    q, kpg, vpg, bt, lengths = _paged_fixture()
    got = paged_decode_attention(q, kpg, vpg, bt, lengths,
                                 page_size=32, block_kv=16)
    k_dense = gather_pages(kpg, bt)
    v_dense = gather_pages(vpg, bt)
    want = decode_attention(q, k_dense, v_dense, lengths, block_kv=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_paged_generic_target_matches_kernel():
    q, kpg, vpg, bt, lengths = _paged_fixture(seed=3)
    with ctx.target("generic"):
        want = paged_decode_attention(q, kpg, vpg, bt, lengths)
    got = paged_decode_attention(q, kpg, vpg, bt, lengths,
                                 page_size=16, block_kv=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_repage_preserves_gather():
    """Logical re-paging (contiguous page split) must name the same
    tokens in the same order."""
    _, kpg, _, bt, _ = _paged_fixture(ps=32)
    for ps_l in (8, 16, 32):
        pool_l, bt_l = repage(kpg, bt, ps_l)
        np.testing.assert_array_equal(np.asarray(gather_pages(pool_l, bt_l)),
                                      np.asarray(gather_pages(kpg, bt)))
    with pytest.raises(ValueError, match="divide"):
        repage(kpg, bt, 24)


def test_paged_window_and_softcap():
    q, kpg, vpg, bt, lengths = _paged_fixture(seed=5)
    got = paged_decode_attention(q, kpg, vpg, bt, lengths, window=20,
                                 softcap=30.0, page_size=32, block_kv=32)
    want = decode_attention_ref(q, gather_pages(kpg, bt),
                                gather_pages(vpg, bt), lengths,
                                window=20, softcap=30.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_non_dividing_block_kv_clamps_to_divisor():
    """A block_kv that doesn't divide page_size (e.g. a table winner
    tuned at a different page size) is clamped to the largest divisor,
    never an error and never a page-spanning block."""
    q, kpg, vpg, bt, lengths = _paged_fixture()
    got = paged_decode_attention(q, kpg, vpg, bt, lengths,
                                 page_size=32, block_kv=12)   # -> 8
    want = paged_decode_attention(q, kpg, vpg, bt, lengths,
                                  page_size=32, block_kv=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=0, rtol=0)


PS, TABLE = 16, 5          # pages of 16 tokens, 5 table entries a slot
MIXED = [0, 1, PS, 2 * PS + 5, TABLE * PS]
PARITY = {
    "len_0": dict(lengths=[0, 0]),
    "len_1": dict(lengths=[1, 1]),
    "len_page_boundary": dict(lengths=[PS, 3 * PS]),
    "len_full_table": dict(lengths=[TABLE * PS, TABLE * PS]),
    "gqa_4": dict(hq=8, hkv=2),
    "gqa_6": dict(hq=12, hkv=2),
    "hkv_1": dict(hq=4, hkv=1),
    "softcap": dict(softcap=30.0),
    "window": dict(window=20),
    "int8_scales": dict(quantized=True),
    "nan_past_length": dict(nan_past_length=True),
}


@pytest.mark.parametrize("steps", ["one_run", "runs_of_2"])
@pytest.mark.parametrize("case", sorted(PARITY))
def test_paged_kernel_matches_reference(case, steps, monkeypatch):
    """The paged kernel against the gathered dense oracle, with the whole
    table in one grid step and in runs of two table entries (the
    step budget shrunk to two pages).  In ``nan_past_length`` every
    table entry past a slot's length names a real page filled with NaN:
    the kernel never brings one into the result."""
    c = dict(dict(hq=8, hkv=2, lengths=MIXED, softcap=None, window=None,
                  quantized=False, nan_past_length=False), **PARITY[case])
    hq, hkv, d = c["hq"], c["hkv"], 32
    if steps == "runs_of_2":
        monkeypatch.setattr(paged_kernel, "STEP_KV_BYTES",
                            2 * hkv * PS * 2 * d * 4)
    lengths = jnp.asarray(c["lengths"], jnp.int32)
    b = lengths.shape[0]
    n_pages = 1 + b * TABLE
    kpg = _rand((hkv, n_pages, PS, d), 1)
    vpg = _rand((hkv, n_pages, PS, d), 2)
    q = _rand((b, hq, d), 3)
    perm = np.random.default_rng(4).permutation(np.arange(1, n_pages))
    bt = perm.reshape(b, TABLE).astype(np.int32)
    live = -(-np.asarray(lengths) // PS)
    past = np.arange(TABLE)[None, :] >= live[:, None]
    if not c["nan_past_length"]:      # the allocator's table: NULL past it
        bt[past] = paging.NULL_PAGE
    bt = jnp.asarray(bt)
    kw = dict(window=c["window"], softcap=c["softcap"])
    if c["quantized"]:
        from repro.quant import spec_for_storage
        s = spec_for_storage(jnp.int8)
        kpg, ks = s.quantize_pages(kpg)
        vpg, vs = s.quantize_pages(vpg)
        want = quant_paged_decode_attention_ref(
            q, kpg, vpg, ks, vs, bt, lengths, return_residuals=True, **kw)
        kw.update(k_scales=ks, v_scales=vs)
        pools = (kpg, vpg)
    else:
        want = paged_decode_attention_ref(q, kpg, vpg, bt, lengths,
                                          return_residuals=True, **kw)
        pools = (kpg, vpg)
        if c["nan_past_length"]:
            dead = np.asarray(bt)[past]
            pools = tuple(p.at[:, dead].set(jnp.nan) for p in pools)
    acc, m, l = paged_kernel.paged_decode_attention_fwd(
        q, *pools, bt, lengths, block_kv=PS // 2, **kw)
    w_acc, w_m, w_l = want
    assert np.isfinite(np.asarray(acc)).all()
    np.testing.assert_allclose(np.asarray(l), np.asarray(w_l),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(acc), np.asarray(w_acc),
                               atol=2e-5, rtol=2e-5)
    has = np.asarray(w_l) > 0                 # m is -inf-like where empty
    np.testing.assert_allclose(np.asarray(m)[has], np.asarray(w_m)[has],
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("ppb", [1, 2, 3, TABLE])
def test_run_table_names_only_live_pages_and_ends_each_slot(ppb):
    """Each slot's live runs fill its last grid steps in table order;
    every other entry repeats a live page (or the slot's first page when
    it is empty), never one past the length."""
    b = len(MIXED)
    bt = np.arange(1, 1 + b * TABLE, dtype=np.int32).reshape(b, TABLE)
    lengths = np.asarray(MIXED, np.int32)
    tbl = np.asarray(paged_kernel.run_table(
        jnp.asarray(bt), jnp.asarray(lengths), PS, ppb))
    nr = -(-TABLE // ppb)
    assert tbl.shape == (b, nr * ppb)
    for s, n in enumerate(lengths):
        last = max(int(n) - 1, 0) // PS
        assert set(tbl[s]) <= set(bt[s, :last + 1])
        runs = max(-(-int(n) // (ppb * PS)), 1)
        walk = tbl[s, (nr - runs) * ppb:]
        live = [col for col in range(runs * ppb) if col <= last]
        assert [walk[col] for col in live] == list(bt[s, :last + 1])


def test_search_space_constraint_prunes_spanning_blocks():
    """The declared constraint must reject block_kv > page_size (a KV
    block cannot span non-contiguous pages), so the autotuner never
    measures an illegal schedule."""
    cfgs = paged_decode_attention_op.candidate_configs(
        base={"page_size": 64, "block_kv": 64})
    assert all(c["page_size"] % c["block_kv"] == 0 for c in cfgs)
    assert {(c["page_size"], c["block_kv"]) for c in cfgs} >= \
        {(64, 64), (32, 32), (16, 16), (64, 16)}


def test_paged_op_autotunes():
    """The registered search space is real: the autotuner can sweep it
    with the stubbed clock and write a winner back."""
    from repro.core import autotune as at
    from repro.core import tuning
    calls = []

    def fake_measure(run, cfg):
        calls.append(dict(cfg))
        return 1.0 + len(calls) * 0.1       # first candidate wins

    snap = tuning.table.snapshot()
    try:
        res = at.autotune_op(paged_decode_attention_op, arch="interpret",
                             budget=3, measurer=fake_measure)
        assert res.tuned_ms <= res.baseline_ms
        assert len(calls) >= 2
        assert res.written
    finally:
        tuning.table.restore(snap)


# ------------------------------------------------------------ pool write ----

def test_fused_page_write_then_attend():
    """Writing the new token's KV into its page then attending must
    equal attending over the dense cache with the token appended."""
    b, hq, hkv, d, ps, t = 2, 4, 2, 32, 16, 3
    q, kpg, vpg, bt, _ = _paged_fixture(b, hq, hkv, d, t, ps, seed=7)
    lengths = jnp.array([ps + 3, 2 * ps - 1], jnp.int32)   # mid/edge of page
    k_new = _rand((b, hkv, d), 11)
    v_new = _rand((b, hkv, d), 12)
    page_idx = lengths // ps
    write_page = jnp.take_along_axis(bt, page_idx[:, None], axis=1)[:, 0]
    out, kp2, vp2 = sharded_paged_decode_update_attend(
        q, k_new, v_new, kpg, vpg, bt, write_page, lengths % ps,
        lengths + 1, page_size=ps)

    k_dense = gather_pages(kpg, bt)
    v_dense = gather_pages(vpg, bt)
    idx = jnp.arange(k_dense.shape[2])[None, :]
    sel = (idx == lengths[:, None])[:, None, :, None]
    k_dense = jnp.where(sel, k_new[:, :, None, :], k_dense)
    v_dense = jnp.where(sel, v_new[:, :, None, :], v_dense)
    want = decode_attention_ref(q, k_dense, v_dense, lengths + 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # and the pool rows really hold the new KV
    got_row = kp2[:, write_page[0], int(lengths[0]) % ps]
    np.testing.assert_allclose(np.asarray(got_row), np.asarray(k_new[0].T).T,
                               atol=0, rtol=0)


# ------------------------------------------------------- paged cache tree ----

def test_init_paged_caches_pages_every_attention_kind():
    """Global-attention KV pages through the global pool, sliding-window
    ("local") KV through its own O(window)-sized window pool; only
    recurrent/cross caches keep a dense slot-major layout."""
    from repro.configs.smoke import smoke_config
    from repro.models.registry import build_model
    cfg = smoke_config("gemma2-2b", num_layers=2)   # local+global pattern
    model = build_model(cfg)
    slots, cache_len, ps = 2, 32, 16
    total = 1 + slots * paging.pages_per_slot(cache_len, ps)
    total_w = 1 + slots * paging.window_table_width(cfg.window, ps)
    caches = paging.init_paged_caches(model, slots, cache_len, ps, total)
    names = set()
    for seg in caches:
        for c in seg:
            names.update(c.keys())
            for nm, leaf in c.items():
                if nm in ("kp", "vp"):
                    assert leaf.shape[2:4] == (total, ps)
                elif nm in ("kw", "vw"):
                    # default window-pool sizing: slots can always hold
                    # a full ring table each, plus the trash page
                    assert leaf.shape[2:4] == (total_w, ps)
                else:
                    assert leaf.shape[1] == slots    # slot-major
    assert "kp" in names and "vp" in names
    # gemma's local ring layers (window=16 < cache_len) page windowed
    assert "kw" in names and "vw" in names
    assert "k" not in names and "v" not in names


def test_init_paged_caches_window_pool_size_override():
    from repro.configs.smoke import smoke_config
    from repro.models.registry import build_model
    cfg = smoke_config("gemma2-2b", num_layers=2)
    model = build_model(cfg)
    caches = paging.init_paged_caches(model, 2, 32, 16, 9,
                                      total_pages_window=7)
    kw = [c["kw"] for seg in caches for c in seg if "kw" in c]
    assert kw and all(leaf.shape[2] == 7 for leaf in kw)


# --------------------------------------------------- quarantine + audit ----

def test_quarantine_allocated_and_free_pages_shrink_usable():
    a = paging.PageAllocator(8)                   # pages 1..7 usable
    got = a.alloc_many(3)
    a.quarantine([got[0]])                        # from the allocated set
    free_page = next(p for p in range(1, 8)
                     if p not in got)
    a.quarantine([free_page])                     # from the free list
    assert a.quarantined == 2
    assert a.usable == 7 - 2
    assert a.in_use == 2                          # got[1], got[2] still out
    assert a.pressure()["quarantined"] == 2
    # quarantined pages never come back: drain the free list fully
    rest = a.alloc_many(a.available)
    assert free_page not in rest and got[0] not in rest


def test_quarantine_validates_batch_before_mutating():
    a = paging.PageAllocator(6)
    got = a.alloc_many(2)
    with pytest.raises(ValueError, match="not a real pool page"):
        a.quarantine([got[0], paging.NULL_PAGE])
    with pytest.raises(ValueError, match="not a real pool page"):
        a.quarantine([99])
    assert a.quarantined == 0                     # nothing half-applied
    a.quarantine([got[0]])
    with pytest.raises(ValueError, match="already quarantined"):
        a.quarantine([got[0]])
    with pytest.raises(ValueError, match="already quarantined"):
        a.quarantine([got[1], got[1]])            # dup inside one batch
    assert a.quarantined == 1


def _audit_fixture(slots=2, pages_per_slot=3, page_size=4):
    a = paging.PageAllocator(1 + slots * pages_per_slot)
    bt = np.full((slots, pages_per_slot), paging.NULL_PAGE, np.int32)
    lengths = np.zeros((slots,), np.int64)
    active = np.zeros((slots,), bool)
    return a, bt, lengths, active, page_size


def test_audit_clean_state_and_live_prefix():
    a, bt, lengths, active, ps = _audit_fixture()
    assert paging.audit(a, bt, lengths, active, ps) == []
    bt[0, :2] = a.alloc_many(2)
    lengths[0], active[0] = 6, True               # 6 tokens -> 2 pages
    assert paging.audit(a, bt, lengths, active, ps) == []


def test_audit_flags_null_in_live_prefix():
    a, bt, lengths, active, ps = _audit_fixture()
    bt[0, 0] = a.alloc()
    lengths[0], active[0] = 6, True               # needs 2 pages, has 1
    errs = paging.audit(a, bt, lengths, active, ps)
    assert any("NULL_PAGE inside the live prefix" in e for e in errs)


def test_audit_flags_leak_past_prefix_and_inactive_rows():
    a, bt, lengths, active, ps = _audit_fixture()
    bt[0, 0] = a.alloc()
    lengths[0], active[0] = 2, True               # 1 live page
    bt[0, 2] = a.alloc()                          # past the prefix
    errs = paging.audit(a, bt, lengths, active, ps)
    assert any("past the live prefix" in e for e in errs)
    # move the leak to an inactive row: still flagged (whole row is dead)
    bt[1, 0], bt[0, 2] = bt[0, 2], paging.NULL_PAGE
    errs = paging.audit(a, bt, lengths, active, ps)
    assert any("past the live prefix" in e for e in errs)


def test_audit_flags_double_lease_and_in_use_mismatch():
    a, bt, lengths, active, ps = _audit_fixture()
    p = a.alloc()
    bt[0, 0] = p
    bt[1, 0] = p                                  # same page, two rows
    lengths[:] = 2
    active[:] = True
    errs = paging.audit(a, bt, lengths, active, ps)
    assert any("leased to both" in e for e in errs)
    assert any("in_use" in e for e in errs)       # 1 allocated != 2 needed


def test_audit_flags_free_list_corruption():
    a, bt, lengths, active, ps = _audit_fixture()
    page = a.alloc()
    a._free.append(page)                          # corrupt: free AND allocated
    errs = paging.audit(a, bt, lengths, active, ps)
    assert any("both free and allocated" in e for e in errs)


def test_audit_accounts_quarantined_pages():
    a, bt, lengths, active, ps = _audit_fixture()
    bt[0, 0] = a.alloc()
    lengths[0], active[0] = 2, True
    a.quarantine([a.alloc()])                     # quarantine a second page
    assert paging.audit(a, bt, lengths, active, ps) == []
    # a live table entry pointing at a quarantined page is flagged (the
    # engine must NULL quarantined entries before reclaiming the row)
    q = a.alloc()
    a.quarantine([q])
    bt[0, 1] = q
    lengths[0] = 6                                # prefix now covers index 1
    errs = paging.audit(a, bt, lengths, active, ps)
    assert any("quarantine" in e for e in errs)
