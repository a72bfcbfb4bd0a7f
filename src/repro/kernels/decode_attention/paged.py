"""Paged flash-decode Pallas kernel (block-table KV gather).

The serving engine stores KV in fixed-size *pages* drawn from a shared
pool instead of one contiguous row per slot; a per-slot block table
names the pages that hold its sequence.  This kernel runs the same
online-softmax accumulation as the dense decode kernel (the math is
``online_softmax_update``, shared), but the KV blocks reach VMEM
through a block-table index map: the table and lengths ride as
scalar-prefetch operands (``kernel_call(num_scalar_prefetch=2)``, the
runtime facade's analogue of OpenMP's device-resident control data), so
the DMA engine can resolve ``pool[:, page]`` before the body runs.
One kernel source serves compiled TPU and the CPU interpreter — the
gather is expressed in the portable BlockSpec layer, not in
target-specific scatter/gather intrinsics.

Layouts
  q           (B, Hq, D)        one new token per slot
  k/v pools   (Hkv, P, ps, D)   head-major page pool; page 0 is the
                                allocator's reserved null page
  block_tables(B, T) int32      page id per (slot, logical page)
  lengths     (B,)   int32      valid tokens per slot

Grid: ``(B, cdiv(T, ppb))`` — one step per slot and *run* of ``ppb``
table entries, every local KV head at once.  Each entry of a run is its
own K and V operand, a ``(Hkv, 1, ps, D)`` block (a strided DMA of Hkv
contiguous pages), so one step moves ``ppb`` pages of all heads and the
pipeline fetches the next step's run while this one computes.
``ppb`` (``pages_per_step``) comes from shapes alone: the run whose K+V
bytes of all heads fill ``STEP_KV_BYTES`` (1 MiB), at least one page
and at most the table — four pages at granite-8b's 8 heads of 128 in
bf16, fewer for wide MLA pools.  The body folds the run into the
accumulators ``block_kv`` tokens at a time, all heads batched.

The walk stops at each slot's length: ``run_table`` (a small XLA op
before the call) names the page each step's operand reads.  A slot's
live runs take its last grid steps; the steps before them, and every
entry past the last live page, repeat a block the operand already
holds, so they issue no DMA and skip the math, and the table's
``NULL_PAGE`` or stale entries past the length are never read.

``page_size`` is *logical*: when it divides the pool's physical page
size the pool is re-viewed as ``(Hkv, P*r, page_size, D)`` — a
contiguous split, free under XLA — so the autotuner can sweep page
granularity against one physical example pool.  ``block_kv`` (tokens
per online-softmax update) must divide ``page_size``: an update never
spans two non-contiguous pages.

With ``k_scales``/``v_scales`` (per-page-per-head f32 scale pools
``(Hkv, P)``, repro.quant) the same launch also serves the *quantized*
pools: each page's scale tiles ride the identical index map as its KV
block (a ``(Hkv, 1, 1, 1)`` block of ``scale_tiles``), and the dequant
fuses into the body as one multiply per head after the DMA.
``quant.py`` wraps this as the ``quant_paged_decode_attention`` op.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.runtime import DeviceRuntime, kernel_call
from repro.kernels.decode_attention.decode_attention import (
    LANES, NEG_INF, SUBLANES, flash_decode_step, online_softmax_update)


# K+V bytes one grid step brings in: enough to hide the step's fixed cost
# behind its DMA, small enough that the double-buffered blocks sit well
# inside scoped VMEM at any head count.
STEP_KV_BYTES = 1 << 20


def pages_per_step(hkv: int, page_size: int, d: int, dv: int,
                   itemsize: int, table_width: int) -> int:
    """Table entries one grid step covers: the run whose K+V pages of
    every local head fill ``STEP_KV_BYTES``, at least one page and at
    most the whole table."""
    page_bytes = hkv * page_size * (d + dv) * itemsize
    return max(1, min(table_width, STEP_KV_BYTES // page_bytes))


def _live_runs(length, run_tokens: int):
    """Runs of ``run_tokens`` that hold a slot's live tokens; at least
    one, so an empty slot still initializes and emits its
    accumulators."""
    return jnp.maximum(pl.cdiv(length, run_tokens), 1)


def run_table(block_tables, lengths, page_size: int, ppb: int):
    """The page each grid step's K/V operand ``i`` reads: ``(B, NR*ppb)``
    with ``NR = cdiv(T, ppb)`` grid steps per slot.

    A slot's live runs take its *last* grid steps; the dead steps before
    them hold run 0's pages, so the next slot's first run is fetched
    while this slot's last run computes and the dead steps issue no DMA.
    An entry past the slot's last live page repeats the page its operand
    held one step earlier (the previous run's entry, or in run 0 the last
    live page): it issues no new fetch, and the table's NULL_PAGE or
    stale entries past the length are never read.
    """
    b, t = block_tables.shape
    nr = pl.cdiv(t, ppb)
    last = (jnp.maximum(lengths - 1, 0) // page_size)[:, None, None]
    first = nr - _live_runs(lengths, ppb * page_size)
    run = jnp.maximum(jnp.arange(nr)[None, :] - first[:, None], 0)[..., None]
    col = run * ppb + jnp.arange(ppb)                       # (B, NR, ppb)
    col = jnp.where(col <= last, col, jnp.where(run > 0, col - ppb, last))
    return jnp.take_along_axis(block_tables, col.reshape(b, nr * ppb),
                               axis=1)


def _paged_decode_kernel(tbl_ref, len_ref, q_ref, *refs, rt: DeviceRuntime,
                         scale: float, window: Optional[int],
                         softcap: Optional[float], page_size: int,
                         block_kv: int, ppb: int, quantized: bool):
    # operands: the run table and lengths (scalar prefetch, consumed by
    # the maps and here), q, then per table entry of the run its K and
    # V page [and their scale tiles], then three outputs and three
    # scratch accumulators.
    del tbl_ref
    per = 4 if quantized else 2
    pages = [refs[i * per:(i + 1) * per] for i in range(ppb)]
    o_ref, m_out_ref, l_out_ref, acc_ref, m_ref, l_ref = refs[ppb * per:]
    ib, ir = rt.team_id(0), rt.team_id(1)
    nr = rt.num_teams(1)
    length = len_ref[ib]
    first = nr - _live_runs(length, ppb * page_size)   # the step of run 0

    @rt.when(ir == first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @rt.when(ir >= first)
    def _run():
        q = q_ref[0].astype(jnp.float32) * scale           # (Hkv, G8, D)
        for i, (k_ref, v_ref, *scale_refs) in enumerate(pages):
            for j in range(page_size // block_kv):
                k_start = ((ir - first) * ppb + i) * page_size + j * block_kv

                @rt.when(k_start < length)
                def _update():
                    rows = pl.ds(j * block_kv, block_kv)
                    k = k_ref[:, 0, rows].astype(jnp.float32)  # (Hkv, bkv, D)
                    v = v_ref[:, 0, rows].astype(jnp.float32)
                    if quantized:            # (Hkv, 1, 1) per-page scales
                        k = k * scale_refs[0][:, 0]
                        v = v * scale_refs[1][:, 0]
                    online_softmax_update(
                        q, k, v, acc_ref, m_ref, l_ref, rt=rt,
                        window=window, softcap=softcap, k_start=k_start,
                        horizon=length)

    @rt.when(ir == nr - 1)
    def _finalize():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)        # unnormalized
        m_out_ref[0] = m_ref[...].astype(m_out_ref.dtype)
        l_out_ref[0] = l_ref[...].astype(l_out_ref.dtype)


def repage(pool, block_tables, page_size: int):
    """Re-view ``(H, P, ps, D)`` pool + table at a smaller logical page.

    ``page_size`` must divide the physical page size; each physical
    page becomes ``r = ps // page_size`` logical pages (a contiguous
    axis split — no data movement) and the block table expands to name
    them.  Identity when sizes already agree.
    """
    h, p, ps, d = pool.shape
    if page_size == ps:
        return pool, block_tables
    if ps % page_size:
        raise ValueError(f"logical page_size {page_size} must divide the "
                         f"pool's physical page size {ps}")
    r = ps // page_size
    pool = pool.reshape(h, p * r, page_size, d)
    bt = (block_tables[:, :, None] * r
          + jnp.arange(r, dtype=block_tables.dtype)[None, None, :])
    return pool, bt.reshape(block_tables.shape[0], -1)


def repage_scales(scales, page_size: int, ps_phys: int):
    """Per-page scales at a smaller logical page: every logical page
    carved from a physical page shares its scale (identity when sizes
    agree)."""
    if page_size == ps_phys:
        return scales
    r = ps_phys // page_size
    h, p = scales.shape
    return jnp.repeat(scales, r, axis=1).reshape(h, p * r)


def scale_tiles(scales):
    """``(H, P)`` scale pool as ``(H, P, 1, 1)``: a page's scales then
    ride a ``(H|1, 1, 1, 1)`` VMEM block whose last two dims equal the
    array's, which Mosaic accepts (a ``(1, 1)`` block of ``(H, P)`` is
    refused, in VMEM and SMEM alike).  A free reshape.  Prefetching
    the pools whole into SMEM (1 MiB on v5e) would instead cap two f32
    pools of 8 heads near 16k pages."""
    return scales.reshape(*scales.shape, 1, 1)


def paged_decode_attention_fwd(q, k_pages, v_pages, block_tables, lengths, *,
                               window: Optional[int] = None,
                               softcap: Optional[float] = None,
                               scale: Optional[float] = None,
                               page_size: Optional[int] = None,
                               block_kv: int = 64,
                               k_scales=None, v_scales=None,
                               rt: Optional[DeviceRuntime] = None):
    """q: (B, Hq, D); pools: (Hkv, P, ps, D); block_tables: (B, T);
    lengths: (B,) int32.

    One grid step per slot and run of table entries, all KV heads per
    step; the walk stops at each slot's length (module docstring).
    Returns unnormalized (acc (B,Hq,Dv), m (B,Hq), l (B,Hq)) — the same
    residual contract as the dense decode kernel, so callers normalize
    or LSE-combine identically.  With ``k_scales``/``v_scales``
    (per-page-per-head (Hkv, P) f32; both or neither) the pools are
    quantized storage and the per-block dequant fuses into the flash
    body (the quant_paged_decode_attention op).
    """
    from repro.core.runtime import runtime
    rt = rt or runtime()
    quantized = k_scales is not None
    assert (v_scales is None) == (k_scales is None)
    b, hq, d = q.shape
    hkv = k_pages.shape[0]
    ps_phys = k_pages.shape[2]
    dv = v_pages.shape[3]
    page_size = ps_phys if page_size is None else page_size
    if quantized:
        k_scales = repage_scales(k_scales, page_size, ps_phys)
        v_scales = repage_scales(v_scales, page_size, ps_phys)
    k_pages, bt = repage(k_pages, block_tables, page_size)
    v_pages, _ = repage(v_pages, block_tables, page_size)
    n_pages = bt.shape[1]

    group = hq // hkv
    g8 = max(SUBLANES, group)
    scale = (d ** -0.5) if scale is None else scale
    # block_kv (tokens per online-softmax update) must divide page_size.
    # The tuning table may hand us a value tuned for a different page
    # size (e.g. the engine clamped page_size to an odd cache_len);
    # clamp to the largest divisor rather than crash — it is a
    # scheduling hint, not semantics.
    block_kv = min(block_kv, page_size)
    while page_size % block_kv:
        block_kv -= 1
    ppb = pages_per_step(hkv, page_size, d, dv, k_pages.dtype.itemsize,
                         n_pages)

    qg = q.reshape(b, hkv, group, d)
    if g8 != group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g8 - group), (0, 0)))

    kern = functools.partial(
        _paged_decode_kernel, rt=rt, scale=scale, window=window,
        softcap=softcap, page_size=page_size, block_kv=block_kv, ppb=ppb,
        quantized=quantized)

    def slot_map(ib, ir, tbl_ref, len_ref):
        del ir, tbl_ref, len_ref
        return (ib, 0, 0, 0)

    def page_map(i):
        def index(ib, ir, tbl_ref, len_ref):
            del len_ref
            return (0, tbl_ref[ib, ir * ppb + i], 0, 0)
        return index

    in_specs = [pl.BlockSpec((1, hkv, g8, d), slot_map)]
    operands = [qg]
    for i in range(ppb):
        in_specs += [pl.BlockSpec((hkv, 1, page_size, d), page_map(i)),
                     pl.BlockSpec((hkv, 1, page_size, dv), page_map(i))]
        operands += [k_pages, v_pages]
        if quantized:
            # a page's scale tiles ride the same map as its KV block
            in_specs += [pl.BlockSpec((hkv, 1, 1, 1), page_map(i))] * 2
            operands += [scale_tiles(k_scales), scale_tiles(v_scales)]

    acc, m, l = kernel_call(
        kern,
        out_shape=(
            jax.ShapeDtypeStruct((b, hkv, g8, dv), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, g8, LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, g8, LANES), jnp.float32),
        ),
        grid=(b, pl.cdiv(n_pages, ppb)),
        num_scalar_prefetch=2,
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((1, hkv, g8, dv), slot_map),
            pl.BlockSpec((1, hkv, g8, LANES), slot_map),
            pl.BlockSpec((1, hkv, g8, LANES), slot_map),
        ),
        scratch_shapes=[
            rt.alloc_shared((hkv, g8, dv), jnp.float32),
            rt.alloc_shared((hkv, g8, LANES), jnp.float32),
            rt.alloc_shared((hkv, g8, LANES), jnp.float32),
        ],
        dimension_semantics=("parallel", "arbitrary"),
        name=("portable_quant_paged_decode_attention" if quantized
              else "portable_paged_decode_attention"),
        rt=rt,
    )(run_table(bt, lengths, page_size, ppb), lengths, *operands)

    acc = acc[:, :, :group].reshape(b, hq, dv)
    m = m[:, :, :group, 0].reshape(b, hq)
    l = l[:, :, :group, 0].reshape(b, hq)
    return acc, m, l


# ------------------------------------------------ windowed ring tables ----

def _window_paged_decode_kernel(*refs, rt: DeviceRuntime, scale: float,
                                window: int, softcap: Optional[float],
                                page_size: int, spp: int, block_kv: int,
                                quantized: bool):
    # operand order matches _paged_decode_kernel: bt, len, q, k, v,
    # [k_scales, v_scales,] outputs, scratch.  The block table is a
    # *ring*: the index maps already resolved the page DMA, so the body
    # only has to recover each grid step's true token position —
    # k_start is measured from the window's first live page, which it
    # derives from the same prefetched length the maps used.
    _, len_ref, q_ref, k_ref, v_ref = refs[:5]
    if quantized:
        ks_ref, vs_ref = refs[5:7]
        k_scale, v_scale = ks_ref[0, 0], vs_ref[0, 0]   # (1, 1): broadcasts
        rest = refs[7:]
    else:
        k_scale = v_scale = None
        rest = refs[5:]
    o_ref, m_out_ref, l_out_ref, acc_ref, m_ref, l_ref = rest
    ib = rt.team_id(0)
    ik = rt.team_id(2)
    nk = rt.num_teams(2)
    base = len_ref[ib]
    first = jnp.maximum(base - window, 0) // page_size
    k_start = (first + ik // spp) * page_size + (ik % spp) * block_kv
    # flash_decode_step's window mask supplies the partial-first-block
    # masking relative to the window start; blocks past the live range
    # have k_start >= base and are skipped whole.
    flash_decode_step(
        q_ref, k_ref, v_ref, o_ref, m_out_ref, l_out_ref,
        acc_ref, m_ref, l_ref, rt=rt, scale=scale, window=window,
        softcap=softcap, k_start=k_start,
        length=base, ik=ik, nk=nk,
        k_scale=k_scale, v_scale=v_scale)


def window_paged_decode_attention_fwd(q, k_pages, v_pages, block_tables,
                                      lengths, *, window: int,
                                      softcap: Optional[float] = None,
                                      scale: Optional[float] = None,
                                      page_size: Optional[int] = None,
                                      block_kv: int = 64,
                                      k_scales=None, v_scales=None,
                                      rt: Optional[DeviceRuntime] = None):
    """Sliding-window decode over a *ring* block table.

    q: (B, Hq, D); pools: (Hkv, P, ps, D); block_tables: (B, T_w) with
    ``T_w = window_table_width(window, ps)`` — global page ``g`` sits
    at column ``g % T_w``; lengths: (B,) int32 post-write length.

    Instead of masking a full-context table, the index maps gather from
    the window's first live page: grid step ``ik`` reads the page at
    column ``(first_live + ik // spp) % T_w``, so the grid is O(window)
    wide no matter how long the context ran.  Logical re-paging keeps
    the ring law — ``(g*r + sub) % (T_w*r) == (g % T_w)*r + sub`` — so
    the autotuner sweeps ``page_size``/``block_kv`` exactly as for the
    prefix-table kernel.  Returns the same unnormalized (acc, m, l)
    residual contract; ``k_scales``/``v_scales`` switch on the fused
    per-page dequant.
    """
    from repro.core.runtime import runtime
    rt = rt or runtime()
    quantized = k_scales is not None
    assert (v_scales is None) == (k_scales is None)
    if window is None:
        raise ValueError("window_paged_decode_attention requires a window "
                         "(use paged_decode_attention for full-context "
                         "tables)")
    b, hq, d = q.shape
    hkv = k_pages.shape[0]
    ps_phys = k_pages.shape[2]
    dv = v_pages.shape[3]
    page_size = ps_phys if page_size is None else page_size
    if quantized:
        k_scales = repage_scales(k_scales, page_size, ps_phys)
        v_scales = repage_scales(v_scales, page_size, ps_phys)
    k_pages, bt = repage(k_pages, block_tables, page_size)
    v_pages, _ = repage(v_pages, block_tables, page_size)
    tw = bt.shape[1]                      # logical ring width

    group = hq // hkv
    g8 = max(SUBLANES, group)
    scale = (d ** -0.5) if scale is None else scale
    block_kv = min(block_kv, page_size)
    while page_size % block_kv:
        block_kv -= 1
    spp = page_size // block_kv
    nk = tw * spp                         # O(window) grid, not O(context)

    qg = q.reshape(b, hkv, group, d)
    if g8 != group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g8 - group), (0, 0)))

    kern = functools.partial(
        _window_paged_decode_kernel, rt=rt, scale=scale, window=window,
        softcap=softcap, page_size=page_size, spp=spp, block_kv=block_kv,
        quantized=quantized)

    def _col(ib, ik, len_ref):
        first = jnp.maximum(len_ref[ib] - window, 0) // page_size
        return (first + ik // spp) % tw

    def kv_map(ib, ih, ik, bt_ref, len_ref):
        return (ih, bt_ref[ib, _col(ib, ik, len_ref)], ik % spp, 0)

    def sc_map(ib, ih, ik, bt_ref, len_ref):
        return (ih, bt_ref[ib, _col(ib, ik, len_ref)], 0, 0)

    def q_map(ib, ih, ik, bt_ref, len_ref):
        del ik, bt_ref, len_ref
        return (ib, ih, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, g8, d), q_map),
        pl.BlockSpec((1, 1, block_kv, d), kv_map),
        pl.BlockSpec((1, 1, block_kv, dv), kv_map),
    ]
    operands = [qg, k_pages, v_pages]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, 1, 1), sc_map)] * 2
        operands += [scale_tiles(k_scales), scale_tiles(v_scales)]

    grid = (b, hkv, nk)
    acc, m, l = kernel_call(
        kern,
        out_shape=(
            jax.ShapeDtypeStruct((b, hkv, g8, dv), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, g8, LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, g8, LANES), jnp.float32),
        ),
        grid=grid,
        num_scalar_prefetch=2,
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((1, 1, g8, dv), q_map),
            pl.BlockSpec((1, 1, g8, LANES), q_map),
            pl.BlockSpec((1, 1, g8, LANES), q_map),
        ),
        scratch_shapes=[
            rt.alloc_shared((g8, dv), jnp.float32),
            rt.alloc_shared((g8, LANES), jnp.float32),
            rt.alloc_shared((g8, LANES), jnp.float32),
        ],
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        name=("portable_quant_window_paged_decode_attention" if quantized
              else "portable_window_paged_decode_attention"),
        rt=rt,
    )(bt, lengths, *operands)

    acc = acc[:, :, :group].reshape(b, hq, dv)
    m = m[:, :, :group, 0].reshape(b, hq)
    l = l[:, :, :group, 0].reshape(b, hq)
    return acc, m, l
