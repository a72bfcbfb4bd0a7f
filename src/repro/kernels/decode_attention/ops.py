"""Public decode-attention op, declared against ``core/op.py``.

Declared ``differentiable=False``: decode is inference-only, so the op
dispatches straight through the variant registry with no ``custom_vjp``
wrapper.  The op returns the unnormalized (acc, m, l) residuals; this
module's public wrapper normalizes, and sequence-parallel decode
combines residuals across shards instead (``combine_partials``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.op import device_op
from repro.kernels.decode_attention import ref as _ref
from repro.kernels.decode_attention import decode_attention as _kern
from repro.kernels.decode_attention import paged as _paged
from repro.kernels.decode_attention import quant as _quant
from repro.kernels.decode_attention import spec as _spec


def _ref_impl(q, k_cache, v_cache, lengths, *, window, softcap, scale,
              block_kv, kv_offset):
    del block_kv
    return _ref.decode_attention_ref(
        q, k_cache, v_cache, lengths, window=window, softcap=softcap,
        scale=scale, kv_offset=kv_offset, return_residuals=True)


def _kernel_impl(q, k_cache, v_cache, lengths, *, window, softcap, scale,
                 block_kv, kv_offset):
    return _kern.decode_attention_fwd(
        q, k_cache, v_cache, lengths, window=window, softcap=softcap,
        scale=scale, block_kv=block_kv, kv_offset=kv_offset)


def _example(key):
    kq, kk, kv = jax.random.split(key, 3)
    b, hq, hkv, s, d = 2, 4, 2, 128, 64
    q = jax.random.normal(kq, (b, hq, d), jnp.float32)
    kc = jax.random.normal(kk, (b, hkv, s, d), jnp.float32)
    vc = jax.random.normal(kv, (b, hkv, s, d), jnp.float32)
    lengths = jnp.array([s, s // 2], jnp.int32)
    return (q, kc, vc, lengths), dict(window=None, softcap=None, scale=None,
                                      block_kv=None, kv_offset=0)


decode_attention_op = device_op(
    name="decode_attention",
    ref=_ref_impl,
    kernel=_kernel_impl,
    tunables={"block_kv": 512},
    tuning={"tpu": {"block_kv": 1024}},
    # One query row per (batch, head): block_kv is the only tile axis.
    search_space={"block_kv": (64, 128, 256, 512, 1024)},
    differentiable=False,
    example=_example,
)


def decode_attention(q, k_cache, v_cache, lengths, *,
                     window: Optional[int] = None,
                     softcap: Optional[float] = None,
                     scale: Optional[float] = None,
                     block_kv: Optional[int] = None,
                     kv_offset: int = 0,
                     return_residuals: bool = False):
    """Single-token GQA decode attention.

    q: (B, Hq, D); caches: (B, Hkv, S, D); lengths: (B,) int32 (valid
    prefix; the query is the newest token).  With return_residuals the
    unnormalized (acc, m, l) come back for cross-shard LSE combines
    (sequence-parallel decode over a sharded KV cache).  ``block_kv``
    defaults to the per-target tuning table.
    """
    acc, m, l = decode_attention_op(
        q, k_cache, v_cache, lengths, window=window, softcap=softcap,
        scale=scale, block_kv=block_kv, kv_offset=kv_offset)
    if return_residuals:
        return acc, m, l
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return (acc / l_safe[..., None]).astype(q.dtype)


combine_partials = _ref.combine_partials


# ------------------------------------------------------------ paged ------

def _paged_ref_impl(q, k_pages, v_pages, block_tables, lengths, *, window,
                    softcap, scale, page_size, block_kv, layer=None):
    # Paging granularity is a scheduling choice; the oracle is the
    # page-gathered dense computation, identical for every (page_size,
    # block_kv) candidate — which is exactly what makes them tunable.
    del page_size, block_kv
    if layer is not None:
        k_pages, v_pages = k_pages[layer], v_pages[layer]
    return _ref.paged_decode_attention_ref(
        q, k_pages, v_pages, block_tables, lengths, window=window,
        softcap=softcap, scale=scale, return_residuals=True)


def _paged_kernel_impl(q, k_pages, v_pages, block_tables, lengths, *, window,
                       softcap, scale, page_size, block_kv, layer=None):
    return _paged.paged_decode_attention_fwd(
        q, k_pages, v_pages, block_tables, lengths, window=window,
        softcap=softcap, scale=scale, page_size=page_size, block_kv=block_kv,
        layer=layer)


def _paged_example(key):
    kq, kk, kv, kp = jax.random.split(key, 4)
    b, hq, hkv, d = 2, 4, 2, 64
    pages_per_slot, page_size = 4, 64          # physical ps = search-space max
    n_pages = 1 + b * pages_per_slot           # page 0 = reserved null page
    q = jax.random.normal(kq, (b, hq, d), jnp.float32)
    kpg = jax.random.normal(kk, (hkv, n_pages, page_size, d), jnp.float32)
    vpg = jax.random.normal(kv, (hkv, n_pages, page_size, d), jnp.float32)
    # a deliberately scrambled page assignment — the gather must work for
    # any permutation the allocator hands out, not just identity layout
    perm = jax.random.permutation(kp, jnp.arange(1, n_pages, dtype=jnp.int32))
    bt = perm.reshape(b, pages_per_slot)
    bt = bt.at[1, -1].set(0)                   # slot 1 tail unallocated
    lengths = jnp.array([3 * page_size + 17, 2 * page_size + 5], jnp.int32)
    return (q, kpg, vpg, bt, lengths), dict(
        window=None, softcap=None, scale=None, page_size=None, block_kv=None)


paged_decode_attention_op = device_op(
    name="paged_decode_attention",
    ref=_paged_ref_impl,
    kernel=_paged_kernel_impl,
    tunables={"page_size": 64, "block_kv": 64},
    # interpret favors fewer, larger grid steps; leave TPU to the tuner.
    search_space={"page_size": (16, 32, 64), "block_kv": (16, 32, 64)},
    # a KV block cannot span two non-contiguous pages, and the logical
    # page must split the example's physical page evenly
    constraints=(lambda cfg: cfg["page_size"] % cfg["block_kv"] == 0,),
    differentiable=False,
    example=_paged_example,
)


def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths, *,
                           window: Optional[int] = None,
                           softcap: Optional[float] = None,
                           scale: Optional[float] = None,
                           page_size: Optional[int] = None,
                           block_kv: Optional[int] = None,
                           layer=None,
                           return_residuals: bool = False):
    """Single-token GQA decode attention over a paged KV pool.

    q: (B, Hq, D); pools: (Hkv, P, ps, D), or every layer's pools
    stacked (L, Hkv, P, ps, D) and read at the int32 scalar ``layer``;
    block_tables: (B, T) int32 page ids; lengths: (B,) valid prefix.
    Semantics match ``decode_attention`` over the page-gathered dense
    cache of ``pool[layer]``; tunables (``page_size`` logical
    granularity, ``block_kv`` tokens per grid step) default to the
    per-target tuning table.
    """
    acc, m, l = paged_decode_attention_op(
        q, k_pages, v_pages, block_tables, lengths, window=window,
        softcap=softcap, scale=scale, page_size=page_size, block_kv=block_kv,
        layer=layer)
    if return_residuals:
        return acc, m, l
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return (acc / l_safe[..., None]).astype(q.dtype)


# ------------------------------------------------ windowed ring paged ------

def _window_paged_ref_impl(q, k_pages, v_pages, block_tables, lengths, *,
                           window, softcap, scale, page_size, block_kv):
    del page_size, block_kv            # scheduling-only, as for the paged op
    return _ref.window_paged_decode_attention_ref(
        q, k_pages, v_pages, block_tables, lengths, window=window,
        softcap=softcap, scale=scale, return_residuals=True)


def _window_paged_kernel_impl(q, k_pages, v_pages, block_tables, lengths, *,
                              window, softcap, scale, page_size, block_kv):
    return _paged.window_paged_decode_attention_fwd(
        q, k_pages, v_pages, block_tables, lengths, window=window,
        softcap=softcap, scale=scale, page_size=page_size, block_kv=block_kv)


def _window_paged_example(key):
    # Ring block tables: T_w = (window-1)//ps + 2 columns, global page g
    # at column g % T_w.  window=96 over ps=64 gives T_w=3; slot 0 has
    # run long enough that its live pages {2,3,4} wrap the ring (columns
    # {2,0,1}), slot 1 is still short (pages {0,1}, column 2 NULL) — the
    # example pins both the wrap gather and the partial-first-block mask.
    kq, kk, kv, kp = jax.random.split(key, 4)
    b, hq, hkv, d = 2, 4, 2, 64
    window, page_size = 96, 64
    tw = (window - 1) // page_size + 2
    n_pages = 1 + b * tw                       # page 0 = reserved null page
    q = jax.random.normal(kq, (b, hq, d), jnp.float32)
    kpg = jax.random.normal(kk, (hkv, n_pages, page_size, d), jnp.float32)
    vpg = jax.random.normal(kv, (hkv, n_pages, page_size, d), jnp.float32)
    perm = jax.random.permutation(kp, jnp.arange(1, n_pages, dtype=jnp.int32))
    lengths = jnp.array([4 * page_size + 17, page_size + 5], jnp.int32)
    bt = jnp.zeros((b, tw), jnp.int32)
    for i, g in enumerate(range(2, 5)):        # slot 0: live pages 2..4
        bt = bt.at[0, g % tw].set(perm[i])
    for i, g in enumerate(range(0, 2)):        # slot 1: live pages 0..1
        bt = bt.at[1, g % tw].set(perm[3 + i])
    return (q, kpg, vpg, bt, lengths), dict(
        window=window, softcap=None, scale=None, page_size=None, block_kv=None)


window_paged_decode_attention_op = device_op(
    name="window_paged_decode_attention",
    ref=_window_paged_ref_impl,
    kernel=_window_paged_kernel_impl,
    tunables={"page_size": 64, "block_kv": 64},
    search_space={"page_size": (16, 32, 64), "block_kv": (16, 32, 64)},
    constraints=(lambda cfg: cfg["page_size"] % cfg["block_kv"] == 0,),
    differentiable=False,
    example=_window_paged_example,
)


def window_paged_decode_attention(q, k_pages, v_pages, block_tables, lengths,
                                  *, window: int,
                                  softcap: Optional[float] = None,
                                  scale: Optional[float] = None,
                                  page_size: Optional[int] = None,
                                  block_kv: Optional[int] = None,
                                  return_residuals: bool = False):
    """Sliding-window GQA decode attention over a *ring* block table.

    q: (B, Hq, D); pools: (Hkv, P, ps, D); block_tables: (B, T_w) int32
    ring tables (``T_w = window_table_width(window, ps)``, global page
    ``g`` at column ``g % T_w``); lengths: (B,) valid prefix.  Semantics
    match ``decode_attention(window=window)`` over the un-rung dense
    cache, but the table — and the kernel grid — stay O(window) wide no
    matter how long the context ran.
    """
    acc, m, l = window_paged_decode_attention_op(
        q, k_pages, v_pages, block_tables, lengths, window=window,
        softcap=softcap, scale=scale, page_size=page_size, block_kv=block_kv)
    if return_residuals:
        return acc, m, l
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return (acc / l_safe[..., None]).astype(q.dtype)


def _quant_window_paged_ref_impl(q, k_pages, v_pages, k_scales, v_scales,
                                 block_tables, lengths, *, window, softcap,
                                 scale, page_size, block_kv):
    del page_size, block_kv
    return _ref.quant_window_paged_decode_attention_ref(
        q, k_pages, v_pages, k_scales, v_scales, block_tables, lengths,
        window=window, softcap=softcap, scale=scale, return_residuals=True)


def _quant_window_paged_kernel_impl(q, k_pages, v_pages, k_scales, v_scales,
                                    block_tables, lengths, *, window, softcap,
                                    scale, page_size, block_kv):
    return _quant.quant_window_paged_decode_attention_fwd(
        q, k_pages, v_pages, k_scales, v_scales, block_tables, lengths,
        window=window, softcap=softcap, scale=scale, page_size=page_size,
        block_kv=block_kv)


def _quant_window_paged_example(key):
    from repro.quant import spec_for_storage
    (q, kpg, vpg, bt, lengths), params = _window_paged_example(key)
    s = spec_for_storage(jnp.int8)
    kq, ks = s.quantize_pages(kpg)
    vq, vs = s.quantize_pages(vpg)
    return (q, kq, vq, ks, vs, bt, lengths), dict(params)


quant_window_paged_decode_attention_op = device_op(
    name="quant_window_paged_decode_attention",
    ref=_quant_window_paged_ref_impl,
    kernel=_quant_window_paged_kernel_impl,
    tunables={"page_size": 64, "block_kv": 64},
    search_space={"page_size": (16, 32, 64), "block_kv": (16, 32, 64)},
    constraints=(lambda cfg: cfg["page_size"] % cfg["block_kv"] == 0,),
    differentiable=False,
    example=_quant_window_paged_example,
)


def quant_window_paged_decode_attention(q, k_pages, v_pages, k_scales,
                                        v_scales, block_tables, lengths, *,
                                        window: int,
                                        softcap: Optional[float] = None,
                                        scale: Optional[float] = None,
                                        page_size: Optional[int] = None,
                                        block_kv: Optional[int] = None,
                                        return_residuals: bool = False):
    """Sliding-window decode over a *quantized* ring-table pool —
    ``window_paged_decode_attention`` semantics over the dequantized
    pools, dequant fused into the kernel body (the PR 4 path)."""
    acc, m, l = quant_window_paged_decode_attention_op(
        q, k_pages, v_pages, k_scales, v_scales, block_tables, lengths,
        window=window, softcap=softcap, scale=scale, page_size=page_size,
        block_kv=block_kv)
    if return_residuals:
        return acc, m, l
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return (acc / l_safe[..., None]).astype(q.dtype)


# -------------------------------------------------- speculative paged ------

def _spec_paged_ref_impl(q, k_pages, v_pages, block_tables, lengths, *,
                         window, softcap, scale, page_size, block_kv):
    del page_size, block_kv            # scheduling-only, as for the paged op
    return _ref.spec_paged_decode_attention_ref(
        q, k_pages, v_pages, block_tables, lengths, window=window,
        softcap=softcap, scale=scale, return_residuals=True)


def _spec_paged_kernel_impl(q, k_pages, v_pages, block_tables, lengths, *,
                            window, softcap, scale, page_size, block_kv):
    return _spec.spec_paged_decode_attention_fwd(
        q, k_pages, v_pages, block_tables, lengths, window=window,
        softcap=softcap, scale=scale, page_size=page_size, block_kv=block_kv)


def _spec_paged_example(key):
    # Same scrambled-page pool as the single-query paged example, with
    # a K1=3 speculation window per slot: the verify must mask each
    # window position to its own causal horizon, including the window
    # rows the engine wrote just before the call (here: whatever the
    # random pool holds at positions lengths..lengths+2 — the kernel
    # and oracle must read identical data either way).
    (q1, kpg, vpg, bt, lengths), params = _paged_example(key)
    b, hq, d = q1.shape
    k1 = 3
    q = jax.random.normal(jax.random.fold_in(key, 7), (b, k1, hq, d),
                          jnp.float32)
    return (q, kpg, vpg, bt, lengths), dict(params)


spec_paged_decode_attention_op = device_op(
    name="spec_paged_decode_attention",
    ref=_spec_paged_ref_impl,
    kernel=_spec_paged_kernel_impl,
    tunables={"page_size": 64, "block_kv": 64},
    search_space={"page_size": (16, 32, 64), "block_kv": (16, 32, 64)},
    constraints=(lambda cfg: cfg["page_size"] % cfg["block_kv"] == 0,),
    differentiable=False,
    example=_spec_paged_example,
)


def spec_paged_decode_attention(q, k_pages, v_pages, block_tables, lengths, *,
                                window: Optional[int] = None,
                                softcap: Optional[float] = None,
                                scale: Optional[float] = None,
                                page_size: Optional[int] = None,
                                block_kv: Optional[int] = None,
                                return_residuals: bool = False):
    """Speculative (multi-query) GQA decode attention over a paged pool.

    q: (B, K1, Hq, D) — the committed token plus k drafts per slot;
    pools: (Hkv, P, ps, D); block_tables: (B, T) int32; lengths: (B,)
    PRE-speculation valid prefix.  Position i attends causally to
    ``lengths + 1 + i`` tokens; all K1 positions are verified in one
    paged-decode call (kernels/decode_attention/spec.py).  Returns
    (B, K1, Hq, Dv) normalized, or the (acc, m, l) residuals.
    """
    acc, m, l = spec_paged_decode_attention_op(
        q, k_pages, v_pages, block_tables, lengths, window=window,
        softcap=softcap, scale=scale, page_size=page_size, block_kv=block_kv)
    if return_residuals:
        return acc, m, l
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return (acc / l_safe[..., None]).astype(q.dtype)


def _quant_spec_paged_ref_impl(q, k_pages, v_pages, k_scales, v_scales,
                               block_tables, lengths, *, window, softcap,
                               scale, page_size, block_kv):
    del page_size, block_kv
    return _ref.quant_spec_paged_decode_attention_ref(
        q, k_pages, v_pages, k_scales, v_scales, block_tables, lengths,
        window=window, softcap=softcap, scale=scale, return_residuals=True)


def _quant_spec_paged_kernel_impl(q, k_pages, v_pages, k_scales, v_scales,
                                  block_tables, lengths, *, window, softcap,
                                  scale, page_size, block_kv):
    return _spec.spec_paged_decode_attention_fwd(
        q, k_pages, v_pages, block_tables, lengths, window=window,
        softcap=softcap, scale=scale, page_size=page_size, block_kv=block_kv,
        k_scales=k_scales, v_scales=v_scales)


def _quant_spec_paged_example(key):
    from repro.quant import spec_for_storage
    (q, kpg, vpg, bt, lengths), params = _spec_paged_example(key)
    s = spec_for_storage(jnp.int8)
    kq, ks = s.quantize_pages(kpg)
    vq, vs = s.quantize_pages(vpg)
    return (q, kq, vq, ks, vs, bt, lengths), dict(params)


quant_spec_paged_decode_attention_op = device_op(
    name="quant_spec_paged_decode_attention",
    ref=_quant_spec_paged_ref_impl,
    kernel=_quant_spec_paged_kernel_impl,
    tunables={"page_size": 64, "block_kv": 64},
    # dtype stays a capability axis, not a tunable — same reasoning as
    # quant_paged_decode_attention below.
    search_space={"page_size": (16, 32, 64), "block_kv": (16, 32, 64)},
    constraints=(lambda cfg: cfg["page_size"] % cfg["block_kv"] == 0,),
    differentiable=False,
    example=_quant_spec_paged_example,
)


def quant_spec_paged_decode_attention(q, k_pages, v_pages, k_scales,
                                      v_scales, block_tables, lengths, *,
                                      window: Optional[int] = None,
                                      softcap: Optional[float] = None,
                                      scale: Optional[float] = None,
                                      page_size: Optional[int] = None,
                                      block_kv: Optional[int] = None,
                                      return_residuals: bool = False):
    """Speculative multi-query decode over a *quantized* paged pool —
    ``spec_paged_decode_attention`` semantics over the dequantized
    pools, with the per-block dequant fused into the kernel body (the
    PR 4 fused-dequant path, unchanged)."""
    acc, m, l = quant_spec_paged_decode_attention_op(
        q, k_pages, v_pages, k_scales, v_scales, block_tables, lengths,
        window=window, softcap=softcap, scale=scale, page_size=page_size,
        block_kv=block_kv)
    if return_residuals:
        return acc, m, l
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return (acc / l_safe[..., None]).astype(q.dtype)


# ---------------------------------------------------- quantized paged ------

def _quant_paged_ref_impl(q, k_pages, v_pages, k_scales, v_scales,
                          block_tables, lengths, *, window, softcap, scale,
                          page_size, block_kv):
    del page_size, block_kv            # scheduling-only, as for the bf16 op
    return _ref.quant_paged_decode_attention_ref(
        q, k_pages, v_pages, k_scales, v_scales, block_tables, lengths,
        window=window, softcap=softcap, scale=scale, return_residuals=True)


def _quant_paged_kernel_impl(q, k_pages, v_pages, k_scales, v_scales,
                             block_tables, lengths, *, window, softcap,
                             scale, page_size, block_kv):
    return _quant.quant_paged_decode_attention_fwd(
        q, k_pages, v_pages, k_scales, v_scales, block_tables, lengths,
        window=window, softcap=softcap, scale=scale, page_size=page_size,
        block_kv=block_kv)


def _quant_paged_example(key):
    # Same paged layout as the bf16 example, but the pools are int8
    # with per-page-per-head scales — quantized through the subsystem
    # so the example exercises the real storage contract.  (int8 is
    # the portable storage floor: the example must run on every arch,
    # including generic, whose capability set has no fp8.)
    from repro.quant import spec_for_storage
    (q, kpg, vpg, bt, lengths), params = _paged_example(key)
    s = spec_for_storage(jnp.int8)
    kq, ks = s.quantize_pages(kpg)
    vq, vs = s.quantize_pages(vpg)
    return (q, kq, vq, ks, vs, bt, lengths), dict(params)


quant_paged_decode_attention_op = device_op(
    name="quant_paged_decode_attention",
    ref=_quant_paged_ref_impl,
    kernel=_quant_paged_kernel_impl,
    tunables={"page_size": 64, "block_kv": 64},
    # Storage dtype is a *capability* axis dispatched through
    # quant/capability.py, not a tunable: the autotuner gates every
    # candidate against one fixed oracle, and changing the dtype
    # changes the semantics, not the schedule.  The kv_quant BENCH
    # section measures the dtype axis instead.
    search_space={"page_size": (16, 32, 64), "block_kv": (16, 32, 64)},
    constraints=(lambda cfg: cfg["page_size"] % cfg["block_kv"] == 0,),
    differentiable=False,
    example=_quant_paged_example,
)


def quant_paged_decode_attention(q, k_pages, v_pages, k_scales, v_scales,
                                 block_tables, lengths, *,
                                 window: Optional[int] = None,
                                 softcap: Optional[float] = None,
                                 scale: Optional[float] = None,
                                 page_size: Optional[int] = None,
                                 block_kv: Optional[int] = None,
                                 return_residuals: bool = False):
    """Single-token GQA decode attention over a *quantized* paged pool.

    q: (B, Hq, D); pools: (Hkv, P, ps, D) int8/fp8-e4m3; scale pools:
    (Hkv, P) f32 per-page-per-head; block_tables: (B, T) int32;
    lengths: (B,).  Semantics match ``paged_decode_attention`` over the
    dequantized pools (dequant fuses into the kernel body after the
    block-table DMA); tunables default to the per-target tuning table.
    """
    acc, m, l = quant_paged_decode_attention_op(
        q, k_pages, v_pages, k_scales, v_scales, block_tables, lengths,
        window=window, softcap=softcap, scale=scale, page_size=page_size,
        block_kv=block_kv)
    if return_residuals:
        return acc, m, l
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return (acc / l_safe[..., None]).astype(q.dtype)
