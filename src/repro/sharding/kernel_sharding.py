"""shard_map wrappers that keep portable kernels per-device under pjit.

Pallas kernels (compiled or interpreted) are *per-device* programs: GSPMD
cannot partition through a ``pallas_call`` (on TPU it is an opaque Mosaic
custom-call; in interpret mode it is a while-loop GSPMD would have to
all-gather).  Production frameworks therefore wrap every kernel in
``shard_map`` with explicit per-operand specs — this module centralizes
those wrappers and the layout policy:

  flash attention   — q/kv HEAD-sharded over 'model' when divisible,
                      otherwise Q-SEQUENCE-sharded (each model shard owns
                      a contiguous q-row slice, KV gathered; the kernel's
                      dynamic ``q_offset`` keeps causal/window masks
                      globally correct).  Batch over ('pod','data').
  decode attention  — head-sharded when divisible; otherwise the KV cache
                      is SEQUENCE-sharded over 'model' (SP decode): each
                      shard computes flash partials on its cache slice and
                      the (acc, m, l) residuals are combined with a
                      cross-shard log-sum-exp (pmax/psum) — flash-decode
                      across chips.
  mamba scan        — d_inner channel-sharded over 'model' (no collectives;
                      the recurrence is channel-local).
  mlstm scan        — Dv (value) channel-sharded over 'model'; q/k/gates
                      replicated (the normalizer n·q needs full Dk).
  rmsnorm           — rows sharded over ('pod','data') x 'model'.

When no mesh is active (single-device tests) every wrapper degrades to a
direct op call.  When the target is ``generic`` (pure-jnp fallback) the
ops are ordinary XLA and GSPMD partitions them without help, so wrappers
pass through as well — the portable-runtime story at the distribution
layer.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from repro.core.runtime import runtime
from repro.kernels.decode_attention.ops import (
    decode_attention, paged_decode_attention, quant_paged_decode_attention,
    quant_spec_paged_decode_attention, quant_window_paged_decode_attention,
    spec_paged_decode_attention, window_paged_decode_attention)
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.mamba_scan.ops import mamba_scan
from repro.kernels.mlstm_scan.ops import mlstm_scan
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.sharding import mesh_ctx

__all__ = [
    "sharded_flash_attention", "sharded_decode_attention",
    "sharded_paged_decode_update_attend",
    "sharded_quant_paged_decode_update_attend",
    "sharded_window_paged_decode_update_attend",
    "sharded_quant_window_paged_decode_update_attend",
    "sharded_spec_paged_decode_update_attend",
    "sharded_quant_spec_paged_decode_update_attend",
    "sharded_mamba_scan", "sharded_mlstm_scan", "sharded_rmsnorm",
    "maybe_mesh", "shard_map",
]


def maybe_mesh() -> Optional[Mesh]:
    try:
        m = mesh_ctx.current_mesh()
    except RuntimeError:
        return None
    if m is not None and m.devices.size == 1:
        return None
    return m


def _use_wrappers(mesh: Optional[Mesh]) -> bool:
    # generic target = plain XLA ops; GSPMD partitions them natively.
    return mesh is not None and runtime().use_pallas


def _dp(mesh: Mesh, b: int):
    """Batch axes: ('pod','data') reduced until the batch divides."""
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    while axes:
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        if b % n == 0:
            return axes
        axes = axes[1:]
    return None


def _tp(mesh: Mesh) -> int:
    return mesh.shape.get("model", 1)


# ------------------------------------------------------------- flash ----

def sharded_flash_attention(q, k, v, *, causal: bool = True,
                            window: Optional[int] = None,
                            softcap: Optional[float] = None,
                            scale: Optional[float] = None,
                            block_q: Optional[int] = None,
                            block_kv: Optional[int] = None):
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D)."""
    mesh = maybe_mesh()
    kw = dict(causal=causal, window=window, softcap=softcap, scale=scale,
              block_q=block_q, block_kv=block_kv)
    if not _use_wrappers(mesh):
        return flash_attention(q, k, v, **kw)

    b, hq, sq, _ = q.shape
    hkv = k.shape[1]
    dp = _dp(mesh, b)
    tp = _tp(mesh)

    if hq % tp == 0 and hkv % tp == 0:
        # head sharding: fully local attention per model shard
        qs = P(dp, "model", None, None)
        kvs = P(dp, "model", None, None)

        def body(q_, k_, v_):
            return flash_attention(q_, k_, v_, **kw)

        return shard_map(body, mesh=mesh, in_specs=(qs, kvs, kvs),
                         out_specs=qs, check_vma=False)(q, k, v)

    # NOTE (§Perf-A.2, refuted): a fused batch×head sharding — flatten
    # (B, H) and shard the merged dim over every axis so attention is
    # fully local — was tried here and REGRESSED collective bytes 4.6×
    # (50.5 → 234 GiB/chip on gemma3-4b train_4k): GSPMD implements the
    # dimension-merging reshape of a sharded dim as a full all-gather +
    # reslice per layer.  Lesson recorded in EXPERIMENTS.md §Perf-A;
    # the q-sequence path below stays.

    if sq % tp == 0:
        # sequence parallelism over q rows; KV gathered per model shard.
        qs = P(dp, None, "model", None)
        kvs = P(dp, None, None, None)
        sq_loc = sq // tp

        def body(q_, k_, v_):
            off = jax.lax.axis_index("model") * sq_loc
            return flash_attention(q_, k_, v_, q_offset=off, **kw)

        return shard_map(body, mesh=mesh, in_specs=(qs, kvs, kvs),
                         out_specs=qs, check_vma=False)(q, k, v)

    # fallback: replicate over 'model' (batch-only sharding)
    qs = P(dp, None, None, None)

    def body(q_, k_, v_):
        return flash_attention(q_, k_, v_, **kw)

    return shard_map(body, mesh=mesh, in_specs=(qs, qs, qs),
                     out_specs=qs, check_vma=False)(q, k, v)


# ------------------------------------------------------------ decode ----

def sharded_decode_update_attend(q, k_new, v_new, k_cache, v_cache,
                                 write_pos, eff_len, *,
                                 window: Optional[int] = None,
                                 softcap: Optional[float] = None,
                                 scale: Optional[float] = None,
                                 block_kv: Optional[int] = None):
    """Fused cache-update + decode attention.

    q: (B,Hq,D); k_new/v_new: (B,Hkv,D) rope'd; caches: (B,Hkv,S,D);
    write_pos/eff_len: (B,).  Returns (out (B,Hq,Dv), new_k, new_v).

    §Perf-B.1: updating the cache with a one-hot select OUTSIDE the
    shard_map made GSPMD all-gather the entire cache in f32 per layer
    per token (measured 256 MiB x 9 attention layers on jamba
    long_500k).  Doing the update inside the shard_map keeps it a local
    elementwise select on each shard's slots."""
    mesh = maybe_mesh()
    b, hq, dk = q.shape
    hkv, s = k_cache.shape[1], k_cache.shape[2]
    dv = v_cache.shape[3]
    kw = dict(window=window, softcap=softcap, scale=scale,
              block_kv=block_kv)

    def update(ck, cv, kn, vn, pos, off):
        slot = jnp.arange(ck.shape[2])[None, None, :, None] + off
        onehot = slot == pos[:, None, None, None]
        ck = jnp.where(onehot, kn[:, :, None, :].astype(ck.dtype), ck)
        cv = jnp.where(onehot, vn[:, :, None, :].astype(cv.dtype), cv)
        return ck, cv

    if not _use_wrappers(mesh):
        ck, cv = update(k_cache, v_cache, k_new, v_new, write_pos, 0)
        return (decode_attention(q, ck, cv, eff_len, **kw), ck, cv)

    dp = _dp(mesh, b)
    tp = _tp(mesh)

    if hq % tp == 0 and hkv % tp == 0:
        qs, ns_, cs = (P(dp, "model", None), P(dp, "model", None),
                       P(dp, "model", None, None))

        def body(q_, kn, vn, ck, cv, pos, ln):
            ck, cv = update(ck, cv, kn, vn, pos, 0)
            return decode_attention(q_, ck, cv, ln, **kw), ck, cv

        return shard_map(
            body, mesh=mesh,
            in_specs=(qs, ns_, ns_, cs, cs, P(dp), P(dp)),
            out_specs=(qs, cs, cs), check_vma=False)(
            q, k_new, v_new, k_cache, v_cache, write_pos, eff_len)

    if s % tp == 0 and window is None:
        qs, ns_ = P(dp, None, None), P(dp, None, None)
        cs = P(dp, None, "model", None)
        s_loc = s // tp

        def body(q_, kn, vn, ck, cv, pos, ln):
            off = jax.lax.axis_index("model") * s_loc
            ck, cv = update(ck, cv, kn, vn, pos, off)
            loc_len = jnp.clip(ln - off, 0, s_loc).astype(jnp.int32)
            acc, m, l = decode_attention(q_, ck, cv, loc_len,
                                         return_residuals=True, **kw)
            m_g = jax.lax.pmax(m, "model")
            w = jnp.exp(m - m_g)
            num = jax.lax.psum(acc.astype(jnp.float32) * w[..., None],
                               "model")
            den = jax.lax.psum(l * w, "model")
            den = jnp.where(den == 0.0, 1.0, den)
            return (num / den[..., None]).astype(q_.dtype), ck, cv

        return shard_map(
            body, mesh=mesh,
            in_specs=(qs, ns_, ns_, cs, cs, P(dp), P(dp)),
            out_specs=(qs, cs, cs), check_vma=False)(
            q, k_new, v_new, k_cache, v_cache, write_pos, eff_len)

    qs, ns_, cs = (P(dp, None, None), P(dp, None, None),
                   P(dp, None, None, None))

    def body(q_, kn, vn, ck, cv, pos, ln):
        ck, cv = update(ck, cv, kn, vn, pos, 0)
        return decode_attention(q_, ck, cv, ln, **kw), ck, cv

    return shard_map(
        body, mesh=mesh, in_specs=(qs, ns_, ns_, cs, cs, P(dp), P(dp)),
        out_specs=(qs, cs, cs), check_vma=False)(
        q, k_new, v_new, k_cache, v_cache, write_pos, eff_len)

def sharded_paged_decode_update_attend(q, k_new, v_new, k_pages, v_pages,
                                       block_tables, write_page, write_off,
                                       eff_len, *,
                                       window: Optional[int] = None,
                                       softcap: Optional[float] = None,
                                       scale: Optional[float] = None,
                                       page_size: Optional[int] = None,
                                       block_kv: Optional[int] = None):
    """Fused page write + paged decode attention.

    q: (B,Hq,D); k_new/v_new: (B,Hkv,D) rope'd; pools: (Hkv,P,ps,D);
    block_tables: (B,T) int32; write_page/write_off/eff_len: (B,).
    Returns (out (B,Hq,Dv), new k_pages, new v_pages).

    The same §Perf-B.1 rule as the dense path: the pool scatter happens
    INSIDE the shard_map region so GSPMD never all-gathers the pool.
    Pools are head-major, so head sharding keeps both the write and the
    gather fully local per model shard; when heads don't divide, pools
    replicate (page-sharded SP is an open item — DESIGN.md §10).
    """
    mesh = maybe_mesh()
    b, hq, _ = q.shape
    hkv = k_pages.shape[0]
    kw = dict(window=window, softcap=softcap, scale=scale,
              page_size=page_size, block_kv=block_kv)

    def update(kp, vp, kn, vn, page, off):
        # page 0 is the allocator's null page: freed slots park there, so
        # their (masked-out) writes land in trash instead of live pages.
        kn = jnp.swapaxes(kn, 0, 1).astype(kp.dtype)      # (Hkv, B, D)
        vn = jnp.swapaxes(vn, 0, 1).astype(vp.dtype)
        kp = kp.at[:, page, off].set(kn)
        vp = vp.at[:, page, off].set(vn)
        return kp, vp

    def body(q_, kn, vn, kp, vp, bt, page, off, ln):
        kp, vp = update(kp, vp, kn, vn, page, off)
        return (paged_decode_attention(q_, kp, vp, bt, ln, **kw), kp, vp)

    if not _use_wrappers(mesh):
        return body(q, k_new, v_new, k_pages, v_pages, block_tables,
                    write_page, write_off, eff_len)

    # no batch sharding here: every shard must see every slot's write
    # (the pool has no batch dim a dp shard could own a slice of).
    dp = None
    tp = _tp(mesh)
    if hq % tp == 0 and hkv % tp == 0:
        qs, ns_ = P(dp, "model", None), P(dp, "model", None)
        ps_ = P("model", None, None, None)
    else:
        qs, ns_ = P(dp, None, None), P(dp, None, None)
        ps_ = P(None, None, None, None)
    return shard_map(
        body, mesh=mesh,
        in_specs=(qs, ns_, ns_, ps_, ps_, P(dp, None), P(dp), P(dp), P(dp)),
        out_specs=(qs, ps_, ps_), check_vma=False)(
        q, k_new, v_new, k_pages, v_pages, block_tables,
        write_page, write_off, eff_len)


def sharded_quant_paged_decode_update_attend(q, k_new, v_new,
                                             k_pages, v_pages,
                                             k_scales, v_scales,
                                             block_tables, write_page,
                                             write_off, eff_len, *,
                                             window: Optional[int] = None,
                                             softcap: Optional[float] = None,
                                             scale: Optional[float] = None,
                                             page_size: Optional[int] = None,
                                             block_kv: Optional[int] = None):
    """Fused re-quantizing page write + quantized paged decode attention.

    q: (B,Hq,D); k_new/v_new: (B,Hkv,D) rope'd; pools: (Hkv,P,ps,D)
    int8/fp8; scale pools: (Hkv,P) f32 per-page-per-head;
    block_tables: (B,T) int32; write_page/write_off/eff_len: (B,).
    Returns (out (B,Hq,Dv), new k_pages, new v_pages, new k_scales,
    new v_scales).

    **Write semantics** — page-granular absmax scales mean a single-row
    write must keep the whole page consistent: the write page is
    gathered, dequantized under its current scale, the new row spliced
    at ``write_off``, rows past the offset zeroed (they are either
    unwritten or stale garbage from a previous tenant of a recycled
    page), and the page re-quantized under the refreshed absmax.  When
    the page's scale is unchanged the re-quantization is *exact*
    (``round(q * s / s) == q``), so error accumulates only on the rare
    steps where a new row raises the page absmax — bounded by half a
    quantization step per scale change, which the documented
    ``quant.DECODE_TOL`` covers.  Dead slots park on null page 0, so
    their (duplicate-index) writes land in trash exactly as in the
    bf16 paged path.

    Sharding follows the §Perf-B.1 rule: the gather-requantize-scatter
    happens INSIDE the shard_map region, with the scale pools sharded
    head-major exactly like the KV pools, so GSPMD never all-gathers
    either.  When heads don't divide, pools and scale pools replicate
    together (page-sharded SP remains the open item — DESIGN.md §10).
    """
    from repro.quant import quantize_absmax
    mesh = maybe_mesh()
    b, hq, _ = q.shape
    hkv = k_pages.shape[0]
    ps = k_pages.shape[2]
    kw = dict(window=window, softcap=softcap, scale=scale,
              page_size=page_size, block_kv=block_kv)

    def update(pool, scales, new_row, page, off):
        new_row = jnp.swapaxes(new_row, 0, 1).astype(jnp.float32)  # (H,B,D)
        pg = pool[:, page]                                  # (H,B,ps,D)
        sc = scales[:, page]                                # (H,B)
        pgf = pg.astype(jnp.float32) * sc[:, :, None, None]
        rows = jnp.arange(ps)[None, None, :, None]
        offb = off[None, :, None, None]
        pgf = jnp.where(rows == offb, new_row[:, :, None, :],
                        jnp.where(rows < offb, pgf, 0.0))
        q_pg, sc_new = quantize_absmax(pgf, dtype=pool.dtype,
                                       axis=(-2, -1))
        return (pool.at[:, page].set(q_pg),
                scales.at[:, page].set(sc_new.astype(scales.dtype)))

    def body(q_, kn, vn, kp, vp, ks, vs, bt, page, off, ln):
        kp, ks = update(kp, ks, kn, page, off)
        vp, vs = update(vp, vs, vn, page, off)
        out = quant_paged_decode_attention(q_, kp, vp, ks, vs, bt, ln, **kw)
        return out, kp, vp, ks, vs

    if not _use_wrappers(mesh):
        return body(q, k_new, v_new, k_pages, v_pages, k_scales, v_scales,
                    block_tables, write_page, write_off, eff_len)

    # no batch sharding (same as the bf16 paged wrapper): every shard
    # must see every slot's write — the pool has no batch dim.
    dp = None
    tp = _tp(mesh)
    if hq % tp == 0 and hkv % tp == 0:
        qs, ns_ = P(dp, "model", None), P(dp, "model", None)
        ps_ = P("model", None, None, None)
        ss_ = P("model", None)
    else:
        qs, ns_ = P(dp, None, None), P(dp, None, None)
        ps_ = P(None, None, None, None)
        ss_ = P(None, None)
    return shard_map(
        body, mesh=mesh,
        in_specs=(qs, ns_, ns_, ps_, ps_, ss_, ss_, P(dp, None),
                  P(dp), P(dp), P(dp)),
        out_specs=(qs, ps_, ps_, ss_, ss_), check_vma=False)(
        q, k_new, v_new, k_pages, v_pages, k_scales, v_scales,
        block_tables, write_page, write_off, eff_len)


def sharded_window_paged_decode_update_attend(q, k_new, v_new, k_pages,
                                              v_pages, block_tables,
                                              write_page, write_off, eff_len,
                                              *, window: int,
                                              softcap: Optional[float] = None,
                                              scale: Optional[float] = None,
                                              page_size: Optional[int] = None,
                                              block_kv: Optional[int] = None):
    """Fused page write + windowed ring-table decode attention.

    Identical contract to ``sharded_paged_decode_update_attend`` except
    ``block_tables`` is the (B, T_w) *ring* (global page ``g`` at column
    ``g % T_w``) and ``window`` is required.  The engine resolves the
    write page from the ring before the call (column ``(L // ps) %
    T_w``), so the scatter itself is position-blind — same §Perf-B.1
    rule, pool writes INSIDE the shard_map region; same layout policy
    (head-sharded when divisible, else replicated; no batch sharding).
    """
    mesh = maybe_mesh()
    b, hq, _ = q.shape
    hkv = k_pages.shape[0]
    kw = dict(window=window, softcap=softcap, scale=scale,
              page_size=page_size, block_kv=block_kv)

    def update(kp, vp, kn, vn, page, off):
        kn = jnp.swapaxes(kn, 0, 1).astype(kp.dtype)      # (Hkv, B, D)
        vn = jnp.swapaxes(vn, 0, 1).astype(vp.dtype)
        kp = kp.at[:, page, off].set(kn)
        vp = vp.at[:, page, off].set(vn)
        return kp, vp

    def body(q_, kn, vn, kp, vp, bt, page, off, ln):
        kp, vp = update(kp, vp, kn, vn, page, off)
        return (window_paged_decode_attention(q_, kp, vp, bt, ln, **kw),
                kp, vp)

    if not _use_wrappers(mesh):
        return body(q, k_new, v_new, k_pages, v_pages, block_tables,
                    write_page, write_off, eff_len)

    dp = None                      # no batch sharding: pool has no batch dim
    tp = _tp(mesh)
    if hq % tp == 0 and hkv % tp == 0:
        qs, ns_ = P(dp, "model", None), P(dp, "model", None)
        ps_ = P("model", None, None, None)
    else:
        qs, ns_ = P(dp, None, None), P(dp, None, None)
        ps_ = P(None, None, None, None)
    return shard_map(
        body, mesh=mesh,
        in_specs=(qs, ns_, ns_, ps_, ps_, P(dp, None), P(dp), P(dp), P(dp)),
        out_specs=(qs, ps_, ps_), check_vma=False)(
        q, k_new, v_new, k_pages, v_pages, block_tables,
        write_page, write_off, eff_len)


def sharded_quant_window_paged_decode_update_attend(
        q, k_new, v_new, k_pages, v_pages, k_scales, v_scales,
        block_tables, write_page, write_off, eff_len, *, window: int,
        softcap: Optional[float] = None, scale: Optional[float] = None,
        page_size: Optional[int] = None, block_kv: Optional[int] = None):
    """Fused re-quantizing page write + quantized windowed decode.

    The write path is byte-for-byte the PR 4 single-row re-quantizing
    update (gather page → dequant → splice → zero stale tail →
    re-absmax → requant) — ring columns recycle pages constantly, and
    the zero-past-offset step is what keeps a recycled page's previous
    tenant out of the refreshed absmax.  Attention goes through the
    windowed ring-table kernel; layouts follow the quant paged wrapper
    (scale pools sharded head-major with the KV pools).
    """
    from repro.quant import quantize_absmax
    mesh = maybe_mesh()
    b, hq, _ = q.shape
    hkv = k_pages.shape[0]
    ps = k_pages.shape[2]
    kw = dict(window=window, softcap=softcap, scale=scale,
              page_size=page_size, block_kv=block_kv)

    def update(pool, scales, new_row, page, off):
        new_row = jnp.swapaxes(new_row, 0, 1).astype(jnp.float32)  # (H,B,D)
        pg = pool[:, page]                                  # (H,B,ps,D)
        sc = scales[:, page]                                # (H,B)
        pgf = pg.astype(jnp.float32) * sc[:, :, None, None]
        rows = jnp.arange(ps)[None, None, :, None]
        offb = off[None, :, None, None]
        pgf = jnp.where(rows == offb, new_row[:, :, None, :],
                        jnp.where(rows < offb, pgf, 0.0))
        q_pg, sc_new = quantize_absmax(pgf, dtype=pool.dtype,
                                       axis=(-2, -1))
        return (pool.at[:, page].set(q_pg),
                scales.at[:, page].set(sc_new.astype(scales.dtype)))

    def body(q_, kn, vn, kp, vp, ks, vs, bt, page, off, ln):
        kp, ks = update(kp, ks, kn, page, off)
        vp, vs = update(vp, vs, vn, page, off)
        out = quant_window_paged_decode_attention(q_, kp, vp, ks, vs, bt,
                                                  ln, **kw)
        return out, kp, vp, ks, vs

    if not _use_wrappers(mesh):
        return body(q, k_new, v_new, k_pages, v_pages, k_scales, v_scales,
                    block_tables, write_page, write_off, eff_len)

    dp = None
    tp = _tp(mesh)
    if hq % tp == 0 and hkv % tp == 0:
        qs, ns_ = P(dp, "model", None), P(dp, "model", None)
        ps_ = P("model", None, None, None)
        ss_ = P("model", None)
    else:
        qs, ns_ = P(dp, None, None), P(dp, None, None)
        ps_ = P(None, None, None, None)
        ss_ = P(None, None)
    return shard_map(
        body, mesh=mesh,
        in_specs=(qs, ns_, ns_, ps_, ps_, ss_, ss_, P(dp, None),
                  P(dp), P(dp), P(dp)),
        out_specs=(qs, ps_, ps_, ss_, ss_), check_vma=False)(
        q, k_new, v_new, k_pages, v_pages, k_scales, v_scales,
        block_tables, write_page, write_off, eff_len)


def sharded_spec_paged_decode_update_attend(q, k_new, v_new, k_pages,
                                            v_pages, block_tables,
                                            write_pages, write_offs,
                                            base_len, *,
                                            window: Optional[int] = None,
                                            softcap: Optional[float] = None,
                                            scale: Optional[float] = None,
                                            page_size: Optional[int] = None,
                                            block_kv: Optional[int] = None):
    """Fused speculation-window page write + multi-query paged verify.

    q: (B,K1,Hq,D) — the committed token plus k drafts per slot;
    k_new/v_new: (B,Hkv,K1,D) rope'd window K/V; pools: (Hkv,P,ps,D);
    block_tables: (B,T) int32; write_pages/write_offs: (B,K1) page and
    in-page row per window position (trash-redirected to null page 0
    past the table's reach); base_len: (B,) PRE-speculation prefix.
    Returns (out (B,K1,Hq,Dv), new k_pages, new v_pages).

    All K1 rows scatter in one indexed write, then one spec-kernel call
    verifies every position — the §Perf-B.1 rule (pool writes INSIDE
    the shard_map region) and the paged wrapper's layout policy apply
    unchanged (head-sharded when divisible, else replicated; no batch
    sharding — the pool has no batch dim).
    """
    mesh = maybe_mesh()
    b, hq = q.shape[0], q.shape[2]
    hkv = k_pages.shape[0]
    kw = dict(window=window, softcap=softcap, scale=scale,
              page_size=page_size, block_kv=block_kv)

    def update(kp, vp, kn, vn, pages, offs):
        # (B,K1)-shaped page/off index arrays scatter all window rows
        # at once; positions parked on null page 0 land in trash.
        kn = jnp.swapaxes(kn, 0, 1).astype(kp.dtype)      # (Hkv, B, K1, D)
        vn = jnp.swapaxes(vn, 0, 1).astype(vp.dtype)
        kp = kp.at[:, pages, offs].set(kn)
        vp = vp.at[:, pages, offs].set(vn)
        return kp, vp

    def body(q_, kn, vn, kp, vp, bt, pages, offs, ln):
        kp, vp = update(kp, vp, kn, vn, pages, offs)
        return (spec_paged_decode_attention(q_, kp, vp, bt, ln, **kw),
                kp, vp)

    if not _use_wrappers(mesh):
        return body(q, k_new, v_new, k_pages, v_pages, block_tables,
                    write_pages, write_offs, base_len)

    dp = None                      # no batch sharding: pool has no batch dim
    tp = _tp(mesh)
    if hq % tp == 0 and hkv % tp == 0:
        qs, ns_ = P(dp, None, "model", None), P(dp, "model", None, None)
        ps_ = P("model", None, None, None)
    else:
        qs, ns_ = P(dp, None, None, None), P(dp, None, None, None)
        ps_ = P(None, None, None, None)
    return shard_map(
        body, mesh=mesh,
        in_specs=(qs, ns_, ns_, ps_, ps_, P(dp, None), P(dp, None),
                  P(dp, None), P(dp)),
        out_specs=(qs, ps_, ps_), check_vma=False)(
        q, k_new, v_new, k_pages, v_pages, block_tables,
        write_pages, write_offs, base_len)


def sharded_quant_spec_paged_decode_update_attend(
        q, k_new, v_new, k_pages, v_pages, k_scales, v_scales,
        block_tables, write_pages, write_offs, base_len, *,
        window: Optional[int] = None, softcap: Optional[float] = None,
        scale: Optional[float] = None, page_size: Optional[int] = None,
        block_kv: Optional[int] = None):
    """Quantized-pool variant of the speculative update+attend.

    Same layouts as the bf16 spec wrapper plus (Hkv,P) f32 scale pools.
    Returns (out (B,K1,Hq,Dv), kp, vp, ks, vs).

    The window's rows are written by a static K1-step loop over the
    single-row re-quantizing update (gather page → dequant → splice →
    zero stale tail → re-absmax → requant): K1 is small, the loop order
    matches token order so each row sees every earlier window row
    already spliced, and the PR 4 write-path invariants (exact requant
    under an unchanged scale, bounded error on absmax growth) hold
    per row exactly as in plain decode.
    """
    from repro.quant import quantize_absmax
    mesh = maybe_mesh()
    b, k1, hq = q.shape[0], q.shape[1], q.shape[2]
    hkv = k_pages.shape[0]
    ps = k_pages.shape[2]
    kw = dict(window=window, softcap=softcap, scale=scale,
              page_size=page_size, block_kv=block_kv)

    def update_row(pool, scales, new_row, page, off):
        # identical to the single-token quant write (PR 4)
        new_row = jnp.swapaxes(new_row, 0, 1).astype(jnp.float32)  # (H,B,D)
        pg = pool[:, page]                                  # (H,B,ps,D)
        sc = scales[:, page]                                # (H,B)
        pgf = pg.astype(jnp.float32) * sc[:, :, None, None]
        rows = jnp.arange(ps)[None, None, :, None]
        offb = off[None, :, None, None]
        pgf = jnp.where(rows == offb, new_row[:, :, None, :],
                        jnp.where(rows < offb, pgf, 0.0))
        q_pg, sc_new = quantize_absmax(pgf, dtype=pool.dtype,
                                       axis=(-2, -1))
        return (pool.at[:, page].set(q_pg),
                scales.at[:, page].set(sc_new.astype(scales.dtype)))

    def body(q_, kn, vn, kp, vp, ks, vs, bt, pages, offs, ln):
        for i in range(k1):                # static: K1 is small
            kp, ks = update_row(kp, ks, kn[:, :, i], pages[:, i],
                                offs[:, i])
            vp, vs = update_row(vp, vs, vn[:, :, i], pages[:, i],
                                offs[:, i])
        out = quant_spec_paged_decode_attention(q_, kp, vp, ks, vs, bt,
                                                ln, **kw)
        return out, kp, vp, ks, vs

    if not _use_wrappers(mesh):
        return body(q, k_new, v_new, k_pages, v_pages, k_scales, v_scales,
                    block_tables, write_pages, write_offs, base_len)

    dp = None
    tp = _tp(mesh)
    if hq % tp == 0 and hkv % tp == 0:
        qs, ns_ = P(dp, None, "model", None), P(dp, "model", None, None)
        ps_ = P("model", None, None, None)
        ss_ = P("model", None)
    else:
        qs, ns_ = P(dp, None, None, None), P(dp, None, None, None)
        ps_ = P(None, None, None, None)
        ss_ = P(None, None)
    return shard_map(
        body, mesh=mesh,
        in_specs=(qs, ns_, ns_, ps_, ps_, ss_, ss_, P(dp, None),
                  P(dp, None), P(dp, None), P(dp)),
        out_specs=(qs, ps_, ps_, ss_, ss_), check_vma=False)(
        q, k_new, v_new, k_pages, v_pages, k_scales, v_scales,
        block_tables, write_pages, write_offs, base_len)


def sharded_decode_attention(q, k_cache, v_cache, lengths, *,
                             window: Optional[int] = None,
                             softcap: Optional[float] = None,
                             scale: Optional[float] = None,
                             block_kv: Optional[int] = None):
    """q: (B, Hq, D); caches: (B, Hkv, S, D); lengths: (B,).

    Returns (B, Hq, D).  SP path: cache slot dim sharded over 'model';
    per-shard partials are LSE-combined with pmax/psum ('flash-decode').
    """
    mesh = maybe_mesh()
    kw = dict(window=window, softcap=softcap, scale=scale, block_kv=block_kv)
    if not _use_wrappers(mesh):
        return decode_attention(q, k_cache, v_cache, lengths, **kw)

    b, hq, _ = q.shape
    hkv, s = k_cache.shape[1], k_cache.shape[2]
    dp = _dp(mesh, b)
    tp = _tp(mesh)

    if hq % tp == 0 and hkv % tp == 0:
        qs = P(dp, "model", None)
        cs = P(dp, "model", None, None)

        def body(q_, ck, cv, ln):
            return decode_attention(q_, ck, cv, ln, **kw)

        return shard_map(
            body, mesh=mesh, in_specs=(qs, cs, cs, P(dp)),
            out_specs=qs, check_vma=False)(q, k_cache, v_cache, lengths)

    if s % tp == 0 and window is None:
        # SP decode: shard the cache sequence dim; combine partials.
        qs = P(dp, None, None)
        cs = P(dp, None, "model", None)
        s_loc = s // tp

        def body(q_, ck, cv, ln):
            off = jax.lax.axis_index("model") * s_loc
            loc_len = jnp.clip(ln - off, 0, s_loc).astype(jnp.int32)
            acc, m, l = decode_attention(q_, ck, cv, loc_len,
                                         return_residuals=True, **kw)
            # cross-shard log-sum-exp combine (the flash-decode reduction)
            m_g = jax.lax.pmax(m, "model")
            w = jnp.exp(m - m_g)
            num = jax.lax.psum(acc.astype(jnp.float32) * w[..., None],
                               "model")
            den = jax.lax.psum(l * w, "model")
            den = jnp.where(den == 0.0, 1.0, den)
            return (num / den[..., None]).astype(q_.dtype)

        return shard_map(
            body, mesh=mesh, in_specs=(qs, cs, cs, P(dp)),
            out_specs=qs, check_vma=False)(q, k_cache, v_cache, lengths)

    qs = P(dp, None, None)
    cs = P(dp, None, None, None)

    def body(q_, ck, cv, ln):
        return decode_attention(q_, ck, cv, ln, **kw)

    return shard_map(
        body, mesh=mesh, in_specs=(qs, cs, cs, P(dp)),
        out_specs=qs, check_vma=False)(q, k_cache, v_cache, lengths)


# ------------------------------------------------------------- mamba ----

def sharded_mamba_scan(x, dt, A, Bm, Cm, D, *, chunk: Optional[int] = None):
    """x/dt: (B,S,d_inner); A: (d_inner,n); Bm/Cm: (B,S,n); D: (d_inner,).

    Channel parallel: the diagonal SSM recurrence never mixes channels,
    so sharding d_inner over 'model' needs zero collectives."""
    mesh = maybe_mesh()
    if not _use_wrappers(mesh):
        return mamba_scan(x, dt, A, Bm, Cm, D, chunk=chunk)

    b, _, d_inner = x.shape
    dp = _dp(mesh, b)
    tp = _tp(mesh)
    ch = "model" if d_inner % tp == 0 else None

    xs = P(dp, None, ch)
    out_specs = (P(dp, None, ch), P(dp, ch, None))

    def body(x_, dt_, A_, B_, C_, D_):
        return mamba_scan(x_, dt_, A_, B_, C_, D_, chunk=chunk)

    return shard_map(
        body, mesh=mesh,
        in_specs=(xs, xs, P(ch, None), P(dp, None, None), P(dp, None, None),
                  P(ch)),
        out_specs=out_specs, check_vma=False)(x, dt, A, Bm, Cm, D)


# ------------------------------------------------------------- mlstm ----

def sharded_mlstm_scan(q, k, v, i_gate, f_gate, *, chunk: Optional[int] = None):
    """q/k: (B,H,S,Dk); v: (B,H,S,Dv); gates: (B,H,S).

    Dv-sharded over 'model': C and the numerator split over value
    channels; the normalizer n·q needs full Dk, so q/k/gates replicate."""
    mesh = maybe_mesh()
    if not _use_wrappers(mesh):
        return mlstm_scan(q, k, v, i_gate, f_gate, chunk=chunk)

    b, h, _, dv = q.shape[0], q.shape[1], q.shape[2], v.shape[3]
    dp = _dp(mesh, b)
    tp = _tp(mesh)
    if h % tp == 0:
        hs, vs = "model", None          # enough heads: shard heads instead
    elif dv % tp == 0:
        hs, vs = None, "model"
    else:
        hs = vs = None

    qs = P(dp, hs, None, None)
    vvs = P(dp, hs, None, vs)
    gs = P(dp, hs, None)

    def body(q_, k_, v_, i_, f_):
        return mlstm_scan(q_, k_, v_, i_, f_, chunk=chunk)

    return shard_map(
        body, mesh=mesh, in_specs=(qs, qs, vvs, gs, gs),
        out_specs=vvs, check_vma=False)(q, k, v, i_gate, f_gate)


# ----------------------------------------------------------- rmsnorm ----

def sharded_rmsnorm(x, w, *, eps: float = 1e-6, weight_offset: float = 0.0,
                    block_rows: Optional[int] = None):
    """RMSNorm under a mesh runs the pure-jnp form; kernel off-mesh.

    §Perf-A iteration history (gemma3-4b train_4k, collective bytes/chip):
      unwrapped pallas kernel under GSPMD   — 390 GiB (partitioner
        all-gathers around the while-loop; roofline fraction 0.078)
      shard_map-wrapped kernel (A.1)        —  50 GiB: forward is clean,
        but every wrapper boundary psums the replicated activations'
        f32 cotangent over 'model' in backward (4-6 norms/layer)
      pure-jnp norm under GSPMD (A.3, this) — norms fuse into the
        surrounding elementwise HLO with zero boundaries.
    The Pallas rmsnorm kernel remains the off-mesh / single-chip path
    and the §4.1 parity subject; on-mesh the norm is memory-bound glue
    where XLA fusion is already optimal — kernelizing it buys nothing
    and the boundary costs an all-reduce per norm."""
    mesh = maybe_mesh()
    if not _use_wrappers(mesh):
        return rmsnorm(x, w, eps=eps, weight_offset=weight_offset,
                       block_rows=block_rows)
    from repro.kernels.rmsnorm.ref import rmsnorm_ref
    return rmsnorm_ref(x, w, eps=eps, weight_offset=weight_offset)
