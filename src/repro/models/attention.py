"""Attention blocks: GQA (global/local/softcap/qk-norm) and DeepSeek MLA.

Three entry points per block:
  init_*            — parameter trees (names match sharding/partition.py)
  apply_* (train/prefill) — full-sequence attention via the portable
                      flash kernel ops (variant-dispatched)
  decode_*          — one-token step against a KV cache via the portable
                      decode kernel (SP-ready residuals handled in serve/)
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MLAConfig, ModelConfig
from repro.sharding.kernel_sharding import (
    sharded_flash_attention as flash_attention,
    sharded_decode_attention as decode_attention,
    sharded_decode_update_attend as decode_update_attend,
    sharded_paged_decode_update_attend as paged_decode_update_attend,
    sharded_quant_paged_decode_update_attend as quant_paged_decode_update_attend,
    sharded_window_paged_decode_update_attend as
    window_paged_decode_update_attend,
    sharded_quant_window_paged_decode_update_attend as
    quant_window_paged_decode_update_attend,
    sharded_spec_paged_decode_update_attend as spec_paged_decode_update_attend,
    sharded_quant_spec_paged_decode_update_attend as
    quant_spec_paged_decode_update_attend,
)
from repro.models import layers as L


def _page_coords(block_tables, lengths, page_size: int):
    """(write_page, write_off) for the token at position ``lengths``.

    Freed slots carry an all-null block table row, so their write page
    resolves to the allocator's trash page 0 — stale ``cur_tok`` rows
    can never land in a live sequence's pages.
    """
    page_idx = (lengths // page_size).astype(jnp.int32)
    write_page = jnp.take_along_axis(block_tables, page_idx[:, None],
                                     axis=1)[:, 0]
    write_off = (lengths % page_size).astype(jnp.int32)
    return write_page, write_off


def _window_page_coords(block_tables, lengths, page_size: int):
    """(write_page, write_off) against a (B, T_w) *ring* block table.

    Global page ``g`` lives at ring column ``g % T_w``, so the write
    page for the token at position ``lengths`` sits at column
    ``(lengths // ps) % T_w`` — the engine's eager prefix free ran
    before the step, so the column's previous tenant (page ``g - T_w``,
    always behind the window) is already back in the pool.  Freed slots
    carry an all-null row, redirecting the write to trash page 0.
    """
    t = block_tables.shape[1]
    page_idx = ((lengths // page_size) % t).astype(jnp.int32)
    write_page = jnp.take_along_axis(block_tables, page_idx[:, None],
                                     axis=1)[:, 0]
    write_off = (lengths % page_size).astype(jnp.int32)
    return write_page, write_off


def _spec_page_coords(block_tables, lengths, k1: int, page_size: int):
    """(write_page, write_off), both (B, K1), for the speculative window
    at positions ``lengths .. lengths + k1 - 1``.

    Positions past the block table's addressable range (the engine caps
    speculation at ``cache_len`` but the table covers exactly
    ``pages_per_slot`` pages) redirect to the allocator's trash page 0,
    same as freed slots in ``_page_coords``.
    """
    t = block_tables.shape[1]
    pos = lengths[:, None] + jnp.arange(k1, dtype=jnp.int32)[None, :]
    page_idx = jnp.minimum(pos // page_size, t - 1).astype(jnp.int32)
    gathered = jnp.take_along_axis(block_tables, page_idx, axis=1)
    write_page = jnp.where(pos < t * page_size, gathered, 0)
    write_off = (pos % page_size).astype(jnp.int32)
    return write_page, write_off


# ------------------------------------------------------------- GQA ------

def init_attn(key, cfg: ModelConfig):
    d, h, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": L.dense_init(ks[0], (d, h, hd), in_axis_size=d),
        "wk": L.dense_init(ks[1], (d, hkv, hd), in_axis_size=d),
        "wv": L.dense_init(ks[2], (d, hkv, hd), in_axis_size=d),
        "wo": L.dense_init(ks[3], (h, hd, d), in_axis_size=h * hd),
    }
    if cfg.use_qk_norm:
        p["q_norm"] = L.norm_param(hd)
        p["k_norm"] = L.norm_param(hd)
    return p


def _qkv(p, x, cfg: ModelConfig, positions, theta: float):
    xd = x.dtype
    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"].astype(xd))
    k = jnp.einsum("bsd,dhk->bhsk", x, p["wk"].astype(xd))
    v = jnp.einsum("bsd,dhk->bhsk", x, p["wv"].astype(xd))
    if cfg.use_qk_norm:
        q = L.apply_norm(p["q_norm"], q, cfg)
        k = L.apply_norm(p["k_norm"], k, cfg)
    cos, sin = L.rope_cache(positions, cfg.head_dim, theta)
    q = L.apply_rope(q, cos, sin)
    k = L.apply_rope(k, cos, sin)
    return q, k, v


def apply_attn(p, x, cfg: ModelConfig, *, kind: str = "global",
               causal: bool = True, positions=None,
               kv_override: Optional[Tuple] = None, theta=None,
               return_kv: bool = False):
    """Full-sequence attention.  kind: 'global' | 'local'.

    kv_override: (k, v) from an encoder for cross-attention (no rope
    reuse; caller passes encoder-side tensors already projected).
    return_kv: also return the rope'd (k, v) for prefill caching."""
    b, s, d = x.shape
    if positions is None:
        positions = jnp.arange(s)
    theta = theta if theta is not None else cfg.rope_theta
    window = cfg.window if kind == "local" else None

    if kv_override is None:
        q, k, v = _qkv(p, x, cfg, positions, theta)
    else:
        xd = x.dtype
        q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"].astype(xd))
        if cfg.use_qk_norm:
            q = L.apply_norm(p["q_norm"], q, cfg)
        cos, sin = L.rope_cache(positions, cfg.head_dim, theta)
        q = L.apply_rope(q, cos, sin)
        k, v = kv_override

    out = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=cfg.attn_softcap)
    y = jnp.einsum("bhsk,hkd->bsd", out, p["wo"].astype(x.dtype))
    if return_kv:
        return y, k, v
    return y


def project_kv(p, x_enc, cfg: ModelConfig, positions=None, theta=None):
    """Encoder-side K/V for cross attention (rope applied)."""
    xd = x_enc.dtype
    s = x_enc.shape[1]
    if positions is None:
        positions = jnp.arange(s)
    theta = theta if theta is not None else cfg.rope_theta
    k = jnp.einsum("bsd,dhk->bhsk", x_enc, p["wk"].astype(xd))
    v = jnp.einsum("bsd,dhk->bhsk", x_enc, p["wv"].astype(xd))
    if cfg.use_qk_norm:
        k = L.apply_norm(p["k_norm"], k, cfg)
    cos, sin = L.rope_cache(positions, cfg.head_dim, theta)
    k = L.apply_rope(k, cos, sin)
    return k, v


def decode_attn(p, x, cache_k, cache_v, lengths, cfg: ModelConfig, *,
                kind: str = "global", theta=None, ring: bool = False,
                block_tables=None, cache_scales=None,
                windowed: bool = False, layer=None):
    """One-token decode.  x: (B, 1, d).  Returns (out (B,1,d), new_k,
    new_v) — the new token's K/V is written into the cache *inside* the
    fused update+attend wrapper (sharded in sharding/kernel_sharding.py)
    and the updated caches come back.

    ring=True: cache length == window, slots addressed mod window.
    block_tables: (B, T) int32 — cache_k/cache_v are then head-major
    paged pools (Hkv, P, ps, D) and the new token's K/V is scattered
    into the slot's current page (paged serving; incompatible with ring).
    layer: an int32 scalar — the plain (unquantized, prefix-table) paged
    pools are then every layer's, stacked (L, Hkv, P, ps, D), written and
    read in place at ``layer``.
    windowed=True: block_tables is the (B, T_w) *ring* table of a
    paged sliding-window layer (``kind`` must be 'local') and the step
    routes through the O(window) ring-table kernel.
    cache_scales: (ks, vs) per-page-per-head scale pools (Hkv, P) —
    the pools are then quantized (repro.quant) and the step routes
    through the re-quantizing write + fused-dequant kernel, returning
    (out, new_k, new_v, new_ks, new_vs).
    """
    b = x.shape[0]
    theta = theta if theta is not None else cfg.rope_theta
    xd = x.dtype
    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"].astype(xd))[:, :, 0]   # (B,H,hd)
    k = jnp.einsum("bsd,dhk->bhsk", x, p["wk"].astype(xd))[:, :, 0]
    v = jnp.einsum("bsd,dhk->bhsk", x, p["wv"].astype(xd))[:, :, 0]
    if cfg.use_qk_norm:
        q = L.apply_norm(p["q_norm"], q, cfg)
        k = L.apply_norm(p["k_norm"], k, cfg)
    cos, sin = L.rope_cache(lengths, cfg.head_dim, theta)   # (B, hd/2)
    q = L.apply_rope(q, cos[:, None, :], sin[:, None, :])
    k = L.apply_rope(k, cos[:, None, :], sin[:, None, :])

    if block_tables is not None:
        if ring:
            # a plain assert vanishes under ``python -O``, silently
            # scattering ring-addressed rows into paged pools
            raise ValueError(
                f"paged decode does not support ring caches (layer kind "
                f"{kind!r}, window={cfg.window}): local layers page "
                f"through windowed ring tables (windowed=True), not "
                f"dense rings")
        ps = cache_k.shape[-2]
        if windowed:
            if kind != "local" or cfg.window is None:
                raise ValueError(
                    f"windowed paged decode requires a local layer with "
                    f"a configured window (got kind={kind!r}, "
                    f"window={cfg.window})")
            write_page, write_off = _window_page_coords(
                block_tables, lengths, ps)
            eff = (lengths + 1).astype(jnp.int32)
            if cache_scales is not None:
                out, ck, cv, ks, vs = quant_window_paged_decode_update_attend(
                    q, k, v, cache_k, cache_v,
                    cache_scales[0], cache_scales[1], block_tables,
                    write_page, write_off, eff, window=cfg.window,
                    softcap=cfg.attn_softcap, page_size=ps)
                o = jnp.einsum("bhk,hkd->bd", out,
                               p["wo"].astype(xd))[:, None, :]
                return o, ck, cv, ks, vs
            out, ck, cv = window_paged_decode_update_attend(
                q, k, v, cache_k, cache_v, block_tables, write_page,
                write_off, eff, window=cfg.window,
                softcap=cfg.attn_softcap, page_size=ps)
            o = jnp.einsum("bhk,hkd->bd", out, p["wo"].astype(xd))[:, None, :]
            return o, ck, cv
        write_page, write_off = _page_coords(block_tables, lengths, ps)
        window = cfg.window if kind == "local" else None
        if cache_scales is not None:
            out, ck, cv, ks, vs = quant_paged_decode_update_attend(
                q, k, v, cache_k, cache_v, cache_scales[0], cache_scales[1],
                block_tables, write_page, write_off,
                (lengths + 1).astype(jnp.int32),
                window=window, softcap=cfg.attn_softcap, page_size=ps)
            o = jnp.einsum("bhk,hkd->bd", out, p["wo"].astype(xd))[:, None, :]
            return o, ck, cv, ks, vs
        out, ck, cv = paged_decode_update_attend(
            q, k, v, cache_k, cache_v, block_tables, write_page, write_off,
            (lengths + 1).astype(jnp.int32),
            window=window,
            softcap=cfg.attn_softcap, page_size=ps, layer=layer)
        o = jnp.einsum("bhk,hkd->bd", out, p["wo"].astype(xd))[:, None, :]
        return o, ck, cv

    s_cache = cache_k.shape[2]
    if ring:
        write_pos = lengths % s_cache
        eff_len = jnp.minimum(lengths + 1, s_cache)
        window = None
    else:
        write_pos = lengths
        eff_len = lengths + 1
        window = cfg.window if kind == "local" else None

    # fused cache-update + attend: the new (k, v) is written at
    # write_pos INSIDE the sharded region (§Perf-B.1)
    out, ck, cv = decode_update_attend(
        q, k, v, cache_k, cache_v, write_pos.astype(jnp.int32),
        eff_len.astype(jnp.int32), window=window, softcap=cfg.attn_softcap)
    o = jnp.einsum("bhk,hkd->bd", out, p["wo"].astype(xd))[:, None, :]
    return o, ck, cv


def spec_decode_attn(p, x, cache_k, cache_v, lengths, cfg: ModelConfig, *,
                     kind: str = "global", theta=None, block_tables=None,
                     cache_scales=None):
    """Speculative k-token decode.  x: (B, K1, d) — the slot's current
    token followed by K1-1 drafted tokens.  All K1 positions' K/V are
    written into the paged cache inside the fused wrapper, and each
    query row qi attends to ``lengths + 1 + qi`` keys (its own causal
    horizon), so one call verifies the whole window.

    Paged caches only; ``lengths`` is the PRE-speculation committed
    prefix.  Returns (out (B,K1,d), new_k, new_v) or the 5-tuple with
    scale pools when ``cache_scales`` is given.
    """
    assert block_tables is not None, "spec decode requires paged caches"
    assert kind == "global", "spec decode supports global attention only"
    b, k1, _ = x.shape
    theta = theta if theta is not None else cfg.rope_theta
    xd = x.dtype
    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"].astype(xd))    # (B,H,K1,hd)
    k = jnp.einsum("bsd,dhk->bhsk", x, p["wk"].astype(xd))
    v = jnp.einsum("bsd,dhk->bhsk", x, p["wv"].astype(xd))
    if cfg.use_qk_norm:
        q = L.apply_norm(p["q_norm"], q, cfg)
        k = L.apply_norm(p["k_norm"], k, cfg)
    pos = lengths[:, None] + jnp.arange(k1, dtype=jnp.int32)[None, :]
    cos, sin = L.rope_cache(pos, cfg.head_dim, theta)         # (B,K1,hd/2)
    q = L.apply_rope(q, cos[:, None], sin[:, None])
    k = L.apply_rope(k, cos[:, None], sin[:, None])

    ps = cache_k.shape[2]
    write_page, write_off = _spec_page_coords(block_tables, lengths, k1, ps)
    q_t = jnp.swapaxes(q, 1, 2)                               # (B,K1,H,hd)
    base = lengths.astype(jnp.int32)
    if cache_scales is not None:
        out, ck, cv, ks, vs = quant_spec_paged_decode_update_attend(
            q_t, k, v, cache_k, cache_v, cache_scales[0], cache_scales[1],
            block_tables, write_page, write_off, base,
            softcap=cfg.attn_softcap, page_size=ps)
        o = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(xd))
        return o, ck, cv, ks, vs
    out, ck, cv = spec_paged_decode_update_attend(
        q_t, k, v, cache_k, cache_v, block_tables, write_page, write_off,
        base, softcap=cfg.attn_softcap, page_size=ps)
    o = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(xd))
    return o, ck, cv


# ------------------------------------------------------------- MLA ------

def init_mla(key, cfg: ModelConfig):
    m: MLAConfig = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq_mla": L.dense_init(ks[0], (d, h, qk), in_axis_size=d),
        "wkv_a": L.dense_init(ks[1], (d, m.kv_lora_rank + m.qk_rope_head_dim),
                              in_axis_size=d),
        "wkv_b": L.dense_init(ks[2], (m.kv_lora_rank, h,
                                      m.qk_nope_head_dim + m.v_head_dim),
                              in_axis_size=m.kv_lora_rank),
        "wo_mla": L.dense_init(ks[3], (h, m.v_head_dim, d),
                               in_axis_size=h * m.v_head_dim),
    }


def apply_mla(p, x, cfg: ModelConfig, positions=None,
              return_kv: bool = False):
    """DeepSeek MLA attention (full sequence, causal).

    Latent c_kv (B,S,lora) + shared rope key; per-head K = [up-projected
    nope | shared rope], Q = [nope | rope].
    """
    m: MLAConfig = cfg.mla
    b, s, d = x.shape
    h = cfg.num_heads
    if positions is None:
        positions = jnp.arange(s)
    xd = x.dtype

    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq_mla"].astype(xd))
    q_nope, q_rope = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    cos, sin = L.rope_cache(positions, m.qk_rope_head_dim, cfg.rope_theta)
    q_rope = L.apply_rope(q_rope, cos, sin)

    kv_a = x @ p["wkv_a"].astype(xd)                       # (B,S,lora+rope)
    c_kv, k_rope = kv_a[..., :m.kv_lora_rank], kv_a[..., m.kv_lora_rank:]
    k_rope = L.apply_rope(k_rope[:, None], cos, sin)       # (B,1,S,rope)
    kv = jnp.einsum("bsl,lhk->bhsk", c_kv, p["wkv_b"].astype(xd))
    k_nope = kv[..., :m.qk_nope_head_dim]
    v = kv[..., m.qk_nope_head_dim:]

    k_rope_b = jnp.broadcast_to(k_rope, (b, h, s, m.qk_rope_head_dim))
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate([k_nope, k_rope_b], axis=-1)

    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    out = flash_attention(q_full, k_full, v, causal=True,
                          scale=qk_dim ** -0.5)
    y = jnp.einsum("bhsk,hkd->bsd", out, p["wo_mla"].astype(xd))
    if return_kv:
        return y, k_full, v
    return y


def decode_mla(p, x, cache_k, cache_v, lengths, cfg: ModelConfig,
               block_tables=None, cache_scales=None, layer=None):
    """MLA decode.  We cache the *materialized* per-head K/V (simple
    variant; latent-cache decode is a further memory optimization —
    DESIGN.md notes it as future work).  With ``block_tables`` the
    caches are paged pools, as in ``decode_attn`` (stacked and read at
    ``layer`` when it is given); with ``cache_scales`` they are
    quantized paged pools and the 5-tuple (out, k, v, ks, vs) comes
    back."""
    m: MLAConfig = cfg.mla
    b = x.shape[0]
    h = cfg.num_heads
    xd = x.dtype
    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq_mla"].astype(xd))[:, :, 0]
    q_nope, q_rope = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    cos, sin = L.rope_cache(lengths, m.qk_rope_head_dim, cfg.rope_theta)
    q_rope = L.apply_rope(q_rope, cos[:, None], sin[:, None])
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)

    kv_a = (x @ p["wkv_a"].astype(xd))[:, 0]
    c_kv, k_rope = kv_a[..., :m.kv_lora_rank], kv_a[..., m.kv_lora_rank:]
    k_rope = L.apply_rope(k_rope[:, None], cos[:, None], sin[:, None])
    kv = jnp.einsum("bl,lhk->bhk", c_kv, p["wkv_b"].astype(xd))
    k_nope = kv[..., :m.qk_nope_head_dim]
    v = kv[..., m.qk_nope_head_dim:]
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, h, m.qk_rope_head_dim))], -1)

    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    if block_tables is not None:
        ps = cache_k.shape[-2]
        write_page, write_off = _page_coords(block_tables, lengths, ps)
        if cache_scales is not None:
            out, ck, cv, ks, vs = quant_paged_decode_update_attend(
                q_full, k_full, v, cache_k, cache_v,
                cache_scales[0], cache_scales[1], block_tables, write_page,
                write_off, (lengths + 1).astype(jnp.int32),
                scale=qk_dim ** -0.5, page_size=ps)
            o = jnp.einsum("bhk,hkd->bd", out,
                           p["wo_mla"].astype(xd))[:, None, :]
            return o, ck, cv, ks, vs
        out, ck, cv = paged_decode_update_attend(
            q_full, k_full, v, cache_k, cache_v, block_tables, write_page,
            write_off, (lengths + 1).astype(jnp.int32),
            scale=qk_dim ** -0.5, page_size=ps, layer=layer)
    else:
        out, ck, cv = decode_update_attend(
            q_full, k_full, v, cache_k, cache_v, lengths.astype(jnp.int32),
            (lengths + 1).astype(jnp.int32), scale=qk_dim ** -0.5)
    o = jnp.einsum("bhk,hkd->bd", out, p["wo_mla"].astype(xd))[:, None, :]
    return o, ck, cv


def spec_decode_mla(p, x, cache_k, cache_v, lengths, cfg: ModelConfig,
                    block_tables=None, cache_scales=None):
    """MLA speculative k-token decode; see ``spec_decode_attn`` for the
    window/horizon contract.  Paged caches only."""
    assert block_tables is not None, "spec decode requires paged caches"
    m: MLAConfig = cfg.mla
    b, k1, _ = x.shape
    h = cfg.num_heads
    xd = x.dtype
    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq_mla"].astype(xd))
    q_nope, q_rope = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    pos = lengths[:, None] + jnp.arange(k1, dtype=jnp.int32)[None, :]
    cos, sin = L.rope_cache(pos, m.qk_rope_head_dim, cfg.rope_theta)
    q_rope = L.apply_rope(q_rope, cos[:, None], sin[:, None])
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)       # (B,H,K1,qk)

    kv_a = x @ p["wkv_a"].astype(xd)                          # (B,K1,lora+r)
    c_kv, k_rope = kv_a[..., :m.kv_lora_rank], kv_a[..., m.kv_lora_rank:]
    k_rope = L.apply_rope(k_rope[:, None], cos[:, None], sin[:, None])
    kv = jnp.einsum("bsl,lhk->bhsk", c_kv, p["wkv_b"].astype(xd))
    k_nope = kv[..., :m.qk_nope_head_dim]
    v = kv[..., m.qk_nope_head_dim:]
    k_full = jnp.concatenate(
        [k_nope,
         jnp.broadcast_to(k_rope, (b, h, k1, m.qk_rope_head_dim))], -1)

    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    ps = cache_k.shape[2]
    write_page, write_off = _spec_page_coords(block_tables, lengths, k1, ps)
    q_t = jnp.swapaxes(q_full, 1, 2)                          # (B,K1,H,qk)
    base = lengths.astype(jnp.int32)
    if cache_scales is not None:
        out, ck, cv, ks, vs = quant_spec_paged_decode_update_attend(
            q_t, k_full, v, cache_k, cache_v,
            cache_scales[0], cache_scales[1], block_tables, write_page,
            write_off, base, scale=qk_dim ** -0.5, page_size=ps)
        o = jnp.einsum("bshk,hkd->bsd", out, p["wo_mla"].astype(xd))
        return o, ck, cv, ks, vs
    out, ck, cv = spec_paged_decode_update_attend(
        q_t, k_full, v, cache_k, cache_v, block_tables, write_page,
        write_off, base, scale=qk_dim ** -0.5, page_size=ps)
    o = jnp.einsum("bshk,hkd->bsd", out, p["wo_mla"].astype(xd))
    return o, ck, cv
