"""Quantized paged flash-decode: fused per-page dequant.

One launch serves both pool dtypes: the block-table gather, grid, and
flash body live in ``paged.py`` (``paged_decode_attention_fwd``), and
passing the per-page-per-head scale pools switches it into quantized
mode — each page's scales ride the *same* index map as its KV block (a
``(Hkv, 1, 1, 1)`` tile of the ``(Hkv, P)`` scale pool,
``paged.scale_tiles``), and the dequant fuses into the body as one
multiply per head after the DMA.  The pools never exist densely in HBM
at bf16.

Logical re-paging works unchanged: a physical page splits into ``r``
contiguous logical pages that all inherit the physical page's scale
(``repage_scales``), so the autotuner sweeps ``page_size``/``block_kv``
against one physical example pool exactly as for the bf16 op.
"""
from __future__ import annotations

from typing import Optional

from repro.core.runtime import DeviceRuntime
from repro.kernels.decode_attention.paged import (  # noqa: F401
    paged_decode_attention_fwd, repage_scales,
    window_paged_decode_attention_fwd)


def quant_paged_decode_attention_fwd(q, k_pages, v_pages, k_scales, v_scales,
                                     block_tables, lengths, *,
                                     window: Optional[int] = None,
                                     softcap: Optional[float] = None,
                                     scale: Optional[float] = None,
                                     page_size: Optional[int] = None,
                                     block_kv: int = 64,
                                     rt: Optional[DeviceRuntime] = None):
    """q: (B, Hq, D); pools: (Hkv, P, ps, D) int8/fp8; scale pools:
    (Hkv, P) f32; block_tables: (B, T) int32; lengths: (B,) int32.

    Returns unnormalized (acc (B,Hq,Dv), m (B,Hq), l (B,Hq)) — the same
    residual contract as the other decode kernels.
    """
    return paged_decode_attention_fwd(
        q, k_pages, v_pages, block_tables, lengths, window=window,
        softcap=softcap, scale=scale, page_size=page_size,
        block_kv=block_kv, k_scales=k_scales, v_scales=v_scales, rt=rt)


def quant_window_paged_decode_attention_fwd(q, k_pages, v_pages, k_scales,
                                            v_scales, block_tables, lengths,
                                            *, window: int,
                                            softcap: Optional[float] = None,
                                            scale: Optional[float] = None,
                                            page_size: Optional[int] = None,
                                            block_kv: int = 64,
                                            rt: Optional[DeviceRuntime] = None):
    """Fused-dequant variant of the windowed ring-table decode: same
    ``(B, T_w)`` ring block table as the bf16 op, same residual
    contract, with the ``(Hkv, P)`` scale pools riding the ring index
    map exactly as the prefix-table quant op rides its own."""
    return window_paged_decode_attention_fwd(
        q, k_pages, v_pages, block_tables, lengths, window=window,
        softcap=softcap, scale=scale, page_size=page_size,
        block_kv=block_kv, k_scales=k_scales, v_scales=v_scales, rt=rt)
