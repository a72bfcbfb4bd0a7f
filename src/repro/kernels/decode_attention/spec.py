"""Speculative paged flash-decode kernel: k+1 query positions per slot.

Self-speculative decoding verifies a whole window of candidate tokens
— the committed ``cur_tok`` plus k drafts — in ONE paged-decode call
per layer instead of k+1 sequential calls.  The kernel is the
multi-query variant of the PR 3 scalar-prefetch paged kernel: the same
block-table gather (block tables + lengths ride as scalar-prefetch
operands), the same shared ``flash_decode_step`` online-softmax body,
and the same fused-dequant composition for quantized pools (PR 4).

The only genuinely new mechanics is the causal mask.  The K1 = k+1
query positions of a slot are *stacked into the GQA group dim*: row
``r = qi * group + gi`` of the (G8, D) query tile is head ``gi`` of
query position ``qi``, so every KV block is still read exactly once
per (slot, kv-head) and the MXU dot shape is unchanged.  Each query
position attends to a different prefix — position ``qi`` sees
``lengths[b] + 1 + qi`` tokens (the pre-speculation prefix, itself,
and the earlier window positions, whose KV rows the engine writes
*before* the verify call) — which the shared body expresses through
its per-row ``row_length`` horizon; the scalar ``length`` (the row
maximum) still gates whole-block skips, so the sequential-grid
early-out is as effective as in the single-query kernel.

Layouts
  q           (B, K1, Hq, D)   the speculation window per slot
  k/v pools   (Hkv, P, ps, D)  head-major page pool (page 0 = null)
  block_tables(B, T) int32     page id per (slot, logical page)
  lengths     (B,)   int32     PRE-speculation valid prefix per slot

Returns unnormalized (acc (B,K1,Hq,Dv), m, l (B,K1,Hq)) — the decode
residual contract, one residual triple per verified position.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.runtime import DeviceRuntime, kernel_call
from repro.kernels.decode_attention.decode_attention import (
    LANES, SUBLANES, flash_decode_step)
from repro.kernels.decode_attention.paged import (
    repage, repage_scales, scale_tiles)


def _spec_paged_decode_kernel(*refs, rt: DeviceRuntime, scale: float,
                              window: Optional[int],
                              softcap: Optional[float], block_kv: int,
                              quantized: bool, k1: int, group: int,
                              g8: int):
    # operand order: bt, len, q, k, v, [k_scales, v_scales,] then the
    # three outputs and three scratch accumulators (as in paged.py).
    _, len_ref, q_ref, k_ref, v_ref = refs[:5]   # bt consumed by maps
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
    # row r = qi * group + gi: query position qi sees base + 1 + qi
    # tokens; zero-padded rows (r >= k1*group) see nothing.
    ridx = rt.iota((g8, 1), 0)
    row_length = jnp.where(ridx < k1 * group, base + 1 + ridx // group, 0)
    flash_decode_step(
        q_ref, k_ref, v_ref, o_ref, m_out_ref, l_out_ref,
        acc_ref, m_ref, l_ref, rt=rt, scale=scale, window=window,
        softcap=softcap, k_start=ik * block_kv,
        length=base + k1, ik=ik, nk=nk,
        k_scale=k_scale, v_scale=v_scale, row_length=row_length)


def spec_paged_decode_attention_fwd(q, k_pages, v_pages, block_tables,
                                    lengths, *,
                                    window: Optional[int] = None,
                                    softcap: Optional[float] = None,
                                    scale: Optional[float] = None,
                                    page_size: Optional[int] = None,
                                    block_kv: int = 64,
                                    k_scales=None, v_scales=None,
                                    rt: Optional[DeviceRuntime] = None):
    """q: (B, K1, Hq, D); pools: (Hkv, P, ps, D); block_tables: (B, T);
    lengths: (B,) int32 pre-speculation prefix.

    Returns unnormalized (acc (B,K1,Hq,Dv), m (B,K1,Hq), l (B,K1,Hq)).
    With ``k_scales``/``v_scales`` the pools are quantized storage and
    the per-block dequant fuses into the flash body exactly as in the
    single-query quantized kernel (quant_spec_paged_decode_attention).
    """
    from repro.core.runtime import runtime
    rt = rt or runtime()
    quantized = k_scales is not None
    assert (v_scales is None) == (k_scales is None)
    b, k1, hq, d = q.shape
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
    gt = k1 * group                         # stacked query rows per head
    g8 = max(SUBLANES, -(-gt // SUBLANES) * SUBLANES)
    scale = (d ** -0.5) if scale is None else scale
    # same clamp discipline as the single-query paged kernel: block_kv
    # must divide page_size (a grid step never spans two pages)
    block_kv = min(block_kv, page_size)
    while page_size % block_kv:
        block_kv -= 1
    spp = page_size // block_kv
    nk = n_pages * spp

    # stack the speculation window into the group dim, position-major:
    # (B, K1, Hkv, group, D) -> (B, Hkv, K1*group, D), zero-padded to G8
    qg = q.reshape(b, k1, hkv, group, d).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(b, hkv, gt, d)
    if g8 != gt:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g8 - gt), (0, 0)))

    kern = functools.partial(
        _spec_paged_decode_kernel, rt=rt, scale=scale, window=window,
        softcap=softcap, block_kv=block_kv, quantized=quantized,
        k1=k1, group=group, g8=g8)

    def kv_map(ib, ih, ik, bt_ref, len_ref):
        del len_ref
        return (ih, bt_ref[ib, ik // spp], ik % spp, 0)

    def sc_map(ib, ih, ik, bt_ref, len_ref):
        del len_ref
        return (ih, bt_ref[ib, ik // spp], 0, 0)

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
        name=("portable_quant_spec_paged_decode_attention" if quantized
              else "portable_spec_paged_decode_attention"),
        rt=rt,
    )(bt, lengths, *operands)

    # unstack (B, Hkv, K1*group, .) -> (B, K1, Hq, .)
    acc = acc[:, :, :gt].reshape(b, hkv, k1, group, dv)
    acc = acc.transpose(0, 2, 1, 3, 4).reshape(b, k1, hq, dv)
    m = m[:, :, :gt, 0].reshape(b, hkv, k1, group)
    m = m.transpose(0, 2, 1, 3).reshape(b, k1, hq)
    l = l[:, :, :gt, 0].reshape(b, hkv, k1, group)
    l = l.transpose(0, 2, 1, 3).reshape(b, k1, hq)
    return acc, m, l
