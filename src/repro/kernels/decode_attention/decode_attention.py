"""Flash-decode Pallas kernel (single new token vs. a long KV cache).

TPU adaptation: one grid step per (batch, kv_head, kv_block); the KV
block axis is sequential on-core, carrying (acc, m, l) in team-shared
VMEM scratch.  All Hq/Hkv query heads of a group are processed together
so each KV block is read once (GQA-aware), padded up to the 8-sublane
MXU granule.

Residual outputs (unnormalized acc + m + l) support sequence-parallel
decode: shards of the KV cache compute partials that are merged with a
log-sum-exp combine across chips (ref.combine_partials) — the SP path
used by the long_500k shapes.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.runtime import DeviceRuntime, kernel_call

NEG_INF = -1e30
LANES = 128
SUBLANES = 8


def online_softmax_update(q, k, v, acc_ref, m_ref, l_ref, *,
                          rt: DeviceRuntime, window: Optional[int],
                          softcap: Optional[float], k_start, horizon):
    """Fold one KV block into the running (acc, m, l) accumulators.

    The flash math every decode kernel shares.  ``q`` (…, G8, D) is
    scaled f32, ``k``/``v`` (…, bkv, D|Dv) dequantized f32; the leading
    axes, if any, are batch axes (the paged kernel passes all KV heads
    of a slot at once), and the refs carry the same leading axes.
    ``k_start`` is the global position of the block's first row and
    ``horizon`` the valid prefix: a scalar, or a (G8, 1) per-row bound.
    """
    nd = q.ndim
    batch = tuple(range(nd - 2))
    s = jax.lax.dot_general(q, k, (((nd - 1,), (nd - 1,)), (batch, batch)),
                            preferred_element_type=jnp.float32)  # (…, G8, bkv)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    k_pos = k_start + rt.iota(s.shape, nd - 1)
    mask = k_pos < horizon
    if window is not None:
        mask = jnp.logical_and(mask, (horizon - 1 - k_pos) < window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[..., :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=nd - 1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    p = jnp.where(m_new > NEG_INF / 2, p, 0.0)
    alpha = jnp.where(m_new > NEG_INF / 2, alpha, 0.0)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(
        p, axis=nd - 1, keepdims=True) * jnp.ones_like(l_ref)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((nd - 1,), (nd - 2,)), (batch, batch)),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new * jnp.ones_like(m_ref)


def flash_decode_step(q_ref, k_ref, v_ref, o_ref, m_out_ref, l_out_ref,
                      acc_ref, m_ref, l_ref, *, rt: DeviceRuntime,
                      scale: float, window: Optional[int],
                      softcap: Optional[float], k_start, length, ik, nk,
                      k_scale=None, v_scale=None, row_length=None):
    """One KV-block update of the online-softmax accumulation.

    The shared body of the dense, windowed-paged and speculative decode
    kernels: they differ only in how KV blocks reach VMEM (contiguous
    BlockSpec walk vs. block-table gather); the flash math itself is
    ``online_softmax_update``, which the paged kernel calls directly.  ``k_start`` is the global token
    position of this block's first row, ``length`` the valid prefix,
    ``ik``/``nk`` this step's position on the sequential KV grid axis
    (init on first, emit on last).  ``k_scale``/``v_scale`` are
    optional per-block dequantization scalars (quantized pools store
    int8/fp8; the dequant fuses here, in VMEM, after the block DMA).
    ``row_length`` is an optional (G8, 1) per-query-row valid prefix:
    the speculative verify kernel stacks k+1 query positions into the
    group dim, each with its own causal horizon, while the scalar
    ``length`` (the maximum over rows) still gates whole-block skips.
    """
    @rt.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @rt.when(k_start < length)
    def _update():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (G8, D)
        k = k_ref[0, 0].astype(jnp.float32)               # (bkv, D)
        v = v_ref[0, 0].astype(jnp.float32)
        if k_scale is not None:
            k = k * k_scale
        if v_scale is not None:
            v = v * v_scale
        # per-row horizon when given ((G8,1) broadcasts against (G8,bkv));
        # scalar length otherwise — the single-query kernels' fast path
        online_softmax_update(
            q, k, v, acc_ref, m_ref, l_ref, rt=rt, window=window,
            softcap=softcap, k_start=k_start,
            horizon=length if row_length is None else row_length)

    @rt.when(ik == nk - 1)
    def _finalize():
        o_ref[0, 0] = acc_ref[...].astype(o_ref.dtype)    # unnormalized
        m_out_ref[0, 0] = m_ref[...].astype(m_out_ref.dtype)
        l_out_ref[0, 0] = l_ref[...].astype(l_out_ref.dtype)


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_out_ref, l_out_ref,
                   acc_ref, m_ref, l_ref, *, rt: DeviceRuntime, scale: float,
                   window: Optional[int], softcap: Optional[float],
                   block_kv: int, kv_offset: int):
    ib = rt.team_id(0)
    ik = rt.team_id(2)
    nk = rt.num_teams(2)
    flash_decode_step(
        q_ref, k_ref, v_ref, o_ref, m_out_ref, l_out_ref,
        acc_ref, m_ref, l_ref, rt=rt, scale=scale, window=window,
        softcap=softcap, k_start=kv_offset + ik * block_kv,
        length=len_ref[ib], ik=ik, nk=nk)


def decode_attention_fwd(q, k_cache, v_cache, lengths, *,
                         window: Optional[int] = None,
                         softcap: Optional[float] = None,
                         scale: Optional[float] = None,
                         block_kv: int = 512,
                         kv_offset: int = 0,
                         rt: Optional[DeviceRuntime] = None):
    """q: (B, Hq, D); caches: (B, Hkv, S, D); lengths: (B,) int32.

    Returns unnormalized (acc (B,Hq,D), m (B,Hq), l (B,Hq)); callers
    normalize (ops.py) or combine across KV shards (SP decode).
    ``kv_offset`` is this shard's global position of cache slot 0.
    """
    from repro.core.runtime import runtime
    rt = rt or runtime()
    b, hq, d = q.shape
    hkv, s = k_cache.shape[1], k_cache.shape[2]
    dv = v_cache.shape[3]                       # may differ from d (MLA)
    group = hq // hkv
    g8 = max(SUBLANES, group)
    scale = (d ** -0.5) if scale is None else scale
    block_kv = min(block_kv, s)
    nk = pl.cdiv(s, block_kv)

    # lay q out GQA-wise: (B, Hkv, G8, D), zero-padding the group dim
    qg = q.reshape(b, hkv, group, d)
    if g8 != group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g8 - group), (0, 0)))

    kern = functools.partial(
        _decode_kernel, rt=rt, scale=scale, window=window, softcap=softcap,
        block_kv=block_kv, kv_offset=kv_offset)

    def q_map(ib, ih, ik, len_ref):
        del ik, len_ref
        return (ib, ih, 0, 0)

    def kv_map(ib, ih, ik, len_ref):
        del len_ref
        return (ib, ih, ik, 0)

    # lengths ride as a scalar-prefetch operand (SMEM, whole array): the
    # compiler refuses a (1,) SMEM block of a (B,) array
    grid = (b, hkv, nk)
    acc, m, l = kernel_call(
        kern,
        out_shape=(
            jax.ShapeDtypeStruct((b, hkv, g8, dv), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, g8, LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, g8, LANES), jnp.float32),
        ),
        grid=grid,
        num_scalar_prefetch=1,
        in_specs=[
            pl.BlockSpec((1, 1, g8, d), q_map),
            pl.BlockSpec((1, 1, block_kv, d), kv_map),
            pl.BlockSpec((1, 1, block_kv, dv), kv_map),
        ],
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
        name="portable_decode_attention",
        rt=rt,
    )(lengths, qg, k_cache, v_cache)

    acc = acc[:, :, :group].reshape(b, hq, dv)
    m = m[:, :, :group, 0].reshape(b, hq)
    l = l[:, :, :group, 0].reshape(b, hq)
    return acc, m, l
