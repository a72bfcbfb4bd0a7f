"""Grouped matmul (megablox-style) Pallas kernel, portable-runtime form.

Capacity-layout MoE expert matmul: tokens are pre-gathered into dense
(E, C, K) per-expert buffers (repro.models.moe does the all_to_all),
and each expert's (C, K) @ (K, N) runs as a blocked MXU matmul with a
K-sequential accumulator in shared VMEM.  ``group_sizes`` rides in SMEM
(scalar memory, as a scalar-prefetch operand) and masks both compute
(fully-empty blocks are skipped — the worksharing analogue of the
paper's dynamic loop scheduling) and the padded capacity rows at
writeback.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.runtime import DeviceRuntime, kernel_call


def _gmm_kernel(gs_ref, lhs_ref, rhs_ref, o_ref, acc_ref, *,
                rt: DeviceRuntime, block_c: int, nk: int):
    e = rt.team_id(0)
    ic = rt.team_id(1)
    ik = rt.team_id(3)
    size = gs_ref[e]

    @rt.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # skip K-blocks for capacity blocks that hold no valid token
    @rt.when(ic * block_c < size)
    def _compute():
        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[0].astype(jnp.float32), rhs_ref[0].astype(jnp.float32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @rt.when(ik == nk - 1)
    def _finalize():
        row = ic * block_c + rt.iota(acc_ref.shape, 0)
        o_ref[0] = jnp.where(row < size, acc_ref[...], 0.0).astype(o_ref.dtype)


def gmm_fwd(lhs, rhs, group_sizes, *, block_c: int = 512, block_n: int = 512,
            block_k: int = 512, rt: DeviceRuntime = None):
    from repro.core.runtime import runtime
    rt = rt or runtime()
    e, c, k = lhs.shape
    n = rhs.shape[2]
    block_c = min(block_c, c)
    block_n = min(block_n, n)
    block_k = min(block_k, k)

    kern = functools.partial(_gmm_kernel, rt=rt, block_c=block_c,
                             nk=pl.cdiv(k, block_k))
    # group_sizes ride as a scalar-prefetch operand (SMEM, whole array):
    # the compiler refuses a (1,) SMEM block of an (E,) array
    return kernel_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((e, c, n), lhs.dtype),
        grid=(e, pl.cdiv(c, block_c), pl.cdiv(n, block_n), pl.cdiv(k, block_k)),
        num_scalar_prefetch=1,
        in_specs=[
            pl.BlockSpec((1, block_c, block_k),
                         lambda ie, ic, jn, ik, gs: (ie, ic, ik)),
            pl.BlockSpec((1, block_k, block_n),
                         lambda ie, ic, jn, ik, gs: (ie, ik, jn)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_n),
                               lambda ie, ic, jn, ik, gs: (ie, ic, jn)),
        scratch_shapes=[rt.alloc_shared((block_c, block_n), jnp.float32)],
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        name="portable_gmm",
        rt=rt,
    )(group_sizes, lhs, rhs)
