"""Fused RMSNorm Pallas kernel (portable-runtime form)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.runtime import DeviceRuntime, kernel_call

#: Full-tile float32 values ``_rms_kernel`` makes: x widened, x * x and
#: the normalised y.  Mosaic's own scoped allocation counts only the
#: four I/O tiles and the scale; the f32 values are budgeted too, so the
#: limit holds wherever they land.
F32_TEMPS = 3
SUBLANES = 8


def _row_bytes(d: int, itemsize: int) -> int:
    # one row of the input and output tiles, each double-buffered, and
    # of the F32_TEMPS float32 tiles
    return 4 * d * itemsize + 4 * F32_TEMPS * d


def step_vmem_bytes(block_rows: int, d: int, itemsize: int) -> int:
    """VMEM of one grid step: ``block_rows`` rows of the tiles and the
    double-buffered scale vector."""
    return block_rows * _row_bytes(d, itemsize) + 2 * d * itemsize


def vmem_request(block_rows: int, d: int, itemsize: int) -> int:
    """The scoped VMEM a step asks for: its bytes rounded up to a MiB,
    which a ``vmem_limit`` of whole MiB holds."""
    need = step_vmem_bytes(block_rows, d, itemsize)
    return -(-need // 2 ** 20) * 2 ** 20


def row_blocks(rows: int, d: int, itemsize: int, want: int,
               vmem_limit: int):
    """``(block, padded_rows)``: rows per grid step and the row count the
    grid covers.  The block is at most ``want`` (the tuning table's) and
    small enough that ``vmem_request`` fits ``vmem_limit``.  All the rows
    fit one block where they can (legal at any count); otherwise the
    block is a multiple of 8 rows that divides the padded count, which
    is ``rows`` rounded up to 8.  No grid step reads past the end of its
    operand: XLA may place that operand in VMEM, and a partial last block
    there reads past it, on v5e past the end of VMEM, which hangs the
    chip."""
    fit = max((vmem_limit - 2 * d * itemsize) // _row_bytes(d, itemsize),
              SUBLANES)
    cap = min(want, fit)
    if rows <= cap:
        return rows, rows
    padded = -(-rows // SUBLANES) * SUBLANES
    block = max(cap // SUBLANES * SUBLANES, SUBLANES)
    while padded % block:
        block -= SUBLANES
    return block, padded


def _rms_kernel(x_ref, w_ref, o_ref, *, rt: DeviceRuntime, eps: float,
                weight_offset: float, d: int):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.sum(x * x, axis=-1, keepdims=True) * (1.0 / d)
    y = x * jax.lax.rsqrt(var + eps)
    y = y * (w_ref[...].astype(jnp.float32) + weight_offset)
    o_ref[...] = y.astype(o_ref.dtype)


def rmsnorm_fwd(x, w, *, eps: float = 1e-6, weight_offset: float = 0.0,
                block_rows: int = 256, vmem_limit: int = 16 * 2 ** 20,
                rt: DeviceRuntime = None):
    from repro.core.runtime import runtime
    rt = rt or runtime()
    orig_shape = x.shape
    d = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    itemsize = x.dtype.itemsize
    block_rows, padded = row_blocks(rows, d, itemsize, block_rows,
                                    vmem_limit)
    if padded != rows:
        x2 = jnp.pad(x2, ((0, padded - rows), (0, 0)))

    kern = functools.partial(_rms_kernel, rt=rt, eps=eps,
                             weight_offset=weight_offset, d=d)
    out = kernel_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((padded, d), x.dtype),
        grid=(padded // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        dimension_semantics=("parallel",),
        vmem_limit_bytes=vmem_request(block_rows, d, itemsize),
        name="portable_rmsnorm",
        rt=rt,
    )(x2, w)
    if padded != rows:
        out = out[:rows]
    return out.reshape(orig_shape)
