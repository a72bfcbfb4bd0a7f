"""Public fused-RMSNorm op, declared against ``core/op.py``.

Pure declaration: dispatch, ref-recompute backward, and the
``block_rows`` tuning default all come from the ``device_op`` layer.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.op import device_op
from repro.kernels.rmsnorm import ref as _ref
from repro.kernels.rmsnorm import rmsnorm as _kern


def _ref_impl(x, w, *, eps, weight_offset, block_rows, vmem_limit):
    del block_rows, vmem_limit
    return _ref.rmsnorm_ref(x, w, eps=eps, weight_offset=weight_offset)


def _kernel_impl(x, w, *, eps, weight_offset, block_rows, vmem_limit):
    return _kern.rmsnorm_fwd(x, w, eps=eps, weight_offset=weight_offset,
                             block_rows=block_rows, vmem_limit=vmem_limit)


def _example(key):
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (64, 256), jnp.float32)
    w = jax.random.normal(kw, (256,), jnp.float32) * 0.1
    return (x, w), dict(eps=1e-6, weight_offset=1.0, block_rows=None,
                        vmem_limit=None)


rmsnorm_op = device_op(
    name="rmsnorm",
    ref=_ref_impl,
    kernel=_kernel_impl,
    # vmem_limit: the most scoped VMEM a grid step may ask for, in whole
    # MiB; the block shrinks below block_rows to fit it.  The default is
    # Mosaic's own limit; a v5e core has 128 MiB of VMEM.
    tunables={"block_rows": 256, "vmem_limit": 16 * 2 ** 20},
    tuning={"tpu": {"block_rows": 512},
            ("tpu", "v5e"): {"vmem_limit": 48 * 2 ** 20}},
    # Row-blocked 1D grid: any block height is legal (the kernel fits it
    # to the row count), so the space is a pure sweep.
    search_space={"block_rows": (32, 64, 128, 256, 512)},
    example=_example,
    tol={"atol": 1e-5, "rtol": 1e-5},
)


def rmsnorm(x, w, *, eps: float = 1e-6, weight_offset: float = 0.0,
            block_rows: Optional[int] = None,
            vmem_limit: Optional[int] = None):
    """Fused RMSNorm: x * rsqrt(mean(x^2)+eps) * (w + offset).
    ``block_rows`` and ``vmem_limit`` default to the per-target tuning
    table."""
    return rmsnorm_op(x, w, eps=eps, weight_offset=weight_offset,
                      block_rows=block_rows, vmem_limit=vmem_limit)
