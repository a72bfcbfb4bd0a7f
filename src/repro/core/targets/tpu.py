"""TPU (Mosaic-compiled) target-specific part.

The analogue of the paper's nvptx implementation file: every function
here wraps a compiler intrinsic (``pltpu.*``) and is selected by
``match(device={arch(tpu)})``.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import intrinsics as I
from repro.core.variant import declare_variant, match, arch


@declare_variant(I.approx_reciprocal, match=match(device=arch("tpu")))
def _approx_reciprocal_tpu(x):
    return pl.reciprocal(x, approx=True)


@declare_variant(I.repeat, match=match(device=arch("tpu")))
def _repeat_tpu(x, repeats, axis):
    return pltpu.repeat(x, repeats, axis)


@declare_variant(I.roll, match=match(device=arch("tpu")))
def _roll_tpu(x, shift, axis):
    return pltpu.roll(x, shift, axis)


@declare_variant(I.make_async_copy, match=match(device=arch("tpu")))
def _make_async_copy_tpu(src_ref, dst_ref, sem):
    return pltpu.make_async_copy(src_ref, dst_ref, sem)


#: Scoped VMEM Mosaic gives a kernel that asks for none (v4, v5e).
DEFAULT_SCOPED_VMEM = 16 * 2 ** 20


@declare_variant(I.compiler_params, match=match(device=arch("tpu")))
def _compiler_params_tpu(dimension_semantics=None, vmem_limit_bytes=None):
    """A scoped-VMEM request the default covers is left out: an explicit
    limit, even the default's, changes how XLA places the buffers around
    the kernel, so the kernel compiles as if it had asked for none."""
    kw = {}
    if dimension_semantics is not None:
        kw["dimension_semantics"] = tuple(dimension_semantics)
    if vmem_limit_bytes is not None and \
            vmem_limit_bytes > DEFAULT_SCOPED_VMEM:
        kw["vmem_limit_bytes"] = int(vmem_limit_bytes)
    return pltpu.CompilerParams(**kw)
