"""DeviceRuntime — the facade Pallas kernels are written against.

This is ``libomptarget-device`` for Pallas: kernels call these entry
points instead of target intrinsics, so one kernel source serves every
target (compiled TPU, CPU interpreter, pure-jnp fallback).  The facade
resolves each primitive through the ``declare_variant`` registry at
trace time; after tracing the chosen implementation is baked into the
jaxpr, so dispatch is zero-cost (parity checked in benchmarks/parity.py).

Worksharing & teams (DESIGN.md §3): an OpenMP *team* maps to a Pallas
grid step; ``#pragma omp for`` over teams maps to block partitioning of
the iteration space across the grid.
"""
from __future__ import annotations

import dataclasses
import functools
import re
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import atomics as _atomics
from repro.core import context as _context
from repro.core import intrinsics as _intrinsics
from repro.core import memory as _memory
import repro.core.targets  # noqa: F401  (register all variants)

__all__ = ["DeviceRuntime", "runtime", "kernel_call", "compiled_kernels"]

_TPU_CUSTOM_CALL = re.compile(
    r"%([A-Za-z_]\w*?)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"")


@dataclasses.dataclass(frozen=True)
class DeviceRuntime:
    """Bound runtime for the target context active at construction."""

    ctx: _context.TargetContext

    # -- team / thread hierarchy (omp_get_team_num etc.) -------------------
    @staticmethod
    def team_id(axis: int = 0):
        return pl.program_id(axis)

    @staticmethod
    def num_teams(axis: int = 0):
        return pl.num_programs(axis)

    # -- worksharing (#pragma omp for schedule(static)) ---------------------
    @staticmethod
    def static_partition(total: int, num_teams: int, team: Any) -> Tuple[Any, Any]:
        """Contiguous static schedule: [lo, hi) owned by ``team``."""
        chunk = pl.cdiv(total, num_teams)
        lo = team * chunk
        hi = jnp.minimum(lo + chunk, total)
        return lo, hi

    @staticmethod
    def grid_size(total: int, block: int) -> int:
        return pl.cdiv(total, block)

    # -- memory (allocate directive) ----------------------------------------
    alloc_shared = staticmethod(_memory.alloc_shared)
    alloc_scalar = staticmethod(_memory.alloc_scalar)
    alloc_semaphore = staticmethod(_memory.alloc_semaphore)

    # -- atomics (Listing 3/4) -----------------------------------------------
    atomic_add = staticmethod(_atomics.atomic_add)
    atomic_max = staticmethod(_atomics.atomic_max)
    atomic_min = staticmethod(_atomics.atomic_min)
    atomic_exchange = staticmethod(_atomics.atomic_exchange)
    atomic_cas = staticmethod(_atomics.atomic_cas)
    atomic_inc = staticmethod(_atomics.atomic_inc)

    # -- vector intrinsics (variant-dispatched) -------------------------------
    iota = staticmethod(_intrinsics.iota)
    repeat = staticmethod(_intrinsics.repeat)
    roll = staticmethod(_intrinsics.roll)
    approx_reciprocal = staticmethod(_intrinsics.approx_reciprocal)
    reduce_sum = staticmethod(_intrinsics.reduce_sum)
    reduce_max = staticmethod(_intrinsics.reduce_max)
    make_async_copy = staticmethod(_intrinsics.make_async_copy)

    # -- masking / predication (omp if/masked analogue) ----------------------
    when = staticmethod(pl.when)

    # -- target knobs ---------------------------------------------------------
    def compiler_params(self, dimension_semantics: Optional[Sequence[str]] = None,
                        vmem_limit_bytes: Optional[int] = None):
        return _intrinsics.compiler_params(dimension_semantics, vmem_limit_bytes)

    @property
    def interpret(self) -> bool:
        return self.ctx.interpret

    @property
    def use_pallas(self) -> bool:
        return self.ctx.use_pallas

    @property
    def arch(self) -> str:
        return self.ctx.arch


def runtime() -> DeviceRuntime:
    """Bind a DeviceRuntime to the current target context."""
    return DeviceRuntime(_context.current_context())


def kernel_call(kernel_fn, *, out_shape, grid=None, in_specs=None,
                out_specs=None, scratch_shapes=(), dimension_semantics=None,
                vmem_limit_bytes=None, name=None, rt: Optional[DeviceRuntime] = None,
                num_scalar_prefetch: int = 0, **kwargs):
    """``pallas_call`` with the target decided by the runtime.

    The single entry point kernels launch through — the analogue of the
    kernel-launch glue the device runtime provides.  The ``generic``
    target has no Pallas lowering (ops dispatch to their ``ref.py``), so
    reaching this there raises instead of silently interpreting: a
    kernel that was meant to compile must never quietly run elsewhere.

    ``num_scalar_prefetch``: the leading N operands are small integer
    control arrays (block tables, lengths) made available *before* the
    kernel body runs so BlockSpec index maps can compute data-dependent
    DMA source blocks — the paged-KV gather path.  Index maps then
    receive the prefetched refs as trailing arguments after the grid
    indices.  The interpreter honors the same descriptor, so this stays
    in the common part of the runtime.
    """
    rt = rt or runtime()
    if not rt.use_pallas:
        raise RuntimeError(
            f"kernel_call({name or getattr(kernel_fn, '__name__', 'kernel')}) "
            f"on the {rt.arch!r} target, which has no Pallas lowering; "
            f"dispatch to the reference there, or select the 'interpret' "
            f"target to run kernels in the CPU interpreter")
    params = rt.compiler_params(dimension_semantics, vmem_limit_bytes)
    pk = dict(kwargs)
    if params is not None:
        pk["compiler_params"] = params
    interpret = rt.interpret
    if num_scalar_prefetch:
        from jax.experimental.pallas import tpu as pltpu
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=num_scalar_prefetch,
            grid=grid,
            in_specs=list(in_specs) if in_specs is not None else [],
            out_specs=out_specs,
            scratch_shapes=list(scratch_shapes),
        )
        call = pl.pallas_call(
            kernel_fn,
            out_shape=out_shape,
            grid_spec=grid_spec,
            interpret=interpret,
            name=name,
            **pk,
        )
    else:
        call = pl.pallas_call(
            kernel_fn,
            out_shape=out_shape,
            grid=grid,
            in_specs=in_specs if in_specs is not None else [],
            out_specs=out_specs,
            scratch_shapes=list(scratch_shapes),
            interpret=interpret,
            name=name,
            **pk,
        )
    return call


def compiled_kernels(hlo_text: str) -> set:
    """Names of the Mosaic-compiled kernels in a compiled module's text
    (``jit(f).lower(...).compile().as_text()``): each ``kernel_call``
    that reached the chip's compiler is a ``tpu_custom_call`` named
    after its ``name=``.  Empty for interpret or generic lowerings."""
    return set(_TPU_CUSTOM_CALL.findall(hlo_text))
