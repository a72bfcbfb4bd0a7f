"""``device_op`` — the declarative op layer over variant dispatch.

The paper's architecture is one *common* runtime layer plus thin
target-dependent variants.  The kernel packages originally violated
that split in miniature: every ``kernels/*/ops.py`` hand-rolled the
same ~60 lines of ``declare_target`` + ``declare_variant`` +
``jax.custom_vjp`` + ref-recompute-backward glue.  ``device_op``
collapses that boilerplate into one declaration per kernel:

* **dispatch** — the reference implementation becomes the
  ``declare_target`` base (it *is* the generic target), and the Pallas
  kernel is registered as a ``declare_variant`` for the compiled/
  interpreted archs.  Resolution goes through the OpenMP 5.1 selector
  scoring in :mod:`repro.core.variant`, so isa-specific kernel variants
  can still be layered on top with ``op.declare_variant(...)``.

* **differentiation** — one shared ``jax.custom_vjp`` wrapper supplies
  the flash-style recompute backward (re-run the *reference* under
  ``jax.vjp`` from saved operands; nothing quadratic is kept alive)
  for every op by default.  Integer/bool operands automatically get a
  ``None`` cotangent.  Ops with a bespoke backward (gmm's einsum rules,
  flash attention's dynamic ``q_offset``) override via ``bwd=``.

* **tuning** — block/tile sizes are *target-dependent* scheduling
  choices, so they live in :mod:`repro.core.tuning` keyed by
  ``(op, param, arch, isa)`` instead of being hardcoded per signature.
  A call site passing ``block_q=None`` gets the table entry for the
  active :class:`~repro.core.context.TargetContext`; explicit values
  win.  Each op also declares a ``search_space=`` (candidate values per
  tunable) plus ``constraints=`` (predicates over a full config that
  prune illegal tile/shape combos); :mod:`repro.core.autotune` sweeps
  :meth:`DeviceOp.candidate_configs` and writes measured winners back.

* **registry** — every declaration lands in :data:`op_registry`, with
  an ``example`` input builder and parity tolerances, so parity tests
  and ``benchmarks/parity.py`` enumerate ops instead of naming them.

Usage — a complete op declaration (rmsnorm, abridged)::

    from repro.core.op import device_op

    def _ref_impl(x, w, *, eps, weight_offset, block_rows):
        del block_rows                      # ref ignores scheduling params
        return rmsnorm_ref(x, w, eps=eps, weight_offset=weight_offset)

    def _kernel_impl(x, w, *, eps, weight_offset, block_rows):
        return rmsnorm_fwd(x, w, eps=eps, weight_offset=weight_offset,
                           block_rows=block_rows)

    rmsnorm_op = device_op(
        name="rmsnorm",
        ref=_ref_impl,
        kernel=_kernel_impl,
        tunables={"block_rows": 256},
        example=_example,                   # key -> (operands, params)
    )

    def rmsnorm(x, w, *, eps=1e-6, weight_offset=0.0, block_rows=None):
        return rmsnorm_op(x, w, eps=eps, weight_offset=weight_offset,
                          block_rows=block_rows)

Adding a kernel is now one declaration; adding a target is one
``tuning=`` entry plus (optionally) one ``op.declare_variant``.
DESIGN.md §8 walks through both.
"""
from __future__ import annotations

import functools
import itertools
from typing import Any, Callable, Dict, Iterable, List, Mapping, Optional, \
    Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import context as ctx_mod
from repro.core import tuning as tuning_mod
from repro.core import variant as variant_mod

__all__ = ["DeviceOp", "device_op", "op_registry", "get_op", "all_ops",
           "compare_outputs"]

#: name -> DeviceOp; parity tests and benchmarks enumerate this.
op_registry: Dict[str, "DeviceOp"] = {}

_Params = Tuple[Tuple[str, Any], ...]


def _freeze(params: Mapping[str, Any]) -> _Params:
    try:
        return tuple(sorted(params.items()))
    except TypeError as e:  # unsortable key mix — should not happen
        raise TypeError(f"op params must have str keys: {params}") from e


def _key_bytes(key) -> bytes:
    """Stable bytes for a PRNG key (old uint32 pair or new typed key)."""
    try:
        arr = np.asarray(key)
    except TypeError:
        arr = np.asarray(jax.random.key_data(key))
    return arr.tobytes()


def compare_outputs(got, want, tol: Mapping[str, float]) -> Dict[str, Any]:
    """THE output comparison: structure + per-leaf float32 allclose.

    The single comparison implementation behind the parity suite,
    ``benchmarks/parity.py --smoke``, and the autotuner's correctness
    gate — one site to fix if tolerances or comparison semantics ever
    change.
    """
    structure_match = (jax.tree_util.tree_structure(got)
                       == jax.tree_util.tree_structure(want))
    max_abs = 0.0
    within = structure_match
    if structure_match:
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            a32 = jnp.asarray(a, jnp.float32)
            b32 = jnp.asarray(b, jnp.float32)
            if a32.shape != b32.shape:
                within = False
                max_abs = float("inf")
                continue
            max_abs = max(max_abs, float(jnp.max(jnp.abs(a32 - b32))))
            within &= bool(jnp.allclose(a32, b32, atol=tol["atol"],
                                        rtol=tol["rtol"]))
    return {"max_abs_diff": max_abs, "within_tol": within,
            "structure_match": structure_match}


class DeviceOp:
    """One declared device op: dispatch + vjp + tuning + registry entry.

    Instances are hashable by identity (they ride through
    ``custom_vjp``'s ``nondiff_argnums``) and callable with the op's
    operands positionally and every static/tunable parameter by
    keyword.
    """

    def __init__(self, *, name: str,
                 ref: Callable,
                 kernel: Optional[Callable] = None,
                 kernel_archs: Sequence[str] = (ctx_mod.ARCH_TPU,
                                                ctx_mod.ARCH_INTERPRET),
                 tunables: Optional[Mapping[str, Any]] = None,
                 tuning: Optional[Mapping[Any, Mapping[str, Any]]] = None,
                 search_space: Optional[Mapping[str, Sequence[Any]]] = None,
                 constraints: Optional[Sequence[Callable[[Dict[str, Any]],
                                                         bool]]] = None,
                 bwd: Optional[Callable] = None,
                 differentiable: bool = True,
                 diff_operands: Optional[Sequence[int]] = None,
                 example: Optional[Callable] = None,
                 tol: Optional[Mapping[str, float]] = None,
                 doc: Optional[str] = None):
        if name in op_registry:
            raise ValueError(f"device_op {name!r} already registered")
        self.name = name
        self.ref = ref
        self.kernel = kernel
        self.tunables = tuple((tunables or {}).keys())
        self.search_space = {k: tuple(v)
                             for k, v in (search_space or {}).items()}
        unknown = set(self.search_space) - set(self.tunables)
        if unknown:
            raise ValueError(f"device_op {name!r}: search_space names "
                             f"non-tunable params {sorted(unknown)}")
        self.constraints = tuple(constraints or ())
        self._example_cache: Dict[bytes, Tuple[Tuple, Dict[str, Any]]] = {}
        self.differentiable = differentiable
        self.diff_operands = (tuple(diff_operands)
                              if diff_operands is not None else None)
        self.example = example
        self.tol = dict(tol or {"atol": 2e-5, "rtol": 2e-5})
        self._bwd = bwd
        self.__doc__ = doc or ref.__doc__

        # (a) dispatch: ref is the declare_target base; the kernel is a
        # match_any variant over the pallas-capable archs.
        self.base = variant_mod.declare_target(ref, name=f"{name}_impl")
        if kernel is not None:
            variant_mod.declare_variant(
                self.base,
                match=variant_mod.match(
                    device=variant_mod.arch(*kernel_archs),
                    implementation="match_any"))(kernel)

        # (c) tuning: wildcard defaults + per-target entries.
        if tunables:
            tuning_mod.register_defaults(name, dict(tunables))
        for target_key, entries in (tuning or {}).items():
            arch, isa = (target_key if isinstance(target_key, tuple)
                         else (target_key, None))
            for param, value in entries.items():
                tuning_mod.table.set(name, param, value,
                                     arch=arch, isa=isa, source="target")

        # (d) registry.
        op_registry[name] = self

    # -- declaration extension points -------------------------------------
    def declare_variant(self, *, match: variant_mod.Matcher):
        """Layer an extra (e.g. isa-specific) variant on this op."""
        return variant_mod.declare_variant(self.base, match=match)

    def defbwd(self, fn: Callable) -> Callable:
        """Decorator alternative to ``bwd=``: custom backward override.

        ``fn(params: dict, residuals: tuple, g) -> tuple`` of one
        cotangent (or ``None``) per operand.
        """
        self._bwd = fn
        return fn

    # -- call path ---------------------------------------------------------
    def resolve_params(self, params: Mapping[str, Any],
                       tc: Optional[ctx_mod.TargetContext] = None
                       ) -> Dict[str, Any]:
        """Fill ``None`` tunables from the per-target table."""
        params = dict(params)
        for p in self.tunables:
            if params.get(p) is None:
                params[p] = tuning_mod.block_size(self.name, p, tc)
        return params

    def candidate_configs(self, *, base: Optional[Mapping[str, Any]] = None,
                          budget: Optional[int] = None
                          ) -> List[Dict[str, Any]]:
        """Enumerate tunable configs for the autotuner.

        The ``base`` (current-table) config always comes first — it is
        the measured baseline and the fallback if every other candidate
        fails the correctness gate.  The rest is the constraint-filtered
        cartesian product of ``search_space``, deduplicated against the
        base; ``budget`` caps the total number returned (base included).
        """
        base_cfg = dict(base or {})
        names = [p for p in self.tunables if p in self.search_space]
        configs: List[Dict[str, Any]] = [dict(base_cfg)]
        seen = {_freeze(base_cfg)}
        for combo in itertools.product(*(self.search_space[p]
                                         for p in names)):
            cfg = dict(base_cfg)
            cfg.update(zip(names, combo))
            if not all(pred(cfg) for pred in self.constraints):
                continue
            frozen = _freeze(cfg)
            if frozen in seen:
                continue
            seen.add(frozen)
            configs.append(cfg)
        if budget is not None:
            configs = configs[:max(1, budget)]
        return configs

    def example_inputs(self, key) -> Tuple[Tuple, Dict[str, Any]]:
        """``example(key)``, memoized per key value.

        Example construction traces through ``jax.random``; sweeps that
        visit every op repeatedly (parity smoke, the autotuner's
        baseline + oracle + candidates) would otherwise re-trace it
        from scratch each time.
        """
        if self.example is None:
            raise ValueError(f"op {self.name!r} declares no example inputs")
        kb = _key_bytes(key)
        hit = self._example_cache.get(kb)
        if hit is None:
            hit = self.example(key)
            self._example_cache[kb] = hit
        return hit

    def __call__(self, *operands, **params):
        params = self.resolve_params(params)
        if not self.differentiable:
            return self.base(*operands, **params)
        return _op_call(self, tuple(operands), _freeze(params))

    def ref_call(self, operands: Sequence[Any],
                 params: Mapping[str, Any]):
        """The reference (oracle) output for ``operands``/``params``."""
        return self.ref(*operands, **self.resolve_params(params))

    def variant_for(self, arch_name: str) -> Callable:
        """The implementation the dispatcher would pick for ``arch``."""
        return self.base.variant_for(arch_name)

    # -- parity ------------------------------------------------------------
    def parity_diff(self, key, *, arch_a: str = ctx_mod.ARCH_INTERPRET,
                    arch_b: str = ctx_mod.ARCH_GENERIC) -> Dict[str, Any]:
        """Run the op on its example inputs under two archs and compare.

        The single comparison implementation behind both the parity
        test suite and ``benchmarks/parity.py --smoke`` — one site to
        fix if tolerances or comparison semantics ever change.
        """
        operands, params = self.example_inputs(key)
        with ctx_mod.target(arch_a):
            got = self(*operands, **params)
        with ctx_mod.target(arch_b):
            want = self(*operands, **params)
        return {"op": self.name, **compare_outputs(got, want, self.tol)}

    # -- backward helpers --------------------------------------------------
    def _diff_indices(self, operands: Sequence[Any]) -> Tuple[int, ...]:
        if self.diff_operands is not None:
            return self.diff_operands
        return tuple(i for i, x in enumerate(operands)
                     if jnp.issubdtype(jnp.result_type(x), jnp.inexact))

    def _backward(self, params: Dict[str, Any], residuals: Tuple,
                  g) -> Tuple:
        if self._bwd is not None:
            return tuple(self._bwd(params, residuals, g))
        # Default: flash-style recompute through the *reference* under
        # jax.vjp — identical to what every seed ops.py hand-wrote.
        diff_idx = self._diff_indices(residuals)

        def rerun(*diff_args):
            full = list(residuals)
            for i, x in zip(diff_idx, diff_args):
                full[i] = x
            return self.ref(*full, **params)

        _, vjp = jax.vjp(rerun, *(residuals[i] for i in diff_idx))
        cotangents = vjp(g)
        grads: list = [None] * len(residuals)
        for i, ct in zip(diff_idx, cotangents):
            grads[i] = ct
        return tuple(grads)

    def __repr__(self):
        return (f"DeviceOp({self.name!r}, tunables={list(self.tunables)}, "
                f"differentiable={self.differentiable})")


# ---------------------------------------------------------------------------
# The one shared custom_vjp every differentiable op routes through.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 2))
def _op_call(op: DeviceOp, operands: Tuple, params: _Params):
    return op.base(*operands, **dict(params))


def _op_fwd(op: DeviceOp, operands: Tuple, params: _Params):
    out = op.base(*operands, **dict(params))
    # Residuals are the operands themselves: recompute-style backward
    # keeps nothing quadratic (no softmax matrix, no per-step states).
    return out, operands


def _op_bwd(op: DeviceOp, params: _Params, residuals: Tuple, g):
    return (op._backward(dict(params), residuals, g),)


_op_call.defvjp(_op_fwd, _op_bwd)


# ---------------------------------------------------------------------------
# Declaration + registry access
# ---------------------------------------------------------------------------

def device_op(**kwargs) -> DeviceOp:
    """Declare a device op; see the module docstring for the fields."""
    return DeviceOp(**kwargs)


def get_op(name: str) -> DeviceOp:
    return op_registry[name]


def all_ops() -> Iterable[DeviceOp]:
    return tuple(op_registry[k] for k in sorted(op_registry))
