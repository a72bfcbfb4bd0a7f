"""Tests for the DeviceRuntime primitives inside real Pallas kernels."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.experimental import pallas as pl

from repro.core.runtime import kernel_call, runtime
from repro.core import context as ctx
from repro.core import intrinsics as I


def test_intrinsic_dispatch_per_target():
    x = jnp.full((8, 128), 2.0, jnp.float32)
    with ctx.target("interpret"):
        np.testing.assert_allclose(I.approx_reciprocal(x), 0.5)
    with ctx.target("generic"):
        np.testing.assert_allclose(I.approx_reciprocal(x), 0.5)
    # tpu variant resolves to pl.reciprocal (can't execute on CPU, but
    # the registry must pick it).
    from repro.core.variant import base_registry
    fn = base_registry["approx_reciprocal"].variant_for("tpu")
    assert "tpu" in fn.__name__


def test_repeat_roll_portable():
    x = jnp.arange(8 * 128, dtype=jnp.float32).reshape(8, 128)
    with ctx.target("interpret"):
        r = I.repeat(x, 2, 0)
        assert r.shape == (16, 128)
        np.testing.assert_array_equal(np.asarray(r[:8]), np.asarray(x))
        np.testing.assert_array_equal(np.asarray(I.roll(x, 3, 1)),
                                      np.roll(np.asarray(x), 3, axis=1))


def test_iota_is_2d_safe():
    got = I.iota((8, 128), 1)
    assert got.shape == (8, 128)
    np.testing.assert_array_equal(np.asarray(got[0]), np.arange(128))


def test_kernel_call_scratch_and_teams():
    """A kernel using teams, worksharing, shared memory, and atomics."""
    rt = runtime()

    def kern(x_ref, o_ref, acc_ref):
        team = rt.team_id(0)

        @rt.when(team == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        rt.atomic_add(acc_ref, x_ref[...])
        o_ref[...] = acc_ref[...]

    x = jnp.ones((4, 8, 128), jnp.float32)
    out = kernel_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((4, 8, 128), jnp.float32),
        grid=(4,),
        in_specs=[pl.BlockSpec((1, 8, 128), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, 8, 128), lambda i: (i, 0, 0)),
        scratch_shapes=[rt.alloc_shared((1, 8, 128), jnp.float32)],
        dimension_semantics=("arbitrary",),
    )(x)
    # grid is sequential: accumulator sees 1,2,3,4 as it sweeps
    np.testing.assert_allclose(np.asarray(out[..., 0, 0]), [1, 2, 3, 4])


def test_static_partition_covers_iteration_space():
    rt = runtime()
    total, teams = 1000, 7
    seen = []
    for t in range(teams):
        lo, hi = rt.static_partition(total, teams, jnp.int32(t))
        seen.append((int(lo), int(hi)))
    flat = sorted(seen)
    assert flat[0][0] == 0 and max(h for _, h in flat) == total
    # no gaps/overlap
    for (l0, h0), (l1, h1) in zip(flat, flat[1:]):
        assert h0 == l1 or (h0 == total and l1 >= total)


def test_atomics_semantics():
    from repro.core import atomics as A

    class FakeRef:
        def __init__(self, v):
            self.v = jnp.asarray(v)

        def __getitem__(self, idx):
            return self.v

        def __setitem__(self, idx, val):
            self.v = jnp.asarray(val)

    r = FakeRef(jnp.float32(5))
    assert A.atomic_add(r, 3.0) == 5 and r.v == 8
    assert A.atomic_max(r, 2.0) == 8 and r.v == 8
    assert A.atomic_max(r, 11.0) == 8 and r.v == 11
    assert A.atomic_exchange(r, 1.0) == 11 and r.v == 1
    assert A.atomic_cas(r, 1.0, 9.0) == 1 and r.v == 9
    assert A.atomic_cas(r, 1.0, 0.0) == 9 and r.v == 9  # no match -> unchanged
    # CUDA-spec inc wraparound: x = x >= e ? 0 : x+1
    r2 = FakeRef(jnp.int32(2))
    assert A.atomic_inc(r2, 3) == 2 and r2.v == 3
    assert A.atomic_inc(r2, 3) == 3 and r2.v == 0


def test_atomic_inc_wraps_like_cuda_spec_sequence():
    from repro.core import atomics as A

    class FakeRef:
        def __init__(self, v):
            self.v = jnp.asarray(v)

        def __getitem__(self, idx):
            return self.v

        def __setitem__(self, idx, val):
            self.v = jnp.asarray(val)

    r = FakeRef(jnp.int32(0))
    seq = [int(A.atomic_inc(r, 2)) for _ in range(6)]
    assert seq == [0, 1, 2, 0, 1, 2]


def test_kernel_call_refuses_the_generic_target():
    """The generic target has no Pallas lowering: reaching kernel_call
    there is an error, never a silent run in the interpreter."""
    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    with ctx.target("generic"):
        with pytest.raises(RuntimeError, match="no Pallas lowering"):
            kernel_call(kern,
                        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
                        name="copy")
