"""gmm / rmsnorm / mamba_scan / mlstm_scan vs their oracles (shape sweeps)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import context as ctx


def _rand(shape, dtype=jnp.float32, seed=0, scale=1.0):
    return (jax.random.normal(jax.random.PRNGKey(seed), shape,
                              jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------- gmm ----
from repro.kernels.gmm.ops import gmm
from repro.kernels.gmm.ref import gmm_ref


@pytest.mark.parametrize("e,c,k,n,dtype", [
    (4, 64, 128, 128, jnp.float32),
    (2, 128, 256, 128, jnp.float32),
    (8, 32, 64, 64, jnp.bfloat16),
])
def test_gmm_matches_ref(e, c, k, n, dtype):
    lhs = _rand((e, c, k), dtype, 0)
    rhs = _rand((e, k, n), dtype, 1)
    sizes = jnp.arange(e, dtype=jnp.int32) * (c // max(e - 1, 1))
    got = gmm(lhs, rhs, sizes, block_c=32, block_n=64, block_k=64)
    want = gmm_ref(lhs, rhs, sizes)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(got, jnp.float32),
                               np.asarray(want, jnp.float32), atol=tol, rtol=tol)


def test_gmm_grad_and_generic():
    lhs = _rand((2, 32, 64), jnp.float32, 0)
    rhs = _rand((2, 64, 32), jnp.float32, 1)
    sizes = jnp.array([32, 20], jnp.int32)

    def loss(l, r):
        return jnp.sum(gmm(l, r, sizes, block_c=16, block_n=16, block_k=32) ** 2)

    g1 = jax.grad(loss, (0, 1))(lhs, rhs)
    with ctx.target("generic"):
        g2 = jax.grad(loss, (0, 1))(lhs, rhs)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


# ------------------------------------------------------------ rmsnorm ----
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.rmsnorm.native import rmsnorm_native


@pytest.mark.parametrize("shape,offset,dtype", [
    ((4, 64, 256), 0.0, jnp.float32),
    ((2, 128, 512), 1.0, jnp.float32),   # gemma convention
    ((8, 256), 0.0, jnp.bfloat16),
])
def test_rmsnorm_matches_ref(shape, offset, dtype):
    x = _rand(shape, dtype, 0)
    w = _rand(shape[-1:], dtype, 1, scale=0.1)
    got = rmsnorm(x, w, weight_offset=offset, block_rows=64)
    want = rmsnorm_ref(x, w, weight_offset=offset)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, jnp.float32),
                               np.asarray(want, jnp.float32), atol=tol, rtol=tol)


def test_rmsnorm_native_twin_identical():
    x = _rand((64, 256), jnp.float32, 0)
    w = _rand((256,), jnp.float32, 1)
    a = rmsnorm(x, w, block_rows=32)
    b = rmsnorm_native(x, w, block_rows=32, interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


V5E_VMEM = 48 * 2 ** 20          # the v5e tuning row's vmem_limit


@pytest.mark.parametrize("d", [4096, 6144, 8192])
@pytest.mark.parametrize("itemsize", [2, 4])
def test_rmsnorm_block_fits_scoped_vmem(d, itemsize):
    """The row block stays within the VMEM limit at every row count, and
    the grid covers whole blocks only: all the rows in one block, or a
    multiple of 8 rows dividing the count rounded up to 8.  The tuned 512
    survives where it fits."""
    from repro.kernels.rmsnorm.rmsnorm import (
        row_blocks, step_vmem_bytes, vmem_request)
    for rows in (1, 12, 32, 256, 2048, 3584, 4001, 4032, 4096, 5632,
                 7168, 8192):
        b, padded = row_blocks(rows, d, itemsize, 512, V5E_VMEM)
        assert 1 <= b <= min(rows, 512)
        assert padded % b == 0 and rows <= padded < rows + 8
        assert b == rows or b % 8 == 0
        assert step_vmem_bytes(b, d, itemsize) <= \
            vmem_request(b, d, itemsize) <= V5E_VMEM
    fits = step_vmem_bytes(512, d, itemsize) <= V5E_VMEM
    assert (row_blocks(8192, d, itemsize, 512, V5E_VMEM)[0] == 512) == fits


def test_rmsnorm_block_at_granite_shapes_is_unchanged():
    """granite-8b's cells (d 4096, bf16) keep the v5e tuning's 512-row
    block (all the rows of a shorter prompt) in every prefill up to 3584
    rows, and a decode batch is one block of all its rows, within the
    default scoped VMEM, so it asks for none."""
    from repro.core.targets.tpu import DEFAULT_SCOPED_VMEM
    from repro.kernels.rmsnorm.rmsnorm import row_blocks, vmem_request
    for rows in (256, 512, 1024, 2048, 3072, 3584):
        assert row_blocks(rows, 4096, 2, 512, V5E_VMEM) == \
            (min(rows, 512), rows)
    for slots in (1, 8, 12, 32):
        assert row_blocks(slots, 4096, 2, 512, V5E_VMEM) == (slots, slots)
        assert vmem_request(slots, 4096, 2) <= DEFAULT_SCOPED_VMEM


def test_rmsnorm_block_at_internlm2_shapes():
    """InternLM2's prefills (d 6144) take the largest whole block that
    fits the v5e limit; a decode batch is one block."""
    from repro.kernels.rmsnorm.rmsnorm import row_blocks
    assert row_blocks(32, 6144, 2, 512, V5E_VMEM) == (32, 32)
    for rows, block in ((256, 256), (512, 256), (2048, 256), (4096, 256),
                        (5632, 352), (7168, 256)):
        assert row_blocks(rows, 6144, 2, 512, V5E_VMEM) == (block, rows)


@pytest.mark.parametrize("rows", [40, 41, 64])
def test_rmsnorm_whole_blocks_match_reference(rows):
    """Blocks smaller than the row count, a count padded up to 8 among
    them, give the reference's output row for row."""
    from repro.kernels.rmsnorm.rmsnorm import rmsnorm_fwd, row_blocks
    x = _rand((rows, 128), jnp.float32, 0)
    w = _rand((128,), jnp.float32, 1, scale=0.1)
    block, padded = row_blocks(rows, 128, 4, 24, 16 * 2 ** 20)
    assert block < rows and padded % block == 0
    got = rmsnorm_fwd(x, w, eps=1e-5, weight_offset=1.0, block_rows=24)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(rmsnorm_ref(x, w, eps=1e-5,
                                                weight_offset=1.0)),
        rtol=1e-5, atol=1e-5)


def test_rms_norm_eps_reaches_the_kernel():
    """``ModelConfig.rms_norm_eps`` is the eps of the model's norms: at
    small d and small activations 1e-5 and 1e-6 give other outputs, each
    the reference's at its eps."""
    import dataclasses
    from repro.configs import get_config
    from repro.models.layers import apply_norm
    x = _rand((8, 64), jnp.float32, 0, scale=3e-3)
    w = _rand((64,), jnp.float32, 1, scale=0.1)
    base = get_config("granite-8b")
    outs = {}
    for eps in (1e-5, 1e-6):
        cfg = dataclasses.replace(base, rms_norm_eps=eps)
        outs[eps] = np.asarray(apply_norm(w, x, cfg))
        np.testing.assert_allclose(
            outs[eps], np.asarray(rmsnorm_ref(x, w, eps=eps,
                                              weight_offset=1.0)),
            rtol=1e-5, atol=1e-5)
    assert np.max(np.abs(outs[1e-5] - outs[1e-6])) > 0.1


def test_configs_state_their_norm_eps_and_rope_base():
    from repro.configs import get_config
    ivl = get_config("internvl2-26b")
    assert (ivl.rope_theta, ivl.rms_norm_eps) == (1e6, 1e-5)
    granite = get_config("granite-8b")
    assert (granite.rope_theta, granite.rms_norm_eps) == (1e4, 1e-6)


def test_rmsnorm_grad():
    x = _rand((16, 128), jnp.float32)
    w = _rand((128,), jnp.float32, 1)
    g1 = jax.grad(lambda x_, w_: jnp.sum(rmsnorm(x_, w_) ** 2), (0, 1))(x, w)
    g2 = jax.grad(lambda x_, w_: jnp.sum(rmsnorm_ref(x_, w_) ** 2), (0, 1))(x, w)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


# --------------------------------------------------------- mamba_scan ----
from repro.kernels.mamba_scan.ops import mamba_scan
from repro.kernels.mamba_scan.ref import mamba_scan_ref


@pytest.mark.parametrize("b,s,d,n,chunk", [
    (2, 64, 32, 8, 16),
    (1, 128, 64, 16, 32),
])
def test_mamba_scan_matches_ref(b, s, d, n, chunk):
    x = _rand((b, s, d), jnp.float32, 0)
    dt = jax.nn.softplus(_rand((b, s, d), jnp.float32, 1))
    A = -jnp.exp(_rand((d, n), jnp.float32, 2, scale=0.5))
    Bm = _rand((b, s, n), jnp.float32, 3)
    Cm = _rand((b, s, n), jnp.float32, 4)
    D = _rand((d,), jnp.float32, 5)
    y_k, h_k = mamba_scan(x, dt, A, Bm, Cm, D, chunk=chunk)
    y_r, h_r = mamba_scan_ref(x, dt, A, Bm, Cm, D)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r),
                               atol=1e-4, rtol=1e-4)


def test_mamba_scan_grad():
    b, s, d, n = 1, 32, 16, 8
    x = _rand((b, s, d), jnp.float32, 0)
    dt = jax.nn.softplus(_rand((b, s, d), jnp.float32, 1))
    A = -jnp.exp(_rand((d, n), jnp.float32, 2, scale=0.5))
    Bm = _rand((b, s, n), jnp.float32, 3)
    Cm = _rand((b, s, n), jnp.float32, 4)
    D = _rand((d,), jnp.float32, 5)

    def loss(x_):
        y, _ = mamba_scan(x_, dt, A, Bm, Cm, D, chunk=16)
        return jnp.sum(y ** 2)

    def loss_ref(x_):
        y, _ = mamba_scan_ref(x_, dt, A, Bm, Cm, D)
        return jnp.sum(y ** 2)

    np.testing.assert_allclose(np.asarray(jax.grad(loss)(x)),
                               np.asarray(jax.grad(loss_ref)(x)),
                               atol=1e-4, rtol=1e-4)


# --------------------------------------------------------- mlstm_scan ----
from repro.kernels.mlstm_scan.ops import mlstm_scan
from repro.kernels.mlstm_scan.ref import mlstm_scan_ref


@pytest.mark.parametrize("b,h,s,dk,dv,chunk", [
    (1, 2, 64, 32, 32, 16),
    (2, 1, 128, 64, 64, 32),
])
def test_mlstm_scan_matches_ref(b, h, s, dk, dv, chunk):
    q = _rand((b, h, s, dk), jnp.float32, 0)
    k = _rand((b, h, s, dk), jnp.float32, 1)
    v = _rand((b, h, s, dv), jnp.float32, 2)
    ig = _rand((b, h, s), jnp.float32, 3)
    fg = _rand((b, h, s), jnp.float32, 4) + 2.0
    got = mlstm_scan(q, k, v, ig, fg, chunk=chunk)
    want = mlstm_scan_ref(q, k, v, ig, fg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def test_mlstm_generic_matches_kernel():
    b, h, s, dk, dv = 1, 1, 32, 16, 16
    args = [_rand((b, h, s, dk), jnp.float32, i) for i in range(2)] + \
           [_rand((b, h, s, dv), jnp.float32, 2)] + \
           [_rand((b, h, s), jnp.float32, 3), _rand((b, h, s), jnp.float32, 4)]
    with ctx.target("generic"):
        a = mlstm_scan(*args, chunk=16)
    bres = mlstm_scan(*args, chunk=16)
    np.testing.assert_allclose(np.asarray(a), np.asarray(bres),
                               atol=2e-5, rtol=2e-5)
