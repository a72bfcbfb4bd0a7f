"""Multi-device correctness worker (run by test_distributed.py with
XLA_FLAGS=--xla_force_host_platform_device_count=8).

Each case compares a mesh execution (shard_map wrappers engaged) against
the single-device reference and prints 'OK <case>' or raises.
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import dataclasses  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import shard_map  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import ShapeConfig  # noqa: E402
from repro.configs.smoke import smoke_config  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.models import moe as MOE  # noqa: E402
from repro.launch.mesh import make_test_mesh as _mesh  # noqa: E402
from repro.sharding import mesh_ctx  # noqa: E402


def _batch(cfg, b=4, s=32, seed=0):
    key = jax.random.PRNGKey(seed)
    return {
        "tokens": jax.random.randint(key, (b, s), 0, cfg.vocab_size,
                                     jnp.int32),
        "labels": jax.random.randint(key, (b, s), 0, cfg.vocab_size,
                                     jnp.int32),
    }


def case_forward_parity():
    """gemma2 smoke (local+global, softcap): mesh == single device."""
    cfg = smoke_config("gemma2-2b", num_layers=4)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    loss_ref, _ = jax.jit(lambda p, b: T.forward_train(p, b, cfg))(
        params, batch)
    mesh = _mesh((2, 2, 2), ("pod", "data", "model"))
    with mesh_ctx.mesh_context(mesh):
        loss_mesh, _ = jax.jit(lambda p, b: T.forward_train(p, b, cfg))(
            params, batch)
    np.testing.assert_allclose(float(loss_ref), float(loss_mesh),
                               rtol=2e-3, atol=2e-3)
    print("OK forward_parity")


def case_grad_parity_sp():
    """TP=4 forces the q-sequence-parallel flash path (kv=2 < 4);
    grads through the dynamic-offset kernel must match single-device."""
    cfg = smoke_config("granite-8b", num_layers=2)
    params = T.init_params(jax.random.PRNGKey(1), cfg)
    batch = _batch(cfg, seed=1)

    def loss_fn(p):
        return T.forward_train(p, batch, cfg)[0]

    g_ref = jax.jit(jax.grad(loss_fn))(params)
    mesh = _mesh((2, 4), ("data", "model"))
    with mesh_ctx.mesh_context(mesh):
        g_mesh = jax.jit(jax.grad(loss_fn))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g_ref),
                    jax.tree_util.tree_leaves(g_mesh)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=5e-3, atol=5e-3)
    print("OK grad_parity_sp")


def case_moe_a2a_parity():
    """EP all_to_all dispatch == local dispatch (no drops)."""
    cfg = smoke_config("jamba-1.5-large-398b")
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    p = MOE.init_moe(jax.random.PRNGKey(2), cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 32, cfg.d_model),
                          jnp.float32)
    y_ref, aux_ref = jax.jit(lambda p_, x_: MOE.apply_moe(p_, x_, cfg))(p, x)
    mesh = _mesh((2, 2, 2), ("pod", "data", "model"))
    with mesh_ctx.mesh_context(mesh):
        y_mesh, aux_mesh = jax.jit(
            lambda p_, x_: MOE.apply_moe(p_, x_, cfg))(p, x)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_mesh),
                               rtol=3e-3, atol=3e-3)
    # aux load-balance is a pmean of per-shard estimators over 32-token
    # subsets vs one 128-token global estimate: same expectation, a few
    # percent of sampling spread
    np.testing.assert_allclose(float(aux_ref["load_balance"]),
                               float(aux_mesh["load_balance"]),
                               rtol=6e-2)
    # grads through a2a + gmm + psum
    gr = jax.jit(jax.grad(
        lambda p_: jnp.sum(MOE.apply_moe(p_, x, cfg)[0] ** 2)))
    g_ref = gr(p)
    with mesh_ctx.mesh_context(mesh):
        g_mesh = gr(p)
    np.testing.assert_allclose(np.asarray(g_ref["we_down"], np.float32),
                               np.asarray(g_mesh["we_down"], np.float32),
                               rtol=5e-3, atol=5e-3)
    print("OK moe_a2a_parity")


def case_moe_small_batch_psum():
    """B=1 (long_500k style): replicated-token psum path == local."""
    cfg = smoke_config("jamba-1.5-large-398b")
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    p = MOE.init_moe(jax.random.PRNGKey(4), cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 8, cfg.d_model),
                          jnp.float32)
    y_ref, _ = jax.jit(lambda p_, x_: MOE.apply_moe(p_, x_, cfg))(p, x)
    mesh = _mesh((4, 2), ("data", "model"))
    with mesh_ctx.mesh_context(mesh):
        y_mesh, _ = jax.jit(lambda p_, x_: MOE.apply_moe(p_, x_, cfg))(p, x)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_mesh),
                               rtol=3e-3, atol=3e-3)
    print("OK moe_small_batch_psum")


def case_sp_decode_parity():
    """Sequence-sharded KV decode (LSE combine) == direct op."""
    from repro.kernels.decode_attention.ops import decode_attention
    from repro.sharding.kernel_sharding import sharded_decode_attention
    key = jax.random.PRNGKey(6)
    b, hq, hkv, s, d = 4, 4, 2, 64, 16
    q = jax.random.normal(key, (b, hq, d), jnp.float32)
    ck = jax.random.normal(jax.random.fold_in(key, 1), (b, hkv, s, d))
    cv = jax.random.normal(jax.random.fold_in(key, 2), (b, hkv, s, d))
    lengths = jnp.array([5, 33, 64, 17], jnp.int32)
    ref = decode_attention(q, ck, cv, lengths)
    mesh = _mesh((2, 4), ("data", "model"))   # hkv=2 < tp=4 -> SP path
    with mesh_ctx.mesh_context(mesh):
        got = jax.jit(lambda *a: sharded_decode_attention(*a))(
            q, ck, cv, lengths)
    np.testing.assert_allclose(np.asarray(ref, np.float32),
                               np.asarray(got, np.float32),
                               rtol=2e-3, atol=2e-3)
    print("OK sp_decode_parity")


def case_compressed_psum():
    """int8 error-feedback all-reduce: close to exact, unbiased over
    steps (the error-feedback residual keeps the running sum faithful)."""
    from repro.optim import compressed_psum
    from jax.sharding import PartitionSpec as P
    mesh = _mesh((8,), ("data",))
    g_global = jax.random.normal(jax.random.PRNGKey(7), (8, 256))
    exact = g_global.mean(0)

    def body(g, ef):
        mean, ef = compressed_psum({"g": g}, {"g": ef}, "data")
        return mean["g"], ef["g"]

    f = jax.jit(shard_map(body, mesh=mesh,
                          in_specs=(P("data", None), P("data", None)),
                          out_specs=(P(None, None), P("data", None)),
                          check_vma=False))
    ef = jnp.zeros((8, 256))
    got, ef = f(g_global, ef)
    rel = float(jnp.linalg.norm(got - exact) / jnp.linalg.norm(exact))
    assert rel < 0.02, rel
    # error feedback: repeating the same gradient, the SUM of quantized
    # means over 2 steps is closer to 2*exact than 2x one-step error
    got2, ef = f(g_global, ef)
    two_step = np.asarray(got) + np.asarray(got2)
    rel2 = float(np.linalg.norm(two_step - 2 * np.asarray(exact))
                 / np.linalg.norm(2 * np.asarray(exact)))
    assert rel2 < rel * 1.5, (rel, rel2)
    print("OK compressed_psum")


CASES = {
    "forward_parity": case_forward_parity,
    "grad_parity_sp": case_grad_parity_sp,
    "moe_a2a_parity": case_moe_a2a_parity,
    "moe_small_batch_psum": case_moe_small_batch_psum,
    "sp_decode_parity": case_sp_decode_parity,
    "compressed_psum": case_compressed_psum,
}

if __name__ == "__main__":
    names = sys.argv[1:] or list(CASES)
    for name in names:
        CASES[name]()
    print("ALL_OK")
