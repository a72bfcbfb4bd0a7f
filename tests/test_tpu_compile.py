"""Ahead-of-time compiles of the main-path kernels for a TPU v5e.

The chip's compiler (Mosaic) is installed even where no chip is attached:
it compiles for a described ``v5e:2x2`` topology and refuses what the
chip would refuse — block shapes off the (8, 128) tiling, rank-1 SMEM
blocks, too much VMEM — which the CPU interpreter accepts.  Each case
lowers one kernel at granite-8b widths (32 query / 8 KV heads of 128)
and asserts the kernel reached Mosaic as a ``tpu_custom_call``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
from __future__ import annotations

import os
import pathlib
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.core.context import target
from repro.core.runtime import compiled_kernels

ROOT = pathlib.Path(__file__).resolve().parents[1]

B, HQ, HKV, D, PS = 8, 32, 8, 128, 64    # granite-8b decode at 8 slots
PAGES, TABLE = 257, 16                  # a 1024-token cache per slot
bf, f32, i32, i8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache without one, so keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _paged(q_shape, kv_dtype, *, kind="paged", quantized=False):
    from repro.kernels.decode_attention.paged import (
        paged_decode_attention_fwd, window_paged_decode_attention_fwd)
    from repro.kernels.decode_attention.spec import (
        spec_paged_decode_attention_fwd)
    table = 5 if kind == "window" else TABLE
    shapes = [(q_shape, bf), ((HKV, PAGES, PS, D), kv_dtype),
              ((HKV, PAGES, PS, D), kv_dtype)]
    if quantized:
        shapes += [((HKV, PAGES), f32)] * 2
    shapes += [((B, table), i32), ((B,), i32)]

    def fn(q, k, v, *rest):
        scales = {}
        if quantized:
            ks, vs, *rest = rest
            scales = dict(k_scales=ks, v_scales=vs)
        bt, lens = rest
        if kind == "window":
            return window_paged_decode_attention_fwd(
                q, k, v, bt, lens, window=256, block_kv=PS, **scales)
        f = (spec_paged_decode_attention_fwd if kind == "spec"
             else paged_decode_attention_fwd)
        return f(q, k, v, bt, lens, block_kv=PS, **scales)
    prefix = "" if kind == "paged" else f"{kind}_"
    name = f"portable_{'quant_' if quantized else ''}{prefix}paged_decode_attention"
    return fn, shapes, name


def _paged_cell(b, table, pages, hq=HQ, hkv=HKV, d=D, dv=D):
    """The paged kernel at a benchmark cell's slots, table width and
    pool, with no VMEM limit given: it must fit the default scoped
    VMEM."""
    from repro.kernels.decode_attention.paged import (
        paged_decode_attention_fwd)
    return (lambda q, k, v, bt, lens: paged_decode_attention_fwd(
                q, k, v, bt, lens, block_kv=PS),
            [((b, hq, d), bf), ((hkv, pages, PS, d), bf),
             ((hkv, pages, PS, dv), bf), ((b, table), i32), ((b,), i32)],
            "portable_paged_decode_attention")


def _dense_decode():
    from repro.kernels.decode_attention.decode_attention import (
        decode_attention_fwd)
    return (lambda q, k, v, lens: decode_attention_fwd(q, k, v, lens,
                                                       block_kv=512),
            [((B, HQ, D), bf), ((B, HKV, 1024, D), bf),
             ((B, HKV, 1024, D), bf), ((B,), i32)],
            "portable_decode_attention")


def _flash_prefill():
    from repro.kernels.flash_attention.flash_attention import (
        flash_attention_fwd)
    return (lambda q, k, v: flash_attention_fwd(q, k, v, block_q=512,
                                                block_kv=512),
            [((4, HQ, 512, D), bf), ((4, HKV, 512, D), bf),
             ((4, HKV, 512, D), bf)],
            "portable_flash_attention")


def _rmsnorm():
    from repro.kernels.rmsnorm.rmsnorm import rmsnorm_fwd
    return (lambda x, w: rmsnorm_fwd(x, w, block_rows=256),
            [((2048, 4096), bf), ((4096,), bf)], "portable_rmsnorm")


def _gmm():
    from repro.kernels.gmm.gmm import gmm_fwd
    return (lambda a, b, g: gmm_fwd(a, b, g, block_c=128, block_n=512,
                                    block_k=512),
            [((8, 128, 4096), bf), ((8, 4096, 1024), bf), ((8,), i32)],
            "portable_gmm")


CASES = {
    "paged_bf16": lambda: _paged((B, HQ, D), bf),
    "paged_int8": lambda: _paged((B, HQ, D), i8, quantized=True),
    "spec_paged": lambda: _paged((B, 5, HQ, D), bf, kind="spec"),
    "spec_paged_int8": lambda: _paged((B, 5, HQ, D), i8, kind="spec",
                                      quantized=True),
    "window_paged": lambda: _paged((B, HQ, D), bf, kind="window"),
    "window_paged_int8": lambda: _paged((B, HQ, D), i8, kind="window",
                                        quantized=True),
    "paged_chat_cell": lambda: _paged_cell(32, 40, 700),
    "paged_longctx_cell": lambda: _paged_cell(12, 64, 760),
    "paged_wide_heads": lambda: _paged_cell(B, TABLE, PAGES, hq=16, hkv=16,
                                            d=192, dv=128),
    "dense_decode": _dense_decode,
    "flash_prefill": _flash_prefill,
    "rmsnorm": _rmsnorm,
    "gmm": _gmm,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, no_compile_cache):
    fn, shapes, name = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    with target("tpu", isa="v5e"):
        text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert name in compiled_kernels(text)


def test_quant_scales_compile_at_thousands_of_pages(one_chip,
                                                    no_compile_cache):
    """The scale pools ride VMEM tiles, not SMEM, so a large pool (here
    4097 pages per head) still compiles."""
    from repro.kernels.decode_attention.paged import (
        paged_decode_attention_fwd)
    pages = 4097
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in [
        ((B, HQ, D), bf), ((HKV, pages, PS, D), i8),
        ((HKV, pages, PS, D), i8), ((HKV, pages), f32),
        ((HKV, pages), f32), ((B, TABLE), i32), ((B,), i32)]]

    def fn(q, k, v, ks, vs, bt, lens):
        return paged_decode_attention_fwd(q, k, v, bt, lens, block_kv=PS,
                                          k_scales=ks, v_scales=vs)
    with target("tpu", isa="v5e"):
        text = jax.jit(fn).lower(*args).compile().as_text()
    assert "portable_quant_paged_decode_attention" in compiled_kernels(text)


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_refuses_to_run_without_a_chip(where, tmp_path):
    """Off the chip the smoke exits non-zero and never prints a result,
    whether run from the checkout or copied out of it alone."""
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        script = pathlib.Path(shutil.copy(script, tmp_path))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, str(script)], capture_output=True,
                       text=True, env=env, cwd=script.parent, timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "no TPU" in r.stderr
