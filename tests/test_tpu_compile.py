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

import dataclasses
import os
import pathlib
import re
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


def _rmsnorm_tuned(rows, d):
    from repro.kernels.rmsnorm.ops import rmsnorm
    return (lambda x, w: rmsnorm(x, w), [((rows, d), bf), ((d,), bf)],
            "portable_rmsnorm")


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
    # prefills that ran out of scoped VMEM at the v5e tuning's fixed
    # 512-row block (d_model 4096 past 3584 rows, InternLM2's d_model
    # 6144), through the op at the v5e tuning row; 4001 rows pad to 4008
    "rmsnorm_4032x4096": lambda: _rmsnorm_tuned(4032, 4096),
    "rmsnorm_8192x4096": lambda: _rmsnorm_tuned(8192, 4096),
    "rmsnorm_4096x6144": lambda: _rmsnorm_tuned(4096, 6144),
    "rmsnorm_7168x6144": lambda: _rmsnorm_tuned(7168, 6144),
    "rmsnorm_4001x6144": lambda: _rmsnorm_tuned(4001, 6144),
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


# ------------------------------------------------- the engine's programs ----

ENGINE_LAYERS, ENGINE_PAGES = 2, 520   # 136 MB a pool: more than VMEM


@pytest.fixture(scope="module")
def granite_engine():
    """A paged ``Engine`` at granite-8b widths with 2 layers and 8 slots,
    weights as shapes only; its pools are small, the lowered ones are
    ``ENGINE_PAGES`` pages of 64."""
    from repro.configs import get_config
    from repro.models.registry import build_model
    from repro.serve import Engine, ServeConfig
    cfg = dataclasses.replace(get_config("granite-8b"),
                              num_layers=ENGINE_LAYERS)
    model = build_model(cfg)
    params = jax.eval_shape(model._init_in_dtype, jax.random.PRNGKey(0))
    return Engine(model, params, ServeConfig(
        slots=B, cache_len=1024, total_pages=65, page_size=PS,
        max_new_tokens=16, paged=True))


def _aliased_params(text):
    """Entry parameter numbers the executable aliases to an output."""
    head = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
    return {int(p) for p in re.findall(r"\}: \((\d+), \{",
                                       head.group(1) if head else "")}


def _pool_results(text, shapes):
    """(opcode, root opcode of the computation a fusion calls) of every
    instruction outside fused computations whose result is an array of
    one of ``shapes``: what writes or moves a pool when it runs."""
    fused = set(re.findall(r" fusion\(.*?calls=%([\w.\-]+)", text))
    comp, root, found = None, {}, []
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(", line)
        if head:
            comp = head.group(1)
            continue
        ins = re.match(r"\s+(ROOT )?%[\w.\-]+ = (\w+\[[\d,]*\])\S* "
                       r"([\w\-]+)\(", line)
        if not ins:
            continue
        if ins.group(1):
            root[comp] = ins.group(3)
        if comp not in fused and ins.group(2) in shapes:
            calls = re.search(r"calls=%([\w.\-]+)", line)
            found.append((ins.group(3), calls and calls.group(1)))
    return [(op, root.get(c)) for op, c in found]


@pytest.mark.parametrize("program", ["step", "admit"])
def test_engine_updates_the_pool_in_place(program, granite_engine,
                                          one_chip, no_compile_cache):
    """The engine's own jitted decode step and admission, compiled for
    a v5e: each aliases its cache parameters to its cache outputs, and
    no op but the pool update (a fusion rooted in a scatter) makes an
    array of the stacked or one layer's pool shape, so nothing slices,
    copies or writes back a pool.  The step still runs the paged kernel
    through Mosaic."""
    from repro.serve import paging
    eng = granite_engine
    sds = lambda tree: jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=one_chip), tree)
    caches = sds(jax.eval_shape(lambda: paging.init_paged_caches(
        eng.model, B, eng.sc.cache_len, PS, ENGINE_PAGES)))
    vec = lambda dt, *s: jax.ShapeDtypeStruct((B,) + s, dt,
                                              sharding=one_chip)
    hist = vec(i32, eng.sc.cache_len + 1)
    params = sds(eng.params)
    with target("tpu", isa="v5e"):
        if program == "step":
            first = len(jax.tree.leaves(params))
            lowered = eng._step_fn.lower(
                params, caches, vec(i32), vec(i32), vec(jnp.bool_),
                vec(i32), jax.ShapeDtypeStruct((2,), jnp.uint32,
                                               sharding=one_chip),
                jax.ShapeDtypeStruct((), i32, sharding=one_chip), vec(i32),
                vec(i32, eng.pages_per_slot), vec(jnp.bool_))
        else:
            first, k, plen = 0, 2, 128
            cache1 = sds(jax.eval_shape(
                lambda p: eng.model.prefill(
                    p, jnp.zeros((k, plen), i32), eng.sc.cache_len, {})[1],
                eng.params))
            kv = lambda dt, *s: jax.ShapeDtypeStruct((k,) + s, dt,
                                                     sharding=one_chip)
            lowered = eng._admit_fn.lower(
                caches, vec(i32), vec(i32), vec(jnp.bool_), vec(i32), hist,
                vec(i32), cache1, kv(i32), kv(i32), kv(i32), kv(jnp.bool_),
                kv(i32), kv(i32), kv(i32, eng.pages_per_slot),
                kv(i32, eng.sc.cache_len + 1), None)
        text = lowered.compile().as_text()
    pools = jax.tree.leaves(caches)
    assert _aliased_params(text) >= set(range(first, first + len(pools)))
    pool = ",".join(map(str, pools[0].shape))
    shapes = {f"bf16[{pool}]", f"bf16[{pool.split(',', 1)[1]}]"}
    ops = _pool_results(text, shapes)
    assert ops, "no pool-shaped op found: the parse missed the pools"
    for op, fused_root in ops:
        assert (op in ("parameter", "get-tuple-element", "bitcast")
                or (op == "fusion" and fused_root == "scatter")), \
            (op, fused_root)
    assert sum(op == "fusion" for op, _ in ops) == len(pools)
    if program == "step":
        assert "portable_paged_decode_attention" in compiled_kernels(text)


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
