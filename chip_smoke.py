#!/usr/bin/env python3
"""Bring-up smoke on one TPU chip: granite-8b served through the engine.

Drives the normal serving path once, at the published widths of
granite-8b (d_model 4096, 32 query / 8 KV heads of 128, d_ff 14336,
vocab 49152) with depth cut to fit one v5e: ``build_model`` -> jitted
bf16 ``init_serving`` -> paged ``Engine`` -> ``submit``/``step`` until
every request is done.  Weights and prompts come from ``--seed``.

Checks, each fatal:

* the device is a TPU and the target arch is ``tpu`` (no interpret or
  CPU fallback: off the chip this exits non-zero before any result);
* all 8 requests finish with their full token count, and the engine's
  NaN/Inf sentinel flags no step;
* the compiled decode step holds the paged-decode kernel, and the
  compiled prefill the flash-attention kernel, as ``tpu_custom_call``s;
* prefill logits and one decode step's logits agree with the plain-XLA
  reference path (``target("generic")``) on the same chip and params.

Times and bytes printed here are bring-up facts of one run, not
benchmark numbers.  The last stdout line is the JSON result.

Run from the checkout root:  python chip_smoke.py
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# Depth cut, read off the AOT compile for a described v5e (bf16 weights):
# at 16 layers the weights are 3.89 B params = 7.25 GiB, the paged KV pool
# 0.50 GiB, and the decode step's program takes 7.75 GiB of arguments,
# returns a 0.50 GiB pool copy and needs ~1 MiB of temporaries: ~8.3 GiB
# of the chip's 16 GiB.  The rest holds admission prefill caches (0.25
# GiB per group) and the reference pass.  36 layers need 15.4 GiB of
# weights alone.
LAYERS = 16
SLOTS, CACHE_LEN, MAX_NEW = 8, 1024, 32
PROMPT_LENS = (128, 512)          # two admission groups of 4
# Reference tolerance: max|kernel - reference| over the logits, divided
# by max|reference|.  Both paths keep activations in bf16 (8-bit
# mantissa, rounding error 2^-9 relative) and re-round the residual
# stream after each attention and MLP block; 16 layers make 32 such
# roundings, which bound the drift near 32 * 2^-9 = 6% if every one
# pushed the same way and near sqrt(32) * 2^-9 = 1% as a random walk.
# 5% admits that; a wrong mask, page or scale moves logits by O(1).
REL_TOL = 5e-2


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def compare(tag, got, want):
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape:
        fail(f"{tag}: shape {got.shape} vs reference {want.shape}")
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        fail(f"{tag}: non-finite logits")
    max_abs = float(np.max(np.abs(got - want)))
    rel = max_abs / float(np.max(np.abs(want)))
    print(f"reference {tag}: shape {got.shape}, max|diff| {max_abs!r}, "
          f"max|diff|/max|ref| {rel!r} (tolerance {REL_TOL})", flush=True)
    if not rel <= REL_TOL:
        fail(f"{tag}: kernel path differs from the reference by {rel!r}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: jax.devices()[0].platform is {dev.platform!r}")

    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core import tuning
    from repro.core.context import current_context, target
    from repro.core.runtime import compiled_kernels
    from repro.launch.compile_cache import place_compile_cache
    from repro.models.registry import build_model
    from repro.serve import Engine, Request, ServeConfig

    arch = current_context().arch
    if arch != "tpu":
        fail(f"target arch is {arch!r}, not 'tpu'")
    cache_dir = place_compile_cache()
    tuning.load_caches()
    print(f"device: {dev.device_kind} x{len(devices)} ({dev.platform}), "
          f"target arch {arch}, compile cache {cache_dir}", flush=True)

    full = get_config("granite-8b")
    cfg = dataclasses.replace(full, num_layers=LAYERS)
    print(f"model: {cfg.name} at published widths (d_model {cfg.d_model}, "
          f"heads {cfg.num_heads}/{cfg.num_kv_heads} x {cfg.head_dim}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, {cfg.dtype}); "
          f"depth cut {full.num_layers} -> {cfg.num_layers} layers",
          flush=True)
    model = build_model(cfg)

    t0 = time.perf_counter()
    params = jax.block_until_ready(
        model.init_serving(jax.random.PRNGKey(args.seed)))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    n_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    print(f"bring-up: weights {n_params} params, {n_bytes} bytes, "
          f"jitted init (compile + run) {time.perf_counter() - t0:.2f} s",
          flush=True)

    sc = ServeConfig(slots=SLOTS, cache_len=CACHE_LEN,
                     max_new_tokens=MAX_NEW, paged=True, seed=args.seed)
    eng = Engine(model, params, sc)
    rng = np.random.default_rng(args.seed)
    reqs = [Request(rid=i, tokens=rng.integers(
        0, cfg.vocab_size, size=PROMPT_LENS[i % 2]).tolist())
        for i in range(SLOTS)]
    for r in reqs:
        eng.submit(r)
    print(f"serving: paged engine, {SLOTS} slots, cache_len {CACHE_LEN}, "
          f"page_size {eng.page_size}, {eng.allocator.total_pages} pages; "
          f"{len(reqs)} requests, prompts {PROMPT_LENS}, max_new {MAX_NEW}",
          flush=True)

    # admission: one batched prefill per prompt length, then the scatter
    # into pages; the decode state it leaves is what the reference replays
    t0 = time.perf_counter()
    eng._admit()
    jax.block_until_ready(eng.caches)
    admit_s = time.perf_counter() - t0
    print(f"bring-up: admission of {len(reqs)} requests in 2 groups "
          f"(compile + run) {admit_s:.2f} s", flush=True)
    state = (eng.params, eng.caches, eng.cur_tok, eng.lengths,
             jnp.asarray(eng.block_tables))

    # each step ends in the engine's one device_get, so host time is the
    # step's; the first also compiles it
    t0 = time.perf_counter()
    busy = eng.step()
    first_s = time.perf_counter() - t0
    steps = int(busy)
    t0 = time.perf_counter()
    while busy or eng.queue or eng.requeue:
        busy = eng.step()
        steps += int(busy)
    rest_s = time.perf_counter() - t0
    tokens = sum(len(r.out) for r in reqs)
    st = eng.stats()
    print(f"bring-up: drain {steps} decode steps, {tokens} tokens generated "
          f"(incl. {len(reqs)} prefill samples); first step (compile + run) "
          f"{first_s:.2f} s, the other {steps - 1} steps {rest_s:.2f} s wall",
          flush=True)
    for r in reqs:
        print(f"  request {r.rid}: prompt {len(r.tokens)}, status "
              f"{r.status}, {len(r.out)} tokens", flush=True)
    bad = [r.rid for r in reqs if r.status != "done" or len(r.out) != MAX_NEW]
    if bad:
        fail(f"requests {bad} did not finish with {MAX_NEW} tokens")
    if st["recoveries_total"] or st["failed_requests"]:
        fail(f"the NaN/Inf sentinel or a fault fired: {st['recoveries']}")

    # the kernels really ran compiled: Mosaic custom calls in the HLO of
    # the engine's own jitted step and prefill
    step_args = (eng.params, eng.caches, eng.cur_tok, eng.lengths,
                 eng.active_mask, eng.n_out, eng._key, jnp.int32(-1),
                 eng.max_new_dev, eng._bt_dev,
                 jnp.zeros((SLOTS,), jnp.bool_))
    step_kernels = compiled_kernels(
        eng._step_fn.lower(*step_args).compile().as_text())
    group = [r for r in reqs if len(r.tokens) == PROMPT_LENS[0]]
    toks = jnp.asarray([r.tokens for r in group], jnp.int32)
    prefill_kernels = compiled_kernels(
        eng._prefill.lower(eng.params, toks).compile().as_text())
    print(f"kernels: decode step {sorted(step_kernels)}", flush=True)
    print(f"kernels: prefill {sorted(prefill_kernels)}", flush=True)
    if "portable_paged_decode_attention" not in step_kernels:
        fail("no paged-decode tpu_custom_call in the compiled decode step")
    if "portable_flash_attention" not in prefill_kernels:
        fail("no flash-attention tpu_custom_call in the compiled prefill")

    # reference: the same params and inputs through plain XLA on the chip
    print(f"reference tolerance: max|diff|/max|ref| <= {REL_TOL} (bf16 "
          f"activations re-rounded after each of {2 * LAYERS} blocks at "
          f"2^-9 relative: ~{2 * LAYERS * 2 ** -9:.3f} if every rounding "
          f"aligned, ~{(2 * LAYERS) ** 0.5 * 2 ** -9:.3f} as a random "
          f"walk)", flush=True)
    def decode_logits():
        # a new function per target: jit keeps one trace per function
        # object, and the target is not part of that key
        return jax.jit(lambda p, c, tok, lens, bt: model.decode_step(
            p, c, tok, lens, block_tables=bt)[0])

    got = eng._prefill(eng.params, toks)[0]
    with target("generic"):
        want = jax.jit(lambda p, t: model.prefill(p, t, CACHE_LEN, {})[0])(
            eng.params, toks)
    compare(f"prefill ({len(group)} x {PROMPT_LENS[0]})", got, want)
    got = decode_logits()(*state)
    with target("generic"):
        want = decode_logits()(*state)
    compare(f"decode step ({SLOTS} slots)", got, want)

    stats = dev.memory_stats() or {}
    print(f"bring-up: peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
          f"of bytes_limit {stats.get('bytes_limit')}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
