import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede any jax import: jax locks the device count on first init,
# and this CPU-only tool must never take a chip from another process.

"""Multi-pod dry-run: .lower().compile() every (arch x shape x mesh) cell.

For each cell this program
  1. builds the production mesh ((16,16) 'data','model' single-pod or
     (2,16,16) 'pod','data','model' multi-pod = 512 chips),
  2. constructs abstract params / optimizer state / inputs
     (ShapeDtypeStruct — nothing is allocated),
  3. lowers + compiles the real step function — train_step for train
     shapes, prefill/decode serve steps for inference shapes — with the
     framework's actual shardings,
  4. records memory_analysis() (proof-of-fit), cost_analysis()
     (per-device FLOPs/bytes), and a collective-bytes breakdown parsed
     from the optimized HLO (per computation, with while-body
     attribution so the roofline can scale scan bodies by trip count),
  into experiments/dryrun/<arch>__<shape>__<mesh>.json.

Run one cell:   python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k --mesh single
Run everything: python -m repro.launch.dryrun --all   (subprocess per cell)
"""
import argparse
import json
import re
import subprocess
import sys
import time
import traceback

import jax
import jax.numpy as jnp

DEFAULT_OUT = os.path.join(os.path.dirname(__file__),
                           "../../../experiments/dryrun")

# grad-accumulation microbatches per arch at train_4k (global batch 256):
# sized so activation/dispatch transients fit v5e HBM (see EXPERIMENTS.md
# §Perf for the memory-term iteration that produced these).
TRAIN_MICROBATCHES = {
    "deepseek-v2-lite-16b": 8,
    "arctic-480b": 8,
    "jamba-1.5-large-398b": 8,
    "gemma3-27b": 4,
    "internvl2-26b": 4,
    "granite-8b": 4,
    "gemma2-2b": 2,
    "gemma3-4b": 2,
    "whisper-base": 2,
    "xlstm-1.3b": 2,
}

# remat policy per arch at train_4k (§Perf-C.1): "dots" saves matmul
# outputs (6ND flops instead of 8ND) where the memory headroom allows.
TRAIN_REMAT = {
    "deepseek-v2-lite-16b": "dots",
}

# MoE capacity factor at train_4k (§Perf-C.2): 1.0 removes the 25%
# capacity-padding flops; the ~2-3% of over-quota tokens drop to the
# residual path (shared experts keep every token covered on deepseek).
TRAIN_CAPACITY = {
    "deepseek-v2-lite-16b": 1.0,
}

_DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "c64": 8, "f32": 4, "s32": 4,
                "u32": 4, "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
                "s8": 1, "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"=\s*(\(?[^=]*?)\s*"
    r"(all-reduce-start|all-gather-start|all-reduce|all-gather|"
    r"reduce-scatter|all-to-all|collective-permute-start|collective-permute)"
    r"\(")
_SHAPE_RE = re.compile(r"(f64|s64|u64|c64|f32|s32|u32|bf16|f16|s16|u16|s8|u8|"
                       r"pred|f8e4m3fn|f8e5m2)\[([0-9,]*)\]")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*(?:\([^)]*\))?\s*->.*{")
_WHILE_BODY_RE = re.compile(r"while\(.*body=%?([\w\.\-]+)")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str):
    """Per-computation collective result-bytes + while-nesting depths.

    Each while body records its parent computation, so the roofline can
    scale a body's bytes by the static trip counts along its ancestry
    (microbatch scan -> segment scan -> ...)."""
    comp = "<module>"
    per_comp = {}
    body_parent = {}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if line and not line.startswith(" "):
            m = _COMP_RE.match(stripped)
            if m:
                comp = m.group(1)
                continue
        wb = _WHILE_BODY_RE.search(stripped)
        if wb:
            body_parent[wb.group(1)] = comp
        m = _COLL_RE.search(stripped)
        if m:
            kind = m.group(2).replace("-start", "")
            nbytes = _shape_bytes(m.group(1))
            d = per_comp.setdefault(comp, {})
            d[kind] = d.get(kind, 0) + nbytes

    def depth(c, seen=()):
        if c not in body_parent or c in seen:
            return 0
        return 1 + depth(body_parent[c], seen + (c,))

    while_bodies = sorted(body_parent)
    return {
        "per_computation": per_comp,
        "while_bodies": while_bodies,
        "body_depth": {c: depth(c) for c in while_bodies},
        "top_level_bytes": {
            k: v for c, kv in per_comp.items() if c not in body_parent
            for k, v in kv.items()},
    }


def _dp_axes(mesh, b: int):
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    while axes:
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        if b % n == 0:
            return axes
        axes = axes[1:]
    return None


def build_cell(arch: str, shape_name: str, multi_pod: bool):
    """Returns (fn, args_abstract, in_shardings, meta)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import SHAPES, get_config, input_specs
    from repro.launch.mesh import make_production_mesh
    from repro.models import transformer as T
    from repro.models.registry import build_model
    from repro.optim import AdamWConfig, adamw_init
    from repro.sharding.partition import param_specs, zero1_spec
    from repro.train.trainer import make_train_step

    import dataclasses
    cfg = get_config(arch)
    if arch in TRAIN_REMAT and shape_name == "train_4k":
        cfg = dataclasses.replace(cfg, remat_policy=TRAIN_REMAT[arch])
    if arch in TRAIN_CAPACITY and shape_name == "train_4k":
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=TRAIN_CAPACITY[arch]))
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = build_model(cfg)
    ns = lambda spec: NamedSharding(mesh, spec)          # noqa: E731

    import math
    params_abs = model.init_abstract()
    p_specs = param_specs(params_abs, mesh)
    n_params = sum(math.prod(l.shape) if l.shape else 1
                   for l in jax.tree_util.tree_leaves(params_abs))
    fsdp = n_params > 100e9
    if fsdp:
        # FSDP/ZeRO-3: also shard every weight over 'data' on a free dim;
        # GSPMD inserts the per-layer all-gather at use (collective cost
        # recorded by the roofline; memory cost drops ~dp-fold)
        p_specs = jax.tree_util.tree_map(
            lambda spec, leaf: zero1_spec(spec, leaf.shape, mesh),
            p_specs, params_abs)
    p_shard = jax.tree_util.tree_map(ns, p_specs)
    dp = _dp_axes(mesh, shape.global_batch)
    meta = {"arch": arch, "shape": shape_name,
            "mesh": "multi" if multi_pod else "single",
            "n_params": n_params, "fsdp": fsdp,
            "seq_len": shape.seq_len, "global_batch": shape.global_batch,
            "kind": shape.kind}

    if shape.kind == "train":
        # >100B models train with int8 Adam moments (DESIGN.md §5)
        quant = n_params > 100e9
        micro = TRAIN_MICROBATCHES.get(arch, 1)
        opt_cfg = AdamWConfig(quantize_moments=quant)
        opt_abs = jax.eval_shape(lambda p: adamw_init(p, opt_cfg), params_abs)
        if quant:
            # row-quantized moments shard exactly like their parameter
            # ('q' = param spec; 's' = param spec with the last dim
            # replicated), plus ZeRO-1 'data' on free dims
            flat_p, treedef = jax.tree_util.tree_flatten(params_abs)
            flat_s = jax.tree_util.tree_leaves(p_specs)

            def qleaf(p, spec):
                spec = zero1_spec(spec, p.shape, mesh)
                full = list(spec) + [None] * (p.ndim - len(spec))
                return {"q": ns(P(*full)),
                        "s": ns(P(*(full[:-1] + [None])))}

            m_shard = jax.tree_util.tree_unflatten(
                treedef, [qleaf(p, s) for p, s in zip(flat_p, flat_s)])
            v_shard = jax.tree_util.tree_unflatten(
                treedef, [ns(zero1_spec(s, p.shape, mesh))
                          for p, s in zip(flat_p, flat_s)])
            o_shard = {"step": ns(P()), "m": m_shard, "v": v_shard}
        else:
            flat_p, treedef = jax.tree_util.tree_flatten(params_abs)
            flat_s = jax.tree_util.tree_leaves(p_specs)
            moment = jax.tree_util.tree_unflatten(
                treedef, [ns(zero1_spec(s, p.shape, mesh))
                          for p, s in zip(flat_p, flat_s)])
            o_shard = {"step": ns(P()), "m": moment, "v": moment}
        batch_abs = input_specs(cfg, shape)
        b_shard = {}
        for k, v in batch_abs.items():
            b_shard[k] = ns(P(dp, *([None] * (len(v.shape) - 1))))
        step = make_train_step(model, opt_cfg, lambda s: 1e-4,
                               microbatches=micro)
        meta["quantized_moments"] = quant
        meta["microbatches"] = micro
        meta["remat_policy"] = cfg.remat_policy
        if cfg.moe is not None:
            meta["capacity_factor"] = cfg.moe.capacity_factor
        return (step, (params_abs, opt_abs, batch_abs),
                (p_shard, o_shard, b_shard), mesh, meta)

    if shape.kind == "prefill":
        batch_abs = input_specs(cfg, shape)
        tokens = batch_abs.pop("tokens")
        extras = batch_abs

        def fn(params, toks, ex):
            logits, caches = model.prefill(params, toks, shape.seq_len, ex)
            return logits, caches

        t_shard = ns(P(dp, None))
        e_shard = {k: ns(P(dp, *([None] * (len(v.shape) - 1))))
                   for k, v in extras.items()}
        return (fn, (params_abs, tokens, extras),
                (p_shard, t_shard, e_shard), mesh, meta)

    # decode
    enc_len = 1500 if cfg.is_encoder_decoder else 0
    caches_abs = model.abstract_decode_caches(
        shape.global_batch, shape.seq_len, enc_len=enc_len)
    c_specs = T.decode_cache_specs(cfg, mesh, shape.seq_len,
                                   batch=shape.global_batch)
    c_shard = jax.tree_util.tree_map(
        lambda leaf, spec: ns(spec), caches_abs,
        _expand_cache_specs(caches_abs, c_specs))
    batch_abs = input_specs(cfg, shape)

    def fn(params, caches, toks, lengths):
        return model.decode_step(params, caches, toks, lengths)

    return (fn, (params_abs, caches_abs, batch_abs["tokens"],
                 batch_abs["lengths"]),
            (p_shard, c_shard, ns(P(dp)), ns(P(dp))), mesh, meta)


def _expand_cache_specs(caches_abs, c_specs):
    """specs are per-layer dicts of P; broadcast to the cache pytree."""
    out = []
    for seg_c, seg_s in zip(caches_abs, c_specs):
        seg = []
        for layer_c, layer_s in zip(seg_c, seg_s):
            seg.append({k: layer_s[k] for k in layer_c})
        out.append(tuple(seg))
    return out


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             force: bool = False):
    from repro.configs import SHAPES, cell_is_supported, get_config
    from repro.sharding import mesh_ctx

    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(
        out_dir, f"{arch}__{shape_name}__{mesh_kind}.json")
    if os.path.exists(out_path) and not force:
        print(f"[skip existing] {out_path}")
        return 0

    cfg = get_config(arch)
    ok, why = cell_is_supported(cfg, SHAPES[shape_name])
    if not ok:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
               "status": "skipped", "reason": why}
        json.dump(rec, open(out_path, "w"), indent=1)
        print(f"[documented skip] {arch} {shape_name}: {why}")
        return 0

    rec = {"status": "failed"}
    try:
        t0 = time.time()
        fn, args, shardings, mesh, meta = build_cell(
            arch, shape_name, mesh_kind == "multi")
        rec.update(meta)
        donate = (0, 1) if meta.get("kind") == "train" else ()
        with mesh_ctx.mesh_context(mesh):
            lowered = jax.jit(fn, in_shardings=shardings,
                              donate_argnums=donate).lower(*args)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        txt = compiled.as_text()
        rec.update({
            "status": "ok",
            "lower_s": round(t1 - t0, 2),
            "compile_s": round(t2 - t1, 2),
            "memory_analysis": {
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
            },
            "cost_analysis": {
                "flops_per_device": ca.get("flops", -1.0),
                "bytes_accessed_per_device": ca.get("bytes accessed", -1.0),
            },
            "hlo_lines": len(txt.splitlines()),
            "collectives": parse_collectives(txt),
        })
        print(f"[ok] {arch} {shape_name} {mesh_kind}: "
              f"lower {rec['lower_s']}s compile {rec['compile_s']}s "
              f"args {ma.argument_size_in_bytes/2**30:.2f}GiB/dev "
              f"temp {ma.temp_size_in_bytes/2**30:.2f}GiB/dev")
    except Exception as e:  # record failures, keep the batch going
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        print(f"[FAIL] {arch} {shape_name} {mesh_kind}: {rec['error']}")
    json.dump(rec, open(out_path, "w"), indent=1)
    return 0 if rec["status"] in ("ok", "skipped") else 1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--out", default=os.path.normpath(DEFAULT_OUT))
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="subprocess per cell over every arch x shape x mesh")
    args = ap.parse_args()

    if args.all:
        from repro.configs import ARCH_IDS, SHAPES
        failures = 0
        for mesh_kind in ("single", "multi"):
            for arch in ARCH_IDS:
                for shape in SHAPES:
                    out_path = os.path.join(
                        args.out, f"{arch}__{shape}__{mesh_kind}.json")
                    if os.path.exists(out_path) and not args.force:
                        try:
                            ok = json.load(open(out_path)).get(
                                "status") in ("ok", "skipped")
                        except Exception:
                            ok = False
                        if ok:
                            continue
                        os.remove(out_path)
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", arch, "--shape", shape,
                           "--mesh", mesh_kind, "--out", args.out]
                    r = subprocess.run(cmd)
                    failures += (r.returncode != 0)
        print(f"done; {failures} failures")
        sys.exit(1 if failures else 0)

    # NOTE: the persistent compilation cache is deliberately OFF here —
    # cache-loaded executables return stub HLO from compiled.as_text(),
    # which silently breaks the collective-bytes records.
    sys.exit(run_cell(args.arch, args.shape, args.mesh, args.out,
                      force=args.force))


if __name__ == "__main__":
    main()
