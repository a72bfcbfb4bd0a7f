"""Compile each cell's programs at real widths for a described TPU v5e,
without a chip, and print what ``memory_analysis`` says of them.  It
sizes the page pools in the traffic files.

    JAX_PLATFORMS=cpu python3 -m chipbench.aot_rehearse [cell ...]

For each cell: the decode step, the largest prefill and the admit
program that the window runs, as the paged ``Engine`` jits them.  The
pool is sized so that the weights, the pool, the step's undonated copy
of it and the larger of the step's and admission's other bytes fit in
``FILL`` of the chip's memory.  Run by hand; nothing here touches a chip.
"""
from __future__ import annotations

import os
import sys

from chipbench import files

HBM_LIMIT = 16_909_336_064      # bytes_limit of one v5e (memory_stats)
FILL = 0.94


def rehearse(cell_name: str, bench, one_chip) -> None:
    import jax
    import jax.numpy as jnp
    from chipbench import run, weights
    from chipbench import traffic as TR
    from repro.core.context import target
    from repro.models.registry import build_model
    from repro.serve import Engine, paging

    cell = files.cell(cell_name, bench)
    spec = files.config(cell["config"])
    traffic = files.traffic(cell["traffic"])
    m = spec["model"]
    cfg = run.program_config(spec)
    model = build_model(cfg)
    sc = run.serve_config(spec, traffic, 0)
    ps = sc.page_size or 64
    sc.page_size = ps
    pps = paging.pages_per_slot(sc.cache_len, ps)
    total = sc.total_pages or 1 + sc.slots * pps

    def sds(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    dt = jnp.dtype(cfg.dtype)
    params = sds(weights.to_program({k: jax.ShapeDtypeStruct(s, dt)
                                     for k, (s, _) in
                                     weights.layout(m).items()}))
    caches = sds(jax.eval_shape(lambda: paging.init_paged_caches(
        model, sc.slots, sc.cache_len, ps, total)))
    eng = Engine.__new__(Engine)          # only the jitted bodies
    eng.model, eng.sc, eng.windowed, eng.window = model, sc, False, None
    i32, b_ = jnp.int32, jnp.bool_
    vec = lambda dtype: jax.ShapeDtypeStruct((sc.slots,), dtype,
                                             sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    scalar = jax.ShapeDtypeStruct((), i32, sharding=one_chip)
    bt = jax.ShapeDtypeStruct((sc.slots, pps), i32, sharding=one_chip)
    wbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    pool = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(caches))
    print(f"== {cell_name}: weights {wbytes} B, pool {total} pages of {ps} "
          f"= {pool} B ({pool / max(total, 1):.0f} B/page)", flush=True)
    out = {}
    with target("tpu", isa="v5e"):
        step = jax.jit(eng._build_step()).lower(
            params, caches, vec(i32), vec(i32), vec(b_), vec(i32), key,
            scalar, vec(i32), bt, vec(b_)).compile()
        out["step"] = step.memory_analysis()
        plen, k = max(TR.warm_shapes(traffic), key=lambda s: s[0] * s[1])
        toks = jax.ShapeDtypeStruct((k, plen), i32, sharding=one_chip)
        pre = jax.jit(lambda p, t: model.prefill(p, t, sc.cache_len, {}))
        lowered = pre.lower(params, toks)
        out["prefill"] = lowered.compile().memory_analysis()
        cache1 = sds(jax.eval_shape(
            lambda p, t: model.prefill(p, t, sc.cache_len, {})[1],
            params, toks))
        kv = lambda dtype, *s: jax.ShapeDtypeStruct(
            (k,) + s, dtype, sharding=one_chip)
        admit = jax.jit(eng._build_admit()).lower(
            caches, vec(i32), vec(i32), vec(b_), vec(i32),
            jax.ShapeDtypeStruct((sc.slots, sc.cache_len + 1), i32,
                                 sharding=one_chip),
            vec(i32), cache1, kv(i32), kv(i32), kv(i32), kv(b_), kv(i32),
            kv(i32), kv(i32, pps), kv(i32, sc.cache_len + 1), None).compile()
        out["admit"] = admit.memory_analysis()
    for name, ma in out.items():
        print(f"{name}: args {ma.argument_size_in_bytes} out "
              f"{ma.output_size_in_bytes} temp {ma.temp_size_in_bytes} "
              f"alias {ma.alias_size_in_bytes}", flush=True)
    page_b = pool / total
    st, pr, ad = out["step"], out["prefill"], out["admit"]
    cache1_b = pr.output_size_in_bytes
    # besides weights + 2 pools: the step's temps, or admission's prefill
    # output held while admit runs, plus admit's temps
    extra = max(st.temp_size_in_bytes,
                cache1_b + ad.temp_size_in_bytes,
                pr.temp_size_in_bytes + cache1_b - pool)
    fit = int((HBM_LIMIT * FILL - wbytes - extra) // (2 * page_b))
    print(f"pool pages that fit beside weights and the step copy at "
          f"{FILL:.0%} of {HBM_LIMIT} B: {fit} ({fit * page_b / 1e9:.2f} GB; "
          f"full reservation would be {1 + sc.slots * pps} pages)",
          flush=True)


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    bench = files.benchmark()
    names = (argv if argv is not None else sys.argv[1:]) or \
        [w["name"] for w in bench["workloads"]]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    for name in names:
        rehearse(name, bench, one_chip)
    return 0


if __name__ == "__main__":
    sys.exit(main())
