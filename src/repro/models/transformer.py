"""Unified model stack for all 10 assigned architectures.

A model is a sequence of *segments*: a segment is a block of layer
descriptors (kind, is_moe) repeated ``reps`` times, applied with
``lax.scan`` over stacked parameters (remat via ``jax.checkpoint``) so
the HLO stays compact for 60+ layer models.  ``plan_segments`` derives
the segmentation from the config's layer pattern — including truncated
tails (gemma3's 62 = 6x10 + 2) and the dense-first-layer exception
(deepseek's ``moe_layers="all_but_first"``).

Three execution modes share the layer definitions:
  train/full — full-sequence forward (flash kernels), returns logits+aux
  prefill    — full-sequence forward that also materializes caches
  decode     — one-token step against caches (decode kernels / recurrences)

Cache kinds per layer: global attention (full KV, SP-shardable), local
attention (ring buffer of window size), MLA (materialized per-head K/V),
mamba (ssm state + conv tail), mlstm (matrix memory), slstm (scalar
state), cross-attention (static encoder K/V).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.models import xlstm as X

Z_LOSS_WEIGHT = 1e-4
ROUTER_Z_WEIGHT = 1e-3


# ---------------------------------------------------------------------------
# segmentation plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SegmentPlan:
    block: Tuple[Tuple[str, bool], ...]   # (kind, is_moe) per position
    reps: int


def plan_segments(cfg: ModelConfig, *, encoder: bool = False) -> List[SegmentPlan]:
    if encoder:
        descs = [("global", False)] * cfg.encoder_layers
        return [SegmentPlan(tuple(descs[:1]), cfg.encoder_layers)] \
            if cfg.encoder_layers else []
    kinds = cfg.layer_kinds()
    descs = [(kinds[i], cfg.is_moe_layer(i)) for i in range(cfg.num_layers)]
    segs: List[SegmentPlan] = []
    i = 0
    if cfg.moe is not None and cfg.moe_layers == "all_but_first":
        segs.append(SegmentPlan((descs[0],), 1))
        i = 1
    p = len(cfg.layer_pattern)
    if cfg.moe is not None and cfg.moe_layers == "every_2" and p % 2:
        p *= 2
    rest = descs[i:]
    k = len(rest) // p
    if k:
        block = tuple(rest[:p])
        for r in range(k):                 # sanity: the block really repeats
            assert tuple(rest[r * p:(r + 1) * p]) == block, (cfg.name, r)
        segs.append(SegmentPlan(block, k))
    rem = rest[k * p:]
    if rem:
        segs.append(SegmentPlan(tuple(rem), 1))
    return segs


def _has_ffn(cfg: ModelConfig, kind: str, is_moe: bool) -> bool:
    if kind in ("mlstm", "slstm"):
        return False                        # xlstm blocks subsume the FFN
    return is_moe or cfg.d_ff > 0


# ---------------------------------------------------------------------------
# single layer
# ---------------------------------------------------------------------------

def init_layer(key, cfg: ModelConfig, kind: str, is_moe: bool,
               *, cross: bool = False):
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"ln1": L.norm_param(d)}
    if kind in ("global", "local"):
        p["attn"] = A.init_mla(ks[0], cfg) if cfg.mla else A.init_attn(ks[0], cfg)
    elif kind == "mamba":
        p["mamba"] = S.init_mamba(ks[0], cfg)
    elif kind == "mlstm":
        p["mlstm"] = X.init_mlstm(ks[0], cfg)
    elif kind == "slstm":
        p["slstm"] = X.init_slstm(ks[0], cfg)
    else:
        raise ValueError(kind)
    if cfg.use_post_norms:
        p["post_ln1"] = L.norm_param(d)
    if cross:
        p["ln_cross"] = L.norm_param(d)
        p["cross_attn"] = A.init_attn(ks[2], cfg)
    if _has_ffn(cfg, kind, is_moe):
        p["ln2"] = L.norm_param(d)
        if is_moe:
            p["moe"] = M.init_moe(ks[1], cfg)
        else:
            p["mlp"] = L.init_mlp(ks[1], d, cfg.d_ff, cfg.mlp_activation)
        if cfg.use_post_norms:
            p["post_ln2"] = L.norm_param(d)
    return p


def _zero_aux():
    return {"load_balance": jnp.zeros((), jnp.float32),
            "router_z": jnp.zeros((), jnp.float32)}


def _theta(cfg: ModelConfig, kind: str) -> float:
    if kind == "local" and cfg.rope_theta_local is not None:
        return cfg.rope_theta_local
    return cfg.rope_theta


def apply_layer_full(p, x, cfg: ModelConfig, kind: str, is_moe: bool, *,
                     positions=None, enc_out=None, causal: bool = True):
    """Full-sequence layer.  Returns (x, aux)."""
    aux = _zero_aux()
    h = L.apply_norm(p["ln1"], x, cfg)
    if kind in ("global", "local"):
        if cfg.mla:
            y = A.apply_mla(p["attn"], h, cfg, positions=positions)
        else:
            y = A.apply_attn(p["attn"], h, cfg, kind=kind, causal=causal,
                             positions=positions, theta=_theta(cfg, kind))
    elif kind == "mamba":
        y, _ = S.apply_mamba(p["mamba"], h, cfg)
    elif kind == "mlstm":
        y = X.apply_mlstm(p["mlstm"], h, cfg)
    elif kind == "slstm":
        y = X.apply_slstm(p["slstm"], h, cfg)
    else:
        raise ValueError(kind)
    if cfg.use_post_norms:
        y = L.apply_norm(p["post_ln1"], y, cfg)
    x = x + y

    if "cross_attn" in p and enc_out is not None:
        h = L.apply_norm(p["ln_cross"], x, cfg)
        ekv = A.project_kv(p["cross_attn"], enc_out, cfg)
        y = A.apply_attn(p["cross_attn"], h, cfg, causal=False,
                         kv_override=ekv)
        x = x + y

    if _has_ffn(cfg, kind, is_moe):
        h = L.apply_norm(p["ln2"], x, cfg)
        if is_moe:
            y, aux = M.apply_moe(p["moe"], h, cfg)
        else:
            y = L.apply_mlp(p["mlp"], h, cfg.mlp_activation)
        if cfg.use_post_norms:
            y = L.apply_norm(p["post_ln2"], y, cfg)
        x = x + y
    return x, aux


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def layer_cache(cfg: ModelConfig, kind: str, batch: int, cache_len: int,
                dtype, *, cross: bool = False, enc_len: int = 0):
    """Zero-initialized cache for one layer."""
    c: Dict[str, Any] = {}
    if kind in ("global", "local"):
        if cfg.mla:
            qk = cfg.mla.qk_nope_head_dim + cfg.mla.qk_rope_head_dim
            c["k"] = jnp.zeros((batch, cfg.num_heads, cache_len, qk), dtype)
            c["v"] = jnp.zeros((batch, cfg.num_heads, cache_len,
                                cfg.mla.v_head_dim), dtype)
        else:
            s = min(cache_len, cfg.window) if kind == "local" and cfg.window \
                else cache_len
            c["k"] = jnp.zeros((batch, cfg.num_kv_heads, s, cfg.head_dim),
                               dtype)
            c["v"] = jnp.zeros((batch, cfg.num_kv_heads, s, cfg.head_dim),
                               dtype)
    elif kind == "mamba":
        c.update(S.mamba_cache(cfg, batch, dtype))
    elif kind == "mlstm":
        c.update(X.mlstm_cache(cfg, batch, dtype))
    elif kind == "slstm":
        c.update(X.slstm_cache(cfg, batch, dtype))
    if cross:
        c["ek"] = jnp.zeros((batch, cfg.num_kv_heads, enc_len, cfg.head_dim),
                            dtype)
        c["ev"] = jnp.zeros((batch, cfg.num_kv_heads, enc_len, cfg.head_dim),
                            dtype)
    return c


def _ring_from_full(k_full, s_total: int, w: int):
    """Map full-sequence K/V (B,H,S,D) -> ring cache (B,H,W,D), slot p%W."""
    s = k_full.shape[2]
    if s <= w:
        pad = [(0, 0), (0, 0), (0, w - s), (0, 0)]
        return jnp.pad(k_full, pad)
    j = jnp.arange(w)
    src = s - w + ((j - (s % w)) % w)      # token index stored at slot j
    return jnp.take(k_full, src, axis=2)


def apply_layer_prefill(p, x, cfg: ModelConfig, kind: str, is_moe: bool,
                        cache_len: int, *, positions=None, enc_out=None):
    """Full-sequence layer that also returns its decode cache."""
    b, s, _ = x.shape
    dtype = x.dtype
    cache: Dict[str, Any] = {}
    h = L.apply_norm(p["ln1"], x, cfg)
    if kind in ("global", "local"):
        if cfg.mla:
            y, kf, vf = A.apply_mla(p["attn"], h, cfg, positions=positions,
                                    return_kv=True)
            pad = cache_len - s
            cache["k"] = jnp.pad(kf, [(0, 0), (0, 0), (0, pad), (0, 0)])
            cache["v"] = jnp.pad(vf, [(0, 0), (0, 0), (0, pad), (0, 0)])
        else:
            y, kf, vf = A.apply_attn(p["attn"], h, cfg, kind=kind,
                                     positions=positions,
                                     theta=_theta(cfg, kind), return_kv=True)
            if kind == "local" and cfg.window and cfg.window < cache_len:
                cache["k"] = _ring_from_full(kf, s, cfg.window)
                cache["v"] = _ring_from_full(vf, s, cfg.window)
            else:
                pad = cache_len - s
                cache["k"] = jnp.pad(kf, [(0, 0), (0, 0), (0, pad), (0, 0)])
                cache["v"] = jnp.pad(vf, [(0, 0), (0, 0), (0, pad), (0, 0)])
    elif kind == "mamba":
        y, mc = S.apply_mamba(p["mamba"], h, cfg, return_cache=True)
        cache.update(mc)
    elif kind == "mlstm":
        y, mc = X.apply_mlstm(p["mlstm"], h, cfg, return_cache=True)
        cache.update(mc)
    elif kind == "slstm":
        y, mc = X.apply_slstm(p["slstm"], h, cfg, return_cache=True)
        cache.update(mc)
    else:
        raise ValueError(kind)
    if cfg.use_post_norms:
        y = L.apply_norm(p["post_ln1"], y, cfg)
    x = x + y

    if "cross_attn" in p and enc_out is not None:
        hh = L.apply_norm(p["ln_cross"], x, cfg)
        ek, ev = A.project_kv(p["cross_attn"], enc_out, cfg)
        y = A.apply_attn(p["cross_attn"], hh, cfg, causal=False,
                         kv_override=(ek, ev))
        x = x + y
        cache["ek"], cache["ev"] = ek, ev

    if _has_ffn(cfg, kind, is_moe):
        hh = L.apply_norm(p["ln2"], x, cfg)
        if is_moe:
            y, _ = M.apply_moe(p["moe"], hh, cfg)
        else:
            y = L.apply_mlp(p["mlp"], hh, cfg.mlp_activation)
        if cfg.use_post_norms:
            y = L.apply_norm(p["post_ln2"], y, cfg)
        x = x + y
    return x, cache


def apply_layer_decode(p, x, cache, cfg: ModelConfig, kind: str,
                       is_moe: bool, lengths, block_tables=None, layer=None):
    """One-token layer step.  x: (B,1,d).

    A cache carrying ``kp``/``vp`` holds paged pools (serve/paging.py)
    routed through the paged update+attend kernel; ``kw``/``vw`` holds
    a paged *window* group (ring block tables, O(window) pool pressure)
    routed through the windowed ring-table kernel.  ``block_tables`` is
    then either the plain (B, T) array (global-only models) or a dict
    with ``"global"`` / ``"window"`` entries for hybrid models.  A cache
    that also carries ``ks``/``vs`` scale pools holds *quantized* pools
    (repro.quant) and routes through the re-quantizing write +
    fused-dequant kernel.  Recurrent/cross caches are never paged and
    take their usual path.  With ``layer`` (an int32 scalar) the plain
    ``kp``/``vp`` pools are every layer's, stacked, and the step writes
    and reads them in place at ``layer`` (``seg_apply_decode``'s carry).
    """
    h = L.apply_norm(p["ln1"], x, cfg)
    new_cache = dict(cache)
    if kind in ("global", "local"):
        paged_g = "kp" in cache
        paged_w = "kw" in cache
        quantized = "ks" in cache
        scales = (cache["ks"], cache["vs"]) if quantized else None
        if isinstance(block_tables, dict):
            bt_g = block_tables.get("global")
            bt_w = block_tables.get("window")
        else:
            bt_g, bt_w = block_tables, None
        if paged_w:
            out = A.decode_attn(p["attn"], h, cache["kw"], cache["vw"],
                                lengths, cfg, kind=kind,
                                theta=_theta(cfg, kind),
                                block_tables=bt_w, cache_scales=scales,
                                windowed=True)
        else:
            ck_in = cache["kp"] if paged_g else cache["k"]
            cv_in = cache["vp"] if paged_g else cache["v"]
            bt = bt_g if paged_g else None
            ring = (not paged_g and kind == "local"
                    and cfg.window is not None
                    and cache["k"].shape[2] == cfg.window)
            if cfg.mla:
                out = A.decode_mla(p["attn"], h, ck_in, cv_in,
                                   lengths, cfg, block_tables=bt,
                                   cache_scales=scales, layer=layer)
            else:
                out = A.decode_attn(p["attn"], h, ck_in, cv_in,
                                    lengths, cfg, kind=kind, ring=ring,
                                    theta=_theta(cfg, kind),
                                    block_tables=bt, cache_scales=scales,
                                    layer=layer)
        if quantized:
            y, ck, cv, ks, vs = out
            new_cache["ks"], new_cache["vs"] = ks, vs
        else:
            y, ck, cv = out
        if paged_w:
            new_cache["kw"], new_cache["vw"] = ck, cv
        elif paged_g:
            new_cache["kp"], new_cache["vp"] = ck, cv
        else:
            new_cache["k"], new_cache["v"] = ck, cv
    elif kind == "mamba":
        y, nc = S.decode_mamba(p["mamba"], h, cache, cfg)
        new_cache.update(nc)
    elif kind == "mlstm":
        y, nc = X.decode_mlstm(p["mlstm"], h, cache, cfg)
        new_cache.update(nc)
    elif kind == "slstm":
        y, nc = X.decode_slstm(p["slstm"], h, cache, cfg)
        new_cache.update(nc)
    else:
        raise ValueError(kind)
    if cfg.use_post_norms:
        y = L.apply_norm(p["post_ln1"], y, cfg)
    x = x + y

    if "cross_attn" in p and "ek" in cache:
        hh = L.apply_norm(p["ln_cross"], x, cfg)
        y = A.apply_attn(p["cross_attn"], hh, cfg, causal=False,
                         kv_override=(cache["ek"], cache["ev"]))
        x = x + y

    if _has_ffn(cfg, kind, is_moe):
        hh = L.apply_norm(p["ln2"], x, cfg)
        if is_moe:
            y, _ = M.apply_moe(p["moe"], hh, cfg)
        else:
            y = L.apply_mlp(p["mlp"], hh, cfg.mlp_activation)
        if cfg.use_post_norms:
            y = L.apply_norm(p["post_ln2"], y, cfg)
        x = x + y
    return x, new_cache


def apply_layer_spec_decode(p, x, cache, cfg: ModelConfig, kind: str,
                            is_moe: bool, lengths, block_tables=None):
    """Speculative K1-token layer step.  x: (B,K1,d).

    Only paged global-attention caches (GQA or MLA) are supported —
    recurrent/ring/cross layers have sequential state that a batched
    verify cannot roll back, and the engine refuses spec mode for them
    up front.  FFN/MoE/norm blocks are shape-generic over S=K1.
    """
    if kind != "global":
        raise ValueError(
            f"spec decode supports global-attention layers only, got {kind!r}")
    if "kp" not in cache:
        raise ValueError("spec decode requires paged caches")
    h = L.apply_norm(p["ln1"], x, cfg)
    new_cache = dict(cache)
    quantized = "ks" in cache
    scales = (cache["ks"], cache["vs"]) if quantized else None
    if cfg.mla:
        out = A.spec_decode_mla(p["attn"], h, cache["kp"], cache["vp"],
                                lengths, cfg, block_tables=block_tables,
                                cache_scales=scales)
    else:
        out = A.spec_decode_attn(p["attn"], h, cache["kp"], cache["vp"],
                                 lengths, cfg, kind=kind,
                                 theta=_theta(cfg, kind),
                                 block_tables=block_tables,
                                 cache_scales=scales)
    if quantized:
        y, ck, cv, ks, vs = out
        new_cache["ks"], new_cache["vs"] = ks, vs
    else:
        y, ck, cv = out
    new_cache["kp"], new_cache["vp"] = ck, cv
    if cfg.use_post_norms:
        y = L.apply_norm(p["post_ln1"], y, cfg)
    x = x + y

    if _has_ffn(cfg, kind, is_moe):
        hh = L.apply_norm(p["ln2"], x, cfg)
        if is_moe:
            y, _ = M.apply_moe(p["moe"], hh, cfg)
        else:
            y = L.apply_mlp(p["mlp"], hh, cfg.mlp_activation)
        if cfg.use_post_norms:
            y = L.apply_norm(p["post_ln2"], y, cfg)
        x = x + y
    return x, new_cache


# ---------------------------------------------------------------------------
# segments (scan over stacked reps)
# ---------------------------------------------------------------------------

def init_segment(key, cfg: ModelConfig, plan: SegmentPlan, *,
                 cross: bool = False):
    pos_params = []
    for i, (kind, is_moe) in enumerate(plan.block):
        # one vmapped init over the reps' keys: the same values as
        # stacking per-rep inits, but traced (and compiled) once
        keys = jnp.stack([jax.random.fold_in(key, r * len(plan.block) + i)
                          for r in range(plan.reps)])
        pos_params.append(jax.vmap(
            lambda k: init_layer(k, cfg, kind, is_moe, cross=cross))(keys))
    return tuple(pos_params)


def seg_apply_full(seg_p, x, cfg: ModelConfig, plan: SegmentPlan, *,
                   positions=None, enc_out=None, causal: bool = True,
                   remat: bool = True):
    def body(carry, lp):
        x_, aux = carry
        for i, (kind, is_moe) in enumerate(plan.block):
            x_, aux_i = apply_layer_full(lp[i], x_, cfg, kind, is_moe,
                                         positions=positions,
                                         enc_out=enc_out, causal=causal)
            aux = jax.tree_util.tree_map(jnp.add, aux, aux_i)
        return (x_, aux), None

    if remat:
        if cfg.remat_policy == "dots":
            fn = jax.checkpoint(
                body,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        else:
            fn = jax.checkpoint(body)
    else:
        fn = body
    (x, aux), _ = jax.lax.scan(fn, (x, _zero_aux()), seg_p)
    return x, aux


def seg_apply_prefill(seg_p, x, cfg: ModelConfig, plan: SegmentPlan,
                      cache_len: int, *, positions=None, enc_out=None):
    def body(x_, lp):
        caches = []
        for i, (kind, is_moe) in enumerate(plan.block):
            x_, c = apply_layer_prefill(lp[i], x_, cfg, kind, is_moe,
                                        cache_len, positions=positions,
                                        enc_out=enc_out)
            caches.append(c)
        return x_, tuple(caches)

    x, caches = jax.lax.scan(body, x, seg_p)
    return x, caches


POOL_KEYS = ("kp", "vp")


def pool_in_place(cache) -> bool:
    """Whether a decode-cache entry's pools ride ``seg_apply_decode``'s
    carry: plain paged ``kp``/``vp`` pools, with no ``ks``/``vs`` scale
    pools and no ``kw``/``vw`` window group."""
    return "kp" in cache and "ks" not in cache and "kw" not in cache


def seg_apply_decode(seg_p, caches, x, cfg: ModelConfig, plan: SegmentPlan,
                     lengths, block_tables=None):
    """One decode step through a segment's stacked layers.

    The plain paged pools of an entry (``pool_in_place``) ride the
    scan's carry whole, beside the layer index: each layer writes its
    token's K/V into them and the paged kernel reads them at that
    index, so no layer slices a pool out or writes one back, and with
    the step's caches donated the pools are updated in place.  The
    weights and every other cache leaf stay in ``xs``/``ys``.
    """
    carried = [pool_in_place(c) for c in caches]
    pools = tuple({k: c[k] for k in POOL_KEYS} if keep else {}
                  for c, keep in zip(caches, carried))
    rest = tuple({k: v for k, v in c.items()
                  if not (keep and k in POOL_KEYS)}
                 for c, keep in zip(caches, carried))

    def body(carry, xs):
        x_, layer, pools_ = carry
        lp, cs = xs
        new_pools, new_rest = [], []
        for i, (kind, is_moe) in enumerate(plan.block):
            x_, nc = apply_layer_decode(
                lp[i], x_, {**cs[i], **pools_[i]}, cfg, kind, is_moe,
                lengths, block_tables=block_tables,
                layer=layer if carried[i] else None)
            new_pools.append({k: nc.pop(k) for k in pools_[i]})
            new_rest.append(nc)
        return (x_, layer + 1, tuple(new_pools)), tuple(new_rest)

    (x, _, pools), rest = jax.lax.scan(
        body, (x, jnp.int32(0), pools), (seg_p, rest))
    return x, tuple({**r, **p} for r, p in zip(rest, pools))


def seg_apply_spec_decode(seg_p, caches, x, cfg: ModelConfig,
                          plan: SegmentPlan, lengths, block_tables=None):
    def body(x_, xs):
        lp, cs = xs
        new = []
        for i, (kind, is_moe) in enumerate(plan.block):
            x_, nc = apply_layer_spec_decode(lp[i], x_, cs[i], cfg, kind,
                                             is_moe, lengths,
                                             block_tables=block_tables)
            new.append(nc)
        return x_, tuple(new)

    x, new_caches = jax.lax.scan(body, x, (seg_p, caches))
    return x, new_caches


# ---------------------------------------------------------------------------
# whole model
# ---------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig):
    ks = jax.random.split(key, 8)
    embed, unembed = L.init_embed(ks[0], cfg)
    params: Dict[str, Any] = {
        "embed": embed,
        "unembed": unembed,
        "final_norm": L.norm_param(cfg.d_model),
    }
    cross = cfg.is_encoder_decoder
    params["segments"] = [
        init_segment(jax.random.fold_in(ks[1], i), cfg, plan, cross=cross)
        for i, plan in enumerate(plan_segments(cfg))]
    if cfg.is_encoder_decoder:
        params["encoder"] = {
            "segments": [
                init_segment(jax.random.fold_in(ks[2], i), cfg, plan)
                for i, plan in enumerate(plan_segments(cfg, encoder=True))],
            "final_norm": L.norm_param(cfg.d_model),
        }
    return params


def _encode(params, cfg: ModelConfig, encoder_embeds):
    """Whisper-style encoder over precomputed frame embeddings (stub
    frontend): sinusoidal positions + bidirectional segments."""
    x = encoder_embeds.astype(L.dtype_of(cfg))
    s = x.shape[1]
    x = x + L.sinusoidal_positions(s, cfg.d_model, x.dtype)[None]
    for plan, seg_p in zip(plan_segments(cfg, encoder=True),
                           params["encoder"]["segments"]):
        x, _ = seg_apply_full(seg_p, x, cfg, plan, causal=False)
    return L.apply_norm(params["encoder"]["final_norm"], x, cfg)


def _splice_vision(x, vision_embeds, cfg: ModelConfig):
    """VLM stub: the first ``frontend_tokens`` positions carry patch
    embeddings (keeps sequence length uniform across shape cells)."""
    n = vision_embeds.shape[1]
    return jnp.concatenate(
        [vision_embeds.astype(x.dtype), x[:, n:, :]], axis=1)


def _logits(params, x, cfg: ModelConfig):
    x = L.apply_norm(params["final_norm"], x, cfg)
    logits = x @ params["unembed"]["table"].astype(x.dtype)
    logits = logits.astype(jnp.float32)
    if cfg.final_softcap is not None:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    # mask padded vocab tail
    v = L.padded_vocab(cfg.vocab_size)
    if v != cfg.vocab_size:
        pad_mask = jnp.arange(v) >= cfg.vocab_size
        logits = jnp.where(pad_mask[None, None], -1e30, logits)
    return logits


def forward_train(params, batch: Dict[str, jax.Array], cfg: ModelConfig):
    """Returns (loss, metrics).  batch: tokens, labels (+ stub inputs)."""
    tokens, labels = batch["tokens"], batch["labels"]
    x = L.embed_tokens(params["embed"], tokens, cfg)
    label_mask = jnp.ones(labels.shape, jnp.float32)
    if cfg.frontend == "vision" and "vision_embeds" in batch:
        x = _splice_vision(x, batch["vision_embeds"], cfg)
        n = batch["vision_embeds"].shape[1]
        label_mask = label_mask.at[:, :n].set(0.0)
    enc_out = None
    if cfg.is_encoder_decoder and "encoder_embeds" in batch:
        enc_out = _encode(params, cfg, batch["encoder_embeds"])

    aux = _zero_aux()
    for plan, seg_p in zip(plan_segments(cfg), params["segments"]):
        x, aux_i = seg_apply_full(seg_p, x, cfg, plan, enc_out=enc_out)
        aux = jax.tree_util.tree_map(jnp.add, aux, aux_i)

    logits = _logits(params, x, cfg)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    ce_tok = (lse - ll) * label_mask
    denom = jnp.maximum(label_mask.sum(), 1.0)
    ce = ce_tok.sum() / denom
    z_loss = Z_LOSS_WEIGHT * ((lse ** 2) * label_mask).sum() / denom

    moe_w = cfg.moe.aux_loss_weight if cfg.moe is not None else 0.0
    loss = (ce + z_loss + moe_w * aux["load_balance"]
            + ROUTER_Z_WEIGHT * aux["router_z"])
    metrics = {"loss": loss, "ce": ce, "z_loss": z_loss,
               "load_balance": aux["load_balance"],
               "router_z": aux["router_z"]}
    return loss, metrics


def init_decode_caches(cfg: ModelConfig, batch: int, cache_len: int,
                       *, enc_len: int = 0):
    dtype = L.dtype_of(cfg)
    caches = []
    for plan in plan_segments(cfg):
        seg = []
        for kind, is_moe in plan.block:
            one = layer_cache(cfg, kind, batch, cache_len, dtype,
                              cross=cfg.is_encoder_decoder, enc_len=enc_len)
            seg.append(jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (plan.reps,) + x.shape), one))
        caches.append(tuple(seg))
    return caches


def decode_cache_specs(cfg: ModelConfig, mesh, cache_len: int,
                       batch: Optional[int] = None):
    """PartitionSpecs for the decode-cache pytree, mirroring the layout
    policy in sharding/kernel_sharding.py: KV head-sharded over 'model'
    when head counts divide, else sequence-sharded (SP decode) for
    global-attention caches; ring (local) caches and recurrent states
    batch-sharded with channel dims over 'model' when divisible."""
    from jax.sharding import PartitionSpec as P
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    while dp and batch is not None and batch % _axes_size(dp, mesh) != 0:
        dp = dp[1:]                       # small batches drop DP axes
    dp = dp or None
    tp = mesh.shape.get("model", 1)

    def attn_spec(kind: str):
        hq, hkv = cfg.num_heads, cfg.num_kv_heads
        if cfg.mla:
            hkv = cfg.num_heads
        local = kind == "local" and cfg.window
        s = min(cache_len, cfg.window) if local else cache_len
        ring = bool(local and cfg.window < cache_len)
        if hq % tp == 0 and hkv % tp == 0:
            return P(None, dp, "model", None, None)
        # SP over cache slots: global caches, and ring caches (the ring
        # passes window=None to the decode wrapper, so SP applies there too)
        if (not local or ring) and s % tp == 0:
            return P(None, dp, None, "model", None)
        return P(None, dp, None, None, None)

    def leaf_spec(kind: str, name: str, ndim: int):
        if name in ("k", "v"):
            if kind in ("global", "local"):
                return attn_spec(kind)
            return P(None, dp)
        if name in ("ek", "ev"):
            return P(None, dp, None, None, None)
        if kind == "mamba":
            d_inner = cfg.ssm.expand * cfg.d_model
            ch = "model" if d_inner % tp == 0 else None
            if name == "h":
                return P(None, dp, ch, None)
            if name == "conv":
                return P(None, dp, None, ch)
        if kind == "mlstm":
            h = cfg.xlstm.num_heads
            d_inner = int(cfg.d_model * cfg.xlstm.proj_factor_mlstm)
            dh = d_inner // h
            hs = "model" if h % tp == 0 else None
            vs = None if hs else ("model" if dh % tp == 0 else None)
            if name == "C":
                return P(None, dp, hs, None, vs)
            if name == "n":
                return P(None, dp, hs, None)
            if name == "m":
                return P(None, dp, hs)
            if name == "conv":
                ch = "model" if d_inner % tp == 0 else None
                return P(None, dp, None, ch)
        # slstm states & anything else: batch-sharded only
        return P(*((None, dp) + (None,) * (ndim - 2)))

    specs = []
    for plan in plan_segments(cfg):
        seg = []
        for kind, is_moe in plan.block:
            one = layer_cache(cfg, kind, 8, max(cache_len, 8), jnp.bfloat16,
                              cross=cfg.is_encoder_decoder,
                              enc_len=8)
            seg.append({name: leaf_spec(kind, name, leaf.ndim + 1)
                        for name, leaf in one.items()})
        specs.append(tuple(seg))
    return specs


def _axes_size(axes, mesh) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def prefill(params, cfg: ModelConfig, tokens, cache_len: int,
            batch_extras: Optional[Dict[str, jax.Array]] = None):
    """Full-sequence prefill.  Returns (last-position logits, caches)."""
    batch_extras = batch_extras or {}
    x = L.embed_tokens(params["embed"], tokens, cfg)
    if cfg.frontend == "vision" and "vision_embeds" in batch_extras:
        x = _splice_vision(x, batch_extras["vision_embeds"], cfg)
    enc_out = None
    if cfg.is_encoder_decoder and "encoder_embeds" in batch_extras:
        enc_out = _encode(params, cfg, batch_extras["encoder_embeds"])

    caches = []
    for plan, seg_p in zip(plan_segments(cfg), params["segments"]):
        x, c = seg_apply_prefill(seg_p, x, cfg, plan, cache_len,
                                 enc_out=enc_out)
        caches.append(c)
    logits = _logits(params, x[:, -1:, :], cfg)
    return logits[:, 0], caches


def decode_step(params, cfg: ModelConfig, caches, tokens, lengths,
                block_tables=None):
    """One decode step.  tokens: (B,) int32; lengths: (B,) tokens already
    in cache.  Returns (logits (B, V), new caches).  ``block_tables``
    routes paged caches (``kp``/``vp`` pools) through the paged kernel."""
    x = L.embed_tokens(params["embed"], tokens[:, None], cfg)
    new_caches = []
    for plan, seg_p, c in zip(plan_segments(cfg), params["segments"], caches):
        x, nc = seg_apply_decode(seg_p, c, x, cfg, plan, lengths,
                                 block_tables=block_tables)
        new_caches.append(nc)
    logits = _logits(params, x, cfg)
    return logits[:, 0], new_caches


def spec_decode_step(params, cfg: ModelConfig, caches, tokens, lengths,
                     block_tables):
    """Speculative verify step.  tokens: (B, K1) int32 — current token
    plus K1-1 drafts; lengths: (B,) committed tokens already in cache.
    Returns (logits (B, K1, V), new caches) — logits[:, i] conditions on
    ``tokens[:, :i+1]``, so row i greedily argmaxes the token that
    *should* follow draft i.  All K1 rows' K/V land in the paged cache;
    the engine rolls back rejected rows via block-table truncation."""
    x = L.embed_tokens(params["embed"], tokens, cfg)
    new_caches = []
    for plan, seg_p, c in zip(plan_segments(cfg), params["segments"], caches):
        x, nc = seg_apply_spec_decode(seg_p, c, x, cfg, plan, lengths,
                                      block_tables=block_tables)
        new_caches.append(nc)
    logits = _logits(params, x, cfg)
    return logits, new_caches
