"""Plain reference of a dense GQA decoder (llama-style blocks), written
from the published description and from nothing of the program.

  x = embed[tokens]
  per layer:  h = rms(x) * (1 + ln1);  q, k, v = h Wq, h Wk, h Wv
              rotary position embedding on q and k (halves rotated,
              base ``rope_theta``); causal softmax(q k^T / sqrt(hd)) v
              with each KV head shared by num_heads/num_kv_heads query
              heads; x += attn Wo
              h = rms(x) * (1 + ln2);  x += (silu(h Wg) * (h Wu)) Wd
  logits = (rms(x) * (1 + final_norm)) Unembed

``mode="f32"`` computes every matmul in float32 at the highest
precision.  ``mode="fp8"`` is the control: the same computation with
every matmul's inputs rounded to float8 e4m3 under a per-tensor scale,
the step below the bfloat16 the configuration states.

Memory: the weights stay in their served type and one layer at a time
is widened; attention runs over blocks of queries; logits are formed in
blocks of positions.  So a sequence of some thousands of tokens fits
beside the weights on one chip.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List

import numpy as np

Q_BLOCK = 512           # queries per attention block
LOGIT_BLOCK = 512       # positions per block of logits
BUCKET = 1024           # sequences are padded up to a multiple of this
FP8_MAX = 448.0


def _mm(a, b, spec, mode):
    import jax
    import jax.numpy as jnp
    if mode == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _fp8(x):
    import jax.numpy as jnp
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, w, eps):
    import jax.numpy as jnp
    x = x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps))
    return x * (1.0 + w.astype(jnp.float32))


def _rope(x, pos, theta):
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0
                    / x.shape[-1])
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, w, *, m, mode):
    """One decoder layer over x (S, d) in float32."""
    import jax
    import jax.numpy as jnp
    f32 = lambda a: a.astype(jnp.float32)
    s = x.shape[0]
    h_q, h_kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    pos = jnp.arange(s)
    h = _rms(x, w["ln1"], m["rms_norm_eps"])
    q = _mm(h, f32(w["attn/wq"]), "sd,dhk->shk", mode)
    k = _mm(h, f32(w["attn/wk"]), "sd,dhk->shk", mode)
    v = _mm(h, f32(w["attn/wv"]), "sd,dhk->shk", mode)
    q = _rope(q, pos, m["rope_theta"]) / np.sqrt(hd)
    k = _rope(k, pos, m["rope_theta"])
    g = h_q // h_kv
    qb = q.reshape(s // Q_BLOCK, Q_BLOCK, h_kv, g, hd)

    def block(args):
        qi, i = args
        sc = _mm(qi, k, "qcgk,tck->cgqt", mode)
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(pos[None, None, None, :] <= rows[None, None, :, None],
                       sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return _mm(p, v, "cgqt,tck->qcgk", mode)

    o = jax.lax.map(block, (qb, jnp.arange(s // Q_BLOCK)))
    o = o.reshape(s, h_q, hd)
    x = x + _mm(o, f32(w["attn/wo"]), "shk,hkd->sd", mode)
    h = _rms(x, w["ln2"], m["rms_norm_eps"])
    gate = _mm(h, f32(w["mlp/w_gate"]), "sd,df->sf", mode)
    up = _mm(h, f32(w["mlp/w_up"]), "sd,df->sf", mode)
    return x + _mm(jax.nn.silu(gate) * up, f32(w["mlp/w_down"]),
                   "sf,fd->sd", mode)


def _logit_block(x, final_norm, unembed, *, m, mode):
    """Logits over the real vocabulary for a block of positions."""
    import jax.numpy as jnp
    h = _rms(x, final_norm, m["rms_norm_eps"])
    return _mm(h, unembed[:, :m["vocab_size"]].astype(jnp.float32),
               "sd,dv->sv", mode)


@functools.lru_cache(maxsize=None)
def _fns(mkey, mode):
    import jax
    m = dict(mkey)
    return (jax.jit(functools.partial(_layer, m=m, mode=mode)),
            jax.jit(functools.partial(_logit_block, m=m, mode=mode)))


def hidden(flat: Dict[str, Any], m: Dict[str, Any], tokens: List[int],
           mode: str = "f32"):
    """Final hidden states (S_padded, d) for ``tokens``; the padding sits
    after the tokens, so causal attention leaves their rows unchanged."""
    import jax.numpy as jnp
    from chipbench.weights import layer_slice
    layer_fn, _ = _fns(tuple(sorted(m.items())), mode)
    n = len(tokens)
    s = -(-n // BUCKET) * BUCKET
    ids = jnp.asarray(list(tokens) + [0] * (s - n), jnp.int32)
    x = jnp.take(flat["embed/table"], ids, axis=0).astype(jnp.float32)
    for i in range(m["num_layers"]):
        x = layer_fn(x, layer_slice(flat, i))
    return x


def logits_blocks(flat, m, x, mode: str = "f32"):
    """Yield (start, logits of LOGIT_BLOCK positions) over x."""
    _, logit_fn = _fns(tuple(sorted(m.items())), mode)
    for s in range(0, x.shape[0], LOGIT_BLOCK):
        yield s, logit_fn(x[s:s + LOGIT_BLOCK], flat["final_norm"],
                          flat["unembed/table"])


def served_gaps(flat, m, prompt: List[int], served: List[int],
                control: bool = False) -> Dict[str, np.ndarray]:
    """At each position that produced a served token: how far the served
    token's reference logit lies below the reference's best.  With
    ``control``, also the gap of the token the fp8 control puts first at
    the same positions."""
    import jax.numpy as jnp
    seq = list(prompt) + list(served)[:-1]
    first = len(prompt) - 1            # position that produced served[0]
    want = np.asarray(served, np.int64)
    x = hidden(flat, m, seq, "f32")
    xc = hidden(flat, m, seq, "fp8") if control else None
    blocks_c = logits_blocks(flat, m, xc, "fp8") if control else None
    gaps, gaps_c = [], []
    for s, lg in logits_blocks(flat, m, x, "f32"):
        lc = next(blocks_c)[1] if control else None
        lo, hi = max(s, first), min(s + LOGIT_BLOCK, first + len(want))
        if lo >= hi:
            continue
        rows = jnp.arange(lo - s, hi - s)
        best = jnp.max(lg[rows], axis=-1)
        tok = jnp.asarray(want[lo - first:hi - first], jnp.int32)
        gaps.append(np.asarray(best - lg[rows, tok]))
        if control:
            ctok = jnp.argmax(lc[rows], axis=-1)
            gaps_c.append(np.asarray(best - lg[rows, ctok]))
    out = {"program": np.concatenate(gaps)}
    if control:
        out["control"] = np.concatenate(gaps_c)
    return out
