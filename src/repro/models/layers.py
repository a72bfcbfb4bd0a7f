"""Shared building blocks: init helpers, norms, RoPE, MLPs, embeddings."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.sharding.kernel_sharding import sharded_rmsnorm as rmsnorm

VOCAB_PAD = 256


def padded_vocab(v: int) -> int:
    return ((v + VOCAB_PAD - 1) // VOCAB_PAD) * VOCAB_PAD


def dtype_of(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def dense_init(key, shape, in_axis_size=None, dtype=jnp.float32):
    fan_in = in_axis_size or shape[0]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def norm_init(shape=()):
    return jnp.zeros(shape, jnp.float32)


def apply_norm(w, x, cfg: ModelConfig):
    """RMSNorm through the portable kernel.

    gemma stores weights around 0 with offset 1 (w+1); other families
    store around 1 with offset 0.  We init at 0 and use offset 1
    uniformly — numerically the gemma convention, which is also the
    identity at init for every family.
    """
    return rmsnorm(x, w.astype(x.dtype), weight_offset=1.0,
                   eps=cfg.rms_norm_eps)


def norm_param(d: int):
    return jnp.zeros((d,), jnp.float32)


# -------------------------------------------------------------- RoPE ----

def rope_cache(positions, head_dim: int, theta: float):
    """positions: (...,) int -> (..., head_dim/2) cos/sin."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x: (..., S, D); cos/sin: (S, D/2) or broadcastable."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin,
                           xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


# -------------------------------------------------------------- MLP -----

def init_mlp(key, d: int, ff: int, activation: str):
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[1], (d, ff)),
         "w_down": dense_init(ks[2], (ff, d), in_axis_size=ff)}
    if activation != "gelu_ungated":
        p["w_gate"] = dense_init(ks[0], (d, ff))
    return p


def apply_mlp(p, x, activation: str):
    xd = x.dtype
    up = x @ p["w_up"].astype(xd)
    if activation == "gelu_ungated":
        h = jax.nn.gelu(up)
    else:
        gate = x @ p["w_gate"].astype(xd)
        act = jax.nn.gelu(gate, approximate=True) if activation == "gelu" \
            else jax.nn.silu(gate)
        h = act * up
    return h @ p["w_down"].astype(xd)


# --------------------------------------------------------- Embedding ----

def init_embed(key, cfg: ModelConfig):
    v = padded_vocab(cfg.vocab_size)
    k1, k2 = jax.random.split(key)
    return ({"table": dense_init(k1, (v, cfg.d_model),
                                 in_axis_size=cfg.d_model)},
            {"table": dense_init(k2, (cfg.d_model, v))})


def embed_tokens(embed, tokens, cfg: ModelConfig):
    x = jnp.take(embed["table"], tokens, axis=0).astype(dtype_of(cfg))
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def sinusoidal_positions(s: int, d: int, dtype):
    pos = jnp.arange(s, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / (10000.0 ** (2 * dim / d))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)
