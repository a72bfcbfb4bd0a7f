"""Model facade: one object per architecture bundling init + the three
execution modes.  ``--arch <id>`` resolves through here (launch/, serve/,
benchmarks all consume this instead of poking at transformer.py)."""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.models import transformer as T


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    def init(self, key) -> Dict[str, Any]:
        return T.init_params(key, self.cfg)

    def init_serving(self, key) -> Dict[str, Any]:
        """Serving weights: built under ``jax.jit`` and emitted in
        ``cfg.dtype``, so no float32 master copy of the model is ever
        materialized on the device (granite-8b is 33 GB in float32, 16.5
        GB in bf16).  Every matmul already casts its weight at use, so
        the logits match ``init``'s float32 tree cast the same way.
        Training keeps ``init``."""
        return jax.jit(self._init_in_dtype)(key)

    def _init_in_dtype(self, key) -> Dict[str, Any]:
        dt = jnp.dtype(self.cfg.dtype)
        return jax.tree.map(
            lambda x: x.astype(dt) if jnp.issubdtype(x.dtype, jnp.floating)
            else x, T.init_params(key, self.cfg))

    def init_abstract(self, key=None) -> Dict[str, Any]:
        """ShapeDtypeStruct params (dry-run: no allocation)."""
        key = key if key is not None else jax.random.PRNGKey(0)
        return jax.eval_shape(lambda k: T.init_params(k, self.cfg), key)

    def loss(self, params, batch):
        return T.forward_train(params, batch, self.cfg)

    def prefill(self, params, tokens, cache_len: int,
                extras: Optional[Dict[str, Any]] = None):
        return T.prefill(params, self.cfg, tokens, cache_len, extras)

    def decode_step(self, params, caches, tokens, lengths,
                    block_tables=None):
        return T.decode_step(params, self.cfg, caches, tokens, lengths,
                             block_tables=block_tables)

    def spec_decode_step(self, params, caches, tokens, lengths,
                         block_tables):
        return T.spec_decode_step(params, self.cfg, caches, tokens, lengths,
                                  block_tables)

    def init_decode_caches(self, batch: int, cache_len: int, *,
                           enc_len: int = 0):
        return T.init_decode_caches(self.cfg, batch, cache_len,
                                    enc_len=enc_len)

    def abstract_decode_caches(self, batch: int, cache_len: int, *,
                               enc_len: int = 0):
        return jax.eval_shape(
            lambda: T.init_decode_caches(self.cfg, batch, cache_len,
                                         enc_len=enc_len))


def build_model(arch_or_cfg) -> Model:
    if isinstance(arch_or_cfg, ModelConfig):
        return Model(arch_or_cfg)
    return Model(get_config(arch_or_cfg))
