"""Config system: architecture + run-shape descriptions.

Every assigned architecture is a ``ModelConfig`` instance in its own
module (one per arch id, exact figures from the brief).  Shapes are the
four assigned (seq_len, global_batch) cells; ``input_specs`` produces
ShapeDtypeStruct stand-ins for the dry-run (no allocation).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int              # routed experts
    top_k: int
    d_ff_expert: int
    num_shared_experts: int = 0   # deepseek-style always-on shared experts
    d_ff_shared: int = 0
    dense_residual: bool = False  # arctic-style parallel dense MLP
    capacity_factor: float = 1.25
    router_noise: float = 0.0
    aux_loss_weight: float = 0.01


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2               # d_inner = expand * d_model
    dt_rank: int = 0              # 0 -> ceil(d_model / 16)


@dataclasses.dataclass(frozen=True)
class XLSTMConfig:
    num_heads: int = 4
    proj_factor_mlstm: float = 2.0     # up-projection for mLSTM blocks
    proj_factor_slstm: float = 4.0 / 3.0
    conv_width: int = 4
    slstm_every: int = 8               # one sLSTM block per this many layers


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                   # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int

    # layer pattern: cycled kinds, len must divide num_layers (decoder)
    # kinds: "global" | "local" (attention), "mamba", "mlstm", "slstm"
    layer_pattern: Tuple[str, ...] = ("global",)
    window: Optional[int] = None          # local-attention window
    attn_softcap: Optional[float] = None
    final_softcap: Optional[float] = None
    use_qk_norm: bool = False
    use_post_norms: bool = False          # gemma2/3 sandwich norms
    rms_weight_offset: float = 0.0        # 1.0 for gemma family
    rms_norm_eps: float = 1e-6            # every RMSNorm of the model
    rope_theta: float = 10_000.0
    rope_theta_local: Optional[float] = None   # gemma3 local layers use 10k
    mlp_activation: str = "silu"          # silu (gated) | gelu (ungated)

    moe: Optional[MoEConfig] = None
    # which decoder layers are MoE: "all", "every_2", "all_but_first", "none"
    moe_layers: str = "none"
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    xlstm: Optional[XLSTMConfig] = None

    # encoder-decoder (whisper): encoder_layers bidirectional + cross-attn
    encoder_layers: int = 0
    # modality frontend stub: None | "audio" | "vision"
    frontend: Optional[str] = None
    frontend_tokens: int = 256            # stub prefix length (vision)

    embed_scale: bool = False             # gemma scales embeds by sqrt(d)
    dtype: str = "bfloat16"
    # activation checkpointing inside the layer scan:
    #   "full" — save nothing, re-forward in backward (8ND flops)
    #   "dots" — save matmul outputs with no batch dims (6ND flops,
    #            more live activation memory)  [§Perf-C.1]
    remat_policy: str = "full"

    # which (arch x shape) cells run; long_500k only for sub-quadratic
    supports_long_context: bool = False

    @property
    def is_encoder_decoder(self) -> bool:
        return self.encoder_layers > 0

    def layer_kinds(self) -> Tuple[str, ...]:
        """Per-layer kinds: the pattern cycles and truncates (gemma3's 62
        layers over a 6-layer 5:1 pattern end mid-cycle, like the real
        model).  'attn' is an alias for 'global'."""
        reps = -(-self.num_layers // len(self.layer_pattern))
        kinds = (tuple(self.layer_pattern) * reps)[: self.num_layers]
        return tuple("global" if k == "attn" else k for k in kinds)

    def is_moe_layer(self, idx: int) -> bool:
        if self.moe is None or self.moe_layers == "none":
            return False
        if self.moe_layers == "all":
            return True
        if self.moe_layers == "every_2":
            return idx % 2 == 1
        if self.moe_layers == "all_but_first":
            return idx > 0
        raise ValueError(self.moe_layers)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str     # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


def cell_is_supported(cfg: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Whether (arch x shape) runs; returns (ok, reason-if-skipped)."""
    if shape.name == "long_500k" and not cfg.supports_long_context:
        return False, ("quadratic full attention at 500k context; skipped per "
                       "brief (see DESIGN.md §6)")
    return True, ""


def input_specs(cfg: ModelConfig, shape: ShapeConfig):
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if shape.kind == "train":
        specs = {
            "tokens": jax.ShapeDtypeStruct((b, s), i32),
            "labels": jax.ShapeDtypeStruct((b, s), i32),
        }
        if cfg.frontend == "vision":
            specs["vision_embeds"] = jax.ShapeDtypeStruct(
                (b, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16)
        if cfg.is_encoder_decoder:
            # audio stub: precomputed frame embeddings for the encoder
            specs["encoder_embeds"] = jax.ShapeDtypeStruct(
                (b, s, cfg.d_model), jnp.bfloat16)
        return specs
    if shape.kind == "prefill":
        specs = {"tokens": jax.ShapeDtypeStruct((b, s), i32)}
        if cfg.frontend == "vision":
            specs["vision_embeds"] = jax.ShapeDtypeStruct(
                (b, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16)
        if cfg.is_encoder_decoder:
            specs["encoder_embeds"] = jax.ShapeDtypeStruct(
                (b, s, cfg.d_model), jnp.bfloat16)
        return specs
    if shape.kind == "decode":
        # one new token against a cache of seq_len
        specs = {
            "tokens": jax.ShapeDtypeStruct((b,), i32),
            "lengths": jax.ShapeDtypeStruct((b,), i32),
        }
        return specs
    raise ValueError(shape.kind)
