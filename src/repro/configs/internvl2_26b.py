"""internvl2-26b [vlm] — arXiv:2404.16821 (hf-verified).

48L d_model=6144 48H (GQA kv=8) d_ff=16384 vocab=92553.
InternViT frontend is a STUB (input_specs provides patch embeddings,
prepended to the token stream); backbone is InternLM2-chat-20B
(arXiv:2403.17297): RMSNorm eps 1e-5, RoPE base 1e6.  Its dynamic NTK
scaling changes the frequencies only past 32768 positions, so within
them RoPE is plain at that base.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="internvl2-26b",
    family="vlm",
    num_layers=48,
    d_model=6144,
    num_heads=48,
    num_kv_heads=8,
    head_dim=128,
    d_ff=16384,
    vocab_size=92553,
    layer_pattern=("global",),
    rope_theta=1_000_000.0,
    rms_norm_eps=1e-5,
    frontend="vision",
    frontend_tokens=256,
    supports_long_context=False,
)
