"""stablelm-1.6b — StableLM 2 1.6B: dense decoder, MHA, LayerNorm, partial
rotary, QKV bias, untied 100k head.

[hf:stabilityai/stablelm-2-1_6b] ``config.json`` (``model_type: stablelm``):
``num_hidden_layers`` 24, ``hidden_size`` 2048, ``num_attention_heads`` 32,
``num_key_value_heads`` 32 (head size 64), ``intermediate_size`` 5632,
``vocab_size`` 100352, ``hidden_act`` silu (gated MLP), ``use_qkv_bias``
true, ``tie_word_embeddings`` false, ``qk_layernorm`` false,
``use_parallel_residual`` false, ``rope_theta`` 10000,
``partial_rotary_factor`` 0.25 (rope rotates the first 16 of 64 dimensions
of each q/k head, frequencies over those 16), ``layer_norm_eps`` 1e-5
(``nn.LayerNorm`` with scale and bias before attention, before the MLP and
as the final norm).
"""
from repro.configs.base import ArchConfig, ArchFamily, AttentionKind, NormKind

CONFIG = ArchConfig(
    name="stablelm-1.6b",
    family=ArchFamily.DENSE,
    num_layers=24,
    d_model=2048,
    num_heads=32,
    num_kv_heads=32,
    d_ff=5632,
    vocab_size=100352,
    qkv_bias=True,
    rope_theta=10000.0,
    partial_rotary_factor=0.25,
    attention=AttentionKind.FULL,
    norm=NormKind.LAYER,
    norm_eps=1e-5,
    source="hf:stabilityai/stablelm-2-1_6b",
)


def smoke_config() -> ArchConfig:
    return CONFIG.with_overrides(
        dtype="float32",
        name="stablelm-smoke",
        num_layers=2,
        d_model=256,
        num_heads=4,
        num_kv_heads=4,
        d_ff=512,
        vocab_size=512,
    )
