"""Architecture / run configuration dataclasses.

Every assigned architecture gets one module in ``repro.configs`` exporting a
``CONFIG`` (the exact full-scale config from the assignment) and a
``smoke_config()`` (a reduced variant of the same family: <=2 layers,
d_model<=512, <=4 experts) used by CPU smoke tests.

Configs are plain frozen dataclasses — hashable so they can be closed over by
``jax.jit``'d functions as static data.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Optional, Tuple


class ArchFamily(str, enum.Enum):
    DENSE = "dense"
    MOE = "moe"
    SSM = "ssm"            # rwkv6
    HYBRID = "hybrid"      # zamba2: mamba2 backbone + shared attention block
    VLM = "vlm"            # qwen2-vl: dense decoder + M-RoPE, stubbed vision
    AUDIO = "audio"        # seamless: encoder-decoder, stubbed codec frontend


class NormKind(str, enum.Enum):
    RMS = "rms"                    # RMSNorm, scale only
    LAYER = "layer"                # LayerNorm, scale and bias


class AttentionKind(str, enum.Enum):
    FULL = "full"                  # causal full attention
    SLIDING = "sliding"            # causal sliding-window attention
    BIDIRECTIONAL = "bidirectional"  # encoder self-attention


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    """A single transformer/SSM/hybrid architecture.

    ``num_layers`` counts *blocks* — the unit of the paper's logical split
    (propagation lengths L_i index into this stack).
    """

    name: str
    family: ArchFamily
    num_layers: int
    d_model: int
    num_heads: int                      # query heads (0 for attention-free)
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    # --- attention ---
    head_dim: int = 0                   # 0 -> d_model // num_heads
    attention: AttentionKind = AttentionKind.FULL
    sliding_window: int = 0             # used when attention == SLIDING
    qkv_bias: bool = False              # qwen-style QKV bias
    rope_theta: float = 10000.0
    partial_rotary_factor: float = 1.0  # share of each head rope rotates
    mrope_sections: Tuple[int, int, int] = ()  # qwen2-vl M-RoPE (t, h, w) splits
    # --- MoE ---
    num_experts: int = 0
    num_shared_experts: int = 0
    top_k: int = 0
    moe_capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    expert_pad_to: int = 1              # pad expert dim for even sharding
    # --- SSM / hybrid ---
    ssm_state: int = 0                  # Mamba2 state size N
    ssm_head_dim: int = 64              # Mamba2 head dim P
    ssm_expand: int = 2                 # d_inner = expand * d_model
    ssm_conv_width: int = 4
    shared_attn_every: int = 0          # zamba2: shared block applied every k layers
    rwkv_head_dim: int = 64
    # --- enc-dec ---
    num_encoder_layers: int = 0         # >0 -> encoder-decoder
    encoder_seq_len: int = 0            # pre-encoded source length for decode stubs
    # --- modality frontend stubs ---
    frontend_tokens: int = 0            # patch/frame embeddings prepended (stub)
    # --- misc ---
    norm: NormKind = NormKind.RMS
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: str = "bfloat16"             # activation/compute dtype
    param_dtype: str = "float32"
    vocab_pad_to: int = 256
    source: str = ""                    # citation for the config

    # ------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        if self.num_heads:
            return self.d_model // self.num_heads
        return 0

    @property
    def rotary_dim(self) -> int:
        """Leading dimensions of each q/k head that rope rotates."""
        return int(self.resolved_head_dim * self.partial_rotary_factor)

    @property
    def padded_vocab(self) -> int:
        return _round_up(self.vocab_size, self.vocab_pad_to)

    @property
    def padded_experts(self) -> int:
        return _round_up(self.num_experts, self.expert_pad_to)

    @property
    def is_attention_free(self) -> bool:
        return self.num_heads == 0

    @property
    def is_encdec(self) -> bool:
        return self.num_encoder_layers > 0

    @property
    def supports_long_context_decode(self) -> bool:
        """True if decode state is sub-quadratic in context length.

        SSM/hybrid decode keeps O(1) state.  Attention archs qualify only via a
        sliding-window variant (bounded KV cache).
        """
        if self.family in (ArchFamily.SSM,):
            return True
        if self.family == ArchFamily.HYBRID:
            # shared attention block uses a sliding window in long-context mode
            return True
        return self.attention == AttentionKind.SLIDING and self.sliding_window > 0

    def param_count(self) -> int:
        """Analytical parameter count (exact for our implementation)."""
        from repro.models.registry import count_params_analytical

        return count_params_analytical(self)

    def active_param_count(self) -> int:
        """Params touched per token (MoE: shared + top_k routed experts)."""
        from repro.models.registry import count_params_analytical

        return count_params_analytical(self, active_only=True)

    def with_overrides(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class InputShape:
    """One of the assigned (seq_len, global_batch) workload shapes."""

    name: str
    seq_len: int
    global_batch: int
    mode: str  # "train" | "prefill" | "decode"


TRAIN_4K = InputShape("train_4k", 4096, 256, "train")
PREFILL_32K = InputShape("prefill_32k", 32768, 32, "prefill")
DECODE_32K = InputShape("decode_32k", 32768, 128, "decode")
LONG_500K = InputShape("long_500k", 524288, 1, "decode")

INPUT_SHAPES = {s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)}
