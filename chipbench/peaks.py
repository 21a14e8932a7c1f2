"""Published chip peaks and the model FLOPs that utilization is counted in.

Peaks are keyed by ``device_kind`` as JAX reports it.  A device that is not
in the table is an error, never a default.

Source for TPU v5e: Google Cloud documentation, "TPU v5e" (system
architecture page): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at
819 GB/s per chip.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def peak(device_kind: str, key: str = "bf16_flops") -> float:
    """One published peak of ``device_kind``; unknown devices raise."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind][key]


def matmul_params(cfg: Dict) -> int:
    """Parameters that enter a matrix multiplication per token: the blocks'
    projections and the output head (the embedding lookup is a gather, and
    a tied head is counted once, as the head).  Biases and norms are not
    matmul parameters."""
    d, ff, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hd = d // cfg["num_attention_heads"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    attn = d * q + 2 * d * kv + q * d
    mlp = 3 * d * ff
    return cfg["num_hidden_layers"] * (attn + mlp) + d * v


def flops_per_token(cfg: Dict, seq_len: int) -> float:
    """Training FLOPs per token, forward and backward, no recomputation:
    6 N + 12 L H Q T (PaLM, Chowdhery et al. 2022, App. B), with N the
    matmul parameters, L layers, H heads, Q head size and T the sequence
    length."""
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    attn = (12 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] * hd
            * seq_len)
    return 6.0 * matmul_params(cfg) + attn
