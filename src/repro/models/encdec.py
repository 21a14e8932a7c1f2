"""Encoder-decoder backbone (seamless-m4t): bidirectional encoder over
stubbed frame embeddings + causal decoder with cross-attention.

The mel-spectrogram/conv codec frontend is a STUB per the assignment
carve-out: the encoder consumes precomputed frame embeddings
``(B, S_enc, d_model)`` supplied by ``input_specs()``.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import common, transformer


def encdec_init(key, cfg: ArchConfig, dtype=jnp.float32) -> Dict:
    ke, kd, kh = jax.random.split(key, 3)
    p = transformer.lm_head_init(kh, cfg, dtype)
    p["encoder"] = transformer.block_stack_init(
        ke, cfg, cfg.num_encoder_layers, cross=False, dtype=dtype)
    p["enc_ln_f"] = common.rms_norm_init(None, cfg.d_model, dtype)
    p["decoder"] = transformer.block_stack_init(
        kd, cfg, cfg.num_layers, cross=True, dtype=dtype)
    return p


def encode(params: Dict, frames: jnp.ndarray, cfg: ArchConfig, *,
           remat: bool = False, residual_sharding=None,
           unroll=1) -> jnp.ndarray:
    """frames (B,S_enc,D) stub embeddings -> encoder output (B,S_enc,D)."""
    frames = frames.astype(jnp.dtype(cfg.dtype))
    S = frames.shape[1]
    pos = jnp.arange(S)[None, :]
    cos, sin = transformer.rope_tables(cfg, pos)
    x, _ = transformer.stack_apply(params["encoder"], frames, cos, sin, cfg,
                                   n_layers=cfg.num_encoder_layers,
                                   causal=False, remat=remat,
                                   residual_sharding=residual_sharding,
                                   unroll=unroll)
    return common.rms_norm(x, params["enc_ln_f"], cfg.norm_eps)


def stacked_cross_kv(params: Dict, enc_out: jnp.ndarray, cfg: ArchConfig
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Precompute per-decoder-layer cross K/V: (L, B, S_enc, Hkv, d)."""
    hd = cfg.resolved_head_dim

    def one(p_cross):
        return attn.encode_cross_kv(enc_out, p_cross, cfg.num_kv_heads, hd)

    ek, ev = jax.vmap(one)(params["decoder"]["cross"])
    dt = jnp.dtype(cfg.dtype)
    return ek.astype(dt), ev.astype(dt)


def decode_train(params: Dict, tokens: jnp.ndarray, enc_out: jnp.ndarray,
                 cfg: ArchConfig, gates: Optional[jnp.ndarray] = None, *,
                 remat: bool = False, residual_sharding=None,
                 unroll=1) -> jnp.ndarray:
    """Teacher-forced decoder pass.  Returns hidden (B,S,D)."""
    x = transformer.embed(params, tokens, cfg)
    S = tokens.shape[1]
    pos = jnp.arange(S)[None, :]
    cos, sin = transformer.rope_tables(cfg, pos)
    ekv = stacked_cross_kv(params, enc_out, cfg)
    x, _ = transformer.stack_apply(params["decoder"], x, cos, sin, cfg,
                                   gates=gates, enc_kv_stacked=ekv,
                                   n_layers=cfg.num_layers, causal=True,
                                   remat=remat,
                                   residual_sharding=residual_sharding,
                                   unroll=unroll)
    return x


def forward(params: Dict, frames: jnp.ndarray, tokens: jnp.ndarray,
            cfg: ArchConfig, gates: Optional[jnp.ndarray] = None, *,
            remat: bool = False, residual_sharding=None,
            unroll=1) -> jnp.ndarray:
    """Full enc-dec forward -> decoder hidden states."""
    enc_out = encode(params, frames, cfg, remat=remat,
                     residual_sharding=residual_sharding, unroll=unroll)
    return decode_train(params, tokens, enc_out, cfg, gates, remat=remat,
                        residual_sharding=residual_sharding, unroll=unroll)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def init_decode_state(params: Dict, enc_out: jnp.ndarray, cfg: ArchConfig,
                      batch: int, spec: attn.CacheSpec) -> Dict:
    """Pre-encode source once; carry decoder KV cache + cross KV."""
    ek, ev = stacked_cross_kv(params, enc_out, cfg)
    return {
        "kv": attn.init_kv_cache(cfg.num_layers, batch, spec, cfg.num_kv_heads,
                                 cfg.resolved_head_dim, jnp.dtype(cfg.dtype)),
        "cross_k": ek.astype(jnp.dtype(cfg.dtype)),
        "cross_v": ev.astype(jnp.dtype(cfg.dtype)),
        "index": jnp.zeros((), jnp.int32),
    }


def decode_step(params: Dict, tokens: jnp.ndarray, state: Dict,
                cfg: ArchConfig, spec: attn.CacheSpec, unroll=1
                ) -> Tuple[jnp.ndarray, Dict]:
    """One decoder token with self-attn cache + precomputed cross KV."""
    x = transformer.embed(params, tokens, cfg)
    index = state["index"]
    pos = jnp.full((1, 1), index, jnp.int32)
    cos, sin = transformer.rope_tables(cfg, pos)
    x, kv = transformer.decode_stack_apply(
        params["decoder"], x, cos, sin, state["kv"], index, spec, cfg,
        enc_kv_stacked=(state["cross_k"], state["cross_v"]), unroll=unroll)
    new_state = dict(state, kv=kv, index=index + 1)
    return x, new_state
