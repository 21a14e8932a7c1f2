"""Generic transformer block stack (dense / MoE / VLM / enc-dec blocks).

Blocks are *stacked* along a leading layer axis and executed with
``jax.lax.scan`` so HLO size is depth-independent.  Every block honors a
per-layer ``gate`` in [0, 1]: the FedPairing logical split multiplies each
residual delta by the gate, so ``gate=0`` turns the layer into identity —
that is how a client "skips" the layers assigned to its partner while
staying a uniform SPMD program (see DESIGN.md §3).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ArchFamily, AttentionKind, NormKind
from repro.models import attention as attn
from repro.models import common, moe as moe_lib


# ---------------------------------------------------------------------------
# the configuration's norm and rope tables (every dense-block path)
# ---------------------------------------------------------------------------

def norm_init(cfg: ArchConfig, n: Optional[int], dtype=jnp.float32):
    """An RMSNorm's scale (ones); a LayerNorm's scale (ones) and bias
    (zeros) as one leaf, ``norm_leaf``: (2, d), or (n, 2, d) over n
    layers.  One leaf per norm keeps every norm a single named leaf of
    the tree, whichever the configuration's kind."""
    scale = common.rms_norm_init(n, cfg.d_model, dtype)
    if cfg.norm == NormKind.LAYER:
        return norm_leaf(scale, jnp.zeros_like(scale))
    return scale


def norm_leaf(scale: jnp.ndarray, bias: jnp.ndarray) -> jnp.ndarray:
    """A LayerNorm's leaf from its scale and bias, ``(..., d)`` each: the
    two stacked on axis -2."""
    return jnp.stack([scale, bias], axis=-2)


def norm_parts(w: jnp.ndarray, cfg: ArchConfig
               ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """(scale, bias) of a norm leaf; an RMSNorm has no bias (None)."""
    if cfg.norm == NormKind.LAYER:
        return w[..., 0, :], w[..., 1, :]
    return w, None


def norm(x: jnp.ndarray, w: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    """The configuration's norm: RMSNorm, or LayerNorm with a bias."""
    with jax.named_scope("norm"):
        scale, bias = norm_parts(w, cfg)
        if bias is None:
            return common.rms_norm(x, scale, cfg.norm_eps)
        return common.layer_norm(x, scale, bias, cfg.norm_eps)


def rope_tables(cfg: ArchConfig, positions: jnp.ndarray
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin ``(..., S, rotary_dim // 2)`` for integer positions: the
    frequencies run over the rotated dimensions only."""
    return common.rope_cos_sin(positions, max(cfg.rotary_dim, 2),
                               cfg.rope_theta)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def block_stack_init(key, cfg: ArchConfig, n: int, *, cross: bool = False,
                     dtype=jnp.float32) -> Dict:
    """Params for ``n`` stacked blocks: norms + attention (+cross) + FFN/MoE."""
    ka, kc, kf, kn = jax.random.split(key, 4)
    hd = cfg.resolved_head_dim
    p: Dict = {
        "ln_attn": norm_init(cfg, n, dtype),
        "ln_mlp": norm_init(cfg, n, dtype),
        "attn": attn.attn_init(ka, n, cfg.d_model, cfg.num_heads,
                               cfg.num_kv_heads, hd, cfg.qkv_bias, dtype),
    }
    if cross:
        p["ln_cross"] = norm_init(cfg, n, dtype)
        p["cross"] = attn.cross_attn_init(kc, n, cfg.d_model, cfg.num_heads,
                                          cfg.num_kv_heads, hd, dtype)
    if cfg.family == ArchFamily.MOE:
        p["moe"] = moe_lib.moe_init(kf, n, cfg, dtype)
    else:
        p["mlp"] = common.swiglu_init(kf, n, cfg.d_model, cfg.d_ff, dtype)
    return p


def lm_head_init(key, cfg: ArchConfig, dtype=jnp.float32) -> Dict:
    ke, ko = jax.random.split(key)
    p = {
        "embed": common.embed_init(ke, cfg.padded_vocab, cfg.d_model, dtype),
        "ln_f": norm_init(cfg, None, dtype),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = common.dense_init(ko, cfg.d_model, cfg.padded_vocab, dtype)
    return p


# ---------------------------------------------------------------------------
# forward (training / prefill)
# ---------------------------------------------------------------------------

def _ffn(p_l: Dict, h: jnp.ndarray, cfg: ArchConfig
         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if cfg.family == ArchFamily.MOE:
        ctx = moe_lib.ep_context()
        if ctx is not None:
            return moe_lib.moe_apply_ep(p_l["moe"], h, cfg, *ctx)
        return moe_lib.moe_apply(p_l["moe"], h, cfg)
    out = common.swiglu(h, p_l["mlp"]["w_gate"], p_l["mlp"]["w_up"],
                        p_l["mlp"]["w_down"])
    return out, jnp.zeros((), jnp.float32)


def block_apply(p_l: Dict, x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray,
                cfg: ArchConfig, gate: jnp.ndarray,
                enc_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
                *, causal: bool = True, sliding_window: Optional[int] = None,
                seq_shardings: Optional[Tuple] = None,
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One block.  ``gate`` scalar (or (B,1,1)-broadcastable) residual gate.

    ``seq_shardings = (sharded, gathered)`` enables Megatron-style sequence
    parallelism: the residual stream lives sequence-sharded over the model
    axis; entering attention/MLP the activations are all-gathered
    (``gathered`` constraint) and the block outputs are reduce-scattered
    back (``sharded`` constraint).  This pins GSPMD to gathering the
    (small) activations instead of the (large) per-layer weights.
    """
    hd = cfg.resolved_head_dim
    if sliding_window is not None:
        window = sliding_window                 # explicit override
    elif cfg.attention == AttentionKind.SLIDING:
        window = cfg.sliding_window
    else:
        window = 0

    def gather(t):
        return jax.lax.with_sharding_constraint(t, seq_shardings[1]) \
            if seq_shardings else t

    def scatter(t):
        return jax.lax.with_sharding_constraint(t, seq_shardings[0]) \
            if seq_shardings else t

    h = gather(norm(x, p_l["ln_attn"], cfg))
    q, k, v = attn.qkv_project(h, p_l["attn"], cfg.num_heads, cfg.num_kv_heads, hd)
    q = common.apply_rope(q, cos[:, :, None, :], sin[:, :, None, :])
    k = common.apply_rope(k, cos[:, :, None, :], sin[:, :, None, :])
    o = attn.attend(q, k, v, causal=causal, sliding_window=window)
    x = x + gate * scatter(attn.output_project(o, p_l["attn"]))

    if enc_kv is not None:
        h = gather(norm(x, p_l["ln_cross"], cfg))
        x = x + gate * scatter(attn.cross_attend(
            h, enc_kv, p_l["cross"], cfg.num_heads, cfg.num_kv_heads, hd))

    h = gather(norm(x, p_l["ln_mlp"], cfg))
    delta, aux = _ffn(p_l, h, cfg)
    x = x + gate * scatter(delta)
    return x, gate * aux


def stack_apply(params: Dict, x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray,
                cfg: ArchConfig, gates: Optional[jnp.ndarray] = None,
                enc_kv_stacked: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
                *, n_layers: Optional[int] = None, causal: bool = True,
                sliding_window: Optional[int] = None, remat: bool = False,
                residual_sharding=None, unroll=1,
                seq_shardings: Optional[Tuple] = None,
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scan the stacked blocks.  ``gates`` (n_layers,) float per-layer gate.

    ``remat`` checkpoints the scan body (activation memory = one residual
    carry per block boundary).  ``residual_sharding`` (a NamedSharding)
    constrains the carried residual — e.g. sequence-sharded over "model"
    so the saved carries fit HBM at train_4k scale.
    """
    n = n_layers if n_layers is not None else cfg.num_layers
    if gates is None:
        gates = jnp.ones((n,), x.dtype)

    def body(carry, scanned):
        xc, aux = carry
        p_l, g = scanned["p"], scanned["g"]
        ekv = (scanned["ek"], scanned["ev"]) if "ek" in scanned else None
        xc, a = block_apply(p_l, xc, cos, sin, cfg, g.astype(xc.dtype), ekv,
                            causal=causal, sliding_window=sliding_window,
                            seq_shardings=seq_shardings)
        if residual_sharding is not None:
            xc = jax.lax.with_sharding_constraint(xc, residual_sharding)
        return (xc, aux + a), None

    if remat:
        body = jax.checkpoint(body)
    scanned = {"p": params, "g": gates}
    if enc_kv_stacked is not None:
        scanned["ek"], scanned["ev"] = enc_kv_stacked
    if residual_sharding is not None:
        x = jax.lax.with_sharding_constraint(x, residual_sharding)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               scanned, unroll=unroll)
    return x, aux


# ---------------------------------------------------------------------------
# decode (single token, KV cache)
# ---------------------------------------------------------------------------

def decode_block_apply(p_l: Dict, x: jnp.ndarray, cos: jnp.ndarray,
                       sin: jnp.ndarray, cache_k: jnp.ndarray,
                       cache_v: jnp.ndarray, index: jnp.ndarray,
                       spec: attn.CacheSpec, cfg: ArchConfig,
                       enc_kv: Optional[Tuple] = None,
                       sp_decode=None,
                       ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One block, one token.  x (B,1,D).  Returns (x, cache_k, cache_v).

    ``sp_decode = (mesh, batch_axes)`` switches cache attention to the
    explicit sequence-parallel flash-decode merge (§Perf)."""
    hd = cfg.resolved_head_dim
    h = norm(x, p_l["ln_attn"], cfg)
    q, k, v = attn.qkv_project(h, p_l["attn"], cfg.num_heads, cfg.num_kv_heads, hd)
    q = common.apply_rope(q, cos[:, :, None, :], sin[:, :, None, :])
    k = common.apply_rope(k, cos[:, :, None, :], sin[:, :, None, :])
    cache_k, cache_v = attn.cache_update(cache_k, cache_v, k, v, index, spec)
    if sp_decode is not None:
        o = attn.decode_attend_seq_parallel(q, cache_k, cache_v, index, spec,
                                            *sp_decode)
    else:
        o = attn.decode_attend(q, cache_k, cache_v, index, spec)
    x = x + attn.output_project(o, p_l["attn"])

    if enc_kv is not None:
        h = norm(x, p_l["ln_cross"], cfg)
        x = x + attn.cross_attend(h, enc_kv, p_l["cross"],
                                  cfg.num_heads, cfg.num_kv_heads, hd)

    h = norm(x, p_l["ln_mlp"], cfg)
    delta, _ = _ffn(p_l, h, cfg)
    return x + delta, cache_k, cache_v


def decode_stack_apply(params: Dict, x: jnp.ndarray, cos, sin,
                       cache: Dict[str, jnp.ndarray], index: jnp.ndarray,
                       spec: attn.CacheSpec, cfg: ArchConfig,
                       enc_kv_stacked: Optional[Tuple] = None,
                       unroll=1, sp_decode=None,
                       ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Scan decode over stacked blocks; cache arrays are (L, B, S, Hkv, d)."""

    def body(xc, scanned):
        p_l, ck, cv = scanned["p"], scanned["ck"], scanned["cv"]
        ekv = (scanned["ek"], scanned["ev"]) if "ek" in scanned else None
        xc, ck, cv = decode_block_apply(p_l, xc, cos, sin, ck, cv, index, spec,
                                        cfg, ekv, sp_decode=sp_decode)
        return xc, {"ck": ck, "cv": cv}

    scanned = {"p": params, "ck": cache["k"], "cv": cache["v"]}
    if enc_kv_stacked is not None:
        scanned["ek"], scanned["ev"] = enc_kv_stacked
    x, new = jax.lax.scan(body, x, scanned, unroll=unroll)
    return x, {"k": new["ck"], "v": new["cv"]}


# ---------------------------------------------------------------------------
# embeddings / head
# ---------------------------------------------------------------------------

def embed(params: Dict, tokens: jnp.ndarray, cfg: ArchConfig,
          dtype=None) -> jnp.ndarray:
    dtype = dtype or jnp.dtype(cfg.dtype)
    return params["embed"].astype(dtype)[tokens]


def lm_logits(params: Dict, x: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    with jax.named_scope("lm_head"):
        h = norm(x, params["ln_f"], cfg)
        if cfg.tie_embeddings:
            # a paired split flow runs its head on the partner's copy of the
            # tied matrix, which the vmapped engine passes as ``tied_head``
            w = params.get("tied_head", params["embed"]).T
        else:
            w = params["unembed"]
        return jnp.einsum("bsd,dv->bsv", h, w.astype(h.dtype))
