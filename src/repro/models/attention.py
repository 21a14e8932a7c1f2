"""Grouped-query attention with RoPE / M-RoPE, sliding windows and KV caches.

The grouped einsum form ``(B,S,Hkv,G,d) x (B,L,Hkv,d)`` is used throughout so
GQA never materializes repeated KV heads — important both for HBM footprint
and for keeping the roofline byte counts honest.

Two execution paths:
* ``attend``            — training / prefill (full sequence, fused softmax).
* ``decode_attend``     — single-token decode against a (possibly ring-buffer)
                          KV cache.
The Pallas flash-attention kernel in ``repro.kernels`` implements the same
contract as ``attend`` and is validated against it; model code selects the
implementation via config (XLA path is the default for CPU + dry-run).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import common

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def attn_init(key, n: Optional[int], d_model: int, num_heads: int,
              num_kv_heads: int, head_dim: int, qkv_bias: bool = False,
              dtype=jnp.float32) -> Dict[str, jnp.ndarray]:
    """Per-layer (or ``n``-stacked) attention projection params."""
    kq, kk, kv, ko, kb = jax.random.split(key, 5)
    q_out, kv_out = num_heads * head_dim, num_kv_heads * head_dim
    if n is None:
        p = {
            "wq": common.dense_init(kq, d_model, q_out, dtype),
            "wk": common.dense_init(kk, d_model, kv_out, dtype),
            "wv": common.dense_init(kv, d_model, kv_out, dtype),
            "wo": common.dense_init(ko, q_out, d_model, dtype),
        }
        if qkv_bias:
            p["bq"] = jnp.zeros((q_out,), dtype)
            p["bk"] = jnp.zeros((kv_out,), dtype)
            p["bv"] = jnp.zeros((kv_out,), dtype)
    else:
        p = {
            "wq": common.stacked_dense_init(kq, n, d_model, q_out, dtype),
            "wk": common.stacked_dense_init(kk, n, d_model, kv_out, dtype),
            "wv": common.stacked_dense_init(kv, n, d_model, kv_out, dtype),
            "wo": common.stacked_dense_init(ko, n, q_out, d_model, dtype),
        }
        if qkv_bias:
            p["bq"] = jnp.zeros((n, q_out), dtype)
            p["bk"] = jnp.zeros((n, kv_out), dtype)
            p["bv"] = jnp.zeros((n, kv_out), dtype)
    return p


def qkv_project(x: jnp.ndarray, p: Dict[str, jnp.ndarray], num_heads: int,
                num_kv_heads: int, head_dim: int
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(B,S,D) -> q (B,S,Hq,d), k/v (B,S,Hkv,d)."""
    dtype = x.dtype
    B, S, _ = x.shape
    q = jnp.einsum("bsd,de->bse", x, p["wq"].astype(dtype))
    k = jnp.einsum("bsd,de->bse", x, p["wk"].astype(dtype))
    v = jnp.einsum("bsd,de->bse", x, p["wv"].astype(dtype))
    if "bq" in p:
        q = q + p["bq"].astype(dtype)
        k = k + p["bk"].astype(dtype)
        v = v + p["bv"].astype(dtype)
    return (q.reshape(B, S, num_heads, head_dim),
            k.reshape(B, S, num_kv_heads, head_dim),
            v.reshape(B, S, num_kv_heads, head_dim))


# ---------------------------------------------------------------------------
# masks
# ---------------------------------------------------------------------------

def build_mask(q_len: int, kv_len: int, *, causal: bool,
               sliding_window: int = 0, q_offset: int = 0) -> jnp.ndarray:
    """Boolean (q_len, kv_len) mask; True = attend.

    ``q_offset`` shifts query positions (decode / chunked prefill).
    """
    qpos = jnp.arange(q_len)[:, None] + q_offset
    kpos = jnp.arange(kv_len)[None, :]
    mask = jnp.ones((q_len, kv_len), bool)
    if causal:
        mask &= kpos <= qpos
    if sliding_window:
        mask &= kpos > qpos - sliding_window
    return mask


# ---------------------------------------------------------------------------
# core attention
# ---------------------------------------------------------------------------

def _grouped_attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    mask: Optional[jnp.ndarray]) -> jnp.ndarray:
    """q (B,S,Hkv,G,d), k/v (B,L,Hkv,d), mask broadcastable (S,L) -> (B,S,Hkv,G,d)."""
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    scores = jnp.einsum("bshgd,blhd->bhgsl", q, k).astype(jnp.float32) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhgsl,blhd->bshgd", probs, v)


def attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
           causal: bool = True, sliding_window: int = 0,
           mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Full-sequence attention. q (B,S,Hq,d), k/v (B,L,Hkv,d) -> (B,S,Hq,d)."""
    B, S, Hq, d = q.shape
    L, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    if mask is None:
        mask = build_mask(S, L, causal=causal, sliding_window=sliding_window)
    out = _grouped_attend(q.reshape(B, S, Hkv, G, d), k, v, mask)
    return out.reshape(B, S, Hq, d)


def output_project(o: jnp.ndarray, p: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    B, S = o.shape[:2]
    return jnp.einsum("bse,ed->bsd", o.reshape(B, S, -1), p["wo"].astype(o.dtype))


# ---------------------------------------------------------------------------
# KV cache (supports plain and ring-buffer/sliding layouts)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CacheSpec:
    """Static description of a per-layer KV cache."""
    cache_len: int          # slots (== window for ring buffers)
    ring: bool              # ring-buffer indexing (sliding window decode)


def make_cache_spec(seq_len: int, sliding_window: int = 0) -> CacheSpec:
    if sliding_window and sliding_window < seq_len:
        return CacheSpec(cache_len=sliding_window, ring=True)
    return CacheSpec(cache_len=seq_len, ring=False)


def init_kv_cache(n_layers: int, batch: int, spec: CacheSpec, num_kv_heads: int,
                  head_dim: int, dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    shape = (n_layers, batch, spec.cache_len, num_kv_heads, head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_update(cache_k: jnp.ndarray, cache_v: jnp.ndarray,
                 k_new: jnp.ndarray, v_new: jnp.ndarray, index: jnp.ndarray,
                 spec: CacheSpec) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Write one step (B,1,Hkv,d) at logical position ``index``."""
    slot = index % spec.cache_len if spec.ring else index
    cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k_new.astype(cache_k.dtype), slot, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v_new.astype(cache_v.dtype), slot, axis=1)
    return cache_k, cache_v


def decode_attend(q: jnp.ndarray, cache_k: jnp.ndarray, cache_v: jnp.ndarray,
                  index: jnp.ndarray, spec: CacheSpec) -> jnp.ndarray:
    """Single-token attention vs cache.

    q (B,1,Hq,d); cache (B,L,Hkv,d); ``index`` = logical position of the new
    token (its K/V must already be written).  Valid slots:
      * plain: slot <= index
      * ring:  slot written within the last ``cache_len`` steps (all slots once
               warm; before that, slot <= index)
    """
    B, _, Hq, d = q.shape
    L, Hkv = cache_k.shape[1], cache_k.shape[2]
    G = Hq // Hkv
    slots = jnp.arange(L)
    if spec.ring:
        # Ring validity: every *written* slot is within the window by
        # construction, so validity is just "has been written": all slots once
        # warm (index >= L-1), otherwise slot <= index.
        valid = jnp.where(index >= L - 1, jnp.ones((L,), bool), slots <= index)
    else:
        valid = slots <= index
    mask = valid[None, :]  # (1, L) broadcast over q_len=1
    out = _grouped_attend(q.reshape(B, 1, Hkv, G, d), cache_k, cache_v, mask)
    return out.reshape(B, 1, Hq, d)


def decode_attend_seq_parallel(q: jnp.ndarray, cache_k: jnp.ndarray,
                               cache_v: jnp.ndarray, index: jnp.ndarray,
                               spec: CacheSpec, mesh, batch_axes) -> jnp.ndarray:
    """Flash-decoding-style decode attention with the KV cache SEQUENCE
    dimension sharded over the "model" axis (shard_map, explicit partial-
    softmax merge) — the beyond-paper §Perf optimization for decode shapes.

    Each model shard computes unnormalized partial attention over its seq
    chunk plus a local (max, denom); the merge is two psums.  Baseline GSPMD
    instead all-gathers the cache per layer.  Plain (non-ring) caches only.
    """
    assert not spec.ring, "seq-parallel decode targets plain caches"
    from jax.sharding import PartitionSpec as P

    B, _, Hq, d = q.shape
    L, Hkv = cache_k.shape[1], cache_k.shape[2]
    G = Hq // Hkv
    n_shards = mesh.shape["model"]
    chunk = L // n_shards
    scale = 1.0 / math.sqrt(d)
    bx = batch_axes if B % _axes_size(mesh, batch_axes) == 0 else None

    def body(q_l, k_l, v_l, index_l):
        shard = jax.lax.axis_index("model")
        offset = shard * chunk
        qg = q_l.reshape(q_l.shape[0], 1, Hkv, G, d)
        s = jnp.einsum("bshgd,blhd->bhgsl", qg, k_l).astype(jnp.float32) * scale
        valid = (offset + jnp.arange(chunk)) <= index_l
        s = jnp.where(valid[None, None, None, None, :], s, -1e30)
        m = jnp.max(s, axis=-1)                      # (B,Hkv,G,1)
        p = jnp.exp(s - m[..., None])
        p = jnp.where(valid[None, None, None, None, :], p, 0.0)
        l = jnp.sum(p, axis=-1)
        o = jnp.einsum("bhgsl,blhd->bshgd", p.astype(v_l.dtype), v_l
                       ).astype(jnp.float32)
        m_max = jax.lax.pmax(m, "model")
        w = jnp.exp(m - m_max)                       # (B,Hkv,G,1)
        w_o = w.transpose(0, 3, 1, 2)[..., None]     # -> (B,1,Hkv,G,1)
        o = jax.lax.psum(o * w_o, "model")
        l = jax.lax.psum(l * w, "model")
        l = jnp.maximum(l, 1e-30)
        out = o / l.transpose(0, 3, 1, 2)[..., None]
        return out.reshape(q_l.shape[0], 1, Hq, d).astype(q_l.dtype)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bx, None, None, None), P(bx, "model", None, None),
                  P(bx, "model", None, None), P()),
        out_specs=P(bx, None, None, None),
        axis_names={"model"} | (set(batch_axes) if bx else set()),
        check_vma=False,
    )(q, cache_k, cache_v, index)


def _axes_size(mesh, axes) -> int:
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


# ---------------------------------------------------------------------------
# cross attention (enc-dec)
# ---------------------------------------------------------------------------

def cross_attn_init(key, n: Optional[int], d_model: int, num_heads: int,
                    num_kv_heads: int, head_dim: int, dtype=jnp.float32):
    return attn_init(key, n, d_model, num_heads, num_kv_heads, head_dim,
                     qkv_bias=False, dtype=dtype)


def cross_attend(x: jnp.ndarray, enc_kv: Tuple[jnp.ndarray, jnp.ndarray],
                 p: Dict[str, jnp.ndarray], num_heads: int, num_kv_heads: int,
                 head_dim: int) -> jnp.ndarray:
    """Decoder cross-attention against precomputed encoder K/V (B,L,Hkv,d)."""
    B, S, _ = x.shape
    q = jnp.einsum("bsd,de->bse", x, p["wq"].astype(x.dtype)
                   ).reshape(B, S, num_heads, head_dim)
    k, v = enc_kv
    out = attend(q, k, v, causal=False)
    return output_project(out, p)


def encode_cross_kv(enc_out: jnp.ndarray, p: Dict[str, jnp.ndarray],
                    num_kv_heads: int, head_dim: int
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Precompute cross-attention K/V once per request from encoder output."""
    B, L, _ = enc_out.shape
    k = jnp.einsum("bld,de->ble", enc_out, p["wk"].astype(enc_out.dtype)
                   ).reshape(B, L, num_kv_heads, head_dim)
    v = jnp.einsum("bld,de->ble", enc_out, p["wv"].astype(enc_out.dtype)
                   ).reshape(B, L, num_kv_heads, head_dim)
    return k, v
