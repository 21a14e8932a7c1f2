"""Shared model building blocks (pure JAX, pytree params, no flax).

Conventions
-----------
* Parameters are nested dicts of ``jnp.ndarray``.  Per-block parameters are
  STACKED along a leading ``num_layers`` axis so the block stack runs under
  ``jax.lax.scan`` — this keeps HLO size (and therefore 256/512-way SPMD
  compile time) independent of depth, and gives the FedPairing split a
  natural per-layer mask axis.
* Linear weights are stored ``(d_in, d_out)``; ``y = x @ W (+ b)``.
* ``dtype`` is the compute/activation dtype (bf16 by default at scale);
  parameters are kept in ``param_dtype`` (fp32) and cast at use.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype=jnp.float32, scale: float | None = None):
    """Truncated-normal fan-in init (llama-style 1/sqrt(d_in) unless given)."""
    if scale is None:
        scale = 1.0 / math.sqrt(d_in)
    return (jax.random.truncated_normal(key, -3.0, 3.0, (d_in, d_out)) * scale).astype(dtype)


def stacked_dense_init(key, n: int, d_in: int, d_out: int, dtype=jnp.float32,
                       scale: float | None = None):
    if scale is None:
        scale = 1.0 / math.sqrt(d_in)
    shape = (n, d_in, d_out)
    return (jax.random.truncated_normal(key, -3.0, 3.0, shape) * scale).astype(dtype)


def embed_init(key, vocab: int, d_model: int, dtype=jnp.float32):
    return (jax.random.normal(key, (vocab, d_model)) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------

def rms_norm(x: jnp.ndarray, gamma: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """RMSNorm in fp32 accumulation, cast back to input dtype."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * gamma.astype(jnp.float32)).astype(dtype)


def rms_norm_init(n: Optional[int], d: int, dtype=jnp.float32):
    shape = (d,) if n is None else (n, d)
    return jnp.ones(shape, dtype)


def layer_norm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
               eps: float = 1e-5) -> jnp.ndarray:
    """LayerNorm (biased variance) in fp32 accumulation, cast back to input
    dtype."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    xc = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xc), axis=-1, keepdims=True)
    out = xc * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# rotary position embeddings (standard + M-RoPE)
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    """Inverse frequencies, shape (head_dim // 2,), fp32."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def rope_cos_sin(positions: jnp.ndarray, head_dim: int, theta: float
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables for integer positions ``(..., S)`` -> ``(..., S, head_dim//2)``."""
    inv = rope_frequencies(head_dim, theta)
    angles = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Apply rotary embedding.

    ``x``: (..., S, H, D); ``cos``/``sin``: broadcastable to (..., S, 1, R/2).
    Uses the paired-halves convention (llama): rotate (x1, x2) of split
    halves of the first R dimensions; with R < D (partial rotary) the other
    D - R dimensions pass through unchanged.
    """
    rot = 2 * cos.shape[-1]
    if rot < x.shape[-1]:
        return jnp.concatenate([apply_rope(x[..., :rot], cos, sin),
                                x[..., rot:]], axis=-1)
    d_half = x.shape[-1] // 2
    x1, x2 = x[..., :d_half], x[..., d_half:]
    cos = cos.astype(x.dtype)
    sin = sin.astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def mrope_cos_sin(positions_thw: jnp.ndarray, head_dim: int, theta: float,
                  sections: Sequence[int]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Multimodal RoPE (qwen2-vl): three position channels (t, h, w).

    ``positions_thw``: (..., S, 3) integer positions.  ``sections`` gives how
    many of the ``head_dim//2`` frequency slots each channel owns
    (sum(sections) == head_dim // 2).  Returns cos/sin of shape
    (..., S, head_dim//2).
    """
    assert sum(sections) == head_dim // 2, (sections, head_dim)
    inv = rope_frequencies(head_dim, theta)  # (D/2,)
    # channel index per frequency slot: [0]*s0 + [1]*s1 + [2]*s2
    chan = jnp.repeat(jnp.arange(3), jnp.array(sections), total_repeat_length=head_dim // 2)
    pos = jnp.take_along_axis(
        positions_thw.astype(jnp.float32),
        jnp.broadcast_to(chan, positions_thw.shape[:-1] + (head_dim // 2,)).astype(jnp.int32),
        axis=-1,
    )  # (..., S, D/2) — picks the right channel per slot
    angles = pos * inv
    return jnp.cos(angles), jnp.sin(angles)


# ---------------------------------------------------------------------------
# feed-forward
# ---------------------------------------------------------------------------

def swiglu(x: jnp.ndarray, w_gate: jnp.ndarray, w_up: jnp.ndarray,
           w_down: jnp.ndarray) -> jnp.ndarray:
    """SwiGLU MLP: down( silu(x@gate) * (x@up) )."""
    dtype = x.dtype
    g = jnp.einsum("...d,df->...f", x, w_gate.astype(dtype))
    u = jnp.einsum("...d,df->...f", x, w_up.astype(dtype))
    return jnp.einsum("...f,fd->...d", jax.nn.silu(g) * u, w_down.astype(dtype))


def swiglu_init(key, n: Optional[int], d_model: int, d_ff: int, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    if n is None:
        return {
            "w_gate": dense_init(k1, d_model, d_ff, dtype),
            "w_up": dense_init(k2, d_model, d_ff, dtype),
            "w_down": dense_init(k3, d_ff, d_model, dtype),
        }
    return {
        "w_gate": stacked_dense_init(k1, n, d_model, d_ff, dtype),
        "w_up": stacked_dense_init(k2, n, d_model, d_ff, dtype),
        "w_down": stacked_dense_init(k3, n, d_ff, d_model, dtype),
    }


# ---------------------------------------------------------------------------
# losses / metrics
# ---------------------------------------------------------------------------

def cross_entropy_logits(logits: jnp.ndarray, labels: jnp.ndarray,
                         vocab_size: int | None = None) -> jnp.ndarray:
    """Mean token cross-entropy.  ``logits`` (..., V), ``labels`` (...,) int.

    When the vocab is padded, ``vocab_size`` masks the pad logits to -inf so
    padded entries never receive probability mass.
    """
    logits = logits.astype(jnp.float32)
    if vocab_size is not None and vocab_size < logits.shape[-1]:
        pad = logits.shape[-1] - vocab_size
        neg = jnp.full(logits.shape[:-1] + (pad,), -1e30, logits.dtype)
        logits = jnp.concatenate([logits[..., :vocab_size], neg], axis=-1)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def tree_has_nan(tree) -> jnp.ndarray:
    leaves = [jnp.any(~jnp.isfinite(l)) for l in jax.tree_util.tree_leaves(tree)
              if jnp.issubdtype(l.dtype, jnp.floating)]
    if not leaves:
        return jnp.asarray(False)
    return jnp.any(jnp.stack(leaves))
