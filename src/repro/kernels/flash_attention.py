"""Flash attention Pallas TPU kernel (causal / sliding-window, GQA-aware).

Online-softmax accumulation over KV blocks (FlashAttention-2 schedule
adapted to the TPU grid model): grid = (batch, q_head, q_block, kv_block)
with the kv_block axis sequential ("arbitrary"); running max / denominator /
accumulator live in VMEM scratch and persist across kv_block steps.

VMEM blocking: q/o tiles (block_q, head_dim), k/v tiles (block_k, head_dim),
scores (block_q, block_k) fp32 — all MXU-aligned multiples of 128 for the
full-size configs (128x128 blocks x head_dim<=128 => ~200 KB working set,
comfortably inside the ~16 MB v5e VMEM with double buffering).

Layout: the kernel sees head-major (B, H, S, d) arrays, so every block's
last two dims are (rows, head_dim) — the TPU lowering requires them to be
(8, 128)-aligned or whole, which a head axis blocked at 1 just before the
feature axis is not.  The wrapper transposes at the kernel boundary.

GQA is handled in the index maps (kv head = q head // group) — repeated KV
heads are never materialized.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale: float, block_q: int, block_k: int, n_kv_blocks: int,
                 causal: bool, sliding_window: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                  # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)                  # (bk, d)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if sliding_window:
        mask &= kpos > qpos - sliding_window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                  # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    # fully-masked rows: exp(NEG_INF - NEG_INF) = 1 would pollute; zero them
    p = jnp.where(mask, p, 0.0)

    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == n_kv_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "sliding_window",
                                             "block_q", "block_k", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, sliding_window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """q (B,S,Hq,d), k/v (B,L,Hkv,d) -> (B,S,Hq,d)."""
    B, S, Hq, d = q.shape
    L, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, L)
    assert S % block_q == 0 and L % block_k == 0, (S, L, block_q, block_k)
    n_q, n_k = S // block_q, L // block_k
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        _attn_kernel, scale=scale, block_q=block_q, block_k=block_k,
        n_kv_blocks=n_k, causal=causal, sliding_window=sliding_window)

    heads_major = lambda a: a.transpose(0, 2, 1, 3)      # (B, H, S, d)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, iq, ik, G=G: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, iq, ik, G=G: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(heads_major(q), heads_major(k), heads_major(v))
    return heads_major(out)
