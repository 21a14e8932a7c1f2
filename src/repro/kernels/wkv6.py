"""RWKV6 WKV recurrence Pallas TPU kernel (data-dependent per-channel decay).

Grid = (batch, head, chunk), chunk axis sequential; the (N x M) state is
VMEM-resident scratch carried across chunks.

The intra-chunk pairwise decay is per *channel* (unlike Mamba2's per-head
scalar), so the factored r~/k~ matmul trick overflows fp32 under aggressive
decays.  In-kernel we can afford the exact scheme: materialize the masked
pairwise log-difference tensor (Q, Q, N) in VMEM, exp AFTER masking, and
contract — at Q=64, N=64 that is 64*64*64*4 B = 1 MB of VMEM, which is the
reason this kernel exists (the XLA path needs tiny Q=16 chunks to bound the
same tensor through HBM; see kernels/ref.py).

  out_t = r_t (diag(u) k_t v_t^T + S_{t-1}),  S_t = diag(w_t) S_{t-1} + k_t v_t^T

Layout: the kernel sees head-major (B, H, S, .) arrays, the state as S^T
(M x N), and the within-chunk cumulative log-decay precomputed by the
wrapper, so every block's last two dims are (8, 128)-aligned or whole as
the TPU lowering requires.  The wrapper transposes at the boundary.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, cum_ref, u_ref, s0_ref, y_ref,
                sfin_ref, s_ref, *, chunk: int, n_chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, 0].astype(jnp.float32)          # (Q, N)
    k = k_ref[0, 0].astype(jnp.float32)          # (Q, N)
    v = v_ref[0, 0].astype(jnp.float32)          # (Q, M)
    lw = lw_ref[0, 0].astype(jnp.float32)        # (Q, N)  (<= 0)
    cum = cum_ref[0, 0]                          # (Q, N) inclusive cumsum
    u = u_ref[0].astype(jnp.float32)             # (1, N)

    cum_tm1 = cum - lw                           # exclusive
    total = cum[chunk - 1:, :]                   # (1, N)

    # ---- intra-chunk, exact masked pairwise decays: (Q, Q, N) in VMEM
    dlog = cum_tm1[:, None, :] - cum[None, :, :]             # [t, i, n]
    n = r.shape[-1]
    tri = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk, n), 0) >
           jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk, n), 1))
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, dlog, 0.0)), 0.0)
    scores = jnp.sum(r[:, None, :] * k[None, :, :] * decay, axis=-1)  # (Q,Q)
    bonus = jnp.sum(r * u * k, axis=-1, keepdims=True)                # (Q,1)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) ==
           jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    scores = scores + jnp.where(eye, bonus, 0.0)
    y = jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # ---- inter-chunk: r decayed to chunk start @ carried state S; the
    # scratch holds S^T (M, N) so the per-channel decay scales lanes
    s_t = s_ref[...]
    r_dec = r * jnp.exp(cum_tm1)
    y += jax.lax.dot_general(r_dec, s_t, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)

    # ---- state update: S = diag(exp(total)) S + (k ⊙ exp(total-cum))^T v
    k_dec = k * jnp.exp(total - cum)
    s_ref[...] = jnp.exp(total) * s_t + jax.lax.dot_general(
        v, k_dec, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ic == n_chunks - 1)
    def _finish():
        sfin_ref[0, 0] = s_ref[...].astype(sfin_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, log_w: jnp.ndarray,
         u: jnp.ndarray, *, chunk: int = 64,
         initial_state: Optional[jnp.ndarray] = None,
         interpret: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """r/k/log_w (B,S,H,N), v (B,S,H,M), u (H,N) -> (out (B,S,H,M),
    final state (B,H,N,M))."""
    B, S, H, N = r.shape
    M = v.shape[-1]
    from repro.kernels.ref import fit_chunk
    chunk = fit_chunk(S, chunk)
    n_chunks = S // chunk
    if initial_state is None:
        initial_state = jnp.zeros((B, H, N, M), jnp.float32)

    kernel = functools.partial(_wkv_kernel, chunk=chunk, n_chunks=n_chunks)
    heads_major = lambda a: a.transpose(0, 2, 1, 3)     # (B, H, S, .)
    lw = heads_major(log_w)
    cum = jnp.cumsum(lw.astype(jnp.float32).reshape(B, H, n_chunks, chunk, N),
                     axis=3).reshape(B, H, S, N)
    seq_block = lambda d: pl.BlockSpec((1, 1, chunk, d),
                                       lambda ib, ih, ic: (ib, ih, ic, 0))
    state_block = pl.BlockSpec((1, 1, M, N), lambda ib, ih, ic: (ib, ih, 0, 0))

    y, sfin_t = pl.pallas_call(
        kernel,
        grid=(B, H, n_chunks),
        in_specs=[
            seq_block(N), seq_block(N), seq_block(M), seq_block(N),
            seq_block(N),
            pl.BlockSpec((1, 1, N), lambda ib, ih, ic: (ih, 0, 0)),
            state_block,
        ],
        out_specs=[seq_block(M), state_block],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, M), v.dtype),
            jax.ShapeDtypeStruct((B, H, M, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((M, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(heads_major(r), heads_major(k), heads_major(v), lw, cum,
      u.reshape(H, 1, N), initial_state.transpose(0, 1, 3, 2))
    return heads_major(y), sfin_t.transpose(0, 1, 3, 2)
