"""Mamba2 SSD chunked-scan Pallas TPU kernel.

Grid = (batch, head, chunk) with the chunk axis sequential; the running
state S (head_dim x state) lives in VMEM scratch and is carried across
chunks — the inter-chunk recurrence never touches HBM.  Per chunk the
kernel computes (all fp32, in VMEM):

  intra:   y_d = ((C B^T) ⊙ exp(segsum(a))) x            — (Q,Q) x (Q,P)
  inter:   y_o = exp(a_cum) ⊙ (C S^T)                    — (Q,N) x (N,P)
  state:   S   = exp(a_tot) S + (B ⊙ exp(a_tot - a_cum))^T x

VMEM working set at Q=128, P=64, N=128: three (Q,N)/(Q,P) tiles + one (Q,Q)
fp32 score tile + the (P,N) state ≈ 300 KB.  The decay is per-head scalar
(Mamba2), so segsum stays a (Q,Q) tile — no per-channel blowup (contrast
WKV6).  B/C are shared over heads (ngroups=1), expressed in the index map.

Layout: x and y are head-major (B, H, S, P) inside the kernel, and the
within-chunk cumulative decay is computed by the wrapper and passed
chunk-major, so every block's last two dims are (8, 128)-aligned or whole
as the TPU lowering requires.  The wrapper transposes at the boundary.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, acol_ref, arow_ref, atot_ref, b_ref, c_ref, s0_ref,
                y_ref, sfin_ref, s_ref, *, chunk: int, n_chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)            # (Q, P)
    a_cum = acol_ref[0, 0, 0]                      # (Q, 1) inclusive cumsum
    a_cum_row = arow_ref[0, 0, 0]                  # (1, Q) the same values
    b = b_ref[0].astype(jnp.float32)               # (Q, N)
    c = c_ref[0].astype(jnp.float32)               # (Q, N)
    a_tot = atot_ref[0, 0, 0, ic]                  # scalar: a_cum[Q - 1]

    # ---- intra-chunk: scores[t, s] = (c_t . b_s) * exp(cum_t - cum_s), s<=t
    diff = a_cum - a_cum_row                       # (Q, Q)
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, diff, 0.0)), 0.0)
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * decay
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # ---- inter-chunk: y += exp(a_cum) * (c @ S^T);  S is (P, N)
    s = s_ref[...]
    y += jnp.exp(a_cum) * jax.lax.dot_general(
        c, s, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    # ---- state update
    b_dec = b * jnp.exp(a_tot - a_cum)             # (Q, N)
    s_ref[...] = jnp.exp(a_tot) * s + jax.lax.dot_general(
        x, b_dec, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ic == n_chunks - 1)
    def _finish():
        sfin_ref[0, 0] = s_ref[...].astype(sfin_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x: jnp.ndarray, log_decay: jnp.ndarray, b: jnp.ndarray,
        c: jnp.ndarray, *, chunk: int = 128,
        initial_state: Optional[jnp.ndarray] = None,
        interpret: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B,S,H,P), log_decay (B,S,H), b/c (B,S,N) -> (y, final (B,H,P,N))."""
    B, S, H, P = x.shape
    N = b.shape[-1]
    from repro.kernels.ref import fit_chunk
    chunk = fit_chunk(S, chunk)
    n_chunks = S // chunk
    if initial_state is None:
        initial_state = jnp.zeros((B, H, P, N), jnp.float32)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=n_chunks)
    # the within-chunk cumulative decay, chunk-major so that its block is
    # a whole (Q, 1) column / (1, Q) row in the two layouts the kernel uses
    a_cum = jnp.cumsum(log_decay.astype(jnp.float32).transpose(0, 2, 1)
                       .reshape(B, H, n_chunks, chunk), axis=-1)

    y, sfin = pl.pallas_call(
        kernel,
        grid=(B, H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, 1, chunk, 1),
                         lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, chunk),
                         lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
            # the chunk totals as SMEM scalars: Mosaic cannot broadcast a
            # (1, 1) vector over both sublanes and lanes of the state
            pl.BlockSpec((1, 1, 1, n_chunks),
                         lambda ib, ih, ic: (ib, ih, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, N), lambda ib, ih, ic: (ib, ic, 0)),
            pl.BlockSpec((1, chunk, N), lambda ib, ih, ic: (ib, ic, 0)),
            pl.BlockSpec((1, 1, P, N), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, P, N), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x.transpose(0, 2, 1, 3), a_cum[..., None], a_cum[..., None, :],
      a_cum[..., None, :, -1], b, c, initial_state)
    return y.transpose(0, 2, 1, 3), sfin
