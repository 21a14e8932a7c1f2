"""Model registry: family dispatch for init / forward / loss / serve.

Public surface used by the trainer, server, dry-run and FedPairing core:

* ``init_params(cfg, key)``                      -> params pytree
* ``forward_logits(params, batch, cfg, gates)``  -> (logits, aux)
* ``loss_fn(params, batch, cfg, gates)``         -> (loss, metrics)
* ``init_serve_state(params, cfg, batch, cache_len, window)`` -> state
* ``serve_step(params, tokens, state, cfg, spec)``-> (logits, state)
* ``make_batch_specs(cfg, shape)``               -> ShapeDtypeStruct batch
* ``count_params_analytical(cfg, active_only)``  -> int

``gates`` is the FedPairing per-layer gate vector (see core.splitting); all
families accept it (hybrid gates its mamba stack; enc-dec gates the decoder —
the split unit named in the assignment).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ArchFamily, AttentionKind, InputShape
from repro.kernels import ref
from repro.models import attention as attn
from repro.models import common, encdec, hybrid, mamba2, rwkv6, transformer

LONG_CONTEXT_WINDOW = 8192   # sliding-window size for long_500k decode


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(cfg: ArchConfig, key) -> Dict:
    dtype = jnp.dtype(cfg.param_dtype)
    if cfg.family == ArchFamily.HYBRID:
        return hybrid.hybrid_init(key, cfg, dtype)
    if cfg.family == ArchFamily.AUDIO:
        return encdec.encdec_init(key, cfg, dtype)
    if cfg.family == ArchFamily.SSM:
        kb, kh = jax.random.split(key)
        p = transformer.lm_head_init(kh, cfg, dtype)
        p["blocks"] = rwkv6.rwkv_stack_init(kb, cfg, cfg.num_layers, dtype)
        return p
    # dense / moe / vlm share the transformer stack
    kb, kh = jax.random.split(key)
    p = transformer.lm_head_init(kh, cfg, dtype)
    p["blocks"] = transformer.block_stack_init(kb, cfg, cfg.num_layers,
                                               dtype=dtype)
    return p


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _positions_cos_sin(cfg: ArchConfig, batch: Dict, S: int):
    hd = cfg.resolved_head_dim
    if cfg.family == ArchFamily.VLM:
        return common.mrope_cos_sin(batch["positions"], hd, cfg.rope_theta,
                                    cfg.mrope_sections)
    return transformer.rope_tables(cfg, jnp.arange(S)[None, :])


def forward_hidden(params: Dict, batch: Dict, cfg: ArchConfig,
                   gates: Optional[jnp.ndarray] = None, *,
                   sliding_window: Optional[int] = None, remat: bool = False,
                   residual_sharding=None, unroll=1, seq_shardings=None,
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Training / prefill forward to final hidden states (pre-head)."""
    aux = jnp.zeros((), jnp.float32)
    if cfg.family == ArchFamily.HYBRID:
        h = hybrid.hybrid_forward(params, batch["tokens"], cfg, gates,
                                  sliding_window=sliding_window, remat=remat,
                                  residual_sharding=residual_sharding,
                                  unroll=unroll)
    elif cfg.family == ArchFamily.AUDIO:
        h = encdec.forward(params, batch["frames"], batch["tokens"], cfg,
                           gates, remat=remat,
                           residual_sharding=residual_sharding, unroll=unroll)
    elif cfg.family == ArchFamily.SSM:
        x = transformer.embed(params, batch["tokens"], cfg)
        if gates is None:
            gates = jnp.ones((cfg.num_layers,), x.dtype)

        def body(xc, scanned):
            p_l, g = scanned
            out = rwkv6.rwkv_block_apply(p_l, xc, cfg, g.astype(xc.dtype))
            if residual_sharding is not None:
                out = jax.lax.with_sharding_constraint(out, residual_sharding)
            return out, None

        if remat:
            body = jax.checkpoint(body)
        h, _ = jax.lax.scan(body, x, (params["blocks"], gates), unroll=unroll)
    else:
        x = transformer.embed(params, batch["tokens"], cfg)
        if cfg.family == ArchFamily.VLM:
            x = jnp.concatenate([batch["patches"].astype(x.dtype), x], axis=1)
        S = x.shape[1]
        cos, sin = _positions_cos_sin(cfg, batch, S)
        h, aux = transformer.stack_apply(params["blocks"], x, cos, sin, cfg,
                                         gates=gates,
                                         sliding_window=sliding_window,
                                         remat=remat,
                                         residual_sharding=residual_sharding,
                                         unroll=unroll,
                                         seq_shardings=seq_shardings)
    return h, aux


def forward_logits(params: Dict, batch: Dict, cfg: ArchConfig,
                   gates: Optional[jnp.ndarray] = None, *,
                   sliding_window: Optional[int] = None, remat: bool = False,
                   residual_sharding=None, unroll=1, seq_shardings=None,
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Training / prefill forward.  Returns (logits, moe aux loss)."""
    h, aux = forward_hidden(params, batch, cfg, gates,
                            sliding_window=sliding_window, remat=remat,
                            residual_sharding=residual_sharding, unroll=unroll,
                            seq_shardings=seq_shardings)
    logits = transformer.lm_logits(params, h, cfg)
    return logits, aux


def _ce_terms(logits: jnp.ndarray, labels: jnp.ndarray, vocab: int
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(sum token loss, valid count); labels < 0 masked; padded vocab cut."""
    with jax.named_scope("lm_head"):
        logits = logits.astype(jnp.float32)
        if vocab < logits.shape[-1]:
            pad = jnp.full(logits.shape[:-1] + (logits.shape[-1] - vocab,),
                           -1e30, logits.dtype)
            logits = jnp.concatenate([logits[..., :vocab], pad], axis=-1)
        valid = labels >= 0
        lbl = jnp.maximum(labels, 0)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lbl[..., None], axis=-1)[..., 0]
        return jnp.sum((logz - gold) * valid), jnp.sum(valid)


def loss_fn(params: Dict, batch: Dict, cfg: ArchConfig,
            gates: Optional[jnp.ndarray] = None, *, remat: bool = False,
            residual_sharding=None, unroll=1, seq_shardings=None,
            ce_chunk: int = 0) -> Tuple[jnp.ndarray, Dict]:
    """Next-token CE (labels < 0 are masked) + MoE aux.

    ``ce_chunk > 0`` computes the head + CE over sequence chunks under a
    scan so the (B, S, V) fp32 logits are never materialized — a large
    memory-term win for the big-vocab configs (see EXPERIMENTS.md §Perf).
    """
    labels = batch["labels"]
    if ce_chunk:
        h, aux = forward_hidden(params, batch, cfg, gates, remat=remat,
                                residual_sharding=residual_sharding,
                                unroll=unroll, seq_shardings=seq_shardings)
        if cfg.family == ArchFamily.VLM:
            h = h[:, h.shape[1] - labels.shape[1]:]
        B, S, D = h.shape
        C = ref.ce_chunk_size(S, ce_chunk)
        nc = S // C
        h_c = h.reshape(B, nc, C, D).transpose(1, 0, 2, 3)
        l_c = labels.reshape(B, nc, C).transpose(1, 0, 2)

        def body(carry, xs):
            s_loss, s_cnt = carry
            hc, lc = xs
            logits = transformer.lm_logits(params, hc, cfg)
            tl, cnt = _ce_terms(logits, lc, cfg.vocab_size)
            return (s_loss + tl, s_cnt + cnt), None

        (tot, cnt), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
            (h_c, l_c))
        denom = jnp.maximum(cnt, 1)
        ce = tot / denom
    else:
        logits, aux = forward_logits(params, batch, cfg, gates, remat=remat,
                                     residual_sharding=residual_sharding,
                                     unroll=unroll,
                                     seq_shardings=seq_shardings)
        if cfg.family == ArchFamily.VLM:
            # patch positions carry no labels; logits cover [patches | text]
            npatch = logits.shape[1] - labels.shape[1]
            logits = logits[:, npatch:]
        tot, cnt = _ce_terms(logits, labels, cfg.vocab_size)
        denom = jnp.maximum(cnt, 1)
        ce = tot / denom
    loss = ce + cfg.router_aux_coef * aux
    return loss, {"ce": ce, "aux": aux, "tokens": denom}


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def cache_spec_for(cfg: ArchConfig, cache_len: int, long_context: bool
                   ) -> attn.CacheSpec:
    window = LONG_CONTEXT_WINDOW if long_context else 0
    return attn.make_cache_spec(cache_len, window)


def init_serve_state(params: Dict, cfg: ArchConfig, batch_size: int,
                     cache_len: int, *, long_context: bool = False,
                     enc_out: Optional[jnp.ndarray] = None) -> Dict:
    """Decode-state pytree for one-token-at-a-time serving."""
    spec = cache_spec_for(cfg, cache_len, long_context)
    if cfg.family == ArchFamily.HYBRID:
        return hybrid.init_decode_state(cfg, batch_size, spec)
    if cfg.family == ArchFamily.SSM:
        st = rwkv6.init_decode_state(cfg, cfg.num_layers, batch_size)
        st["index"] = jnp.zeros((), jnp.int32)
        return st
    if cfg.family == ArchFamily.AUDIO:
        assert enc_out is not None, "enc-dec serving needs pre-encoded source"
        return encdec.init_decode_state(params, enc_out, cfg, batch_size, spec)
    return {
        "kv": attn.init_kv_cache(cfg.num_layers, batch_size, spec,
                                 cfg.num_kv_heads, cfg.resolved_head_dim,
                                 jnp.dtype(cfg.dtype)),
        "index": jnp.zeros((), jnp.int32),
    }


def serve_step(params: Dict, tokens: jnp.ndarray, state: Dict,
               cfg: ArchConfig, spec: attn.CacheSpec,
               mrope_positions: Optional[jnp.ndarray] = None, unroll=1,
               sp_decode=None,
               ) -> Tuple[jnp.ndarray, Dict]:
    """Decode ONE token.  tokens (B,1) -> logits (B,1,V)."""
    if cfg.family == ArchFamily.HYBRID:
        h, state = hybrid.hybrid_decode_step(params, tokens, state, cfg, spec,
                                             unroll=unroll)
        return transformer.lm_logits(params, h, cfg), state
    if cfg.family == ArchFamily.AUDIO:
        h, state = encdec.decode_step(params, tokens, state, cfg, spec,
                                      unroll=unroll)
        return transformer.lm_logits(params, h, cfg), state
    if cfg.family == ArchFamily.SSM:
        x = transformer.embed(params, tokens, cfg)
        scanned = {"p": params["blocks"],
                   "st": {k: state[k] for k in ("tm_shift", "cm_shift", "wkv")}}

        def body(xc, sc):
            xc, nst = rwkv6.rwkv_block_decode(sc["p"], xc, sc["st"], cfg)
            return xc, nst

        x, nst = jax.lax.scan(body, x, scanned, unroll=unroll)
        new_state = dict(nst, index=state["index"] + 1)
        return transformer.lm_logits(params, x, cfg), new_state

    # dense / moe / vlm
    x = transformer.embed(params, tokens, cfg)
    index = state["index"]
    hd = cfg.resolved_head_dim
    if cfg.family == ArchFamily.VLM:
        assert mrope_positions is not None, "vlm decode needs (B,1,3) positions"
        cos, sin = common.mrope_cos_sin(mrope_positions, hd, cfg.rope_theta,
                                        cfg.mrope_sections)
    else:
        cos, sin = transformer.rope_tables(
            cfg, jnp.full((1, 1), index, jnp.int32))
    x, kv = transformer.decode_stack_apply(params["blocks"], x, cos, sin,
                                           state["kv"], index, spec, cfg,
                                           unroll=unroll, sp_decode=sp_decode)
    new_state = dict(state, kv=kv, index=index + 1)
    return transformer.lm_logits(params, x, cfg), new_state


# ---------------------------------------------------------------------------
# batch specs (abstract inputs for dry-run / eval_shape)
# ---------------------------------------------------------------------------

def make_batch_specs(cfg: ArchConfig, shape: InputShape) -> Dict:
    """ShapeDtypeStruct stand-ins for a *training/prefill* batch."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    f = jnp.dtype(cfg.dtype)
    if cfg.family == ArchFamily.VLM:
        F = cfg.frontend_tokens
        return {
            "tokens": jax.ShapeDtypeStruct((B, S - F), i32),
            "labels": jax.ShapeDtypeStruct((B, S - F), i32),
            "patches": jax.ShapeDtypeStruct((B, F, cfg.d_model), f),
            "positions": jax.ShapeDtypeStruct((B, S, 3), i32),
        }
    if cfg.family == ArchFamily.AUDIO:
        return {
            "tokens": jax.ShapeDtypeStruct((B, S), i32),
            "labels": jax.ShapeDtypeStruct((B, S), i32),
            "frames": jax.ShapeDtypeStruct((B, cfg.encoder_seq_len, cfg.d_model), f),
        }
    return {
        "tokens": jax.ShapeDtypeStruct((B, S), i32),
        "labels": jax.ShapeDtypeStruct((B, S), i32),
    }


# ---------------------------------------------------------------------------
# split-boundary payloads (feeds the latency model's per-cut profiles)
# ---------------------------------------------------------------------------

def boundary_elements(cfg: ArchConfig, cut: int, seq_len: int) -> int:
    """Per-SAMPLE element count of the activation crossing the split
    boundary at depth ``cut`` (between block cut-1 and block cut).

    This is the residual stream the FedPairing handoff ships: the hidden
    states every block family carries are (S_eff, d_model) —

    * VLM prepends ``frontend_tokens`` patch embeddings to the text
      sequence, so the stream is wider than the token batch,
    * enc-dec decoders additionally need the encoder memory
      (encoder_seq_len, d_model) on the partner side for cross-attention
      (and its gradient travels back), so it rides the boundary too,
    * dense / MoE / SSM / hybrid streams are exactly (seq_len, d_model)
      (MoE expert routing and Mamba2 state expansion stay *inside* a
      block — the boundary tensor is the residual stream).
    """
    if not 1 <= cut <= cfg.num_layers - 1:
        raise ValueError(f"cut {cut} outside [1, {cfg.num_layers - 1}]")
    s_eff = seq_len
    if cfg.family == ArchFamily.VLM:
        s_eff += cfg.frontend_tokens
    if cfg.is_encdec:
        s_eff += cfg.encoder_seq_len
    return s_eff * cfg.d_model


def boundary_profile(cfg: ArchConfig, seq_len: int,
                     ) -> Tuple[Tuple[float, ...], Tuple[float, ...]]:
    """Per-cut (feature, gradient) boundary payloads in BYTES per sample,
    indexed ``cut - 1`` for cuts 1..W-1 — the real-architecture
    replacement for ``WorkloadModel``'s flat ResNet18 constant (the shape
    ``planning.boundary_bytes`` consumes).  Features travel in the
    activation dtype; the gradient w.r.t. the boundary comes back from the
    fp32 loss in the compute dtype as well (our engines cast the stream),
    so both profiles use ``cfg.dtype``'s width.
    """
    itemsize = jnp.dtype(cfg.dtype).itemsize
    feat = tuple(float(boundary_elements(cfg, cut, seq_len) * itemsize)
                 for cut in range(1, cfg.num_layers))
    return feat, feat


# ---------------------------------------------------------------------------
# param counting
# ---------------------------------------------------------------------------

def count_params_analytical(cfg: ArchConfig, active_only: bool = False) -> int:
    """Exact param count via ``jax.eval_shape`` over ``init_params``.

    Python-int arithmetic throughout — the padded expert stacks exceed
    int32 element counts.
    """
    import math as _math

    shapes = jax.eval_shape(
        functools.partial(init_params, cfg), jax.random.key(0))
    total = sum(_math.prod(l.shape)
                for l in jax.tree_util.tree_leaves(shapes))
    if active_only and cfg.family == ArchFamily.MOE and cfg.num_experts:
        routed = 3 * cfg.num_layers * cfg.padded_experts * cfg.d_model * cfg.d_ff
        active_routed = 3 * cfg.num_layers * cfg.top_k * cfg.d_model * cfg.d_ff
        total = total - routed + active_routed
    return total
