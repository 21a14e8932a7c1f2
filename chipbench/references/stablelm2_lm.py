"""Plain float32 reference of StableLM 2 (``model_type: stablelm``).

Written from the published ``config.json`` of
``stabilityai/stablelm-2-1_6b`` and the layer equations it implies, in
straightforward ``jax.numpy``:

    x = embed[tokens]
    per layer:
      h = LN1(x)                      LayerNorm: biased variance, scale, bias
      q, k, v = h Wq + bq, h Wk + bk, h Wv + bv
      q, k = rope16(q), rope16(k)     split-halves rotation of the first
                                      rotary_ndims = partial_rotary_factor x
                                      head_dim dimensions of each head, with
                                      frequencies theta^(-2i / rotary_ndims);
                                      the other dimensions pass through
      x = x + causal_softmax_attention(q, k, v) Wo
      h = LN2(x)
      x = x + (silu(h Wgate) * (h Wup)) Wdown
    logits = LN_f(x) W_unembed        untied head, (d, V), no bias
    loss = mean next-token cross-entropy over the vocabulary

Every matrix product runs at ``highest`` precision; the cross-entropy is
taken over row chunks so the (tokens, V) logits are never held at once.
There are no departures from the published mathematics: the configuration
has no parallel residual and no q/k LayerNorm, and neither is modelled.

Parameters are read by the names the system under test stores them under
(``embed``, ``blocks/attn/wq`` ...); nothing else of the program is used.
A LayerNorm's scale and bias are one leaf, stacked on its axis -2
(``ln_f`` (2, d); ``ln_attn``, ``ln_mlp`` (2, d) per layer).  One "flow
model" is what one FedPairing flow runs: ``embed`` (the data owner's input
embedding), ``blocks`` stacked over layers, ``ln_f`` and ``head`` (d, V).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def layer_norm(x, scale_bias, eps):
    if scale_bias.shape != (2, x.shape[-1]):
        raise ValueError(
            f"a LayerNorm leaf stacks (scale, bias) as (2, {x.shape[-1]}); "
            f"got {scale_bias.shape}")
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale_bias[0] + scale_bias[1]


def rope(x, positions, theta, rotary_dim):
    """x (B, S, H, D): rotate (first half, second half) pairs of the first
    ``rotary_dim`` dimensions; the rest are returned as they came."""
    inv = 1.0 / (theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                           / rotary_dim))
    ang = positions.astype(jnp.float32)[:, None] * inv        # (S, R/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    half = rotary_dim // 2
    x1, x2 = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rotary_dim:]], -1)


def block(p: Dict, x, cfg: Dict):
    b, s, d = x.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // heads
    rot = int(cfg["partial_rotary_factor"] * hd)
    eps = cfg["eps"]
    pos = jnp.arange(s)
    h = layer_norm(x, p["ln_attn"], eps)
    a = p["attn"]
    q = _mm("bsd,de->bse", h, a["wq"]) + a["bq"]
    k = _mm("bsd,de->bse", h, a["wk"]) + a["bk"]
    v = _mm("bsd,de->bse", h, a["wv"]) + a["bv"]
    q = rope(q.reshape(b, s, heads, hd), pos, cfg["rope_theta"], rot)
    k = rope(k.reshape(b, s, kv, hd), pos, cfg["rope_theta"], rot)
    v = v.reshape(b, s, kv, hd)
    rep = heads // kv
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = _mm("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = pos[None, :] <= pos[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    att = _mm("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + _mm("bse,ed->bsd", att.reshape(b, s, heads * hd), a["wo"])
    h = layer_norm(x, p["ln_mlp"], eps)
    m = p["mlp"]
    gate = jax.nn.silu(_mm("bsd,df->bsf", h, m["w_gate"]))
    up = _mm("bsd,df->bsf", h, m["w_up"])
    return x + _mm("bsf,fd->bsd", gate * up, m["w_down"])


def flow_loss(fp: Dict, tokens, labels, cfg: Dict, ce_rows: int = 256):
    """Mean next-token cross-entropy of one flow model on (B, S) tokens."""
    x = fp["embed"][tokens]

    def body(h, p_l):
        return block(p_l, h, cfg), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, fp["blocks"])
    x = layer_norm(x, fp["ln_f"], cfg["eps"])
    w = fp["head"][:, :cfg["vocab_size"]]
    rows = x.reshape(-1, x.shape[-1])
    lab = labels.reshape(-1)
    chunk = min(ce_rows, rows.shape[0])
    rows = rows.reshape(-1, chunk, rows.shape[-1])
    lab = lab.reshape(-1, chunk)

    @jax.checkpoint
    def ce(r, l):
        logits = _mm("td,dv->tv", r, w)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, l[:, None], axis=-1)[:, 0]
        return jnp.sum(logz - gold)

    total = jnp.sum(jax.lax.map(lambda rl: ce(*rl), (rows, lab)))
    return total / lab.size


def config_view(cfg_file: Dict) -> Dict:
    """The numbers of a configuration file this reference reads."""
    if cfg_file["tie_word_embeddings"] or not cfg_file["use_qkv_bias"]:
        raise ValueError("StableLM 2 has an untied head and a QKV bias")
    return {
        "num_attention_heads": cfg_file["num_attention_heads"],
        "num_key_value_heads": cfg_file["num_key_value_heads"],
        "vocab_size": cfg_file["vocab_size"],
        "rope_theta": float(cfg_file["rope_theta"]),
        "partial_rotary_factor": float(cfg_file["partial_rotary_factor"]),
        "eps": float(cfg_file["layer_norm_eps"]),
    }
