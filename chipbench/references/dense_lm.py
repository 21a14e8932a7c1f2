"""Plain float32 reference of a dense decoder-only language model.

Written from the published descriptions (Qwen2 / StableLM 2 model cards and
``config.json``), in straightforward ``jax.numpy``: token embedding, then per
layer pre-norm causal self-attention with rotary positions (split-halves
rotation) and QKV bias, and a SwiGLU MLP, each added to the residual; a
final RMSNorm and the output head; mean next-token cross-entropy over the
real vocabulary.  Every matrix product runs at ``highest`` precision.

Departures from the published models are those of the configuration file
(``departures``): RMSNorm everywhere, the whole head dimension rotated.

Parameters are read by the names the system under test stores them under
(``embed``, ``blocks/attn/wq`` ...); nothing else of the program is used.
One "flow model" is what one FedPairing flow runs: ``embed`` (the data
owner's input embedding), ``blocks`` stacked over layers, ``ln_f`` and
``head`` ((V, d) for a tied head, (d, V) for an untied one).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def rms_norm(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gamma


def rope(x, positions, theta):
    """x (B, S, H, D): rotate (first half, second half) pairs."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv            # (S, D/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block(p: Dict, x, cfg: Dict):
    b, s, d = x.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // heads
    eps = cfg["eps"]
    pos = jnp.arange(s)
    h = rms_norm(x, p["ln_attn"], eps)
    a = p["attn"]
    q = _mm("bsd,de->bse", h, a["wq"])
    k = _mm("bsd,de->bse", h, a["wk"])
    v = _mm("bsd,de->bse", h, a["wv"])
    if "bq" in a:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = rope(q.reshape(b, s, heads, hd), pos, cfg["rope_theta"])
    k = rope(k.reshape(b, s, kv, hd), pos, cfg["rope_theta"])
    v = v.reshape(b, s, kv, hd)
    rep = heads // kv
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = _mm("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = pos[None, :] <= pos[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    att = _mm("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + _mm("bse,ed->bsd", att.reshape(b, s, heads * hd), a["wo"])
    h = rms_norm(x, p["ln_mlp"], eps)
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
    x = rms_norm(x, fp["ln_f"], cfg["eps"])
    vocab = cfg["vocab_size"]
    head = fp["head"]
    w = head[:vocab].T if cfg["tie_word_embeddings"] else head[:, :vocab]
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
    return {
        "num_attention_heads": cfg_file["num_attention_heads"],
        "num_key_value_heads": cfg_file["num_key_value_heads"],
        "vocab_size": cfg_file["vocab_size"],
        "rope_theta": float(cfg_file["rope_theta"]),
        "eps": float(cfg_file.get("rms_norm_eps",
                                  cfg_file.get("layer_norm_eps"))),
        "tie_word_embeddings": bool(cfg_file["tie_word_embeddings"]),
    }
