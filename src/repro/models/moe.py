"""Mixture-of-Experts layer: top-k router, sort-based capacity dispatch,
optional shared experts (DeepSeekMoE-style), switch-style load-balance loss.

Dispatch design (TPU-honest FLOP accounting)
--------------------------------------------
GShard's one-hot dispatch einsum costs ``O(T * E * C * D)`` dense FLOPs and
would inflate the compiled-FLOP roofline ~10x over the *active* FLOPs for
fine-grained MoE (64 experts, top-6).  Instead we sort token-slots by expert
id, scatter into fixed-capacity per-expert buffers ``(E, C, D)`` (overflow
slots dropped, standard capacity-factor semantics) and run one grouped
einsum over the stacked expert weights.  Sort/scatter/gather are data
movement, so compiled FLOPs ~= 2 * T * top_k * D * F * 3 — the true active
compute.  This is the XLA analogue of a Megablocks grouped-GEMM.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import common


def expert_capacity(tokens: int, num_experts: int, top_k: int,
                    capacity_factor: float) -> int:
    """Static per-expert buffer size (multiple of 4, >= top_k)."""
    c = math.ceil(tokens * top_k / num_experts * capacity_factor)
    c = max(c, top_k)
    return ((c + 3) // 4) * 4


def moe_init(key, n: Optional[int], cfg: ArchConfig, dtype=jnp.float32
             ) -> Dict[str, jnp.ndarray]:
    """Stacked (over ``n`` layers) MoE params: router + routed + shared experts."""
    kr, ke, ks = jax.random.split(key, 3)
    D, F, E = cfg.d_model, cfg.d_ff, cfg.padded_experts
    k1, k2, k3 = jax.random.split(ke, 3)
    shape = lambda *s: (s if n is None else (n, *s))  # noqa: E731
    scale_d = 1.0 / math.sqrt(D)
    scale_f = 1.0 / math.sqrt(F)

    def tn(k, s, scale):
        return (jax.random.truncated_normal(k, -3.0, 3.0, s) * scale).astype(dtype)

    p = {
        "router": tn(kr, shape(D, E), scale_d),
        "w_gate": tn(k1, shape(E, D, F), scale_d),
        "w_up": tn(k2, shape(E, D, F), scale_d),
        "w_down": tn(k3, shape(E, F, D), scale_f),
    }
    if cfg.num_shared_experts:
        Fs = cfg.num_shared_experts * F
        s1, s2, s3 = jax.random.split(ks, 3)
        p["shared"] = {
            "w_gate": tn(s1, shape(D, Fs), scale_d),
            "w_up": tn(s2, shape(D, Fs), scale_d),
            "w_down": tn(s3, shape(Fs, D), 1.0 / math.sqrt(Fs)),
        }
    return p


def _dispatch_indices(expert_idx: jnp.ndarray, num_experts: int
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """For flat token-slots with expert assignment ``expert_idx`` (TK,),
    return (slot position within its expert's buffer, rank order) — both (TK,).

    Stable-sort based: position of a slot = its rank among same-expert slots.
    """
    tk = expert_idx.shape[0]
    order = jnp.argsort(expert_idx, stable=True)              # (TK,)
    sorted_e = expert_idx[order]
    counts = jnp.bincount(expert_idx, length=num_experts)     # (E,)
    starts = jnp.cumsum(counts) - counts                      # (E,)
    pos_sorted = jnp.arange(tk) - starts[sorted_e]            # rank in group
    # invert the permutation: pos[slot] = rank of that slot within its expert
    pos = jnp.zeros((tk,), jnp.int32).at[order].set(pos_sorted.astype(jnp.int32))
    return pos, order


def moe_apply(p: Dict[str, jnp.ndarray], x: jnp.ndarray, cfg: ArchConfig
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Apply one MoE layer.  x (B,S,D) -> (out (B,S,D), aux_loss scalar)."""
    B, S, D = x.shape
    E, K, F = cfg.num_experts, cfg.top_k, cfg.d_ff
    Ep = cfg.padded_experts       # expert dim padded for even sharding
    T = B * S
    C = expert_capacity(T, E, K, cfg.moe_capacity_factor)
    xt = x.reshape(T, D)
    dtype = x.dtype

    # --- routing (fp32; padded expert slots masked out) ---
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    if Ep > E:
        pad_mask = jnp.arange(Ep) >= E
        logits = jnp.where(pad_mask[None, :], -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)                    # (T, Ep)
    top_p, top_i = jax.lax.top_k(probs, K)                     # (T, K)
    combine = top_p / jnp.sum(top_p, axis=-1, keepdims=True)   # normalized

    # --- load-balance aux loss (switch-style; padded slots contribute 0) ---
    frac_tokens = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_i, Ep, dtype=jnp.float32), axis=1), axis=0) / K
    mean_prob = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * mean_prob)

    # --- dispatch: scatter token-slots into (Ep, C, D) buffers ---
    flat_e = top_i.reshape(T * K)
    pos, _ = _dispatch_indices(flat_e, Ep)                     # (TK,)
    token_of_slot = jnp.repeat(jnp.arange(T), K)               # (TK,)
    buffers = jnp.zeros((Ep, C, D), dtype).at[flat_e, pos].set(
        xt[token_of_slot], mode="drop")                        # overflow dropped

    # --- grouped expert SwiGLU ---
    g = jnp.einsum("ecd,edf->ecf", buffers, p["w_gate"].astype(dtype))
    u = jnp.einsum("ecd,edf->ecf", buffers, p["w_up"].astype(dtype))
    h = jax.nn.silu(g) * u
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(dtype))

    # --- combine: gather back + weight (dropped slots read as 0) ---
    gathered = out_buf.at[flat_e, pos].get(mode="fill", fill_value=0)  # (TK, D)
    out = jnp.sum(
        gathered.reshape(T, K, D) * combine[..., None].astype(dtype), axis=1)

    # --- shared experts (always-on dense path) ---
    if "shared" in p:
        sh = p["shared"]
        out = out + common.swiglu(xt, sh["w_gate"], sh["w_up"], sh["w_down"])

    return out.reshape(B, S, D), aux.astype(jnp.float32)


# ---------------------------------------------------------------------------
# expert-parallel dispatch (shard_map + all_to_all) — §Perf optimization
# ---------------------------------------------------------------------------

_EP_CONTEXT: list = []   # [(mesh, data_axes)] — set by the train builder


class expert_parallel_context:
    """Trace-time switch: MoE layers built inside this context use the
    shard_map all_to_all dispatch instead of the global capacity dispatch."""

    def __init__(self, mesh, data_axes):
        self.item = (mesh, data_axes)

    def __enter__(self):
        _EP_CONTEXT.append(self.item)

    def __exit__(self, *exc):
        _EP_CONTEXT.pop()


def ep_context():
    return _EP_CONTEXT[-1] if _EP_CONTEXT else None


def moe_apply_ep(p: Dict[str, jnp.ndarray], x: jnp.ndarray, cfg: ArchConfig,
                 mesh, data_axes) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE: tokens stay batch-sharded; two ``all_to_all``
    hops move routed tokens to their expert's owner shard and back.

    The baseline leaves dispatch to GSPMD, which resolves the
    (expert-sharded weights) x (batch-sharded tokens) conflict with
    per-layer all-gathers (~TB/device/step measured).  Explicit EP moves
    only tokens·top_k·d_model bytes — the information-theoretic minimum for
    this routing (EXPERIMENTS.md §Perf).
    """
    from jax.sharding import PartitionSpec as P

    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.top_k
    Ep = cfg.padded_experts
    n_shards = 1
    for a in (data_axes if isinstance(data_axes, tuple) else (data_axes,)):
        n_shards *= mesh.shape[a]
    assert Ep % n_shards == 0, (Ep, n_shards)
    e_local = Ep // n_shards
    axes = data_axes if isinstance(data_axes, tuple) else (data_axes,)

    def body(x_l, router, w_gate, w_up, w_down, shared):
        Bl = x_l.shape[0]
        T = Bl * S
        xt = x_l.reshape(T, D)
        dtype = x_l.dtype

        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                            router.astype(jnp.float32))
        if Ep > E:
            logits = jnp.where((jnp.arange(Ep) >= E)[None, :], -1e30, logits)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, K)
        combine = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

        frac = jnp.mean(jnp.sum(jax.nn.one_hot(top_i, Ep, dtype=jnp.float32),
                                axis=1), axis=0) / K
        mean_prob = jnp.mean(probs, axis=0)
        aux = E * jnp.sum(
            jax.lax.pmean(frac, axes) * jax.lax.pmean(mean_prob, axes))

        # local capacity buffers per (global) expert
        C = expert_capacity(T, E, K, cfg.moe_capacity_factor)
        flat_e = top_i.reshape(T * K)
        pos, _ = _dispatch_indices(flat_e, Ep)
        token_of_slot = jnp.repeat(jnp.arange(T), K)
        buffers = jnp.zeros((Ep, C, D), dtype).at[flat_e, pos].set(
            xt[token_of_slot], mode="drop")

        # ---- to expert owners: (Ep, C, D) -> (e_local, n_shards*C, D)
        moved = jax.lax.all_to_all(buffers, axes, split_axis=0,
                                   concat_axis=1, tiled=True)
        g = jnp.einsum("ecd,edf->ecf", moved, w_gate.astype(dtype))
        u = jnp.einsum("ecd,edf->ecf", moved, w_up.astype(dtype))
        h = jax.nn.silu(g) * u
        out_move = jnp.einsum("ecf,efd->ecd", h, w_down.astype(dtype))
        # ---- back to token owners
        out_buf = jax.lax.all_to_all(out_move, axes, split_axis=1,
                                     concat_axis=0, tiled=True)

        gathered = out_buf.at[flat_e, pos].get(mode="fill", fill_value=0)
        out = jnp.sum(gathered.reshape(T, K, D) *
                      combine[..., None].astype(dtype), axis=1)
        if shared is not None:
            out = out + common.swiglu(xt, shared["w_gate"], shared["w_up"],
                                      shared["w_down"])
        return out.reshape(Bl, S, D), aux[None]

    shared_p = p.get("shared")
    in_specs = (P(axes), P(), P(axes), P(axes), P(axes),
                None if shared_p is None else jax.tree_util.tree_map(
                    lambda _: P(), shared_p))
    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(axes), P(axes)),
        axis_names=set(axes),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"], shared_p)
    return out, jnp.mean(aux)


def moe_apply_dense_ref(p: Dict[str, jnp.ndarray], x: jnp.ndarray,
                        cfg: ArchConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Oracle: dense (all-experts) routing, no capacity drops.  Test-only."""
    B, S, D = x.shape
    E, K, Ep = cfg.num_experts, cfg.top_k, cfg.padded_experts
    xt = x.reshape(B * S, D)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    if Ep > E:
        logits = jnp.where((jnp.arange(Ep) >= E)[None, :], -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, K)
    combine = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    weights = jnp.zeros((xt.shape[0], Ep), jnp.float32)
    weights = weights.at[jnp.arange(xt.shape[0])[:, None], top_i].set(combine)
    g = jnp.einsum("td,edf->tef", xt, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("td,edf->tef", xt, p["w_up"].astype(x.dtype))
    h = jax.nn.silu(g) * u
    per_e = jnp.einsum("tef,efd->ted", h, p["w_down"].astype(x.dtype))
    out = jnp.einsum("ted,te->td", per_e.astype(jnp.float32), weights)
    frac_tokens = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_i, E, dtype=jnp.float32), axis=1), axis=0) / K
    aux = E * jnp.sum(frac_tokens * jnp.mean(probs, axis=0))
    if "shared" in p:
        sh = p["shared"]
        out = out + common.swiglu(xt, sh["w_gate"], sh["w_up"], sh["w_down"]
                                  ).astype(jnp.float32)
    return out.reshape(B, S, D).astype(x.dtype), aux.astype(jnp.float32)
