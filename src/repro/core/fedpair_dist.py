"""Distributed FedPairing — shard_map + ppermute over the mesh data axis.

This is the TPU-native execution of the paper's protocol (DESIGN.md §3):

* each client lives at one position of the (pod x) data axis and holds its
  own model replica (params have a leading client axis sharded over
  ("pod","data")),
* phase A: every client embeds its own mini-batch and runs its *bottom*
  blocks (per-layer gates; gated-off blocks are identity),
* the boundary feature map x̄ and the labels hop to the partner via
  ``jax.lax.ppermute`` with the pairing involution — the paper's
  client-to-client OFDM transfer, become an ICI collective-permute,
* phase B: every client runs its *top* blocks + head on the received
  activation and computes the partner-flow loss (weighted a_p),
* backward: nothing extra — ``ppermute``'s autodiff transpose is the
  inverse permutation, which IS the paper's boundary-gradient hand-back.

Tensor parallelism stays with GSPMD: the shard_map is entered with
``axis_names`` = client axes only and ``auto`` = the model axis.

Supported families: dense / MoE / SSM (token-LM block stacks).  Hybrid,
VLM and enc-dec run under the vmapped functional core (fedpair.py), which
is semantically identical — see DESIGN.md §4.

Static split ranges (beyond-paper, DESIGN.md §Perf): shard_map is SPMD —
one program for every device — so per-client static slicing is out, but a
*uniform* slice is not: ``split_ranges=(bottom_hi, top_lo)`` (the round
plan's envelope — ``planning.RoundPlan.phase_envelope`` /
``fedbucket.fleet_phase_ranges``) scans only blocks [0, bottom_hi) in
phase A and [top_lo, W) in phase B, gating the per-client residual inside
the slice.  On an all-equal fleet this degenerates to L_i = W/2 and the
gates vanish — the old ``static_half_split`` fast path, kept as an alias —
halving the compute term of the fed step; mildly heterogeneous fleets
still save everything outside the fleet's [min, max] split envelope.

The jitted step donates the client-parameter buffers (params update in
place); pass ``donate=False`` to keep the input tree alive.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core import fedbucket
from repro.models import transformer

# shared flow pieces live in fedbucket (the bucketing engine); these
# aliases keep the historical private names importable.
_stack_gated = fedbucket.stack_gated
_ce = fedbucket.ce
_ce_chunked = fedbucket.ce_chunked


@dataclasses.dataclass(frozen=True)
class FedDistConfig:
    lr: float = 0.1
    overlap_boost: bool = True
    static_half_split: bool = False   # alias for split_ranges=(W/2, W/2)
    split_ranges: Optional[Tuple[int, int]] = None  # (bottom_hi, top_lo)
    client_axes: Tuple[str, ...] = ("data",)
    unroll: int = 1                   # dry-run cost analysis needs full unroll
    ce_chunk: int = 0                 # >0: chunked head+CE (memory term)
    donate: bool = True               # in-place client-param update


def make_dist_fed_step(cfg: ArchConfig, mesh, perm_pairs: Sequence[Tuple[int, int]],
                       agg_w: np.ndarray, masks_bottom: np.ndarray,
                       dist_cfg: FedDistConfig):
    """Build the jitted distributed FedPairing SGD step.

    ``perm_pairs``  — [(src, dst), ...] covering every client position (the
                       pairing involution as a ppermute permutation).
    ``masks_bottom``— (N, W) float bottom masks per client (L_i rule).
    ``agg_w``       — (N,) aggregation weights.
    Returns ``step(client_params, batch)`` with client-axis-stacked inputs.
    """
    axes = dist_cfg.client_axes
    n_clients = len(agg_w)
    W = cfg.num_layers

    if dist_cfg.static_half_split:
        bot_hi, top_lo = W // 2, W // 2
    elif dist_cfg.split_ranges is not None:
        bot_hi, top_lo = dist_cfg.split_ranges
    else:
        bot_hi, top_lo = W, 0
    if not (1 <= bot_hi <= W and 0 <= top_lo <= W):
        raise ValueError(f"split_ranges must satisfy 1 <= bottom_hi <= W and "
                         f"0 <= top_lo <= W; got ({bot_hi}, {top_lo}), W={W}")
    # a sliced envelope must cover every client's protocol blocks: bottom
    # [0, L_i) and top [L_p, W) — skipping owned blocks would silently
    # change training semantics, so refuse rather than truncate.
    lengths_np = np.asarray(masks_bottom).sum(axis=1).astype(np.int64)
    inv_np = np.arange(n_clients)
    for s, d in perm_pairs:
        inv_np[d] = s
    max_l, min_lp = int(lengths_np.max()), int(lengths_np[inv_np].min())
    if bot_hi < max_l or top_lo > min_lp:
        raise ValueError(
            f"split ranges (bottom [0, {bot_hi}), top [{top_lo}, {W})) do "
            f"not cover the fleet's splits (max L_i={max_l}, min "
            f"L_p={min_lp}); derive them from the RoundPlan "
            "(plan.phase_envelope() / fedbucket.fleet_phase_ranges) "
            "or widen the envelope.")
    # the homogeneous alias runs ungated; sliced ranges gate the residual
    static_gates = dist_cfg.static_half_split

    masks_bottom_j = jnp.asarray(masks_bottom, jnp.float32)
    agg_w_j = jnp.asarray(agg_w, jnp.float32)

    def flow_loss(own_slice, batch_slice, mask_own, mask_perm, a_perm):
        """Runs on one client's shard; returns this device's share of loss."""
        own = jax.tree_util.tree_map(lambda a: a[0], own_slice)
        tokens = batch_slice["tokens"][0]
        labels = batch_slice["labels"][0]
        mask_own = mask_own[0]
        mask_perm = mask_perm[0]
        a_perm = a_perm[0]

        x = transformer.embed(own, tokens, cfg)
        S = tokens.shape[1]
        pos = jnp.arange(S)[None, :]
        cos, sin = transformer.rope_tables(cfg, pos)

        bottom = (own["blocks"] if bot_hi == W else
                  jax.tree_util.tree_map(lambda a: a[:bot_hi], own["blocks"]))
        gates_bot = (jnp.ones((bot_hi,)) if static_gates
                     else mask_own[:bot_hi])
        h_bot, aux_b = _stack_gated(bottom, x, cos, sin, cfg, gates_bot,
                                    bot_hi, unroll=dist_cfg.unroll)

        # ---- the paper's x̄ / label handoff: one collective-permute ----
        h_in = jax.lax.ppermute(h_bot, axes, perm_pairs)
        labels_in = jax.lax.ppermute(labels, axes, perm_pairs)

        top = (own["blocks"] if top_lo == 0 else
               jax.tree_util.tree_map(lambda a: a[top_lo:], own["blocks"]))
        gates_top = (jnp.ones((W - top_lo,)) if static_gates
                     else (1.0 - mask_perm)[top_lo:])
        h_top, aux_t = _stack_gated(top, h_in, cos, sin, cfg, gates_top,
                                    W - top_lo, unroll=dist_cfg.unroll)

        if dist_cfg.ce_chunk:
            loss = _ce_chunked(own, h_top, labels_in, cfg, dist_cfg.ce_chunk)
        else:
            logits = transformer.lm_logits(own, h_top, cfg)
            loss = _ce(logits, labels_in, cfg.vocab_size)
        loss = loss + cfg.router_aux_coef * (aux_b + aux_t)
        # pre-weighted by the data owner's aggregation weight (paper mode)
        return (a_perm * loss / n_clients)[None]

    client_spec = P(axes)

    def total_loss(client_params, batch, masks_b, masks_perm, a_perm):
        shard_fn = jax.shard_map(
            flow_loss, mesh=mesh,
            in_specs=(jax.tree_util.tree_map(lambda _: client_spec,
                                             client_params),
                      jax.tree_util.tree_map(lambda _: client_spec, batch),
                      client_spec, client_spec, client_spec),
            out_specs=client_spec,
            check_vma=False,
            axis_names=set(axes),
        )
        per_client = shard_fn(client_params, batch, masks_b, masks_perm,
                              a_perm)
        return jnp.sum(per_client)

    # permuted views (who sends to me == my partner, involution)
    inv = np.arange(n_clients)
    for s, d in perm_pairs:
        inv[d] = s
    masks_perm = masks_bottom_j[inv]
    a_perm = agg_w_j[inv]
    factor = 1.0 + (masks_bottom_j * (1.0 - masks_perm)
                    if dist_cfg.overlap_boost else 0.0)        # (N, W)

    def _step(client_params, batch):
        loss, grads = jax.value_and_grad(total_loss)(
            client_params, batch, masks_bottom_j, masks_perm, a_perm)

        def apply(path, p, g):
            name = str(path[0].key) if path else ""
            if name in ("blocks",) and g.ndim >= 2 and g.shape[1] == W:
                f = factor.astype(g.dtype).reshape(
                    (n_clients, W) + (1,) * (g.ndim - 2))
                g = g * f
            return p - dist_cfg.lr * g

        new_params = jax.tree_util.tree_map_with_path(apply, client_params,
                                                      grads)
        return new_params, loss

    step = jax.jit(_step,
                   donate_argnums=(0,) if dist_cfg.donate else ())
    return step


def pairs_to_ppermute(partner: np.ndarray) -> Sequence[Tuple[int, int]]:
    """Pairing involution -> ppermute (src, dst) list (covers all slots)."""
    return [(int(i), int(partner[i])) for i in range(len(partner))]
