"""Split-length bucketed FedPairing execution (DESIGN.md §Perf).

The paper's split point L_i is a *compute-savings* knob: client i only runs
blocks [0, L_i) of its own flow plus blocks [L_p, W) of its partner's flow —
2·L_i block applications per step, not 2·W.  The dense-masked execution in
``fedpair_dist`` (and the parameter-mix core in ``fedpair``) pays for the
full stack behind per-layer gates, which on a heterogeneous pair is ~2x the
FLOPs the protocol requires.

This module realizes the savings:

* ``plan_buckets`` groups clients whose rounded (L_i, W - L_p) phase shapes
  coincide.  ``bucket_granularity`` rounds the bottom length *up* and the
  top start *down* to multiples of g — wasted (gated-off) blocks inside a
  bucket trade against fewer compiled scan shapes.  Recompilation is
  bounded by ``BucketPlan.num_compiled_shapes`` (<= number of distinct
  (range, group-size) pairs), not by fleet size.
* ``make_bucketed_fed_step`` builds ONE jitted step whose body contains a
  statically sliced scan per bucket: blocks are gathered with static client
  indices (``params["blocks"][idx, lo:hi]``), scanned over exactly
  ``hi - lo`` layers, and the boundary activations are exchanged with a
  static partner gather — autodiff through the gather IS the paper's
  boundary-gradient hand-back.  With ``granularity=1`` no gating remains at
  all; with coarser buckets only the rounding residual is gated.
* ``fleet_phase_ranges`` derives the uniform (SPMD-safe) slice for the
  shard_map core — the generalization of its old homogeneous-only
  ``static_half_split`` fast path.

Semantics are bit-identical (up to float association) to the dense-masked
step — covered by ``tests/test_fedbucket.py``.  Supported families: the
token-LM block stacks (dense / MoE / SSM), same envelope as
``fedpair_dist``.  ``dense=True`` keeps the old gated full-stack execution
as an in-engine baseline for the ``benchmarks/bench_fedstep`` comparison.

Every jitted step donates the client-parameter buffers
(``donate_argnums``): the fleet's parameters update in place, so a step
consumes the tree you pass it — thread the returned tree forward and set
``donate=False`` if you need to keep the input alive (tests do).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, ArchFamily
from repro.core import planning
from repro.kernels.ref import ce_chunk_size
from repro.models import rwkv6, transformer

BUCKET_FAMILIES = (ArchFamily.DENSE, ArchFamily.MOE, ArchFamily.SSM)


# ---------------------------------------------------------------------------
# shared flow pieces (also consumed by fedpair_dist)
# ---------------------------------------------------------------------------

def stack_gated(params_blocks, x, cos, sin, cfg: ArchConfig,
                gates: jnp.ndarray, n_layers: int, unroll=1):
    """Scan ``n_layers`` stacked blocks with per-layer gates (0 = identity)."""
    if cfg.family == ArchFamily.SSM:
        def body(xc, scanned):
            p_l, g = scanned
            return rwkv6.rwkv_block_apply(p_l, xc, cfg, g.astype(xc.dtype)), None

        x, _ = jax.lax.scan(body, x, (params_blocks, gates), unroll=unroll)
        return x, jnp.zeros((), jnp.float32)
    return transformer.stack_apply(params_blocks, x, cos, sin, cfg,
                                   gates=gates, n_layers=n_layers,
                                   unroll=unroll)


def ce(logits: jnp.ndarray, labels: jnp.ndarray, vocab: int) -> jnp.ndarray:
    logits = logits.astype(jnp.float32)
    if vocab < logits.shape[-1]:
        pad = jnp.full(logits.shape[:-1] + (logits.shape[-1] - vocab,), -1e30,
                       logits.dtype)
        logits = jnp.concatenate([logits[..., :vocab], pad], axis=-1)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def ce_chunked(params, h: jnp.ndarray, labels: jnp.ndarray,
               cfg: ArchConfig, chunk: int) -> jnp.ndarray:
    """Head + CE over sequence chunks; never materializes (B,S,V) fp32."""
    B, S, D = h.shape
    C = ce_chunk_size(S, chunk)
    nc = S // C
    h_c = h.reshape(B, nc, C, D).transpose(1, 0, 2, 3)
    l_c = labels.reshape(B, nc, C).transpose(1, 0, 2)

    def body(acc, xs):
        hc, lc = xs
        logits = transformer.lm_logits(params, hc, cfg)
        return acc + ce(logits, lc, cfg.vocab_size), None

    tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (h_c, l_c))
    return tot / nc


# ---------------------------------------------------------------------------
# bucket planning
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PhaseGroup:
    """Clients that scan the same static block range [lo, hi)."""
    lo: int
    hi: int
    clients: Tuple[int, ...]

    @property
    def n_layers(self) -> int:
        return self.hi - self.lo


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    num_layers: int
    granularity: int
    bottom: Tuple[PhaseGroup, ...]      # own-flow phase, ranges [0, hi)
    top: Tuple[PhaseGroup, ...]         # partner-flow phase, ranges [lo, W)
    lengths: Tuple[int, ...]
    partner: Tuple[int, ...]

    @property
    def num_clients(self) -> int:
        return len(self.lengths)

    @property
    def num_compiled_shapes(self) -> int:
        """Upper bound on distinct scan compilations the step contains."""
        return len({(g.n_layers, len(g.clients))
                    for g in self.bottom + self.top if g.n_layers > 0})

    @property
    def scanned_blocks(self) -> int:
        """Block applications per step under this plan (both phases)."""
        return sum(g.n_layers * len(g.clients) for g in self.bottom + self.top)

    @property
    def protocol_blocks(self) -> int:
        """Block applications the paper's protocol requires (granularity 1)."""
        W = self.num_layers
        return sum(l + (W - self.lengths[p])
                   for l, p in zip(self.lengths, self.partner))

    @property
    def dense_blocks(self) -> int:
        """Block applications of the gated full-stack execution."""
        return 2 * self.num_clients * self.num_layers


def plan_buckets(lengths, partner, num_layers: int,
                 granularity: int = 1) -> BucketPlan:
    """Group clients by rounded phase shapes.

    Bottom ranges round ``L_i`` *up* (the slice must cover every owned
    block), top ranges round ``L_p`` *down* (the slice must cover
    [L_p, W)); the rounding residual is gated off inside the bucket, so
    semantics never change — only wasted blocks trade against compiles.
    """
    lengths = np.asarray(lengths, np.int64)
    partner = np.asarray(partner, np.int64)
    W = int(num_layers)
    g = max(1, int(granularity))
    if np.any(lengths < 1) or np.any(lengths > W):
        raise ValueError(f"lengths must lie in [1, {W}], got {lengths}")

    bot: Dict[int, list] = {}
    top: Dict[int, list] = {}
    for i in range(len(lengths)):
        hi = min(W, -(-int(lengths[i]) // g) * g)      # ceil to granularity
        bot.setdefault(hi, []).append(i)
        lp = int(lengths[partner[i]])
        lo = W if lp == W else (lp // g) * g           # floor to granularity
        top.setdefault(lo, []).append(i)

    return BucketPlan(
        num_layers=W, granularity=g,
        bottom=tuple(PhaseGroup(0, hi, tuple(ix))
                     for hi, ix in sorted(bot.items())),
        top=tuple(PhaseGroup(lo, W, tuple(ix))
                  for lo, ix in sorted(top.items())),
        lengths=tuple(int(l) for l in lengths),
        partner=tuple(int(p) for p in partner),
    )


def fleet_phase_ranges(lengths, partner, num_layers: int,
                       granularity: int = 1) -> Tuple[int, int]:
    """Uniform (bottom_hi, top_lo) static slice covering the whole fleet.

    This is what an SPMD core (shard_map: one program for every device) can
    exploit: scan [0, max_i ceil(L_i)) and [min_i floor(L_p), W) instead of
    two full stacks.  Degenerates to (W/2, W/2) on a homogeneous fleet —
    the old ``static_half_split`` — and to (W, 0) for a worst-case fleet.

    Thin wrapper over ``planning.phase_envelope`` (the plan layer owns the
    envelope semantics; ``RoundPlan.phase_envelope`` is the same values) —
    kept because the bucket/dist engines and their tests address it here.
    """
    return planning.phase_envelope(lengths, partner, num_layers, granularity)


# ---------------------------------------------------------------------------
# the bucketed step
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FedBucketConfig:
    lr: float = 0.1
    overlap_boost: bool = True        # Eq. (7) doubled step on overlaps
    aggregation: str = "paper"        # "paper": pre-weighted flows + mean
                                      # "fedavg": plain flows + weighted mean
    bucket_granularity: int = 1
    dense: bool = False               # gated full-stack baseline (bench)
    unroll: int = 1
    ce_chunk: int = 0                 # >0: chunked head+CE
    donate: bool = True               # in-place client-param update


def make_bucketed_fed_step(cfg: ArchConfig, partner, lengths, agg_w,
                           bucket_cfg: FedBucketConfig):
    """Build the jitted bucketed FedPairing SGD step.

    Returns ``(step, plan)`` with ``step(client_params, batch)`` over
    client-axis-stacked inputs (params tree (N, ...), batch tokens/labels
    (N, B, S)).  ``plan`` reports the compiled shapes and block counts.

    The step's loss is the pair-weighted mean over flows (matches
    ``fedpair_dist``: each flow pre-weighted by its data owner's a_i and
    normalized by 1/N), and the update is SGD with the Eq. (7) overlap
    factor fused into the parameter write.
    """
    if cfg.family not in BUCKET_FAMILIES:
        raise ValueError(f"bucketed engine supports {BUCKET_FAMILIES}, "
                         f"got {cfg.family}")
    W = cfg.num_layers
    partner_np = np.asarray(partner, np.int64)
    lengths_np = np.asarray(lengths, np.int64)
    n = len(lengths_np)
    plan = plan_buckets(lengths_np, partner_np, W,
                        bucket_cfg.bucket_granularity)

    masks = np.stack([np.arange(W) < l for l in lengths_np]
                     ).astype(np.float32)                      # (N, W)
    masks_perm = masks[partner_np]
    agg = np.asarray(agg_w, np.float32)
    factor = jnp.asarray(
        1.0 + (masks * (1.0 - masks_perm) if bucket_cfg.overlap_boost
               else np.zeros_like(masks)))                     # (N, W)
    # "fedavg" leaves the flows unweighted (the server aggregation applies
    # the data-size weights instead), mirroring FedPairingConfig.aggregation
    a_perm = jnp.asarray(agg[partner_np]
                         if bucket_cfg.aggregation == "paper"
                         else np.ones_like(agg))
    gates_bottom = jnp.asarray(masks)
    gates_top = jnp.asarray(1.0 - masks_perm)

    if bucket_cfg.dense:
        everyone = tuple(range(n))
        bottom_groups = (PhaseGroup(0, W, everyone),)
        top_groups = (PhaseGroup(0, W, everyone),)
    else:
        bottom_groups, top_groups = plan.bottom, plan.top

    def scan_phase(groups, client_params, h_all, gates_all, cos, sin):
        """Run each bucket's statically sliced scan; reassemble (N,...)."""
        out = h_all
        aux = jnp.zeros((n,), jnp.float32)
        for grp in groups:
            idx = np.asarray(grp.clients)
            if grp.n_layers == 0:       # e.g. self-pairs' empty top range
                continue
            blocks = jax.tree_util.tree_map(
                lambda a: a[idx, grp.lo:grp.hi], client_params["blocks"])
            gates = gates_all[idx, grp.lo:grp.hi]              # (n_g, n_l)
            h_g, aux_g = jax.vmap(
                lambda b, xi, gi: stack_gated(b, xi, cos, sin, cfg, gi,
                                              grp.n_layers,
                                              unroll=bucket_cfg.unroll)
            )(blocks, h_all[idx], gates)
            out = out.at[idx].set(h_g)
            aux = aux.at[idx].set(aux_g)
        return out, aux

    def total_loss(client_params, batch):
        tokens, labels = batch["tokens"], batch["labels"]
        S = tokens.shape[-1]
        pos = jnp.arange(S)[None, :]
        cos, sin = transformer.rope_tables(cfg, pos)

        x = jax.vmap(lambda p, t: transformer.embed(p, t, cfg))(
            client_params, tokens)
        h_bot, aux_b = scan_phase(bottom_groups, client_params, x,
                                  gates_bottom, cos, sin)
        # ---- the paper's x̄ / label handoff: a static partner gather ----
        h_in = h_bot[partner_np]
        labels_in = labels[partner_np]
        h_top, aux_t = scan_phase(top_groups, client_params, h_in,
                                  gates_top, cos, sin)

        def head_loss(p, h, lab):
            if bucket_cfg.ce_chunk:
                return ce_chunked(p, h, lab, cfg, bucket_cfg.ce_chunk)
            return ce(transformer.lm_logits(p, h, cfg), lab, cfg.vocab_size)

        losses = jax.vmap(head_loss)(client_params, h_top, labels_in)
        losses = losses + cfg.router_aux_coef * (aux_b + aux_t)
        return jnp.sum(a_perm * losses) / n, losses

    def _step(client_params, batch):
        (total, losses), grads = jax.value_and_grad(
            total_loss, has_aux=True)(client_params, batch)

        def apply(path, p, g):
            name = str(path[0].key) if path else ""
            if name == "blocks" and g.ndim >= 2 and g.shape[1] == W:
                f = factor.astype(g.dtype).reshape(
                    (n, W) + (1,) * (g.ndim - 2))
                g = g * f
            return p - bucket_cfg.lr * g

        new_params = jax.tree_util.tree_map_with_path(apply, client_params,
                                                      grads)
        return new_params, {"loss": losses, "total": total}

    step = jax.jit(_step,
                   donate_argnums=(0,) if bucket_cfg.donate else ())
    return step, plan
