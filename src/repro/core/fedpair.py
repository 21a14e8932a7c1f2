"""FedPairing training core (paper §II) — functional, vmapped over clients.

Semantics (pair (i, j), propagation lengths L_i + L_j = W):

* flow_i (client i's data): blocks [0,L_i) + embedding from ω^i, blocks
  [L_i,W) + head from ω^j.  Implemented as a differentiable parameter *mix*
  (the ``core.splitting.mix_params`` algebra) — autodiff through the mix
  routes each flow's gradient to the correct owner, which reproduces the
  paper's split-learning gradient hand-back exactly (the boundary-gradient
  transfer is the transpose of the mix/select).
* updates (Eq. 1/2):  ω^i -= η·factor·(a_i·g^i_own + a_j·g^j_incoming),
  where g^j_incoming is the part of partner j's flow gradient that lives on
  ω^i's blocks [L_j, W) — obtained by indexing the vmapped gradient output
  with the pairing involution.
* overlap (Eq. 7): blocks crossed by both flows get factor 2.

Self-paired clients (odd N) degenerate to plain local SGD automatically:
partner == self makes the mix the identity and both gradient terms the
client's own.

Perf notes (DESIGN.md §Perf): the step fuses the partner gather into the
mix (the partner parameter tree is never materialized as a second full
copy), fuses the gradient routing + involution return + Eq. (7) overlap
factor into the single SGD parameter write, and donates the client-param
buffers so the fleet updates in place.  A step therefore consumes the
parameter tree you pass it — thread the returned tree forward, or build
the step with ``FedPairingConfig(donate=False)`` to keep inputs alive.
For length-bucketed execution that also skips the gated-off blocks' FLOPs
entirely, see ``core.fedbucket``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import splitting

LossFn = Callable[[Dict, Dict], jnp.ndarray]   # (params, batch) -> scalar


def pair_weights(data_sizes: np.ndarray, partner: np.ndarray) -> np.ndarray:
    """Per-client aggregation weight a_i, normalized WITHIN each pair:
    a_i = |D_i| / (|D_i| + |D_p(i)|).

    The paper writes a_i = |D_i| / sum_j |D_j| (global), but applying the
    global weight inside the local update (Eq. 1) scales every step by
    ~1/N and then the server's plain mean discounts again — under that
    literal reading FedPairing converges N times slower than FedAvg,
    contradicting the paper's own Figs. 2-3.  Pair-normalization keeps the
    two gradient sources on each model summing to one full-magnitude step
    (each model 'indirectly trains with a larger dataset', §I), which
    reproduces the paper's convergence advantage.  See DESIGN.md §3.
    """
    d = np.asarray(data_sizes, np.float64)
    return (d / (d + d[partner])).astype(np.float32)


@dataclasses.dataclass(frozen=True)
class FedPairingConfig:
    lr: float = 0.1
    overlap_boost: bool = True          # Eq. (7) doubled step on overlaps
    aggregation: str = "paper"          # "paper": pre-weighted grads + mean
                                        # "fedavg": plain grads + weighted mean
    momentum: float = 0.0
    donate: bool = True                 # in-place client-param update


def replicate(params: Dict, n: int, sharding=None) -> Dict:
    """Broadcast a global model to N client replicas (leading client axis).
    With a ``sharding.fleet.FleetSharding`` the replicas are placed with
    the client dim sharded over the fleet mesh axis."""
    out = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), params)
    return out if sharding is None else sharding.place(out)


def make_fed_step(loss_fn: LossFn, plan: Dict, num_layers: int,
                  fed_cfg: FedPairingConfig, tied_head: bool = False):
    """Build the jitted per-batch FedPairing step.

    ``tied_head``: the model ties its output head to ``params["embed"]``
    (``ArchConfig.tie_embeddings``).  Flow i embeds its tokens with its own
    matrix, but its head runs on the partner — the top owner — so the
    loss sees the partner's matrix as ``tied_head`` and that gradient
    returns to the partner's ``embed``, as in the bucketed and dist
    engines.

    Returns ``step(client_params, batches, partner, lengths, agg_w)`` where
    * client_params — pytree stacked over N clients (donated unless
      ``fed_cfg.donate`` is False),
    * batches       — pytree stacked over N clients (one mini-batch each),
    * partner       — (N,) int32 pairing involution,
    * lengths       — (N,) int32 propagation lengths L_i,
    * agg_w         — (N,) float aggregation weights a_i.
    """

    def _bmask(masks, a):
        """(N, W) mask broadcast against a stacked (N, W, ...) leaf."""
        return masks.astype(a.dtype).reshape(masks.shape + (1,) * (a.ndim - 2))

    @functools.partial(jax.jit,
                       donate_argnums=(0,) if fed_cfg.donate else ())
    def step(client_params, batches, partner, lengths, agg_w):
        n = partner.shape[0]
        masks = jax.vmap(splitting.layer_mask, in_axes=(0, None))(
            lengths, num_layers)                                 # (N, W)
        masks_p = masks[partner]

        # fused gather+mix: bottom/stack[<L] from own, rest from the
        # partner — gathered leaf-wise, never held as a full partner tree.
        def mix(a, label):
            if label == "bottom":
                return a
            if label == "top":
                return a[partner]
            m = _bmask(masks, a)
            return a * m + a[partner] * (1.0 - m)

        mixed = jax.tree_util.tree_map(mix, client_params, plan)
        if tied_head:
            mixed = dict(mixed, tied_head=client_params["embed"][partner])
        losses, g_mix = jax.vmap(jax.value_and_grad(loss_fn))(mixed, batches)
        if tied_head:
            g_mix = dict(g_mix)
            g_head = g_mix.pop("tied_head")

        if fed_cfg.aggregation == "paper":
            a_own, a_in = agg_w, agg_w[partner]
        else:  # weighting deferred to the server aggregation
            a_own = a_in = jnp.ones_like(agg_w)
        factor = jax.vmap(splitting.overlap_factor, in_axes=(0, 0, None))(
            masks, masks_p, fed_cfg.overlap_boost)               # (N, W)

        # fused route + involution return + combine + Eq. (7) factor + SGD:
        # g*m is the flow gradient on own blocks, (g*(1-m))[partner] ==
        # g[partner]*(1-m[partner]) is the partner flow's gradient on them.
        def apply(p, g, label):
            b = (n,) + (1,) * (g.ndim - 1)
            if label == "bottom":
                u = a_own.reshape(b) * g
            elif label == "top":
                u = a_in.reshape(b) * g[partner]
            else:
                m = _bmask(masks, g)
                u = (a_own.reshape(b) * (g * m)
                     + a_in.reshape(b) * (g[partner] * (1.0 - _bmask(masks_p, g))))
                u = u * _bmask(factor, g).astype(u.dtype)
            return p - fed_cfg.lr * u

        new_params = jax.tree_util.tree_map(apply, client_params, g_mix, plan)
        if tied_head:
            # the partner flow's head gradient on my matrix: a "top" term
            b = (n,) + (1,) * (g_head.ndim - 1)
            new_params["embed"] = new_params["embed"] - fed_cfg.lr * (
                a_in.reshape(b) * g_head[partner])
        return new_params, {"loss": losses}

    return step


def run_round(step, client_params, batch_iter, partner: np.ndarray,
              lengths: np.ndarray, agg_w: np.ndarray, num_batches: int
              ) -> Tuple[Dict, jnp.ndarray]:
    """One communication round: ``num_batches`` local split-steps."""
    partner = jnp.asarray(partner, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    agg_w = jnp.asarray(agg_w, jnp.float32)
    losses = []
    for _ in range(num_batches):
        batches = next(batch_iter)
        client_params, m = step(client_params, batches, partner, lengths, agg_w)
        losses.append(m["loss"])
    return client_params, jnp.stack(losses)
