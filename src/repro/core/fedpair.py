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

Perf notes (DESIGN.md §Perf): up to ``SLICE_MAX_CLIENTS`` clients on one
device the step reads each partner's rows as one static slice per client
at a traced offset, which the TPU compiler fuses into the mix, the
routed gradients and the SGD write (a traced-index gather ``x[partner]``
became a loop writing a whole copy of the leaf).
The tied head is selected after its cast to the compute dtype, so the
partner's matrix is never held as a second float32 copy.  The gradient
routing + involution return + Eq. (7) overlap factor form the single SGD
parameter write, and the client-param buffers are donated so the fleet
updates in place.  A step therefore consumes the parameter tree you pass
it — thread the returned tree forward, or build the step with
``FedPairingConfig(donate=False)`` to keep inputs alive.
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


# Above this many clients the step keeps the gather ``x[partner]``: the
# slices grow the program with N x leaves; up to 8 their CPU step is within
# 10 % of the gather's, from 12 on it takes 1.1-1.7 times as long (PERF.md §6).
SLICE_MAX_CLIENTS = 8


def _partner_rows(x: jnp.ndarray, partner: jnp.ndarray) -> jnp.ndarray:
    """``x[partner]``.  Up to ``SLICE_MAX_CLIENTS`` clients (a static
    shape) on one device, as N one-row slices at traced offsets: the TPU
    compiler fuses these into their consumer, where it lowers the
    traced-index gather to a loop that writes a whole copy of ``x``.  On
    a mesh of several devices (a fleet-sharded client axis) a slice at a
    traced offset is a collective of its own, and the gather is kept.
    Either form copies the rows as they are, so the values are the same."""
    n = partner.shape[0]
    if n > SLICE_MAX_CLIENTS or jax.typeof(x).sharding.mesh.size > 1:
        return x[partner]
    return jnp.concatenate([jax.lax.dynamic_index_in_dim(x, partner[i], 0)
                            for i in range(n)])


def make_fed_step(loss_fn: LossFn, plan: Dict, num_layers: int,
                  fed_cfg: FedPairingConfig, tied_head_dtype=None):
    """Build the jitted per-batch FedPairing step.

    ``tied_head_dtype``: None, or the model ties its output head to
    ``params["embed"]`` (``ArchConfig.tie_embeddings``) and its loss casts
    that matrix to this dtype (``ArchConfig.dtype``).  Flow i embeds its
    tokens with its own matrix, but its head runs on the partner — the
    top owner — so the loss sees the partner's matrix as ``tied_head``
    and that gradient returns to the partner's ``embed``, as in the
    bucketed and dist engines.  The partner's rows are selected after
    the cast, so no float32 copy of its matrix is held.  The loss must
    make that cast exactly once, outside any scan, so the head's gradient
    is rounded to this dtype once, as it is on the float32 matrix; under
    a scan it would accumulate in this dtype.

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
        rows = functools.partial(_partner_rows, partner=partner)
        masks = jax.vmap(splitting.layer_mask, in_axes=(0, None))(
            lengths, num_layers)                                 # (N, W)
        masks_p = rows(masks)

        # bottom/stack[<L] from own, rest from the partner's rows
        def mix(a, label):
            if label == "bottom":
                return a
            if label == "top":
                return rows(a)
            m = _bmask(masks, a)
            return a * m + rows(a) * (1.0 - m)

        mixed = jax.tree_util.tree_map(mix, client_params, plan)
        if tied_head_dtype is not None:
            head = client_params["embed"].astype(tied_head_dtype)
            mixed = dict(mixed, tied_head=rows(head))
        losses, g_mix = jax.vmap(jax.value_and_grad(loss_fn))(mixed, batches)
        if tied_head_dtype is not None:
            g_mix = dict(g_mix)
            g_head = g_mix.pop("tied_head")

        if fed_cfg.aggregation == "paper":
            a_own, a_in = agg_w, rows(agg_w)
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
                u = a_in.reshape(b) * rows(g)
            else:
                m = _bmask(masks, g)
                u = (a_own.reshape(b) * (g * m)
                     + a_in.reshape(b) * (rows(g) * (1.0 - _bmask(masks_p, g))))
                u = u * _bmask(factor, g).astype(u.dtype)
            return p - fed_cfg.lr * u

        new_params = jax.tree_util.tree_map(apply, client_params, g_mix, plan)
        if tied_head_dtype is not None:
            # the partner flow's head gradient on my matrix: a "top" term
            b = (n,) + (1,) * (g_head.ndim - 1)
            new_params["embed"] = new_params["embed"] - fed_cfg.lr * (
                a_in.reshape(b) * rows(g_head))
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
