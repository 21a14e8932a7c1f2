"""Plain reference of FedPairing rounds (paper §II, Algorithm 2), float32.

Given the initial global model, each round's pairing and propagation
lengths, and the batches every client drew, it replays the rounds:

* flow i (client i's data, partner j, length L_i) runs the input embedding
  and layers [0, L_i) on client i's parameters and layers [L_i, W), the
  final norm and the head on client j's: a tied head is client j's
  embedding matrix;
* each flow's gradient goes back to the owner of each part (split
  learning's boundary-gradient hand-back);
* client i steps ω_i -= (η/N) · F_i ⊙ (a_i g_i→i + a_j g_j→i), with
  a_i = |D_i| / (|D_i| + |D_j|) and F_i = 2 on the layers that both flows
  cross on client i (Eq. 7), 1 elsewhere;
* after the local steps the server takes the mean of the replicas
  (Algorithm 2) and broadcasts it.

η/N and the pair-normalized a_i are the system's stated normalization
(its DESIGN.md §3 and §5).  Each flow's loss and gradient come from the
configuration's plain reference at ``highest`` precision.  Everything stays
on the device, one pair at a time: the two replicas, the two flows'
gradients and one flow's activations.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def overlap_factor(length, partner_length, num_layers: int):
    """(W,) Eq. (7) factor on a client: 2 where its own flow (l < L_i) and
    its partner's flow (l >= L_j) both cross the layer."""
    l = jnp.arange(num_layers)
    return jnp.where((l < length) & (l >= partner_length), 2.0, 1.0)


def _per_layer(mask, leaf):
    return mask.reshape((-1,) + (1,) * (leaf.ndim - 1)).astype(leaf.dtype)


def make_steps(loss: Callable, cfg: Dict, num_layers: int, tied: bool,
               lr: float, n: int):
    """(flow_grad, client_update), jitted."""

    @jax.jit
    def flow_grad(own, part, length, tokens, labels):
        own_layer = jnp.arange(num_layers) < length
        fp = {"embed": own["embed"], "ln_f": part["ln_f"],
              "head": part["embed"] if tied else part["unembed"],
              "blocks": jax.tree_util.tree_map(
                  lambda o, p: jnp.where(
                      own_layer.reshape((-1,) + (1,) * (o.ndim - 1)), o, p),
                  own["blocks"], part["blocks"])}
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(loss)(fp, tokens, labels, cfg)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def client_update(w, g_own, g_in, length, partner_length, a_own, a_in):
        """ω_i's step from its own flow's gradient (the bottom it ran) and
        its partner's flow's gradient (the top the partner ran on ω_i)."""
        c = lr / n
        layer = jnp.arange(num_layers)
        own_m, in_m = layer < length, layer >= partner_length
        f = overlap_factor(length, partner_length, num_layers)
        new = {"embed": w["embed"] - c * a_own * g_own["embed"],
               "ln_f": w["ln_f"] - c * a_in * g_in["ln_f"]}
        if tied:
            new["embed"] = new["embed"] - c * a_in * g_in["head"]
        else:
            new["unembed"] = w["unembed"] - c * a_in * g_in["head"]
        new["blocks"] = jax.tree_util.tree_map(
            lambda p, go, gi: p - c * _per_layer(f, p) * (
                a_own * go * _per_layer(own_m, go)
                + a_in * gi * _per_layer(in_m, gi)),
            w["blocks"], g_own["blocks"], g_in["blocks"])
        return new

    return flow_grad, client_update


def follow_rounds(init: Dict, rounds: Sequence[Dict], data_sizes: np.ndarray,
                  lr: float, loss: Callable, cfg: Dict,
                  on_first_step: Callable[[List[Dict]], None]) -> Dict:
    """Replay ``rounds``; each is {"partner": (N,), "lengths": (N,),
    "batches": [host batch with (N, B, S) tokens/labels per local step]}.

    ``on_first_step(replicas)`` sees the N client replicas after the first
    local step.  Returns {"losses": (R, K, N) float64, "global": the model
    after the last round, on the device}."""
    tied = "unembed" not in init
    n = len(data_sizes)
    num_layers = jax.tree_util.tree_leaves(init["blocks"])[0].shape[0]
    flow_grad, client_update = make_steps(loss, cfg, num_layers, tied, lr, n)
    glob = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                  init)
    reps = [glob] + [jax.tree_util.tree_map(jnp.copy, glob)
                     for _ in range(n - 1)]
    d = np.asarray(data_sizes, np.float64)
    losses, first = [], True
    for rnd in rounds:
        partner = np.asarray(rnd["partner"], np.int64)
        lengths = np.asarray(rnd["lengths"], np.int64)
        a = (d / (d + d[partner])).astype(np.float32)
        step_losses = []
        for batch in rnd["batches"]:
            flow_l = np.zeros(n)
            for i in range(n):
                j = int(partner[i])
                if j < i:
                    continue                   # the pair was done at j
                pair = [(i, j)] if i == j else [(i, j), (j, i)]
                g = {}
                for k, o in pair:
                    val, g[k] = flow_grad(reps[k], reps[o], int(lengths[k]),
                                          batch["tokens"][k],
                                          batch["labels"][k])
                    flow_l[k] = float(val)
                for k, o in pair:
                    reps[k] = client_update(
                        reps[k], g[k], g[o], int(lengths[k]),
                        int(lengths[o]), a[k], a[o])
                del g
            step_losses.append(flow_l)
            if first:
                on_first_step(reps)
                first = False
        losses.append(step_losses)
        glob = jax.tree_util.tree_map(lambda *xs: sum(xs) / np.float32(n),
                                      *reps)
        reps = [glob] + [jax.tree_util.tree_map(jnp.copy, glob)
                         for _ in range(n - 1)]
    return {"losses": np.asarray(losses, np.float64), "global": glob}
