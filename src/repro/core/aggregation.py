"""Server-side model aggregation — the aggregation-policy registry.

Two weighting modes (see DESIGN.md §3 — the paper is internally
inconsistent):
* ``paper``  — Algorithm 2 verbatim: gradients were pre-weighted by a_i
               during local training, server takes the plain mean
               ``ω_g = (1/N) Σ ω_i``.
* ``fedavg`` — classic McMahan weighting at the server:
               ``ω_g = Σ a_i ω_i`` (local updates unweighted).

Orthogonal to the weighting mode, the *policy* registry (DESIGN.md §13,
mirroring ``planning.SplitPolicy`` / ``pairing.PairingPolicy``) selects
HOW the cohort's replicas become the next global model:

* ``mean``     — the historical ``aggregate()`` below, stateless,
                 bit-identical to the pre-registry driver by construction
                 (the policy runs its reduction, written into the
                 replicas, through ``aggregate_into``).
* ``scaffold`` — SCAFFOLD-style variance reduction (Karimireddy et al.,
                 arXiv 1910.06378): a server control variate ``c_global``
                 plus per-client variates ``c_local``, delta-based
                 updates, partial-participation correction.  The round's
                 jointly-trained pair models are attributed to BOTH
                 members' variates weighted by their Eq. (6) layer shares
                 (the pairing-composition rule, DESIGN.md §13).

Mesh-awareness (DESIGN.md §11): both reductions run over the leading
client axis, so when the replicas arrive sharded over the fleet mesh
(``sharding.fleet.FleetSharding``) XLA lowers the mean / weighted sum into
per-shard partial sums plus the cross-device psum-style combine — no
separate collective code path, and the zero-weight hard-mask below is
applied per shard BEFORE the combine, so an excluded replica's values are
never read on any device.  ``broadcast`` accepts the fleet sharding so
the post-round global model lands back on the client placement directly
(device-to-device; fleet state lives sharded across rounds), and the
scaffold policy's per-client variate tree is placed with the same client
rule (``init_state(sharding=...)``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry


class EmptyCohortError(ValueError):
    """``aggregate()`` was asked to average an empty cohort (all-False
    ``active`` mask, zero-size client axis, or weights summing to zero) —
    dividing would NaN the global params.  Raised with the round index
    when the caller supplies one (like ``rounds.NonFiniteLossError``), so
    the failing round is nameable from the stack trace alone; a round
    with no survivors must be SKIPPED by the caller (``rounds`` /
    ``faults``), never aggregated."""

    def __init__(self, round_idx: Optional[int] = None):
        self.round = None if round_idx is None else int(round_idx)
        where = "" if self.round is None else f" in round {self.round}"
        super().__init__(
            f"aggregate() called with an empty cohort{where} (aggregation "
            f"weights sum to zero) — dividing would NaN the global params; "
            f"skip the round instead")


def _host_inputs(*xs) -> Tuple[Optional[np.ndarray], ...]:
    """NumPy copies of the weight inputs.  The driver passes NumPy, which
    costs nothing; a device array among them (direct callers, tests) is
    read in one blocking ``device_get``, counted as one ``host_sync``."""
    if any(isinstance(x, jax.Array) for x in xs):
        telemetry.count("host_sync")
        xs = jax.device_get(xs)
    return tuple(None if x is None else np.asarray(x) for x in xs)


def _unnormalized_weights(agg_w, mode: str, active, staleness
                          ) -> Optional[np.ndarray]:
    """The (N,) float32 pre-normalization weight vector, on the host, or
    ``None`` for the plain-mean fast path (mode "paper", no mask, no
    staleness) — the one place the mode × mask × staleness composition
    is defined, shared by ``aggregate`` and ``aggregation_weights``."""
    if mode not in ("paper", "fedavg"):
        raise ValueError(f"unknown aggregation mode {mode!r}")
    agg_w, active, staleness = _host_inputs(
        agg_w if mode == "fedavg" else None, active, staleness)
    if staleness is not None and not staleness.any():
        staleness = None            # all fresh: keep the synchronous path
    if mode == "paper":
        if active is None and staleness is None:
            return None
        w = (np.ones(staleness.shape, np.float32) if active is None
             else active.astype(np.float32))
    else:
        w = agg_w.astype(np.float32)
        if active is not None:
            w = w * active.astype(np.float32)
    if staleness is not None:
        w = w / (np.float32(1.0) + staleness.astype(np.float32))
    return w


def _normalized(w: np.ndarray, round_idx: Optional[int]) -> np.ndarray:
    """``w / Σw`` in float32, or ``EmptyCohortError`` when the weights sum
    to zero — decided from host values, with no device read."""
    total = w.sum(dtype=np.float32)
    if float(total) <= 0.0:
        raise EmptyCohortError(round_idx)
    return w / total


@jax.jit
def _masked_weighted_mean(w: jnp.ndarray, tree: Dict) -> Dict:
    """``Σ_i w_i · tree[i]`` over the leading client axis, the whole tree
    in one program; ``w`` is a traced (N,) input, so one compile serves
    every mask, mode and staleness pattern of a tree.

    The zero-weight hard-mask: 0 * nan is nan, and an EXCLUDED client's
    values may legitimately be garbage (a late straggler that diverged) —
    exclusion must mean its values are never read.  The sum is an explicit
    multiply and a reduce over the client axis in the leaf's dtype (full
    float32 for float32 replicas), never a default-precision dot."""

    def wmean(a):
        shape = (-1,) + (1,) * (a.ndim - 1)
        keep = (w > 0).reshape(shape)
        term = w.astype(a.dtype).reshape(shape) * a
        return jnp.sum(jnp.where(keep, term, jnp.zeros((), a.dtype)), axis=0)

    return jax.tree_util.tree_map(wmean, tree)


@jax.jit
def _plain_mean(tree: Dict) -> Dict:
    """The unweighted mean over the client axis, the whole tree in one
    program (``jnp.mean``, so the plain-mean bits are unchanged)."""
    return jax.tree_util.tree_map(lambda a: jnp.mean(a, axis=0), tree)


def aggregate(client_params: Dict, agg_w, mode: str = "paper",
              active=None, staleness=None,
              round_idx: Optional[int] = None) -> Dict:
    """client_params stacked (N, ...) -> global params.

    ``active`` (N,) bool restricts the aggregation to a participating
    cohort (partial participation): non-participants' replicas are
    excluded — "paper" becomes the mean over the cohort, "fedavg" the
    cohort-renormalized weighted mean.  An empty cohort (all-False
    ``active``, or weights summing to zero) raises ``EmptyCohortError``
    (naming the round when ``round_idx`` is given) instead of silently
    renormalizing by zero into NaN params.

    ``staleness`` (N,) int — bounded-staleness async rounds (DESIGN.md
    §12): client ``i`` trained from a model ``staleness[i]`` merges
    behind the current one, so its replica's weight is scaled by
    ``1/(1+staleness[i])`` before renormalization — stale updates still
    count, just less, the standard async-FL discount.  Composes with
    ``active`` and the zero-weight hard-mask; ``None`` (the synchronous
    path) or an all-zero vector (async at staleness bound 0) leaves
    every weight untouched, preserving the §12 bit-identity contract.

    The weights are composed on the host (NumPy ``agg_w`` / ``active`` /
    ``staleness`` cost no device read) and the reduction is one jitted
    program over the whole tree.
    """
    w = _unnormalized_weights(agg_w, mode, active, staleness)
    if w is None:
        telemetry.count("agg_program")
        return _plain_mean(client_params)
    w = _normalized(w, round_idx)
    telemetry.count("agg_program")
    return _masked_weighted_mean(w, client_params)


def aggregation_weights(agg_w, n: int, mode: str = "paper",
                        active=None, staleness=None,
                        round_idx: Optional[int] = None) -> np.ndarray:
    """The normalized (N,) per-client weight vector ``aggregate`` reduces
    with — what the variance-reduced policies consume to keep their
    correction term on EXACTLY the weights of the base step (cohort mask,
    staleness discount and hard-mask semantics included).  The plain-mean
    fast path normalizes to the uniform 1/N vector."""
    w = _unnormalized_weights(agg_w, mode, active, staleness)
    if w is None:
        return np.full((n,), 1.0 / n, np.float32)
    return _normalized(w, round_idx)


def _replicas(global_params: Dict, n: int, into=None) -> Dict:
    del into            # donated: its buffers receive the replicas
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), global_params)


@functools.lru_cache(maxsize=16)
def _broadcast_program(out_sharding, donate: bool):
    """The broadcast program for one client placement (``None``: the
    default device), writing into donated replicas or into new ones.
    ``keep_unused`` keeps the donated tree a parameter of the program, so
    XLA aliases it to the output instead of pruning it."""
    placed = {} if out_sharding is None else {"out_shardings": out_sharding}
    return jax.jit(_replicas, static_argnums=1, keep_unused=True,
                   donate_argnums=(2,) if donate else (), **placed)


def broadcast(global_params: Dict, n: int, sharding=None,
              into: Optional[Dict] = None) -> Dict:
    """Global params -> N stacked client replicas, one jitted program over
    the whole tree.  With a ``FleetSharding`` the program's outputs are
    the client placement (leading dim over the mesh's fleet axis), so no
    unsharded (N, ...) tree is materialized and nothing is re-placed.

    ``into`` is the stacked (N, ...) tree the new replicas replace, when
    the caller has no further use for it (the round's trained replicas,
    once aggregated): it is donated and the replicas are written into its
    buffers, so the device holds one set of replicas across the round
    boundary instead of allocating a second set beside it."""
    out_sharding = None if sharding is None else sharding.client_sharding(
        jax.ShapeDtypeStruct((n,), jnp.float32))
    program = _broadcast_program(out_sharding, into is not None)
    telemetry.count("agg_program")
    return program(global_params, n, into)


def _reduce_into(w: Optional[jnp.ndarray], tree: Dict) -> Dict:
    """The mean (weighted by ``w``, or plain for ``None``) written back over
    every row of the stacked ``tree``: ``aggregate``'s reduction and
    ``broadcast``'s replicas, leaf by leaf."""
    g = _plain_mean(tree) if w is None else _masked_weighted_mean(w, tree)
    return jax.tree_util.tree_map(
        lambda m, a: jnp.broadcast_to(m[None], a.shape), g, tree)


@functools.lru_cache(maxsize=16)
def _reduce_into_program(out_sharding):
    """``_reduce_into`` as one program for one client placement, the
    stacked tree donated so every output aliases its buffer."""
    placed = {} if out_sharding is None else {"out_shardings": out_sharding}
    return jax.jit(_reduce_into, donate_argnums=(1,), **placed)


def aggregate_into(client_params: Dict, agg_w, mode: str = "paper",
                   active=None, staleness=None,
                   round_idx: Optional[int] = None, sharding=None) -> Dict:
    """``broadcast(aggregate(client_params, ...), n, sharding,
    into=client_params)`` as ONE jitted program: the same host weights,
    the same reduction, and the new replicas written into the donated
    ``client_params``.

    The global model is then never a device buffer of its own: the
    program holds one leaf's mean at a time as a temporary (the largest
    leaf, not the whole model), and every output aliases a donated
    buffer, so nothing is allocated.  That matters where the local step
    reserves most of the memory the replicas leave free.  The driver
    dispatches the aggregation as soon as the last step's loss is read,
    while the runtime may still hold that step's reservation; a whole
    global model allocated then fails, and the runtime defragments and
    retries, at times for seconds (StableLM 2 at 6 layers on one 16 GB
    v5e: 5.76 GB of replicas, 8.74 GB reserved by the step, a 2.88 GB
    global model; the largest leaf is 0.82 GB)."""
    n = jax.tree_util.tree_leaves(client_params)[0].shape[0]
    w = _unnormalized_weights(agg_w, mode, active, staleness)
    if w is not None:
        w = _normalized(w, round_idx)
    out_sharding = None if sharding is None else sharding.client_sharding(
        jax.ShapeDtypeStruct((n,), jnp.float32))
    telemetry.count("agg_program")
    return _reduce_into_program(out_sharding)(w, client_params)


# ---------------------------------------------------------------------------
# aggregation-policy registry (DESIGN.md §13)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AggContext:
    """Round-side inputs the stateful policies need, handed over by the
    driver per round (the stateless ``mean`` policy never sees one).

    ``prev_global`` is the pre-round broadcast model x (snapshotted
    before the donating engines consume the replica buffers);
    ``partner``/``lengths`` come from the executed ``RoundPlan`` — the
    DEGRADED plan under faults, so ownership attribution follows what
    actually trained; ``lr``/``steps`` define the round's nominal local
    step product K·η that turns a model delta back into a mean gradient.
    """

    prev_global: Dict
    partner: np.ndarray          # (N,) executed pairing (self = solo)
    lengths: np.ndarray          # (N,) per-flow bottom depths l_i
    num_layers: int              # W
    lr: float                    # nominal per-step learning rate η
    steps: int                   # K = batches_per_round


class AggregationPolicy:
    """One rule mapping the cohort's trained replicas (plus optional
    policy state) to the next global model.

    ``stateful`` declares whether the policy carries cross-round state on
    ``RoundState`` (checkpointed, sharded); stateful policies also need
    the per-round ``AggContext``.  ``apply`` returns ``(replicas,
    new_state)``: the next global model's stacked replicas, one per row
    of ``client_params`` and placed by ``sharding``, written into the
    buffers of ``client_params``, which are donated — the aggregation
    and the broadcast in one call.  Stateless policies return their
    input state untouched.
    """

    spec: str = "?"
    stateful: bool = False

    def init_state(self, params_like: Dict, n: int, sharding=None):
        """Fresh policy state for an N-client fleet (None if stateless)."""
        return None

    def apply(self, client_params: Dict, agg_w: jnp.ndarray,
              mode: str = "paper", *,
              active: Optional[jnp.ndarray] = None,
              staleness: Optional[jnp.ndarray] = None,
              state=None, ctx: Optional[AggContext] = None,
              round_idx: Optional[int] = None,
              sharding=None) -> Tuple[Dict, object]:
        raise NotImplementedError

    # -- checkpoint hooks (rounds.save_state / load_state) ---------------

    def state_tree(self, state) -> Optional[Dict]:
        """The array leaves of ``state`` to checkpoint (None if none)."""
        return None

    def state_like(self, params_like: Dict, n: int) -> Optional[Dict]:
        """A like-tree for restoring ``state_tree`` leaves."""
        return None

    def restore_state(self, tree: Optional[Dict], meta: Dict,
                      sharding=None):
        """Policy state back from its checkpointed leaves + host meta."""
        return None


class MeanAggregation(AggregationPolicy):
    """The historical cohort-masked weighted mean — ``aggregate()``'s
    reduction and ``broadcast``'s replicas as one program
    (``aggregate_into``), so every row it returns is bit-identical to
    the pre-registry driver's global model."""

    spec = "mean"

    def apply(self, client_params, agg_w, mode="paper", *, active=None,
              staleness=None, state=None, ctx=None, round_idx=None,
              sharding=None):
        return aggregate_into(client_params, agg_w, mode, active=active,
                              staleness=staleness, round_idx=round_idx,
                              sharding=sharding), state


@dataclasses.dataclass
class ScaffoldState:
    """SCAFFOLD control-variate state (lives on ``RoundState.agg``).

    ``c_global`` estimates the full-fleet mean gradient; ``c_local`` is
    the stacked (N, ...) per-CLIENT variate tree (client-axis fleet
    state: sharded over the mesh like the parameter replicas, DESIGN.md
    §11).  ``applied`` is False until the first variate update — while
    False the correction is skipped entirely, which keeps the first
    scaffold round bit-identical to ``mean`` (mathematically the zero
    variates contribute nothing; skipping keeps it exact at the bit
    level too)."""

    c_global: Dict
    c_local: Dict
    applied: bool = False


class ScaffoldAggregation(AggregationPolicy):
    """SCAFFOLD-over-pairs: variance-reduced aggregation for non-IID
    cohorts (DESIGN.md §13).

    Per round, with x the pre-round global model, y_i the trained
    replicas, K·η the round's nominal local step product and w̃ the base
    step's normalized weights (cohort mask × staleness discount):

    1. base step        g  = Σ w̃_i y_i                    (``aggregate``)
    2. correction       x⁺ = g + K·η · (Σ w̃_i c_i − c)
       — the partial-participation correction: at full participation
       Σ w̃ c_i tracks c and the term vanishes; under cohort sampling it
       re-centers the sampled cohort's update toward the full-fleet
       gradient estimate, which is exactly where the non-IID gap opens.
    3. variate refresh  G_f = (x − y_f)/(K·η) per trained flow f — the
       flow's observed mean gradient; each cohort member's new variate is
       the Eq. (6) layer-share-weighted convex combination of the flows
       it computed (the pair-ownership rule):

           c_k⁺ = (s_own·G_k + s_part·G_p) / (s_own + s_part)
           s_own  = l_k / W         (its own flow's bottom stack)
           s_part = (W − l_p) / W   (the top stack of its partner's flow)

       Solo flows (partner == self) reduce to c_k⁺ = G_k; complementary
       pair cuts (l_p = W − l_k, the planner's invariant) reduce to the
       pair mean (G_k + G_p)/2 — the pair co-owns ONE drift estimate,
       weighted apart again only when granularity rounding or degraded
       re-pairing makes the shares asymmetric.  Excluded clients (fault
       hard-mask / outside the cohort) keep their variates and NEVER
       move ``c_global``.
    4. server variate   c ← c + (1/N) Σ_{k∈S} (c_k⁺ − c_k)
       — SCAFFOLD's |S|/N partial-participation scaling of the cohort
       mean delta.
    """

    spec = "scaffold"
    stateful = True

    def init_state(self, params_like, n, sharding=None):
        c_global = jax.tree_util.tree_map(jnp.zeros_like, params_like)
        c_local = jax.tree_util.tree_map(
            lambda a: jnp.zeros((n,) + a.shape, a.dtype), params_like)
        if sharding is not None:
            c_global = sharding.place_replicated(c_global)
            c_local = sharding.place(c_local)
        return ScaffoldState(c_global=c_global, c_local=c_local,
                             applied=False)

    def apply(self, client_params, agg_w, mode="paper", *, active=None,
              staleness=None, state=None, ctx=None, round_idx=None,
              sharding=None):
        if state is None or ctx is None:
            raise ValueError(
                "scaffold aggregation needs its ScaffoldState and the "
                "round's AggContext (prev model, executed plan, K, lr) — "
                "drive it through rounds.RoundDriver or supply both")
        n = int(np.asarray(ctx.partner).shape[0])
        g = aggregate(client_params, agg_w, mode, active=active,
                      staleness=staleness, round_idx=round_idx)
        w = aggregation_weights(agg_w, n, mode, active=active,
                                staleness=staleness, round_idx=round_idx)
        k_eta = float(ctx.steps) * float(ctx.lr)
        if state.applied:
            telemetry.count("agg_program")
            cbar = _masked_weighted_mean(w, state.c_local)
            g = jax.tree_util.tree_map(
                lambda x, cb, cg: x + jnp.asarray(k_eta, x.dtype)
                * (cb - cg).astype(x.dtype),
                g, cbar, state.c_global)

        # -- variate refresh over the trained cohort ----------------------
        (act,) = _host_inputs(active)
        act = np.ones(n, bool) if act is None else act.astype(bool)
        partner = np.asarray(ctx.partner, np.int64)
        lengths = np.asarray(ctx.lengths, np.float64)
        W = float(ctx.num_layers)
        s_own = lengths / W
        # the top stack of the partner's flow is what k computed for it;
        # solo flows and excluded partners attribute nothing
        s_part = np.where(partner != np.arange(n),
                          (W - lengths[partner]) / W, 0.0)
        s_part = s_part * act[partner].astype(np.float64)
        denom = np.maximum(s_own + s_part, 1e-12)
        act_j = jnp.asarray(act)
        so_j, sp_j, dn_j = (jnp.asarray(v, jnp.float32)
                            for v in (s_own, s_part, denom))

        def refresh(x, y, c_old):
            keep = act_j.reshape((-1,) + (1,) * (y.ndim - 1))
            # hard-mask BEFORE use: an excluded replica's params may be
            # garbage (faulted straggler) — its flow gradient must read 0
            G = jnp.where(keep, (x - y) / jnp.asarray(k_eta, y.dtype),
                          jnp.zeros((), y.dtype))
            shape = (-1,) + (1,) * (y.ndim - 1)
            c_new = (so_j.reshape(shape).astype(y.dtype) * G
                     + sp_j.reshape(shape).astype(y.dtype) * G[partner]) \
                / dn_j.reshape(shape).astype(y.dtype)
            delta = jnp.where(keep, c_new - c_old, jnp.zeros((), y.dtype))
            return jnp.where(keep, c_new, c_old), delta

        new_local, deltas = {}, []
        flat, treedef = jax.tree_util.tree_flatten(client_params)
        flat_x = jax.tree_util.tree_leaves(ctx.prev_global)
        flat_c = jax.tree_util.tree_leaves(state.c_local)
        out_c, out_d = [], []
        for x, y, c_old in zip(flat_x, flat, flat_c):
            c_new, delta = refresh(x, y, c_old)
            out_c.append(c_new)
            out_d.append(delta)
        c_local = jax.tree_util.tree_unflatten(treedef, out_c)
        # c += (|S|/N) · mean_S(Δc) == (1/N) Σ_S Δc — excluded deltas are
        # already zeroed, so the sum IS the cohort sum
        c_global = jax.tree_util.tree_map(
            lambda cg, d: cg + jnp.sum(d, axis=0) / n,
            state.c_global,
            jax.tree_util.tree_unflatten(treedef, out_d))
        with telemetry.span("broadcast"):
            g = broadcast(g, n, sharding=sharding, into=client_params)
        return g, ScaffoldState(c_global=c_global, c_local=c_local,
                                applied=True)

    # -- checkpoint hooks -------------------------------------------------

    def state_tree(self, state):
        return {"c_global": state.c_global, "c_local": state.c_local}

    def state_like(self, params_like, n):
        return {"c_global": params_like,
                "c_local": jax.tree_util.tree_map(
                    lambda a: np.zeros((n,) + a.shape,
                                       np.asarray(a).dtype), params_like)}

    def restore_state(self, tree, meta, sharding=None):
        c_global = jax.tree_util.tree_map(jnp.asarray, tree["c_global"])
        c_local = jax.tree_util.tree_map(jnp.asarray, tree["c_local"])
        if sharding is not None:
            c_global = sharding.place_replicated(c_global)
            c_local = sharding.place(c_local)
        return ScaffoldState(c_global=c_global, c_local=c_local,
                             applied=bool(meta.get("agg_applied", True)))


AGG_POLICY_SPECS: Tuple[str, ...] = ("mean", "scaffold")


def get_aggregation_policy(spec) -> AggregationPolicy:
    """Resolve a policy spec string (``mean`` | ``scaffold``) to an
    ``AggregationPolicy``; passes policy instances through (the
    benchmarks' recording wrappers)."""
    if isinstance(spec, AggregationPolicy):
        return spec
    if spec == "mean":
        return MeanAggregation()
    if spec == "scaffold":
        return ScaffoldAggregation()
    raise ValueError(f"unknown aggregation policy {spec!r}; expected one "
                     f"of {AGG_POLICY_SPECS}")
