"""Multi-round federated simulation driver (paper Algorithm 2 at round
scale — the loop behind Figs. 2-3 and Tables I/II).

Each round the driver, in this fixed order (the determinism contract,
DESIGN.md §5):

1. realizes the time-varying channel (``latency.drift_fleet`` position
   random walk; skipped without an rng draw when ``drift_sigma_m <= 0``),
2. samples the participating cohort (``participation.sample_cohort``),
3. plans the round: under the configured ``pair_policy`` (a
   ``pairing.PairingPolicy`` spec; Table-I mechanisms are aliases) the
   cohort is re-matched on the current channel realization — cost-driven
   policies go through ``planning.build_joint_plan`` (pairing AND cuts
   chosen together), the weight heuristics through
   ``participation.cohort_partner`` — yielding the round's
   ``planning.RoundPlan``: the single source of truth for split lengths
   (under ``RoundConfig.split_policy``), envelopes, baseline cuts and the
   Eq. (4) objective.  With ``replan_threshold > 0`` the matching is
   ADAPTIVE: the previous plan is re-priced on the drifted channel
   (``planning.plan_objective``) and kept — same pairing, same compiled
   steps — unless its objective moved by more than the threshold
   (relative) or the cohort changed (DESIGN.md §7).  Cost-driven
   re-matchings consult the driver's cross-round ``PlannerCache``: a
   kept cohort's candidate-edge cut search is reused (cuts re-priced on
   the current rates, O(N^2)) instead of re-run (O(N^2 W)), invalidated
   by the same relative-drift signal (``RoundRecord.cut_cache`` records
   hit/miss/invalidated per round; DESIGN.md §8),
4. (fedpairing) realizes the round's faults and evaluates the Eq. (3)
   analytical latency of the executed plan into simulated wall-clock
   (straggler = round max; ``faults.faulted_clock``, equal to
   ``latency.round_clock_plan`` when no fault is realized); a round the
   clock declares lost trains nothing.  The baselines accumulate their
   own Eq. (3) clocks after training,
5. executes ``batches_per_round`` fed steps on one of the three FedPairing
   engines — vmapped / bucketed / dist — or one of the paper's baselines
   (vanilla FL / vanilla SL / SplitFed from ``core.baselines``),
6. applies pair-then-global aggregation over the cohort and broadcasts.

All randomness flows from ONE ``np.random.Generator`` seeded with
``RoundConfig.seed`` and consumed in the order above, so two drivers with
the same config (engine aside) see identical cohorts, channel
realizations, pairings and lengths — that is what makes round-level
cross-engine equivalence testable (``tests/test_rounds.py``).

Engine normalization: the bucketed and dist engines differentiate a total
loss pre-normalized by 1/N, while the vmapped parameter-mix core applies
per-client gradients directly — the driver builds the vmapped step with
``lr / N`` so all three engines take identical parameter steps (cf.
``tests/test_fedbucket.py::test_bucketed_matches_vmapped_mix_core``).

Re-pairing vs recompilation: the vmapped step takes partner/lengths as
*traced* arguments (one compile covers every round), while the bucketed
and dist steps specialize on the pairing — the driver memoizes built steps
by (``RoundPlan.cache_key()``, agg weights), so recompiles are bounded by
the number of *distinct* plans the channel process visits, not by the
number of rounds (``RoundRecord.cached_steps`` tracks the bound).
"""
from __future__ import annotations

import copy
import dataclasses
import json
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat, telemetry
from repro.core import aggregation, baselines, fedpair, latency, pairing
from repro.core import faults, participation, planning, splitting
from repro.core.latency import ChannelModel, ClientFleet, WorkloadModel
from repro.core.planning import RoundPlan
from repro.sharding.fleet import FleetSharding

ALGORITHMS = ("fedpairing", "fl", "sl", "splitfed")
ENGINES = ("vmapped", "bucketed", "dist")

# Table-I pairing mechanisms selectable per round.  ALL of them (including
# "random", whose per-round seed comes from the driver rng) resolve through
# the ONE registry resolver ``pairing.get_pairing_policy`` — an unknown
# mechanism or policy raises at RoundConfig construction, not mid-round.
PAIRINGS: Tuple[str, ...] = pairing.TABLE1_MECHANISMS


@dataclasses.dataclass(frozen=True)
class RoundConfig:
    """Static knobs of the multi-round loop (see module docstring)."""

    algorithm: str = "fedpairing"       # fedpairing | fl | sl | splitfed
    engine: str = "vmapped"             # fedpairing only: vmapped|bucketed|dist
    rounds: int = 3
    batches_per_round: int = 4
    participation: float = 1.0          # cohort fraction per round
    drift_sigma_m: float = 0.0          # channel realization: position walk
    pair_mechanism: str = "fedpairing"  # Table-I mechanisms (PAIRINGS)
    pair_policy: str = ""               # pairing.PAIRING_SPECS; "" -> the
                                        # Table-I mechanism above
    split_policy: str = "paper"         # paper | fixed:K | latency-opt
    replan_threshold: float = 0.0       # adaptive re-matching: keep the
                                        # previous plan while its re-priced
                                        # objective moved less than this
                                        # (relative); 0 -> re-plan each round
    cut_cache: bool = True              # cross-round cut-search cache for
                                        # cost-driven pairing (re-plans
                                        # re-price cached cuts instead of
                                        # re-searching; tolerance =
                                        # replan_threshold, DESIGN.md §8)
    lr: float = 0.05
    aggregation: str = "paper"          # paper | fedavg (DESIGN.md §3)
    agg_policy: str = "mean"            # aggregation-policy registry
                                        # (mean | scaffold, DESIGN.md §13);
                                        # orthogonal to the weighting mode
                                        # above
    overlap_boost: bool = True
    bucket_granularity: int = 1
    server_cut: int = 0                 # sl/splitfed split; 0 -> W//2
    donate: bool = True                 # thread params in place (engines)
    seed: int = 0
    # fault injection (core.faults): None means FaultConfig().  Every
    # fedpairing round runs through the fault layer; one that realizes
    # no fault gives the fault-free result bit for bit (the zero-cost
    # contract, DESIGN.md §9).
    faults: Optional[faults.FaultConfig] = None
    # async pipelined rounds (DESIGN.md §12): event-driven clock instead
    # of the round-max barrier.  At staleness_bound 0 with
    # overlap_planning off the trace is bit-identical to the synchronous
    # driver (the §12 equality contract); bound S > 0 lets a unit start
    # from a model up to S merges old, discounted 1/(1+s) at aggregation.
    async_rounds: bool = False
    staleness_bound: int = 0
    overlap_planning: bool = False      # pre-build the predicted next plan
                                        # off the critical path (cost-
                                        # driven pairing only)

    def __post_init__(self):
        if self.algorithm not in ALGORITHMS:
            raise ValueError(f"algorithm must be one of {ALGORITHMS}, "
                             f"got {self.algorithm!r}")
        if self.engine not in ENGINES:
            raise ValueError(f"engine must be one of {ENGINES}, "
                             f"got {self.engine!r}")
        if not 0.0 < self.participation <= 1.0:
            raise ValueError(f"participation must lie in (0, 1], got "
                             f"{self.participation} (a cohort fraction)")
        if self.batches_per_round < 1:
            raise ValueError(f"batches_per_round must be >= 1, got "
                             f"{self.batches_per_round}")
        if self.faults is not None:
            if not isinstance(self.faults, faults.FaultConfig):
                raise ValueError(f"faults must be a faults.FaultConfig, "
                                 f"got {type(self.faults).__name__}")
            if self.faults.enabled and self.algorithm != "fedpairing":
                raise ValueError(
                    f"fault injection is wired through the fedpairing "
                    f"round (pair degradation, Eq. (3) clock); algorithm "
                    f"{self.algorithm!r} does not support it")
        if self.pair_mechanism not in PAIRINGS:
            raise ValueError(f"pair_mechanism must be one of "
                             f"{PAIRINGS}, got {self.pair_mechanism!r}")
        if self.pair_policy and self.pair_mechanism != "fedpairing":
            raise ValueError(
                f"pair_policy={self.pair_policy!r} and pair_mechanism="
                f"{self.pair_mechanism!r} are one knob — set at most one "
                f"(pair_policy generalizes the Table-I mechanisms)")
        pairing.get_pairing_policy(self.resolved_pair_policy)
        planning.get_policy(self.split_policy)   # raises on unknown spec
        if self.replan_threshold < 0:
            raise ValueError(f"replan_threshold must be >= 0, got "
                             f"{self.replan_threshold}")
        if self.aggregation not in ("paper", "fedavg"):
            raise ValueError(f"aggregation must be 'paper' or 'fedavg', "
                             f"got {self.aggregation!r}")
        agg_pol = aggregation.get_aggregation_policy(self.agg_policy)
        if agg_pol.stateful and self.algorithm not in ("fedpairing", "fl"):
            raise ValueError(
                f"stateful aggregation policy {agg_pol.spec!r} keeps "
                f"per-client control variates on the stacked replica axis "
                f"(fedpairing, fl); algorithm {self.algorithm!r} trains a "
                f"shared relay tree with no per-client axis to correct")
        if self.staleness_bound < 0:
            raise ValueError(f"staleness_bound must be >= 0, got "
                             f"{self.staleness_bound}")
        if self.async_rounds and self.algorithm != "fedpairing":
            raise ValueError(
                f"async_rounds drives the fedpairing unit decomposition "
                f"(per-pair completion events); algorithm "
                f"{self.algorithm!r} has no units to pipeline")
        if not self.async_rounds and (self.staleness_bound > 0
                                      or self.overlap_planning):
            raise ValueError(
                "staleness_bound / overlap_planning modify the async "
                "scheduler — set async_rounds=True (the synchronous path "
                "has no staleness and nothing to overlap)")

    @property
    def resolved_pair_policy(self) -> str:
        """The effective PairingPolicy spec (``pair_policy`` wins; the
        Table-I ``pair_mechanism`` is the backwards-compatible alias)."""
        return self.pair_policy or self.pair_mechanism


@dataclasses.dataclass(frozen=True)
class RoundRecord:
    """Per-round trace entry (host-side; tuples so traces compare ==)."""

    round: int
    cohort: Tuple[int, ...]
    pairs: Tuple[Tuple[int, int], ...]   # global ids, i < j, sorted
    lengths: Tuple[int, ...]             # (N,) propagation lengths
    mean_loss: float                     # over the active cohort
    sim_round_s: float                   # Eq. (3) straggler-bounded
    sim_total_s: float                   # accumulated simulated wall-clock
    cached_steps: int                    # engine step-cache size (compiles)
    objective: Optional[float] = None    # Eq. (4) of the executed plan
    replanned: bool = True               # False -> adaptive keep (no
                                         # re-matching, cached steps reused)
    cut_cache: str = "n/a"               # cut-search cache provenance:
                                         # hit | miss | invalidated (a
                                         # re-matching consulted the
                                         # PlannerCache), kept (no
                                         # re-matching), n/a (weight
                                         # policy / cache disabled)
    status: str = "ok"                   # ok | degraded (survivors only) |
                                         # skipped (no survivors) |
                                         # aborted (naive abort) |
                                         # empty (zero-client cohort)
    failed: Tuple[int, ...] = ()         # clients excluded by faults
    retries: int = 0                     # link retry attempts this round
    wait_s: float = 0.0                  # barrier idle: sum over units of
                                         # (straggler max - own finish) —
                                         # what the sync path wastes and
                                         # the async clock recovers
    overlap_s: float = 0.0               # async only: seconds of this
                                         # round's execution overlapped
                                         # with earlier rounds

    def __eq__(self, other):
        # field-by-field with NaN-aware float compare: skipped/aborted
        # rounds record mean_loss = nan, and the trace-equality contract
        # ("tuples so traces compare ==") must survive them
        if not isinstance(other, RoundRecord):
            return NotImplemented
        for f in dataclasses.fields(self):
            a, b = getattr(self, f.name), getattr(other, f.name)
            if isinstance(a, float) and isinstance(b, float):
                if a != b and not (a != a and b != b):   # nan == nan here
                    return False
            elif a != b:
                return False
        return True


@dataclasses.dataclass
class RoundState:
    """Everything that survives from round to round."""

    round: int
    fleet: ClientFleet                   # current channel realization
    client_params: Dict                  # stacked (N, ...) or single (sl)
    server_params: Optional[Dict]        # sl / splitfed server side
    rng: np.random.Generator
    sim_time_s: float
    history: List[RoundRecord]
    plan: Optional[RoundPlan] = None     # adaptive anchor: the last plan a
                                         # re-matching produced, with its
                                         # at-adoption objective (the drift
                                         # reference replan_threshold
                                         # compares against)
    clock: Optional[latency.EventClockState] = None
                                         # async rounds only (DESIGN.md
                                         # §12): per-client availability +
                                         # recent merge publishes; None on
                                         # the synchronous path
    agg: Optional[object] = None         # aggregation-policy state
                                         # (DESIGN.md §13): e.g. the
                                         # scaffold control variates; None
                                         # for stateless policies (mean)


# ---------------------------------------------------------------------------
# FedPairing engines behind one interface (all consume a RoundPlan)
# ---------------------------------------------------------------------------

class _VmappedEngine:
    """Functional parameter-mix core; partner/lengths traced -> 1 compile."""

    def __init__(self, cfg, rc: RoundConfig, n: int, gparams: Dict,
                 loss_fn: Callable):
        plan = splitting.split_plan(cfg, gparams)
        fed_cfg = fedpair.FedPairingConfig(
            lr=rc.lr / n, overlap_boost=rc.overlap_boost,
            aggregation=rc.aggregation, donate=rc.donate)
        tied = getattr(cfg, "tie_embeddings", False) and "embed" in gparams
        self._step = fedpair.make_fed_step(
            loss_fn, plan, cfg.num_layers, fed_cfg,
            tied_head_dtype=cfg.dtype if tied else None)
        self.cached_steps = 1

    def step(self, params, batch, plan: RoundPlan, agg_w):
        with telemetry.span("step.args"):
            partner = jnp.asarray(plan.partner_array(), jnp.int32)
            lengths = jnp.asarray(plan.lengths_array(), jnp.int32)
            w = jnp.asarray(agg_w, jnp.float32)
        with telemetry.span("step.dispatch"):
            new, m = self._step(params, batch, partner, lengths, w)
        return new, m["loss"]

    def prebuild(self, plan: RoundPlan, agg_w) -> bool:
        """Overlap-planning hook: nothing to pre-build — the one traced
        step already covers every plan."""
        return False


def _plan_key(plan: RoundPlan, agg_w) -> Tuple:
    """Step-cache key: the plan's compiled-shape identity + the agg
    weights baked into the specialized steps."""
    return plan.cache_key() + (np.asarray(agg_w, np.float32).tobytes(),)


class _BucketedEngine:
    """Length-bucketed engine; steps specialize on the plan -> memoized."""

    def __init__(self, cfg, rc: RoundConfig, n: int, gparams, loss_fn):
        from repro.core import fedbucket
        self._cfg = cfg
        self._bcfg = fedbucket.FedBucketConfig(
            lr=rc.lr, overlap_boost=rc.overlap_boost,
            aggregation=rc.aggregation,
            bucket_granularity=rc.bucket_granularity, donate=rc.donate)
        self._make = fedbucket.make_bucketed_fed_step
        self._cache: Dict[Tuple, Callable] = {}

    @property
    def cached_steps(self) -> int:
        return len(self._cache)

    def step(self, params, batch, plan: RoundPlan, agg_w):
        key = _plan_key(plan, agg_w)
        built = self._cache.get(key)
        if built is None:
            built, _bplan = self._make(self._cfg, plan.partner_array(),
                                       plan.lengths_array(), agg_w,
                                       self._bcfg)
            self._cache[key] = built
        new, m = built(params, batch)
        return new, m["loss"]

    def prebuild(self, plan: RoundPlan, agg_w) -> bool:
        """Overlap-planning hook: build (and memoize) the predicted
        plan's specialized step off the critical path, so an adopted
        prediction's first step is a cache hit.  Returns whether a new
        step was actually built."""
        key = _plan_key(plan, agg_w)
        if key in self._cache:
            return False
        built, _bplan = self._make(self._cfg, plan.partner_array(),
                                   plan.lengths_array(), agg_w, self._bcfg)
        self._cache[key] = built
        return True


class _DistEngine:
    """shard_map + ppermute engine; pairing is baked into the collective."""

    def __init__(self, cfg, rc: RoundConfig, n: int, gparams, loss_fn):
        from repro.core import fedpair_dist
        ndev = len(jax.devices())
        if ndev < n:
            raise RuntimeError(
                f"dist engine needs >= {n} devices, have {ndev} (set "
                f"XLA_FLAGS=--xla_force_host_platform_device_count={n})")
        self._cfg = cfg
        self._rc = rc
        self._dist = fedpair_dist
        self.mesh = compat.make_mesh((n,), ("data",))
        self._cache: Dict[Tuple, Callable] = {}

    @property
    def cached_steps(self) -> int:
        return len(self._cache)

    def step(self, params, batch, plan: RoundPlan, agg_w):
        key = _plan_key(plan, agg_w)
        built = self._cache.get(key)
        with jax.set_mesh(self.mesh):
            if built is None:
                dcfg = self._dist.FedDistConfig(
                    lr=self._rc.lr, overlap_boost=self._rc.overlap_boost,
                    split_ranges=plan.phase_envelope(),
                    donate=self._rc.donate)
                built = self._dist.make_dist_fed_step(
                    self._cfg, self.mesh,
                    self._dist.pairs_to_ppermute(plan.partner_array()),
                    np.asarray(agg_w, np.float32), plan.masks(), dcfg)
                self._cache[key] = built
            new, loss = built(params, batch)
        return new, loss

    def prebuild(self, plan: RoundPlan, agg_w) -> bool:
        """Overlap-planning hook (see _BucketedEngine.prebuild)."""
        key = _plan_key(plan, agg_w)
        if key in self._cache:
            return False
        with jax.set_mesh(self.mesh):
            dcfg = self._dist.FedDistConfig(
                lr=self._rc.lr, overlap_boost=self._rc.overlap_boost,
                split_ranges=plan.phase_envelope(),
                donate=self._rc.donate)
            self._cache[key] = self._dist.make_dist_fed_step(
                self._cfg, self.mesh,
                self._dist.pairs_to_ppermute(plan.partner_array()),
                np.asarray(agg_w, np.float32), plan.masks(), dcfg)
        return True


_ENGINE_CLASSES = {"vmapped": _VmappedEngine, "bucketed": _BucketedEngine,
                   "dist": _DistEngine}


# ---------------------------------------------------------------------------
# the driver
# ---------------------------------------------------------------------------

class RoundDriver:
    """Owns the per-round loop for one (algorithm, engine, fleet) triple.

    ``batch_fn`` yields one client-axis-stacked batch pytree per call
    (leading dim N); the driver calls it exactly ``batches_per_round``
    times per round for every algorithm, so data streams line up across
    algorithms and engines.  ``loss_fn``/``init_fn`` default to the LM
    registry but accept any (params, batch) -> scalar pair (the vision
    example drives a conv net through the same loop).

    ``sharding`` (a ``sharding.fleet.FleetSharding``) shards the CLIENT
    axis of all fleet state — parameter replicas, batches, aggregation
    buffers — over the mesh's fleet axis (DESIGN.md §11): state is
    placed at init/load, the engines' donated steps keep it sharded in
    place across rounds, and aggregation reduces mesh-wide and writes
    the next replicas back onto the placement device-to-device (a lost
    round keeps the placed replicas as they are).  Only the
    stacked-replica algorithms support it (fedpairing on the vmapped /
    bucketed engines, fl); the sl/splitfed relays train single trees,
    and the dist engine owns its own one-client-per-device mesh, which
    the driver then uses as its placement.
    """

    def __init__(self, cfg, rc: RoundConfig, fleet: ClientFleet,
                 chan: Optional[ChannelModel] = None,
                 workload: Optional[WorkloadModel] = None,
                 batch_fn: Optional[Callable[[], Dict]] = None,
                 loss_fn: Optional[Callable] = None,
                 init_fn: Optional[Callable] = None,
                 sharding: Optional[FleetSharding] = None):
        from repro.models import registry
        self.cfg = cfg
        self.rc = rc
        self.fleet0 = fleet
        self.n = fleet.n
        self.sharding = sharding
        if sharding is not None:
            if rc.algorithm not in ("fedpairing", "fl"):
                raise ValueError(
                    f"fleet-axis sharding covers the stacked-replica "
                    f"algorithms (fedpairing, fl); {rc.algorithm!r} "
                    f"trains a single shared tree through a sequential "
                    f"relay — nothing to shard over clients")
            if rc.algorithm == "fedpairing" and rc.engine == "dist":
                raise ValueError(
                    "the dist engine owns its own one-client-per-device "
                    "mesh (shard_map + ppermute); FleetSharding applies "
                    "to the vmapped and bucketed engines")
            sharding.validate(self.n)
        self.chan = chan or ChannelModel()
        self.workload = workload or WorkloadModel(
            num_layers=cfg.num_layers,
            batches_per_epoch=rc.batches_per_round, local_epochs=1)
        # per-client cycles vector (device classes, DESIGN.md §10) —
        # validated against the fleet ONCE at construction so a workload
        # built for another fleet fails here, not rounds later inside the
        # accounting; None for fleet-global workloads
        self._cycles = planning.client_cycles(self.workload, self.n)
        if (loss_fn or init_fn) and rc.algorithm == "fedpairing" \
                and rc.engine != "vmapped":
            # the bucketed/dist steps hard-code the LM flow from cfg; a
            # custom objective would be silently ignored — refuse early.
            raise ValueError(
                f"custom loss_fn/init_fn only run on the vmapped engine "
                f"(the {rc.engine} engine builds its loss from cfg)")
        self.loss_fn = loss_fn or (lambda p, b: registry.loss_fn(p, b, cfg)[0])
        self.init_fn = init_fn or (lambda key: registry.init_params(cfg, key))
        self.batch_fn = _validated_batch_fn(
            batch_fn or make_lm_batch_fn(cfg, self.n, seed=rc.seed), self.n)
        # the seeded initial global model, kept on the host: at published
        # widths a second device-resident copy beside the N replicas is
        # what tips one chip's memory over (``_init_global`` copies it in)
        self._gparams = jax.device_get(self.init_fn(jax.random.key(rc.seed)))
        self._engine = None
        self._baseline_step = None
        # cross-round cut-search cache (DESIGN.md §8): re-plans of a kept
        # cohort re-price the cached candidate-edge cuts instead of
        # re-searching them; invalidated by the same relative-drift signal
        # replan_threshold consumes.  Lifetime = driver lifetime (the
        # drift-invariant key carries the cohort identity, so resampled
        # cohorts key their own entries).
        self._cost_driven = pairing.get_pairing_policy(
            rc.resolved_pair_policy).cost_driven
        self.plan_cache = planning.PlannerCache(
            tolerance=rc.replan_threshold) \
            if (rc.cut_cache and rc.algorithm == "fedpairing"
                and self._cost_driven) else None
        # overlap planning (DESIGN.md §12): the predicted next-round plan
        # pre-built off the critical path, adopted by _build_plan when the
        # prediction's inputs (positions, active set) still hold.  Only
        # meaningful for cost-driven policies (seed-free matchings);
        # predicted_adoptions counts how often the prediction paid off.
        self._predicted: Optional[Tuple[RoundPlan, np.ndarray,
                                        np.ndarray]] = None
        self.predicted_adoptions = 0
        # fault layer (DESIGN.md §9): stateless per-round realization —
        # NEVER consumes the driver rng — and the reliability-pricing
        # vector the planner sees (None when every probability is zero,
        # so fault-free planning stays bit-identical)
        self.fault_cfg = rc.faults or faults.FaultConfig()
        self.fault_model = faults.FaultModel(self.fault_cfg, self.n,
                                             seed=rc.seed)
        self._fail = self.fault_model.fail_prob()
        # aggregation policy (DESIGN.md §13): resolved ONCE here so an
        # unknown spec raises at construction; stateful policies keep
        # their state on RoundState.agg (initialized/checkpointed below)
        self.agg_policy = aggregation.get_aggregation_policy(rc.agg_policy)
        if rc.algorithm == "fedpairing":
            self._engine = _ENGINE_CLASSES[rc.engine](
                cfg, rc, self.n, self._gparams, self.loss_fn)
            if rc.engine == "dist":
                # the engine's one-client-per-device mesh is the fleet
                # placement: replicas, batches and broadcasts live one
                # client per device instead of all on the default device
                self.sharding = FleetSharding(mesh=self._engine.mesh)
        if self.sharding is not None:
            # batches are fleet state too: place every drawn batch with
            # its client dim over the fleet axis (host-to-device, once
            # per draw — the engines then never re-lay it out)
            raw_batch_fn, place = self.batch_fn, self.sharding.place
            self.batch_fn = lambda: place(raw_batch_fn())

    # -- state ------------------------------------------------------------

    def _init_global(self) -> Dict:
        """A device copy of the seeded initial global model."""
        return jax.tree_util.tree_map(jnp.asarray, self._gparams)

    def init_state(self) -> RoundState:
        g = self._init_global()
        if self.rc.algorithm == "sl":
            client, server = g, g
        elif self.rc.algorithm == "splitfed":
            client, server = fedpair.replicate(g, self.n), g
        else:
            client = fedpair.replicate(g, self.n, self.sharding)
            server = None
        return RoundState(round=0, fleet=self.fleet0, client_params=client,
                          server_params=server,
                          rng=np.random.default_rng(self.rc.seed),
                          sim_time_s=0.0, history=[],
                          clock=(latency.initial_event_clock(self.n)
                                 if self.rc.async_rounds else None),
                          agg=self.agg_policy.init_state(
                              g, self.n, sharding=self.sharding))

    def global_params(self, state: RoundState) -> Dict:
        """The post-broadcast global model.  For sl the single shared tree;
        otherwise row 0 of the stacked tree (all rows equal after
        broadcast)."""
        if self.rc.algorithm == "sl":
            return state.client_params
        return jax.tree_util.tree_map(lambda a: a[0], state.client_params)

    def run(self, state: Optional[RoundState] = None,
            rounds: Optional[int] = None) -> RoundState:
        state = state or self.init_state()
        for _ in range(self.rc.rounds if rounds is None else rounds):
            state = self.run_round(state)
        return state

    # -- checkpoint / resume (DESIGN.md §9) -------------------------------

    def save_state(self, state: RoundState, path: str) -> None:
        """Serialize a RoundState to a msgpack checkpoint
        (``repro.checkpoint.io``): params + fleet arrays as leaves, the
        host-side remainder (round counter, rng bit-generator state,
        RoundRecord history, adaptive anchor plan) as metadata.  A driver
        built from the same config restores it with ``load_state`` and
        continues bit-identically (``tests/test_faults.py``)."""
        from repro.checkpoint import io as ckpt_io
        tree = {"client": state.client_params,
                "fleet": {"positions": np.asarray(state.fleet.positions),
                          "cpu_hz": np.asarray(state.fleet.cpu_hz),
                          "data_sizes": np.asarray(state.fleet.data_sizes)}}
        if state.server_params is not None:
            tree["server"] = state.server_params
        agg_tree = self.agg_policy.state_tree(state.agg)
        if agg_tree is not None:
            tree["agg"] = agg_tree
        meta = {
            "version": 1,
            "algorithm": self.rc.algorithm,
            "seed": self.rc.seed,
            "n": self.n,
            "batches_per_round": self.rc.batches_per_round,
            "round": int(state.round),
            "sim_time_s": float(state.sim_time_s),
            # json round-trip: the PCG64 state dict carries 128-bit ints
            # msgpack cannot represent
            "rng": json.dumps(state.rng.bit_generator.state),
            "history": [dataclasses.asdict(r) for r in state.history],
            "plan": (None if state.plan is None
                     else dataclasses.asdict(state.plan)),
            # async event clock (DESIGN.md §12): plain float lists —
            # the msgpack round-trip preserves float64 exactly, so a
            # resumed async trace stays bit-identical
            # aggregation policy (DESIGN.md §13): the variate ARRAYS ride
            # in the leaf tree above; the host-side remainder (which
            # policy, whether its correction has armed) rides here
            "agg_policy": self.agg_policy.spec,
            "agg_applied": bool(getattr(state.agg, "applied", False)),
            "async_rounds": bool(self.rc.async_rounds),
            "staleness_bound": int(self.rc.staleness_bound),
            "clock": (None if state.clock is None
                      else {"avail": [float(a) for a in state.clock.avail],
                            "merges": [float(m)
                                       for m in state.clock.merges]}),
        }
        ckpt_io.save_checkpoint(path, tree, meta)

    def load_state(self, path: str, fast_forward: bool = True
                   ) -> RoundState:
        """Restore a ``save_state`` checkpoint into a fresh driver.

        The driver must be configured compatibly (same algorithm, client
        count, seed and batches_per_round — validated, since the resume
        contract replays the SAME cohort/channel/batch streams).  With
        ``fast_forward`` (default) the driver's batch stream is advanced
        ``round x batches_per_round`` calls so round k consumes the same
        batches the uninterrupted run gave it — every round outcome
        (trained, degraded, skipped, empty) consumes exactly
        ``batches_per_round`` calls, which is what makes this product
        exact."""
        from repro.checkpoint import io as ckpt_io
        meta = ckpt_io.load_checkpoint_meta(path)
        if int(meta.get("version", -1)) != 1:
            raise ValueError(f"unsupported checkpoint version "
                             f"{meta.get('version')!r} in {path}")
        for key, mine in (("algorithm", self.rc.algorithm),
                          ("n", self.n), ("seed", self.rc.seed),
                          ("batches_per_round",
                           self.rc.batches_per_round)):
            if meta.get(key) != mine:
                raise ValueError(
                    f"checkpoint {path} was written with {key}="
                    f"{meta.get(key)!r}; this driver has {key}={mine!r} "
                    f"— resume replays the checkpointed run's streams "
                    f"and needs a matching config")
        # normalized compare (missing on pre-async checkpoints == sync)
        if (bool(meta.get("async_rounds", False)) != self.rc.async_rounds
                or int(meta.get("staleness_bound", 0))
                != self.rc.staleness_bound):
            raise ValueError(
                f"checkpoint {path} was written with async_rounds="
                f"{bool(meta.get('async_rounds', False))!r} / "
                f"staleness_bound={int(meta.get('staleness_bound', 0))}; "
                f"this driver has async_rounds={self.rc.async_rounds!r} / "
                f"staleness_bound={self.rc.staleness_bound} — the event "
                f"clock is part of the resumed trace")
        ckpt_agg = meta.get("agg_policy", "mean")   # pre-§13 ckpts == mean
        if ckpt_agg != self.agg_policy.spec:
            raise ValueError(
                f"checkpoint {path} was written with agg_policy="
                f"{ckpt_agg!r}; this driver has agg_policy="
                f"{self.agg_policy.spec!r} — the control-variate state is "
                f"part of the resumed trace")
        g = self._init_global()
        if self.rc.algorithm == "sl":
            client_like, server_like = g, g
        elif self.rc.algorithm == "splitfed":
            client_like, server_like = fedpair.replicate(g, self.n), g
        else:
            client_like, server_like = fedpair.replicate(g, self.n), None
        like = {"client": client_like,
                "fleet": {"positions": self.fleet0.positions,
                          "cpu_hz": self.fleet0.cpu_hz,
                          "data_sizes": self.fleet0.data_sizes}}
        if server_like is not None:
            like["server"] = server_like
        agg_like = self.agg_policy.state_like(g, self.n)
        if agg_like is not None:
            like["agg"] = agg_like
        tree = ckpt_io.load_checkpoint(path, like)
        # jnp conversion copies (frombuffer leaves are read-only; the
        # donate=True engines need owned device buffers); a sharded
        # driver restores the checkpoint straight onto the fleet
        # placement, so resume keeps the sharded-across-rounds lifecycle
        client = jax.tree_util.tree_map(jnp.asarray, tree["client"])
        if self.sharding is not None:
            client = self.sharding.place(client)
        server = (jax.tree_util.tree_map(jnp.asarray, tree["server"])
                  if "server" in tree else None)
        f = tree["fleet"]
        fleet = ClientFleet(positions=np.array(f["positions"]),
                            cpu_hz=np.array(f["cpu_hz"]),
                            data_sizes=np.array(f["data_sizes"]))
        rng = np.random.default_rng(self.rc.seed)
        rng.bit_generator.state = json.loads(meta["rng"])
        history = [_record_from_dict(d) for d in meta["history"]]
        plan = (None if meta["plan"] is None
                else _plan_from_dict(meta["plan"]))
        clock_meta = meta.get("clock")
        clock = (None if clock_meta is None else latency.EventClockState(
            avail=tuple(float(a) for a in clock_meta["avail"]),
            merges=tuple(float(m) for m in clock_meta["merges"])))
        agg = (self.agg_policy.restore_state(tree["agg"], meta,
                                             sharding=self.sharding)
               if "agg" in tree
               else self.agg_policy.init_state(g, self.n,
                                               sharding=self.sharding))
        if fast_forward:
            for _ in range(int(meta["round"]) * self.rc.batches_per_round):
                self.batch_fn()
        return RoundState(round=int(meta["round"]), fleet=fleet,
                          client_params=client, server_params=server,
                          rng=rng,
                          sim_time_s=float(meta["sim_time_s"]),
                          history=history, plan=plan, clock=clock,
                          agg=agg)

    # -- one round --------------------------------------------------------

    def run_round(self, state: RoundState) -> RoundState:
        """One round; value semantics for the driver-owned state — the
        input state is left intact (its rng is deep-copied, its history
        is never mutated), so a kept snapshot re-runs with the identical
        cohort/pairing/latency trace.  Two stateful caveats: the data
        stream is owned by ``batch_fn`` and advances monotonically across
        calls, and with the default ``donate=True`` the engines consume
        the input parameter buffers in place — re-running the *training*
        of a kept snapshot additionally needs ``RoundConfig(donate=False)``
        (the equivalence tests do)."""
        with telemetry.span("round"):
            return self._run_round(state)

    def _run_round(self, state: RoundState) -> RoundState:
        rc = self.rc
        rng = copy.deepcopy(state.rng)
        fleet = latency.drift_fleet(state.fleet, rng, rc.drift_sigma_m)
        cohort = participation.sample_cohort(self.n, rc.participation, rng)
        # pairing seed: drawn every round for every algorithm (in fixed
        # order, after cohort sampling) so the rng stream stays
        # algorithm- and mechanism-invariant; only 'random' consumes it.
        pair_seed = int(rng.integers(2 ** 31))
        active = np.zeros(self.n, bool)
        active[cohort] = True
        if cohort.size == 0:
            record, client, server, plan, clock, agg = self._empty_round(
                state, fleet, cohort)
        else:
            run = {"fedpairing": self._fedpairing_round,
                   "fl": self._fl_round, "sl": self._sl_round,
                   "splitfed": self._splitfed_round}
            record, client, server, plan, clock, agg = run[rc.algorithm](
                state, fleet, cohort, active, pair_seed)
        return dataclasses.replace(
            state, round=state.round + 1, fleet=fleet, client_params=client,
            server_params=server, rng=rng, sim_time_s=record.sim_total_s,
            history=state.history + [record], plan=plan, clock=clock,
            agg=agg)

    def _next_batch(self) -> Dict:
        with telemetry.span("batch"):
            return self.batch_fn()

    def _train(self, step: Callable, params) -> Tuple[Dict, List]:
        """``batches_per_round`` local steps ``params, loss = step(params,
        batch)``; returns the params and the per-step losses on the host."""
        losses = []
        for _ in range(self.rc.batches_per_round):
            batch = self._next_batch()
            with telemetry.span("step"):
                params, loss = step(params, batch)
            losses.append(_host_loss(loss))
        return params, losses

    def _record(self, state, cohort, pairs, lengths, mean_loss, round_s,
                cached, objective=None, replanned=True,
                cut_cache="n/a", status="ok", failed=(),
                retries=0, wait_s=0.0, overlap_s=0.0) -> RoundRecord:
        return RoundRecord(
            round=state.round, cohort=tuple(int(c) for c in cohort),
            pairs=pairs, lengths=tuple(int(l) for l in lengths),
            mean_loss=float(mean_loss), sim_round_s=float(round_s),
            sim_total_s=float(state.sim_time_s + round_s),
            cached_steps=cached,
            objective=None if objective is None else float(objective),
            replanned=bool(replanned), cut_cache=str(cut_cache),
            status=str(status), failed=tuple(int(c) for c in failed),
            retries=int(retries), wait_s=float(wait_s),
            overlap_s=float(overlap_s))

    def _empty_round(self, state, fleet, cohort):
        """A participation fraction that rounds to zero clients: a defined
        no-op round (``status == "empty"``) — params untouched, zero
        simulated seconds, mean_loss = nan.  The data stream is still
        advanced ``batches_per_round`` calls so round k always consumes
        the same batches regardless of cohort sizes (the checkpoint/resume
        fast-forward contract)."""
        for _ in range(self.rc.batches_per_round):
            self._next_batch()
        cached = self._engine.cached_steps if self._engine is not None else 1
        rec = self._record(state, cohort, (),
                           (self.cfg.num_layers,) * self.n, float("nan"),
                           0.0, cached, replanned=False, status="empty")
        clock = state.clock
        if clock is not None:
            # zero-duration merge: the event clock still publishes so the
            # staleness window slides the same way the sync round counter
            # does (an empty round is a round)
            clock, _ = latency.advance_event_clock(
                clock, (), np.zeros(0), 0.0, self.rc.staleness_bound)
        return (rec, state.client_params, state.server_params, state.plan,
                clock, state.agg)

    def round_plan(self, fleet: ClientFleet, partner: np.ndarray,
                   active: np.ndarray, num_layers: Optional[int] = None
                   ) -> RoundPlan:
        """The round's RoundPlan — the single source of truth the engines,
        the latency accounting and the trace record all consume."""
        rc = self.rc
        return planning.build_round_plan(
            fleet, self.chan, partner,
            self.cfg.num_layers if num_layers is None else num_layers,
            policy=rc.split_policy, workload=self.workload, active=active,
            granularity=rc.bucket_granularity, server_cut=rc.server_cut,
            fail=self._fail)

    def _latency_plan(self, fleet: ClientFleet, partner: np.ndarray,
                      active: np.ndarray, plan: RoundPlan) -> RoundPlan:
        """The plan the Eq. (3) clock is evaluated on.  Normally the
        executed plan itself; when the workload model is calibrated at a
        different depth than the trained architecture (e.g. the tiny smoke
        model accounted against the paper's 18-layer ResNet18 workload,
        bench_roundtime), the same policy/pairing is re-planned at the
        WORKLOAD's depth so simulated times stay comparable to the
        baselines' full-stack accounting."""
        if self.workload.num_layers == plan.num_layers:
            return plan
        return self.round_plan(fleet, partner, active,
                               num_layers=self.workload.num_layers)

    def _build_plan(self, fleet, cohort, active, pair_seed: int) -> RoundPlan:
        """One fresh re-matching under the configured pairing policy.
        Cost-driven policies take the joint path (pairing x cut chosen
        together, ``planning.build_joint_plan``); the weight heuristics
        keep the historical cohort_partner -> build_round_plan path
        bit-identically."""
        rc = self.rc
        pred, self._predicted = self._predicted, None
        if pred is not None:
            pplan, ppos, pact = pred
            if (np.array_equal(ppos, np.asarray(fleet.positions))
                    and np.array_equal(pact, np.asarray(active, bool))):
                # the overlap planner's prediction still holds (no drift
                # moved the channel, same cohort): adopt the pre-built
                # plan — identical to what the fresh matching below would
                # produce (cost-driven matchings are seed-free), its
                # engine step already compiled off the critical path
                self.predicted_adoptions += 1
                return pplan
        policy = pairing.get_pairing_policy(rc.resolved_pair_policy)
        if policy.cost_driven:
            return planning.build_joint_plan(
                fleet, self.chan, self.cfg.num_layers, pair_policy=policy,
                split_policy=rc.split_policy, workload=self.workload,
                active=active, granularity=rc.bucket_granularity,
                server_cut=rc.server_cut, seed=pair_seed,
                cache=self.plan_cache, fail=self._fail)
        ctx = pairing.PairingContext(
            num_layers=self.cfg.num_layers, workload=self.workload,
            split_policy=rc.split_policy, seed=pair_seed)
        partner, _ = participation.cohort_partner(fleet, self.chan, cohort,
                                                  policy, ctx=ctx)
        return self.round_plan(fleet, partner, active)

    def _adaptive_plan(self, state: RoundState, fleet, cohort, active,
                       pair_seed: int) -> Tuple[RoundPlan, RoundPlan, bool]:
        """(executed plan, anchor plan, replanned).  With
        ``replan_threshold > 0`` the previous anchor plan is re-priced on
        the drifted channel (``planning.plan_objective``) and KEPT — same
        pairing, same lengths, same ``cache_key`` so the engines' compiled
        steps are reused — unless the cohort changed or the objective
        moved by more than the (relative) threshold.  The anchor keeps its
        at-adoption objective as the drift reference; the executed plan
        carries the re-priced objective so the simulated clock and the
        trace follow the adapted plan."""
        rc = self.rc
        prev = state.plan
        if (rc.replan_threshold > 0 and prev is not None
                and prev.active == tuple(bool(a) for a in active)):
            new_obj = planning.plan_objective(prev, fleet, self.chan,
                                              self.workload,
                                              fail=self._fail)
            if abs(new_obj - prev.objective) \
                    <= rc.replan_threshold * abs(prev.objective):
                kept = dataclasses.replace(prev, objective=new_obj)
                return kept, prev, False
        plan = self._build_plan(fleet, cohort, active, pair_seed)
        return plan, plan, True

    def _cut_cache_status(self, replanned: bool) -> str:
        if self.plan_cache is None:      # weight policy / cache disabled
            return "n/a"
        if not replanned:
            return "kept"
        return self.plan_cache.last_status

    def _overlap_prebuild(self, fleet: ClientFleet, active) -> None:
        """Overlap next-round planning with current execution (DESIGN.md
        §12): predict the next round's plan under the CURRENT channel
        realization and cohort (the best forecast available without a
        channel model — ROADMAP's learned/forecast re-planning item plugs
        in here), re-pricing the planner cache's cut search
        (``planning.price_cuts`` inside ``build_joint_plan``) and
        pre-building the predicted plan's engine step off the critical
        path.  ``_build_plan`` adopts the prediction next round iff its
        inputs still hold; the simulated clock charges NOTHING here —
        planning happens during the round's simulated execution, which is
        exactly the overlap being modeled (host wall-clock pays it, the
        event clock does not; the records' ``overlap_s`` accounts the
        execution-side overlap explicitly).  Cost-driven policies only:
        weight/random matchings are pair-seed-dependent, so a prediction
        could not be validated as identical to the fresh matching."""
        if self.plan_cache is None:
            return
        rc = self.rc
        plan = planning.build_joint_plan(
            fleet, self.chan, self.cfg.num_layers,
            pair_policy=pairing.get_pairing_policy(rc.resolved_pair_policy),
            split_policy=rc.split_policy, workload=self.workload,
            active=np.asarray(active, bool),
            granularity=rc.bucket_granularity, server_cut=rc.server_cut,
            seed=0, cache=self.plan_cache, fail=self._fail)
        agg_w = fedpair.pair_weights(fleet.data_sizes, plan.partner_array())
        self._engine.prebuild(plan, agg_w)
        self._predicted = (plan, np.array(fleet.positions),
                           np.asarray(active, bool).copy())

    def _advance_clock(self, state, cohort, units, times, upload_s,
                       cap_s=None, resync=()):
        """Advance the async event clock by this round's surviving units:
        the cohort's admission stream (``participation.admission_stream``)
        feeds per-unit start times into ``latency.advance_event_clock``
        (DESIGN.md §12)."""
        floor = latency.event_clock_floor(state.clock,
                                          self.rc.staleness_bound)
        stream = participation.admission_stream(cohort, state.clock.avail,
                                                floor)
        admit = participation.admission_times(self.n, stream)
        return latency.advance_event_clock(
            state.clock, units, np.asarray(times, np.float64),
            float(upload_s), self.rc.staleness_bound, admit_s=admit,
            cap_s=cap_s, resync=resync)

    @staticmethod
    def _staleness_arg(ac: Optional[latency.AsyncRoundClock]):
        """The aggregation ``staleness`` argument: None on the sync path
        AND when every unit is fresh (staleness bound 0, or an async round
        that happened to catch up) — keeps the aggregation jaxpr (and the
        §12 bit-identity) unchanged whenever there is nothing to
        discount."""
        if ac is None or not any(ac.staleness):
            return None
        return np.asarray(ac.staleness, np.int32)

    # -- aggregation-policy plumbing (DESIGN.md §13) ----------------------

    def _agg_snapshot(self, state: RoundState) -> Optional[Dict]:
        """The pre-round global model x, copied BEFORE training (the
        donate=True engines consume the replica buffers in place).  Row 0
        of the stacked tree — all rows equal after the previous broadcast.
        Only the stateful policies need it; the copy is skipped for
        ``mean`` so the historical path pays nothing."""
        if not self.agg_policy.stateful:
            return None
        return jax.tree_util.tree_map(lambda a: jnp.array(a[0]),
                                      state.client_params)

    def _agg_ctx(self, g_prev: Optional[Dict], partner, lengths,
                 eta: float) -> Optional[aggregation.AggContext]:
        """The round's AggContext for the stateful policies (None for
        stateless).  ``eta`` is the EFFECTIVE per-flow per-step rate —
        lr/N on the fedpairing engines (the engine-normalization
        contract in the module docstring), lr on the fl baseline."""
        if g_prev is None:
            return None
        return aggregation.AggContext(
            prev_global=g_prev, partner=np.asarray(partner, np.int64),
            lengths=np.asarray(lengths, np.float64),
            num_layers=self.cfg.num_layers, lr=float(eta),
            steps=self.rc.batches_per_round)

    def _aggregate(self, state: RoundState, params, fleet, active, ac,
                   mode: str, ctx) -> Tuple[Dict, object]:
        """One policy aggregation and broadcast with the driver's standard
        arguments (cohort mask, staleness discount, round index for the
        EmptyCohortError), all host NumPy: the weights are composed and
        the empty cohort refused on the host, with no device read.  The
        trained ``params`` are donated and the next round's replicas come
        back written into them."""
        return self.agg_policy.apply(
            params, np.asarray(fleet.data_sizes, np.float32), mode,
            active=np.asarray(active, bool),
            staleness=self._staleness_arg(ac),
            state=state.agg, ctx=ctx, round_idx=state.round,
            sharding=self.sharding)

    def _fedpairing_round(self, state, fleet, cohort, active, pair_seed):
        """One FedPairing round (DESIGN.md §9).

        Plan, realize the round's faults (``no_faults`` when nothing is
        stochastic: no draw), degrade the plan around dropouts, and
        evaluate the Eq. (3) clock with its deadline — before any
        training, so the clock decides whether the round is lost.  A
        completed round trains the executed plan and aggregates over the
        survivors; a lost one (skipped / aborted) hands back the pre-round
        replicas untouched.  The data stream advances
        ``batches_per_round`` calls on every outcome (the resume
        contract)."""
        rc, fcfg = self.rc, self.fault_cfg
        with telemetry.span("plan"):
            plan, anchor, replanned = self._adaptive_plan(
                state, fleet, cohort, active, pair_seed)
            rf = self.fault_model.realize(state.round, active, plan.pairs)
            exec_plan, exec_active = plan, np.asarray(active, bool)
            if fcfg.mode == "graceful" and rf.dropped:
                partner, exec_active = faults.degrade_partner(
                    plan.partner_array(), exec_active, rf, fcfg.orphan)
                exec_plan = self.round_plan(fleet, partner, exec_active)
            partner = exec_plan.partner_array()
            agg_w = fedpair.pair_weights(fleet.data_sizes, partner)
            g_prev = self._agg_snapshot(state)
        with telemetry.span("clock"):
            clock = faults.faulted_clock(
                self._latency_plan(fleet, partner, exec_active, exec_plan),
                fleet, self.chan, self.workload, rf, fcfg)
            excluded = sorted(set(rf.dropped) | set(clock.late)
                              | set(clock.link_failed))
            event_clock, ac = state.clock, None
            round_s, wait_s, overlap_s = clock.round_s, 0.0, 0.0
            if rc.async_rounds and not clock.completed:
                # a lost round is a barrier event: the faulted cost is
                # global (the server waited out the deadline) and every
                # client resyncs at the publish — nothing to pipeline
                event_clock, _ = latency.advance_event_clock_barrier(
                    event_clock, clock.round_s, rc.staleness_bound)
            elif rc.async_rounds:
                # replay the surviving units on the event clock, capped by
                # the deadline the sync accounting obeys; excluded clients
                # resync to the merge (they rejoin fresh)
                event_clock, ac = self._advance_clock(
                    state, cohort, clock.units, clock.times, clock.upload_s,
                    cap_s=(clock.deadline_s
                           if np.isfinite(clock.deadline_s) else None),
                    resync=excluded)
                round_s, wait_s, overlap_s = (ac.round_s, ac.wait_s,
                                              ac.overlap_s)
            elif clock.completed:
                wait_s = latency.barrier_wait_s(clock.times)
        if not clock.completed:
            for _ in range(rc.batches_per_round):
                self._next_batch()
            params, agg, mean_loss = (state.client_params, state.agg,
                                      float("nan"))
            status = "aborted" if fcfg.mode == "abort" else "skipped"
        else:
            final_active = exec_active.copy()
            final_active[excluded] = False
            params, losses = self._train(
                lambda p, b: self._engine.step(p, b, exec_plan, agg_w),
                state.client_params)
            mean_loss = _mean_active_loss(losses, final_active,
                                          round_idx=state.round)
            # variate attribution follows the DEGRADED plan and the
            # post-fault survivor mask: an excluded client's variate
            # stays put and never moves c_global (the hard-mask contract)
            with telemetry.span("aggregate"):
                params, agg = self._aggregate(
                    state, params, fleet, final_active, ac, rc.aggregation,
                    self._agg_ctx(g_prev, partner, exec_plan.lengths_array(),
                                  eta=rc.lr / self.n))
            status = "degraded" if excluded else "ok"
        with telemetry.span("record"):
            rec = self._record(state, cohort, exec_plan.pairs,
                               exec_plan.lengths, mean_loss, round_s,
                               self._engine.cached_steps,
                               objective=exec_plan.objective,
                               replanned=replanned,
                               cut_cache=self._cut_cache_status(replanned),
                               status=status, failed=excluded,
                               retries=rf.retry_total(fcfg.retries),
                               wait_s=wait_s, overlap_s=overlap_s)
            if rc.overlap_planning:
                self._overlap_prebuild(fleet, active)
        return rec, params, None, anchor, event_clock, agg

    def _fl_round(self, state, fleet, cohort, active, pair_seed):
        rc = self.rc
        if self._baseline_step is None:
            self._baseline_step = baselines.make_fl_step(self.loss_fn,
                                                         lr=rc.lr)
        g_prev = self._agg_snapshot(state)
        params, losses = self._train(self._baseline_step,
                                     state.client_params)
        # fl is the degenerate pairing (everyone solo, full stack): the
        # scaffold ownership rule reduces to classic per-client variates
        with telemetry.span("aggregate"):
            params, agg = self._aggregate(
                state, params, fleet, active, None, "fedavg",
                self._agg_ctx(g_prev, np.arange(self.n),
                              np.full(self.n, self.cfg.num_layers),
                              eta=rc.lr))
        plan = planning.baseline_plan(self.n, self.cfg.num_layers,
                                      active=active,
                                      server_cut=rc.server_cut,
                                      full_stack=True)
        sub = latency.subfleet(fleet, cohort)
        sub_cycles = (self._cycles[cohort] if self._cycles is not None
                      else None)
        round_s = latency.round_time_vanilla_fl(
            sub, self.chan, self.workload, cycles=sub_cycles)
        wait_s = latency.barrier_wait_s(latency.local_full_stack_time(
            sub.cpu_hz, self.workload, cycles=sub_cycles))
        rec = self._record(state, cohort, (), plan.lengths,
                           _mean_active_loss(losses, active,
                                             round_idx=state.round),
                           round_s, 1, wait_s=wait_s)
        return rec, params, None, state.plan, state.clock, agg

    def _sl_round(self, state, fleet, cohort, active, pair_seed):
        rc = self.rc
        plan = planning.baseline_plan(self.n, self.cfg.num_layers,
                                      active=active, server_cut=rc.server_cut)
        cut = plan.server_cut
        if self._baseline_step is None:
            split = splitting.split_plan(self.cfg, self._gparams)
            self._baseline_step = baselines.make_sl_step(
                self.loss_fn, split, self.cfg.num_layers, cut, rc.lr)
        client, server = state.client_params, state.server_params
        batches = [self._next_batch() for _ in range(rc.batches_per_round)]
        losses = []
        for i in cohort:                 # sequential client relay
            for b in batches:
                mine = jax.tree_util.tree_map(lambda a: a[int(i)], b)
                with telemetry.span("step"):
                    client, server, l = self._baseline_step(client, server,
                                                            mine)
                losses.append(float(_host_loss(l)))
        sub = latency.subfleet(fleet, cohort)
        round_s = latency.round_time_vanilla_sl(
            sub, self.chan, self.workload, client_layers=cut,
            sequential=True,
            cycles=self._cycles[cohort] if self._cycles is not None else None)
        mean_loss = float(np.mean(losses))
        if not np.isfinite(mean_loss):
            raise NonFiniteLossError(state.round)
        # sequential relay: each client hands off to the next — there is
        # no barrier, so no idle to record (wait_s stays 0.0)
        rec = self._record(state, cohort, (), plan.lengths,
                           mean_loss, round_s, 1)
        return rec, client, server, state.plan, state.clock, state.agg

    def _splitfed_round(self, state, fleet, cohort, active, pair_seed):
        rc = self.rc
        plan = planning.baseline_plan(self.n, self.cfg.num_layers,
                                      active=active, server_cut=rc.server_cut)
        cut = plan.server_cut
        if self._baseline_step is None:
            split = splitting.split_plan(self.cfg, self._gparams)
            self._baseline_step = baselines.make_splitfed_step(
                self.loss_fn, split, self.cfg.num_layers, cut, rc.lr)
        client, server = state.client_params, state.server_params
        idx = np.asarray(cohort)
        sub_params = jax.tree_util.tree_map(lambda a: a[idx], client)
        losses = []
        for _ in range(rc.batches_per_round):
            b = self._next_batch()
            sub_b = jax.tree_util.tree_map(lambda a: a[idx], b)
            with telemetry.span("step"):
                sub_params, server, l = self._baseline_step(sub_params,
                                                            server, sub_b)
            losses.append(_host_loss(l))
        # round end: FedAvg the cohort's bottoms, broadcast to everyone
        with telemetry.span("aggregate"):
            sub_w = np.asarray(fleet.data_sizes[idx], np.float32)
            g = aggregation.aggregate(sub_params, sub_w, "fedavg",
                                      round_idx=state.round)
        with telemetry.span("broadcast"):
            client = aggregation.broadcast(g, self.n)
        sub = latency.subfleet(fleet, cohort)
        sub_cycles = (self._cycles[cohort] if self._cycles is not None
                      else None)
        round_s = latency.round_time_splitfed(
            sub, self.chan, self.workload, client_layers=cut,
            cycles=sub_cycles)
        # the per-batch fed-server barrier: idle = sum over clients of
        # (slowest client-side batch - own), paid every batch
        wait_s = latency.barrier_wait_s(latency.splitfed_client_times(
            sub, self.chan, self.workload, client_layers=cut,
            cycles=sub_cycles)) \
            * self.workload.batches_per_epoch * self.workload.local_epochs
        per_client = np.stack([np.asarray(l, np.float64) for l in losses])
        bad = ~np.isfinite(per_client).all(axis=0)
        if bad.any():
            raise NonFiniteLossError(state.round, idx[bad])
        rec = self._record(state, cohort, (), plan.lengths,
                           float(per_client.mean()), round_s, 1,
                           wait_s=wait_s)
        return rec, client, server, state.plan, state.clock, state.agg


def _record_from_dict(d: Dict) -> RoundRecord:
    """RoundRecord from its msgpack round-trip (lists back to tuples)."""
    return RoundRecord(
        round=int(d["round"]),
        cohort=tuple(int(c) for c in d["cohort"]),
        pairs=tuple((int(a), int(b)) for a, b in d["pairs"]),
        lengths=tuple(int(l) for l in d["lengths"]),
        mean_loss=float(d["mean_loss"]),
        sim_round_s=float(d["sim_round_s"]),
        sim_total_s=float(d["sim_total_s"]),
        cached_steps=int(d["cached_steps"]),
        objective=(None if d["objective"] is None
                   else float(d["objective"])),
        replanned=bool(d["replanned"]), cut_cache=str(d["cut_cache"]),
        status=str(d["status"]),
        failed=tuple(int(c) for c in d["failed"]),
        retries=int(d["retries"]),
        wait_s=float(d.get("wait_s", 0.0)),
        overlap_s=float(d.get("overlap_s", 0.0)))


def _plan_from_dict(d: Dict) -> RoundPlan:
    """RoundPlan from its msgpack round-trip (lists back to tuples)."""
    return RoundPlan(
        kind=str(d["kind"]), policy=str(d["policy"]),
        num_layers=int(d["num_layers"]),
        partner=tuple(int(p) for p in d["partner"]),
        lengths=tuple(int(l) for l in d["lengths"]),
        active=tuple(bool(a) for a in d["active"]),
        pairs=tuple((int(a), int(b)) for a, b in d["pairs"]),
        server_cut=int(d["server_cut"]),
        granularity=int(d["granularity"]),
        objective=(None if d["objective"] is None
                   else float(d["objective"])),
        pair_policy=str(d["pair_policy"]),
        seq_objective=(None if d.get("seq_objective") is None
                       else float(d["seq_objective"])),
        cycles=(None if d.get("cycles") is None
                else tuple(float(c) for c in d["cycles"])))


class BatchValidationError(ValueError):
    """``batch_fn`` returned a pytree violating the driver's client-axis
    contract (every leaf stacked (N, ...) with a numeric dtype) — raised
    at the driver boundary with the offending leaf named, instead of the
    opaque vmap/scan trace error a shape mismatch produces deep inside
    the engine step."""

    def __init__(self, leaf_idx: int, detail: str):
        self.leaf_idx = int(leaf_idx)
        super().__init__(
            f"batch_fn returned an invalid batch: leaf #{self.leaf_idx} "
            f"{detail} — every leaf must be an array stacked over the "
            f"client axis (leading dim N) with a numeric dtype")


def _validated_batch_fn(fn: Callable[[], Dict], n: int) -> Callable[[], Dict]:
    """Wrap ``batch_fn`` with the client-axis contract check (leading dim
    N, numeric dtypes) so a bad data pipeline fails at the boundary with
    ``BatchValidationError``, not rounds later inside a traced step."""

    def validated() -> Dict:
        batch = fn()
        for k, leaf in enumerate(jax.tree_util.tree_leaves(batch)):
            shape = getattr(leaf, "shape", None)
            dtype = getattr(leaf, "dtype", None)
            if shape is None or dtype is None:
                raise BatchValidationError(
                    k, f"is a {type(leaf).__name__}, not an array")
            if len(shape) < 1 or int(shape[0]) != n:
                raise BatchValidationError(
                    k, f"has shape {tuple(shape)}; expected a leading "
                       f"client dim of {n}")
            np_dtype = np.dtype(dtype)
            if not (np.issubdtype(np_dtype, np.number)
                    or np_dtype == np.bool_):
                raise BatchValidationError(
                    k, f"has non-numeric dtype {np_dtype}")
        return batch

    return validated


class NonFiniteLossError(RuntimeError):
    """A training round produced NaN/inf losses (divergence, not a fault
    the degradation ladder can mask) — raised with the round index and,
    where per-client losses exist, the offending client ids, so the
    failing round is nameable from the stack trace alone."""

    def __init__(self, round_idx: int, clients: Sequence[int] = ()):
        self.round = int(round_idx)
        self.clients = tuple(int(c) for c in clients)
        who = (f" from clients {list(self.clients)}" if self.clients
               else "")
        super().__init__(
            f"non-finite training loss in round {self.round}{who} — the "
            f"model diverged; lower the learning rate or inspect the "
            f"round's batches (fault handling only masks availability "
            f"failures, never numerical ones)")


def _host_loss(loss) -> np.ndarray:
    """One step's loss copied to the host, which waits for the device."""
    with telemetry.span("loss_sync"):
        telemetry.count("host_sync")
        return np.asarray(loss)


def _mean_active_loss(losses: Sequence[np.ndarray], active: np.ndarray,
                      round_idx: Optional[int] = None) -> float:
    """Mean per-step loss over active positions.  The vmapped and bucketed
    engines disagree on which position holds which flow's loss (bucketed
    lands flow i at partner(i)), but the active set is closed under the
    pairing, so their cohort means agree.  The dist engine only exposes
    one scalar per step — the a_i-pre-weighted total over ALL N flows
    (inactive self-flows included) — so its recorded mean_loss is on a
    different scale (~a_i x the cohort mean); compare losses across
    engines on vmapped/bucketed only.

    With ``round_idx`` given, non-finite losses over the active set raise
    ``NonFiniteLossError`` naming the round and the offending clients
    instead of silently poisoning the trace and (after aggregation) the
    global params."""
    arr = np.stack([np.asarray(l, np.float64) for l in losses])
    if arr.ndim == 1:                    # dist: one scalar per step
        if round_idx is not None and not np.isfinite(arr).all():
            raise NonFiniteLossError(round_idx)
        return float(arr.mean())
    if round_idx is not None:
        bad = ~np.isfinite(arr[:, active]).all(axis=0)
        if bad.any():
            raise NonFiniteLossError(round_idx,
                                     np.flatnonzero(active)[bad])
    return float(arr[:, active].mean())


def make_lm_batch_fn(cfg, n: int, batch: int = 2, seq: int = 32,
                     seed: int = 0) -> Callable[[], Dict]:
    """Stacked synthetic-LM batches from N disjoint corpus shards."""
    from repro.data import LMBatcher, SyntheticLM
    corpus = SyntheticLM(vocab_size=cfg.vocab_size, seed=seed).generate()
    shard = len(corpus) // n
    batchers = [LMBatcher(corpus[i * shard:(i + 1) * shard], batch, seq,
                          seed=seed + i) for i in range(n)]

    def next_batches() -> Dict:
        per = [next(b) for b in batchers]
        return {
            "tokens": jnp.asarray(np.stack([p["tokens"] for p in per])),
            "labels": jnp.asarray(np.stack([p["labels"] for p in per])),
        }

    return next_batches
