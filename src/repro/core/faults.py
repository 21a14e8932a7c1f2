"""Fault model & graceful degradation for the round driver (DESIGN.md §9).

The paper's Eq. (3) round clock is straggler-bounded but assumes every
cohort client *finishes* every round.  Real fleets don't: clients drop
mid-round, links suffer transient outages, and runaway stragglers can hold
a synchronous round hostage.  This module gives the ``RoundDriver`` a
seeded, deterministic failure model and the degradation ladder that keeps
rounds productive under it (cf. *Collaborative Split Federated Learning
with Parallel Training and Aggregation*, arXiv 2504.15724 — partial
aggregation keeps convergence under incomplete cohorts; *Split Federated
Learning Over Heterogeneous Edge Devices*, arXiv 2411.13907 — unreliable
links priced into the split decision).

Fault taxonomy (all realized per round):

* **dropout** — a client never starts the round (device offline, app
  killed).  Scalar rate or a per-client tuple (heterogeneous fleets).
* **straggler slowdown** — a client's effective CPU frequency is divided
  by ``straggler_factor`` for the round (thermal throttling, background
  load).  Slowdowns are priced into the Eq. (3) clock; they only become
  failures when they push a unit past the round deadline.
* **intra-pair link outage** — the boundary-activation link of a pair
  drops; each retry costs exponential backoff seconds on the simulated
  clock.  An outage that burns through ``retries`` retries FAILS the pair.

Determinism contract: fault realization is **stateless** — each round's
draws come from ``np.random.default_rng((seed, fault_seed, round_idx))``,
never from the driver's rng stream.  Two consequences the tests pin down:
(1) with all rates zero the fault layer performs no draws and the driver
trace is bit-identical to a fault-free run, and (2) checkpoint/resume
needs no fault-rng state — round k's faults are a pure function of
(seed, k).

Degradation ladder (graceful mode, applied by the driver in this order):

1. dropped clients leave the cohort (the existing aggregation mask);
2. a pair survivor orphaned by its partner's dropout is re-paired with
   another orphan (``orphan="repair"``, split under the round's split
   policy) or falls back to solo full-stack compute (``orphan="solo"``);
3. units (pairs / solo clients) whose faulted Eq. (3) time exceeds the
   round **deadline** are late: excluded from aggregation, the round
   clock capped at the deadline;
4. a round with no surviving unit is **skipped** cleanly — a defined
   no-op record, global params unchanged (never averaging garbage).

``mode="abort"`` is the naive baseline the benchmarks compare against:
any failure (dropout, dead link, late unit) loses the whole round — no
aggregation, and the server waits at least to the deadline to find out.
With a finite deadline, graceful round time <= abort round time at the
same fault realization BY CONSTRUCTION: graceful is capped at the
deadline, abort pays at least it (``benchmarks/bench_faults.py`` asserts
this at every fault rate).  Without a deadline the bound is not
guaranteed — an orphan's solo full-stack fallback may out-straggle every
planned pair.

This module is host-side numpy only; it imports ``planning`` and
``latency`` (no jax) and is consumed by ``core.rounds``.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.core import latency

ORPHAN_POLICIES = ("repair", "solo")
FAULT_MODES = ("graceful", "abort")


@dataclasses.dataclass(frozen=True)
class FaultConfig:
    """Static fault-injection knobs (all rates per round).

    ``dropout`` accepts a scalar (fleet-wide) or a per-client tuple;
    ``deadline_factor`` sets the round deadline as a multiple of the
    executed plan's fault-free Eq. (3) round time (0 = no deadline).
    ``seed`` offsets the fault stream from the driver seed so fault
    scenarios vary independently of cohorts/drift.
    """

    dropout: object = 0.0               # float | per-client tuple, in [0, 1)
    straggler: float = 0.0              # per-client straggler prob, [0, 1]
    straggler_factor: float = 4.0       # CPU slowdown divisor, >= 1
    outage: float = 0.0                 # per-pair link outage prob, [0, 1)
    retries: int = 3                    # max retry attempts per outage
    backoff_s: float = 5.0              # base retry backoff (simulated s)
    deadline_factor: float = 0.0        # deadline = factor x fault-free
                                        # round time; 0 = no deadline
    orphan: str = "repair"              # repair | solo
    mode: str = "graceful"              # graceful | abort
    seed: int = 0                       # fault-stream seed offset

    def __post_init__(self):
        drop = np.atleast_1d(np.asarray(self.dropout, np.float64))
        if np.any(drop < 0) or np.any(drop >= 1):
            raise ValueError(f"dropout probabilities must lie in [0, 1), "
                             f"got {self.dropout!r}")
        if not 0.0 <= self.straggler <= 1.0:
            raise ValueError(f"straggler must lie in [0, 1], "
                             f"got {self.straggler}")
        if self.straggler_factor < 1.0:
            raise ValueError(f"straggler_factor must be >= 1, "
                             f"got {self.straggler_factor}")
        if not 0.0 <= self.outage < 1.0:
            raise ValueError(f"outage must lie in [0, 1), got {self.outage}")
        if self.retries < 0:
            raise ValueError(f"retries must be >= 0, got {self.retries}")
        if self.backoff_s < 0:
            raise ValueError(f"backoff_s must be >= 0, got {self.backoff_s}")
        if self.deadline_factor < 0:
            raise ValueError(f"deadline_factor must be >= 0, "
                             f"got {self.deadline_factor}")
        if self.orphan not in ORPHAN_POLICIES:
            raise ValueError(f"orphan must be one of {ORPHAN_POLICIES}, "
                             f"got {self.orphan!r}")
        if self.mode not in FAULT_MODES:
            raise ValueError(f"mode must be one of {FAULT_MODES}, "
                             f"got {self.mode!r}")
        # a sequence dropout must be immutable (FaultConfig nests inside
        # the frozen RoundConfig)
        if not np.isscalar(self.dropout) \
                and not isinstance(self.dropout, tuple):
            object.__setattr__(self, "dropout",
                               tuple(float(p) for p in drop))

    @property
    def enabled(self) -> bool:
        """True iff the fault layer can change anything.  When False every
        round realizes no fault, and the driver's one round path gives
        the fault-free result bit for bit — the zero-cost guarantee the
        acceptance tests assert."""
        drop = np.atleast_1d(np.asarray(self.dropout, np.float64))
        return bool(np.any(drop > 0) or self.straggler > 0
                    or self.outage > 0 or self.deadline_factor > 0)

    @property
    def randomized(self) -> bool:
        """True iff any fault is stochastic (a deadline alone is not)."""
        drop = np.atleast_1d(np.asarray(self.dropout, np.float64))
        return bool(np.any(drop > 0) or self.straggler > 0
                    or self.outage > 0)

    def dropout_probs(self, n: int) -> np.ndarray:
        """(N,) per-client dropout probabilities."""
        drop = np.asarray(self.dropout, np.float64)
        if drop.ndim == 0:
            return np.full(n, float(drop))
        if drop.shape != (n,):
            raise ValueError(f"per-client dropout needs {n} entries, "
                             f"got shape {drop.shape}")
        return drop


@dataclasses.dataclass(frozen=True)
class RoundFaults:
    """One round's realized faults (host-side, hashable tuples)."""

    dropped: Tuple[int, ...]                    # clients offline this round
    slowdown: Tuple[float, ...]                 # (N,) CPU divisors, >= 1
    outages: Tuple[Tuple[int, int, int], ...]   # (i, j, retries) recovered
    failed_links: Tuple[Tuple[int, int], ...]   # outage exhausted retries

    @property
    def any_fault(self) -> bool:
        return bool(self.dropped or self.failed_links or self.outages
                    or any(s > 1.0 for s in self.slowdown))

    def retry_total(self, max_retries: int) -> int:
        """Total retry attempts charged this round (recovered outages pay
        their realized attempts, dead links the full budget)."""
        return sum(a for _, _, a in self.outages) \
            + len(self.failed_links) * (max_retries + 1)

    def link_penalty(self, n: int, cfg: FaultConfig) -> np.ndarray:
        """(N,) per-client extra seconds from outage retry/backoff
        (exponential: attempt k costs ``backoff_s * 2**k``), charged to
        both members of the affected pair — a unit's penalty is the max
        over its members, so the shared link is not double-counted.
        Failed links pay the full exhausted budget: the time spent
        discovering the failure."""
        pen = np.zeros(n, np.float64)
        for i, j, attempts in self.outages:
            cost = sum(cfg.backoff_s * 2.0 ** k for k in range(attempts))
            pen[i] += cost
            pen[j] += cost
        full = sum(cfg.backoff_s * 2.0 ** k for k in range(cfg.retries + 1))
        for i, j in self.failed_links:
            pen[i] += full
            pen[j] += full
        return pen


_NO_FAULTS_CACHE = {}


def no_faults(n: int) -> RoundFaults:
    """The trivial realization (interned per fleet size)."""
    rf = _NO_FAULTS_CACHE.get(n)
    if rf is None:
        rf = RoundFaults(dropped=(), slowdown=(1.0,) * n, outages=(),
                         failed_links=())
        _NO_FAULTS_CACHE[n] = rf
    return rf


class FaultModel:
    """Seeded, deterministic per-round fault realization.

    ``realize(round_idx, active, pairs)`` draws dropouts, slowdowns and
    link outages for ONE round from a stateless rng keyed on
    ``(driver seed, fault seed, round_idx)`` — independent of the driver
    rng stream (the zero-cost and resume contracts, module docstring).
    """

    def __init__(self, cfg: FaultConfig, n: int, seed: int = 0):
        self.cfg = cfg
        self.n = int(n)
        self.seed = (int(seed), int(cfg.seed))
        self._drop = cfg.dropout_probs(self.n)

    def fail_prob(self) -> Optional[np.ndarray]:
        """(N,) per-client probability of NOT finishing a round — what the
        planner's expected-latency term prices (the ``fail_i``/``fail_j``
        arguments of ``planning.pair_cost_batch``): dropout, plus the
        chance an outage burns through every retry, attributed half per
        member (the link is shared).  None when pricing would be a no-op
        (every probability zero)."""
        exhaust = self.cfg.outage ** (self.cfg.retries + 1)
        p = 1.0 - (1.0 - self._drop) * (1.0 - 0.5 * exhaust)
        if not np.any(p > 0):
            return None
        return p

    def realize(self, round_idx: int, active: np.ndarray,
                pairs: Sequence[Tuple[int, int]]) -> RoundFaults:
        cfg = self.cfg
        n = self.n
        if not cfg.randomized:
            return no_faults(n)      # deadline-only: nothing to draw
        rng = np.random.default_rng((*self.seed, int(round_idx)))
        act = np.asarray(active, bool)
        # draws in fixed order (dropout, straggler, outage), full-fleet
        # shaped so each client's realization is cohort-independent
        dropped_mask = (rng.uniform(size=n) < self._drop) & act
        slow_mask = (rng.uniform(size=n) < cfg.straggler) & act
        slowdown = np.where(slow_mask, cfg.straggler_factor, 1.0)
        outages: List[Tuple[int, int, int]] = []
        failed: List[Tuple[int, int]] = []
        if cfg.outage > 0:
            for i, j in pairs:
                if rng.uniform() >= cfg.outage:
                    continue
                if dropped_mask[i] or dropped_mask[j]:
                    continue         # the pair is already gone
                attempts = 1
                while attempts <= cfg.retries \
                        and rng.uniform() < cfg.outage:
                    attempts += 1
                if attempts > cfg.retries:
                    failed.append((int(i), int(j)))
                else:
                    outages.append((int(i), int(j), attempts))
        return RoundFaults(
            dropped=tuple(int(i) for i in np.flatnonzero(dropped_mask)),
            slowdown=tuple(float(s) for s in slowdown),
            outages=tuple(outages),
            failed_links=tuple(failed))


# ---------------------------------------------------------------------------
# degradation ladder
# ---------------------------------------------------------------------------

def degrade_partner(partner: np.ndarray, active: np.ndarray,
                    rf: RoundFaults, orphan: str = "repair"
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Apply round-start dropouts to a planned pairing.

    Returns the ``(partner, active)`` of the degraded round: dropped
    clients leave the cohort (self-paired, inactive); their orphaned
    survivors are re-paired among themselves in ascending-index order
    (``"repair"``, deterministic) or left self-paired for solo full-stack
    compute (``"solo"``).  Split lengths of the degraded schedule are the
    planning layer's concern — callers rebuild the plan from the returned
    involution.
    """
    if orphan not in ORPHAN_POLICIES:
        raise ValueError(f"orphan must be one of {ORPHAN_POLICIES}, "
                         f"got {orphan!r}")
    partner = np.array(partner, np.int64)
    active = np.array(active, bool)
    if not rf.dropped:
        return partner, active
    dropped = set(int(d) for d in rf.dropped)
    orphans = []
    for d in dropped:
        p = int(partner[d])
        partner[d] = d
        active[d] = False
        if p != d and p not in dropped:
            partner[p] = p           # survivor: full stack for now
            orphans.append(p)
    if orphan == "repair":
        orphans = sorted(set(orphans))
        for a, b in zip(orphans[0::2], orphans[1::2]):
            partner[a], partner[b] = b, a
    return partner, active


@dataclasses.dataclass(frozen=True)
class FaultedClock:
    """The Eq. (3) round clock under one fault realization."""

    round_s: float                       # what the round actually cost
    late: Tuple[int, ...]                # clients excluded for lateness
    link_failed: Tuple[int, ...]         # clients excluded for dead links
    deadline_s: float                    # inf when no deadline configured
    completed: bool                      # any unit survived to aggregate
    # surviving-unit decomposition of a COMPLETED round (empty otherwise):
    # the on-time units, their realized times and the survivors' upload
    # term — what the event-driven clock (latency.advance_event_clock)
    # replays so the async accounting sees the same realization the
    # synchronous round_s above was computed from (DESIGN.md §12)
    units: Tuple[Tuple[int, ...], ...] = ()
    times: Tuple[float, ...] = ()
    upload_s: float = 0.0


def faulted_clock(plan, fleet, chan, workload, rf: RoundFaults,
                  cfg: FaultConfig, server_rate_bps=None) -> FaultedClock:
    """Evaluate the Eq. (3) clock of an (already degraded) plan under the
    realized slowdowns, retry penalties and the deadline.

    * unit times: ``latency.unit_times_from_partner`` with per-client CPU
      divided by the slowdown and the outage backoff added per unit — a
      per-client workload (``cycles_per_client``, DESIGN.md §10) composes
      there with the slowdown exactly once each (the slowdown scales
      cpu_hz, the cycles vector is gathered unscaled by client id), and
      the reliability pricing the PLANNER applied (``fail`` expected-
      attempts multiplier) never leaks into this realized clock;
    * deadline = ``deadline_factor`` x the plan's FAULT-FREE round time
      (the clock the scheduler promised), inf when the factor is 0;
    * graceful: dead-link pairs and units past the deadline are excluded;
      the round costs the slowest on-time unit + the survivors' model
      upload, capped at the deadline.  No survivor at all -> the round is
      not ``completed`` and costs the deadline (the server waited);
    * abort: any failure (dropout / dead link / late unit) loses the
      round; the server pays max(full faulted straggler bound + upload,
      deadline) to find out.
    """
    n = fleet.n
    partner = plan.partner_array()
    active = plan.active_array()
    lengths = plan.lengths_array()
    slowdown = np.asarray(rf.slowdown, np.float64)
    if slowdown.shape != (n,):
        raise latency.PerClientShapeError(
            f"slowdown needs {n} entries, got {slowdown.shape}")
    extra = rf.link_penalty(n, cfg)
    units, times = latency.unit_times_from_partner(
        partner, fleet, chan, workload, active=active, lengths=lengths,
        cpu_scale=slowdown, extra_s=extra)
    deadline = float("inf")
    if cfg.deadline_factor > 0:
        deadline = cfg.deadline_factor * latency.round_time_plan(
            plan, fleet, chan, workload, server_rate_bps=server_rate_bps)
    dead = set()
    for i, j in rf.failed_links:
        dead.update((int(i), int(j)))
    late = set()
    on_time = []
    on_time_units = []
    for unit, t in zip(units, times):
        if any(c in dead for c in unit):
            continue                 # failure detected at retry exhaustion
        if t > deadline:
            late.update(int(c) for c in unit)
        else:
            on_time.append(float(t))
            on_time_units.append(tuple(int(c) for c in unit))
    excluded = late | dead
    survivors = [int(c) for c in np.flatnonzero(active)
                 if int(c) not in excluded]
    completed = bool(on_time) and bool(survivors)
    srates = latency._server_rates(fleet, chan, server_rate_bps)
    failure = bool(rf.dropped) or bool(dead) or bool(late)
    if cfg.mode == "abort" and failure:
        worst = float(np.max(times)) if len(times) else 0.0
        if active.any():
            worst += float(np.max(workload.model_bytes / srates[active]))
        if np.isfinite(deadline):
            worst = max(worst, deadline)
        return FaultedClock(round_s=worst, late=tuple(sorted(late)),
                            link_failed=tuple(sorted(dead)),
                            deadline_s=deadline, completed=False)
    if not completed:
        worst = deadline if np.isfinite(deadline) \
            else (float(np.max(times)) if len(times) else 0.0)
        return FaultedClock(round_s=worst, late=tuple(sorted(late)),
                            link_failed=tuple(sorted(dead)),
                            deadline_s=deadline, completed=False)
    upload = float(np.max(workload.model_bytes
                          / srates[np.asarray(survivors, np.int64)]))
    total = float(max(on_time)) + upload
    if np.isfinite(deadline):
        total = min(total, deadline)
    return FaultedClock(round_s=total, late=tuple(sorted(late)),
                        link_failed=tuple(sorted(dead)),
                        deadline_s=deadline, completed=True,
                        units=tuple(on_time_units),
                        times=tuple(on_time), upload_s=upload)
