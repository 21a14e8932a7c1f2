"""FedPairing training driver (the paper's Algorithm 2, end to end).

A thin CLI over ``core.rounds.RoundDriver``: simulates a heterogeneous
client fleet and runs the full multi-round loop — per-round channel
realization, cohort sampling, greedy re-pairing, split training, pair-then-
global aggregation, Eq. (3) simulated wall-clock.  Three execution engines:

* ``vmapped`` (default) — functional parameter-mix core (all families);
                          partner/lengths are traced, so ONE compile covers
                          every re-pairing.
* ``bucketed``          — length-bucketed split execution (token-LM
                          families): clients grouped by (L_i, W-L_p) scan
                          only their sliced block ranges, paying the
                          protocol's FLOPs instead of the full stack
                          (DESIGN.md §Perf).  Steps specialize on the
                          pairing; the driver memoizes them, so recompiles
                          are bounded by the number of distinct pairings
                          (``--bucket-granularity`` additionally trades
                          wasted blocks against compiled shapes).
* ``dist``              — shard_map + ppermute over real local devices
                          (token-LM families); set
                          ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
                          before launching to get N>1 CPU devices.

  PYTHONPATH=src python -m repro.launch.fed_train --clients 8 --rounds 3

For the paper's baselines (vanilla FL / SL / SplitFed) through the same
loop, use ``repro.launch.sim``.
"""
from __future__ import annotations

import argparse
import time
from typing import Optional

import jax

from repro.configs import ArchConfig
from repro.core import aggregation, latency, planning, rounds
from repro.core.latency import ChannelModel
from repro.launch import compile_cache, fault_cli, fleet_cli


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    fleet_cli.add_arch_args(ap)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--batches-per-round", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--engine", choices=rounds.ENGINES, default="vmapped")
    ap.add_argument("--pair-policy", default="", metavar="POLICY",
                    help="pairing policy: paper-weight | random | location "
                         "| compute | greedy-cost | blossom-cost (the cost "
                         "policies price every candidate pair at its "
                         "policy-optimal cut — joint pairing x split)")
    ap.add_argument("--split-policy", default="paper", metavar="POLICY",
                    help="per-pair split-point policy: "
                         "paper | fixed:K | latency-opt")
    ap.add_argument("--replan-threshold", type=float, default=0.0,
                    metavar="REL",
                    help="keep the previous pairing (and compiled steps) "
                         "while drift moved its objective less than this "
                         "relative amount (0 = re-pair every round)")
    ap.add_argument("--bucket-granularity", type=int, default=1,
                    help="round split lengths to multiples of this when "
                         "bucketing (1 = exact; larger = fewer compiles)")
    ap.add_argument("--participation", type=float, default=1.0,
                    help="cohort fraction sampled each round")
    ap.add_argument("--drift", type=float, default=0.0, metavar="SIGMA_M",
                    help="per-round client position random walk (meters)")
    ap.add_argument("--no-overlap-boost", action="store_true")
    ap.add_argument("--aggregation", choices=["paper", "fedavg"],
                    default="paper")
    ap.add_argument("--agg-policy", choices=list(aggregation
                                                 .AGG_POLICY_SPECS),
                    default="mean",
                    help="aggregation-policy registry (DESIGN.md §13): "
                         "mean (historical weighted mean) | scaffold "
                         "(control-variate variance reduction for non-IID "
                         "cohorts)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--async-rounds", action="store_true",
                    help="event-driven async round execution: per-unit "
                         "completion events replace the round-max barrier "
                         "(DESIGN.md §12); at --staleness-bound 0 the "
                         "trace is bit-identical to the synchronous driver")
    ap.add_argument("--staleness-bound", type=int, default=0, metavar="S",
                    help="bounded-staleness admission for --async-rounds: "
                         "a unit may train from a model up to S merges old "
                         "(its update is discounted 1/(1+s) at "
                         "aggregation); 0 keeps barrier semantics")
    ap.add_argument("--overlap-planning", action="store_true",
                    help="overlap next-round planning with execution "
                         "(--async-rounds, cost-driven pair policies): "
                         "re-price the planner cache and pre-build the "
                         "predicted plan's engine step off the critical "
                         "path")
    fleet_cli.add_fleet_args(ap)
    fleet_cli.add_mesh_args(ap)
    fault_cli.add_fault_args(ap)
    fault_cli.add_checkpoint_args(ap)
    return ap


def build_driver(args: argparse.Namespace,
                 cfg: Optional[ArchConfig] = None) -> rounds.RoundDriver:
    """The ``RoundDriver`` the parsed flags describe: seeded fleet,
    per-cut workload from the real architecture, round config, fleet
    placement and synthetic LM batches (``chip_smoke.py`` builds its
    drivers here too).  ``cfg`` replaces the architecture the flags
    select."""
    cfg = cfg or fleet_cli.arch_config(args)
    n = args.clients
    # per-cut boundary payloads from the REAL architecture (residual
    # stream bytes per split depth), not the flat ResNet18 constant
    w = latency.workload_from_arch(cfg, seq_len=args.seq,
                                   batch_size=args.batch,
                                   batches_per_epoch=args.batches_per_round,
                                   local_epochs=1)
    # --device-classes grafts a per-client cycles_per_layer vector on top
    # (device heterogeneity beyond the clock spread, DESIGN.md §10)
    w = fleet_cli.apply_device_classes(w, args, n)
    rc = rounds.RoundConfig(
        algorithm="fedpairing", engine=args.engine, rounds=args.rounds,
        pair_policy=args.pair_policy, split_policy=args.split_policy,
        replan_threshold=args.replan_threshold,
        batches_per_round=args.batches_per_round,
        participation=args.participation, drift_sigma_m=args.drift,
        lr=args.lr, aggregation=args.aggregation,
        agg_policy=args.agg_policy,
        overlap_boost=not args.no_overlap_boost,
        bucket_granularity=args.bucket_granularity, seed=args.seed,
        faults=fault_cli.fault_config(args),
        async_rounds=args.async_rounds,
        staleness_bound=args.staleness_bound,
        overlap_planning=args.overlap_planning)
    return rounds.RoundDriver(
        cfg, rc, latency.make_fleet(n=n, seed=args.seed),
        chan=ChannelModel(), workload=w,
        batch_fn=rounds.make_lm_batch_fn(cfg, n, args.batch, args.seq,
                                         args.seed),
        sharding=fleet_cli.fleet_sharding_from_args(args))


def main() -> None:
    args = build_parser().parse_args()
    compile_cache.enable_compile_cache()
    driver = build_driver(args)
    cfg, rc, fleet, chan, w = (driver.cfg, driver.rc, driver.fleet0,
                               driver.chan, driver.workload)
    n = driver.n
    # round-0 plan preview on the initial channel realization: the joint
    # plan (pairing x cut together) vs the sequential pair-then-cut plan
    plan0 = planning.build_joint_plan(
        fleet, chan, cfg.num_layers, pair_policy=rc.resolved_pair_policy,
        split_policy=args.split_policy, workload=w, seed=args.seed)
    print(f"[fed] {n} clients, initial pairs {list(plan0.pairs)} "
          f"(pair policy {plan0.pair_policy})")
    print(f"[fed] split policy {plan0.policy}: lengths {list(plan0.lengths)} "
          f"objective {plan0.objective:.1f} "
          f"(sequential pair-then-cut {plan0.seq_objective:.1f})")
    print(f"[fed] modeled round time: "
          f"{latency.round_time_plan(plan0, fleet, chan, w):.1f}s "
          f"(vanilla FL {latency.round_time_vanilla_fl(fleet, chan, w):.1f}s)")
    if args.fleet_sharding or args.mesh_shape:
        print(f"[fed] fleet axis sharded over {driver.sharding.num_shards} "
              f"device(s)")
    state = fault_cli.initial_state(driver, args)
    for _ in range(max(0, args.rounds - state.round)):
        t0 = time.perf_counter()
        state = driver.run_round(state)
        jax.block_until_ready(state.client_params)
        wall = time.perf_counter() - t0
        r = state.history[-1]
        cache_note = "" if r.cut_cache == "n/a" \
            else f", cut cache {r.cut_cache}"
        fault_note = "" if r.status == "ok" \
            else f", {r.status} (failed {list(r.failed)})"
        print(f"  round {r.round}: pairs {list(r.pairs)} "
              f"lengths {list(r.lengths)} (W={cfg.num_layers}) "
              f"mean client loss {r.mean_loss:.4f} "
              f"sim {r.sim_round_s:.1f}s "
              f"({r.cached_steps} compiled steps, "
              f"{'replanned' if r.replanned else 'kept plan'}"
              f"{cache_note}{fault_note}, {wall:.1f}s wall)")
        fault_cli.maybe_checkpoint(driver, state, args)
    fault_cli.maybe_checkpoint(driver, state, args, final=True)
    print(f"[fed] total simulated wall-clock: {state.sim_time_s:.1f}s")


if __name__ == "__main__":
    main()
