"""Multi-round federated simulation CLI over ``core.rounds.RoundDriver``.

Runs the paper's round loop — per-round channel drift, cohort sampling,
re-pairing, split training on a real engine, aggregation — and reports the
per-round trace plus the accumulated Eq. (3) simulated wall-clock, so
Table I/II round-time claims and Figs. 2-3 convergence trends can be
reproduced from ONE driver for FedPairing and all three baselines.

  PYTHONPATH=src python -m repro.launch.sim --clients 8 --rounds 3 \
      --engine bucketed --participation 0.75 --drift 5

  # paper baselines through the same loop
  PYTHONPATH=src python -m repro.launch.sim --algorithm fl --rounds 3
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

from repro.core import aggregation, latency, rounds
from repro.core.latency import ChannelModel
from repro.launch import compile_cache, fault_cli, fleet_cli


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    fleet_cli.add_arch_args(ap)
    ap.add_argument("--algorithm", choices=rounds.ALGORITHMS,
                    default="fedpairing")
    ap.add_argument("--engine", choices=rounds.ENGINES, default="vmapped")
    ap.add_argument("--pairing", choices=tuple(rounds.PAIRINGS),
                    default="fedpairing",
                    help="Table-I pairing mechanism (fedpairing only)")
    ap.add_argument("--pair-policy", default="", metavar="POLICY",
                    help="pairing policy (generalizes --pairing): "
                         "paper-weight | random | location | compute | "
                         "greedy-cost | blossom-cost (cost policies solve "
                         "pairing x cut jointly)")
    ap.add_argument("--split-policy", default="paper", metavar="POLICY",
                    help="per-pair split-point policy: "
                         "paper | fixed:K | latency-opt")
    ap.add_argument("--replan-threshold", type=float, default=0.0,
                    metavar="REL",
                    help="adaptive re-planning: keep the previous round's "
                         "pairing (and its compiled steps) while channel "
                         "drift moved its objective less than this relative "
                         "amount (0 = re-pair every round)")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--batches-per-round", type=int, default=4)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--participation", type=float, default=1.0,
                    help="cohort fraction sampled each round")
    ap.add_argument("--drift", type=float, default=0.0, metavar="SIGMA_M",
                    help="per-round client position random walk (meters) — "
                         "the time-varying channel realization")
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--aggregation", choices=["paper", "fedavg"],
                    default="paper")
    ap.add_argument("--agg-policy", choices=list(aggregation
                                                 .AGG_POLICY_SPECS),
                    default="mean",
                    help="aggregation-policy registry (DESIGN.md §13): "
                         "mean (historical weighted mean) | scaffold "
                         "(control-variate variance reduction for non-IID "
                         "cohorts; fedpairing/fl)")
    ap.add_argument("--no-overlap-boost", action="store_true")
    ap.add_argument("--bucket-granularity", type=int, default=1)
    ap.add_argument("--server-cut", type=int, default=0,
                    help="sl/splitfed client-side depth (0 -> W//2)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--async-rounds", action="store_true",
                    help="event-driven async round execution (fedpairing): "
                         "per-unit completion events replace the round-max "
                         "barrier (DESIGN.md §12); at --staleness-bound 0 "
                         "the trace is bit-identical to the synchronous "
                         "driver")
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
    ap.add_argument("--json", default="", metavar="PATH",
                    help="dump the round trace as JSON")
    fleet_cli.add_fleet_args(ap)
    fleet_cli.add_mesh_args(ap)
    fault_cli.add_fault_args(ap)
    fault_cli.add_checkpoint_args(ap)
    return ap


def run_sim(args) -> rounds.RoundState:
    cfg = fleet_cli.arch_config(args)
    rc = rounds.RoundConfig(
        algorithm=args.algorithm, engine=args.engine,
        pair_mechanism=args.pairing, pair_policy=args.pair_policy,
        split_policy=args.split_policy,
        replan_threshold=args.replan_threshold,
        rounds=args.rounds,
        batches_per_round=args.batches_per_round,
        participation=args.participation, drift_sigma_m=args.drift,
        lr=args.lr, aggregation=args.aggregation,
        agg_policy=args.agg_policy,
        overlap_boost=not args.no_overlap_boost,
        bucket_granularity=args.bucket_granularity,
        server_cut=args.server_cut, seed=args.seed,
        faults=fault_cli.fault_config(args),
        async_rounds=args.async_rounds,
        staleness_bound=args.staleness_bound,
        overlap_planning=args.overlap_planning)
    fleet = latency.make_fleet(n=args.clients, seed=args.seed)
    # latency accounting sees the REAL architecture's boundary payloads
    # (per-cut residual-stream bytes) — what the cost-driven pairing
    # policies price (ROADMAP item 3)
    workload = latency.workload_from_arch(
        cfg, seq_len=args.seq, batch_size=args.batch,
        batches_per_epoch=args.batches_per_round, local_epochs=1)
    # --device-classes grafts a per-client cycles_per_layer vector on top
    # (device heterogeneity beyond the clock spread, DESIGN.md §10)
    workload = fleet_cli.apply_device_classes(workload, args, args.clients)
    sharding = fleet_cli.fleet_sharding_from_args(args)
    driver = rounds.RoundDriver(
        cfg, rc, fleet, chan=ChannelModel(), workload=workload,
        batch_fn=rounds.make_lm_batch_fn(cfg, args.clients, args.batch,
                                         args.seq, args.seed),
        sharding=sharding)
    shard_note = "" if sharding is None \
        else f", fleet axis over {sharding.num_shards} device(s)"
    print(f"[sim] {args.algorithm}/{args.engine}: {args.clients} clients, "
          f"W={cfg.num_layers}, participation={args.participation}, "
          f"drift={args.drift}m, pair_policy={rc.resolved_pair_policy}"
          f"{shard_note}")
    state = fault_cli.initial_state(driver, args)
    for _ in range(max(0, args.rounds - state.round)):
        t0 = time.time()
        state = driver.run_round(state)
        r = state.history[-1]
        cache_note = "" if r.cut_cache == "n/a" \
            else f", cut cache {r.cut_cache}"
        fault_note = "" if r.status == "ok" \
            else f", {r.status} (failed {list(r.failed)})"
        print(f"  round {r.round}: cohort={list(r.cohort)} "
              f"pairs={list(r.pairs)} loss={r.mean_loss:.4f} "
              f"sim={r.sim_round_s:.1f}s (total {r.sim_total_s:.1f}s, "
              f"{r.cached_steps} compiled steps, "
              f"{'replanned' if r.replanned else 'kept plan'}"
              f"{cache_note}{fault_note}, {time.time()-t0:.1f}s wall)")
        fault_cli.maybe_checkpoint(driver, state, args)
    fault_cli.maybe_checkpoint(driver, state, args, final=True)
    print(f"[sim] simulated wall-clock for {args.rounds} rounds: "
          f"{state.sim_time_s:.1f}s")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"config": vars(args),
                       "rounds": [dataclasses.asdict(r)
                                  for r in state.history],
                       "sim_total_s": state.sim_time_s}, f, indent=2)
            f.write("\n")
        print(f"[sim] trace written to {args.json}")
    return state


def main() -> None:
    args = build_parser().parse_args()
    compile_cache.enable_compile_cache()
    run_sim(args)


if __name__ == "__main__":
    main()
