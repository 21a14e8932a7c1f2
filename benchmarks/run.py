"""Benchmark harness — one module per paper table/figure + perf suites.

Prints ``name,us_per_call,derived`` CSV rows:
  * table1/*, pairing/* — pairing-mechanism round times (paper Table I)
                + split-policy comparison (``core.planning``); writes
                machine-readable ``BENCH_pairing.json``
  * table2/*  — algorithm round times           (paper Table II)
  * fig2/*,fig3/* — convergence IID / Non-IID   (paper Figs. 2-3)
  * convergence/* — aggregation-policy matrix (mean vs scaffold,
                DESIGN.md §13) through the real round driver; writes
                machine-readable ``BENCH_convergence.json``.
  * kernel/*  — kernel micro-benchmarks (framework)
  * fedstep/* — dense-masked vs length-bucketed fed step (DESIGN.md
                §Perf); also writes machine-readable ``BENCH_fedstep.json``
                at the repo root so the perf trajectory is tracked per PR.
  * faults/*  — graceful degradation vs naive abort across fault rates
                (DESIGN.md §9); writes machine-readable
                ``BENCH_faults.json``.
  * shard/*   — fleet-axis sharding: device-count scaling of the client
                dimension on fabricated host devices (DESIGN.md §11);
                writes machine-readable ``BENCH_shard.json``.
  * async/*   — sync barrier vs event-driven async clock across the
                device-class mixes + the real driver sync-vs-async
                (DESIGN.md §12); writes machine-readable
                ``BENCH_async.json``.

Usage: PYTHONPATH=src python -m benchmarks.run [--only table1,table2,...]
       [--tiny]   (shrunken workloads — CI smoke via scripts/bench_smoke.sh)
"""
from __future__ import annotations

import argparse
import functools
import sys


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma list: pairing,roundtime,convergence,kernels,"
                         "fedstep,faults,shard,async")
    ap.add_argument("--tiny", action="store_true",
                    help="shrink workloads (smoke/CI; applies to "
                         "pairing/fedstep/roundtime/convergence)")
    return ap


def main() -> None:
    args = build_parser().parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    only = set(args.only.split(",")) if args.only else None

    suites = []
    if only is None or "pairing" in only:
        from benchmarks import bench_pairing
        suites.append(functools.partial(bench_pairing.run, tiny=args.tiny))
    if only is None or "roundtime" in only:
        from benchmarks import bench_roundtime
        suites.append(functools.partial(bench_roundtime.run, tiny=args.tiny))
    if only is None or "convergence" in only:
        from benchmarks import bench_convergence
        suites.append(functools.partial(bench_convergence.run,
                                        tiny=args.tiny))
    if only is None or "kernels" in only:
        from benchmarks import bench_kernels
        suites.append(bench_kernels.run)
    if only is None or "fedstep" in only:
        from benchmarks import bench_fedstep
        suites.append(functools.partial(bench_fedstep.run, tiny=args.tiny))
    if only is None or "faults" in only:
        from benchmarks import bench_faults
        suites.append(functools.partial(bench_faults.run, tiny=args.tiny))
    if only is None or "shard" in only:
        from benchmarks import bench_shard
        suites.append(functools.partial(bench_shard.run, tiny=args.tiny))
    if only is None or "async" in only:
        from benchmarks import bench_async
        suites.append(functools.partial(bench_async.run, tiny=args.tiny))

    print("name,us_per_call,derived")
    for run in suites:
        for row in run():
            print(f"{row['name']},{row['us_per_call']:.1f},{row['derived']}")
            sys.stdout.flush()


if __name__ == "__main__":
    main()
