"""Shared CLI surface for fault injection and checkpoint/resume.

Both launchers (``fed_train``, ``sim``) expose the same ``--fault-*`` and
``--checkpoint*/--resume`` flags over ``core.faults`` / the driver's
``save_state``/``load_state`` — defined once here so the two parsers (and
the README flag table the docs gate checks) cannot drift apart.
"""
from __future__ import annotations

import argparse
from typing import Optional

from repro.core import faults


def add_fault_args(ap: argparse.ArgumentParser) -> None:
    g = ap.add_argument_group("fault injection (core.faults, DESIGN.md §9)")
    g.add_argument("--fault-dropout", type=float, default=0.0,
                   metavar="P",
                   help="per-round per-client dropout probability")
    g.add_argument("--fault-straggler", type=float, default=0.0,
                   metavar="P",
                   help="per-round probability a client's compute slows "
                        "by --fault-straggler-factor")
    g.add_argument("--fault-straggler-factor", type=float, default=4.0,
                   metavar="X",
                   help="slowdown multiplier for straggling clients")
    g.add_argument("--fault-outage", type=float, default=0.0,
                   metavar="P",
                   help="per-attempt intra-pair link outage probability "
                        "(retried up to --fault-retries times)")
    g.add_argument("--fault-retries", type=int, default=3,
                   help="link retry budget before a pair is declared "
                        "failed")
    g.add_argument("--fault-backoff", type=float, default=5.0,
                   metavar="SEC",
                   help="base retry backoff in seconds (exponential: "
                        "attempt k costs backoff * 2^k)")
    g.add_argument("--fault-deadline", type=float, default=0.0,
                   metavar="FACTOR",
                   help="round deadline as a multiple of the fault-free "
                        "Eq. (3) round time (0 = no deadline; late units "
                        "are excluded from aggregation)")
    g.add_argument("--fault-orphans", choices=faults.ORPHAN_POLICIES,
                   default="repair",
                   help="what pair survivors of a dropout do: re-pair "
                        "among themselves or train solo")
    g.add_argument("--fault-mode", choices=faults.FAULT_MODES,
                   default="graceful",
                   help="graceful degradation (survivors aggregate) vs "
                        "naive abort (any failure voids the round)")
    g.add_argument("--fault-seed", type=int, default=0,
                   help="fault stream seed (independent of --seed; the "
                        "driver rng never sees fault draws)")


def add_checkpoint_args(ap: argparse.ArgumentParser) -> None:
    g = ap.add_argument_group("checkpoint / resume (DESIGN.md §9)")
    g.add_argument("--checkpoint", default="", metavar="PATH",
                   help="write a resumable driver checkpoint here (always "
                        "at exit; also mid-run via --checkpoint-every)")
    g.add_argument("--checkpoint-every", type=int, default=0, metavar="N",
                   help="additionally checkpoint every N rounds (0 = only "
                        "at exit)")
    g.add_argument("--resume", default="", metavar="PATH",
                   help="resume from a checkpoint written by --checkpoint "
                        "(same config required; the resumed trace is "
                        "bit-identical to the uninterrupted run)")


def fault_config(args: argparse.Namespace
                 ) -> Optional[faults.FaultConfig]:
    """A FaultConfig from parsed flags — or None (``FaultConfig()``) when
    every fault flag is at its zero default."""
    if (args.fault_dropout == 0.0 and args.fault_straggler == 0.0
            and args.fault_outage == 0.0 and args.fault_deadline == 0.0):
        return None
    return faults.FaultConfig(
        dropout=args.fault_dropout, straggler=args.fault_straggler,
        straggler_factor=args.fault_straggler_factor,
        outage=args.fault_outage, retries=args.fault_retries,
        backoff_s=args.fault_backoff,
        deadline_factor=args.fault_deadline, orphan=args.fault_orphans,
        mode=args.fault_mode, seed=args.fault_seed)


def initial_state(driver, args):
    """Resume from ``--resume`` if given, else a fresh ``init_state``."""
    if args.resume:
        state = driver.load_state(args.resume)
        print(f"[ckpt] resumed round {state.round} from {args.resume}")
        return state
    return driver.init_state()


def maybe_checkpoint(driver, state, args, final: bool = False) -> None:
    """Write ``--checkpoint`` when due (every N rounds, and at exit)."""
    if not args.checkpoint:
        return
    every = args.checkpoint_every
    if final or (every > 0 and state.round % every == 0):
        driver.save_state(state, args.checkpoint)
        print(f"[ckpt] round {state.round} -> {args.checkpoint}")
