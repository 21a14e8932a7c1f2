"""Shared CLI surface for the model, device-class fleets (per-client
workloads) and fleet-axis sharding.

Both launchers (``fed_train``, ``sim``) expose the same
``--arch``/``--published`` flags over ``configs.get_smoke_config`` /
``configs.get_config``, the same
``--device-classes``/``--class-mix`` flags over
``core.latency.workload_for_classes`` (DESIGN.md §10) and the same
``--fleet-sharding``/``--mesh-shape`` flags over
``sharding.fleet.make_fleet_sharding`` (DESIGN.md §11) — defined once
here so the two parsers (and the README flag table the docs gate checks)
cannot drift apart.
"""
from __future__ import annotations

import argparse

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.core import latency


def add_arch_args(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--arch", choices=ARCH_IDS, default="tinyllama-1.1b")
    ap.add_argument("--published", action="store_true",
                    help="train --arch at its published widths and depth "
                         "(configs.get_config) instead of the reduced "
                         "smoke config")


def arch_config(args: argparse.Namespace):
    """The ``ArchConfig`` the flags select."""
    return (get_config if args.published else get_smoke_config)(args.arch)


def add_fleet_args(ap: argparse.ArgumentParser) -> None:
    classes = " | ".join(sorted(latency.DEVICE_CLASSES))
    g = ap.add_argument_group(
        "device classes (per-client workload, DESIGN.md §10)")
    g.add_argument("--device-classes", default="", metavar="LIST",
                   help=f"comma-separated device classes ({classes}): "
                        f"either one per client (client-id order), or a "
                        f"class menu assigned by --class-mix fractions; "
                        f"empty = fleet-global workload")
    g.add_argument("--class-mix", default="", metavar="FRACTIONS",
                   help="comma-separated fractions, one per entry of "
                        "--device-classes (normalized; largest-remainder "
                        "counts, seeded shuffle over client ids)")


def apply_device_classes(workload, args: argparse.Namespace, n: int):
    """Graft the flags' per-client cycles vector onto ``workload``.

    Returns the workload unchanged when ``--device-classes`` is empty;
    raises (via ``workload_for_classes``) on unknown class names or a
    per-client list whose length is not the fleet size ``n``.
    """
    if not args.device_classes:
        if args.class_mix:
            raise ValueError("--class-mix needs --device-classes (the "
                             "class menu the fractions apply to)")
        return workload
    classes = [c.strip() for c in args.device_classes.split(",") if c.strip()]
    mix = None
    if args.class_mix:
        mix = [float(x) for x in args.class_mix.split(",") if x.strip()]
    return latency.workload_for_classes(classes, mix, n=n, base=workload,
                                        seed=args.seed)


def add_mesh_args(ap: argparse.ArgumentParser) -> None:
    g = ap.add_argument_group(
        "fleet-axis sharding (client dimension over the mesh, "
        "DESIGN.md §11)")
    g.add_argument("--fleet-sharding", action="store_true",
                   help="shard the client axis of all fleet state "
                        "(params, batches, aggregation) over the local "
                        "devices' 'data' mesh axis — vmapped/bucketed "
                        "engines and fl; the client count must divide "
                        "the device count")
    g.add_argument("--mesh-shape", type=int, default=0, metavar="D",
                   help="size of the fleet 'data' mesh axis (devices the "
                        "client dim is split over); 0 = every visible "
                        "device.  Fabricate host devices with XLA_FLAGS="
                        "--xla_force_host_platform_device_count=D before "
                        "launching.  Implies --fleet-sharding when > 0")


def fleet_sharding_from_args(args: argparse.Namespace):
    """The launchers' ``FleetSharding`` (or None when the flags are off).

    Built lazily so launchers that never ask for sharding keep their
    import-time promise of not touching jax device state.
    """
    if not (args.fleet_sharding or args.mesh_shape):
        return None
    from repro.sharding.fleet import make_fleet_sharding
    return make_fleet_sharding(args.mesh_shape or None)
