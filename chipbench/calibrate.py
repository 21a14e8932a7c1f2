#!/usr/bin/env python3
"""Readings that the limits of a cell's correctness check are set from.

  python3 chipbench/calibrate.py --workload qwen1.5-0.5b.pair-k2 \\
      --program 12 --control 3 --half-batch 3 --out chiprun_out/calib.json

In one process, on the chip, at the cell's own sizes, each seed builds the
cell, runs its followed rounds and one window round, and compares with the
plain reference:

* ``program``: the program as the configuration states it; the largest
  reading over these seeds is a number's lower reading;
* ``control``: the program's own lower-precision path, parameters held in
  bfloat16 where the configuration states float32; its smallest reading is
  the upper reading;
* ``half_batch``: a planted fault: the engine's loss is taken over the
  first half of each client's batch.

A step that returns its state unchanged reads 1 on the norm numbers by
their definition and needs no run.  Each reading is printed as one JSON
line and all of them are written to ``--out``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import jax  # noqa: E402

from chipbench import harness  # noqa: E402

VARIANTS = ("program", "control", "half_batch")
SEED_BASE = {"program": 2**31 + 1000, "control": 2**31 + 2000,
             "half_batch": 2**31 + 3000}


def half_batch_loss(arch):
    """The loss over the first half of a client's sequences, or of its
    positions where it has one sequence."""
    from repro.models import registry

    def loss(params, batch):
        b, s = batch["tokens"].shape
        half = {k: (v[:b // 2] if b > 1 else v[:, :s // 2])
                for k, v in batch.items()}
        return registry.loss_fn(params, half, arch)[0]

    return loss


def variant_kwargs(cell, variant: str):
    if variant == "control":
        return {"arch_overrides": {"param_dtype": "bfloat16"}}
    if variant == "half_batch":
        arch = harness.program_config(cell.config)
        return {"plant": {"loss_fn": half_batch_loss(arch)}}
    return {}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    for v in VARIANTS:
        ap.add_argument(f"--{v.replace('_', '-')}", type=int, default=0,
                        metavar="SEEDS")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = harness.resolve(args.workload)
    readings = []
    for v in VARIANTS:
        for i in range(getattr(args, v)):
            seed = SEED_BASE[v] + i
            t0 = time.perf_counter()
            line = harness.run_cell(cell, seed, 0.0, False, t0,
                                    **variant_kwargs(cell, v))
            r = {"variant": v, "seed": seed, "correct": line["correct"],
                 "checks": {k: c["value"] for k, c in line["checks"].items()},
                 "seconds": time.perf_counter() - t0}
            readings.append(r)
            print(json.dumps(r), flush=True)
            # the drivers' compiled programs hold device memory until
            # collected; the next variant must find the chip empty
            gc.collect()
            jax.clear_caches()
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps({"workload": cell.name, "readings": readings},
                              indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
