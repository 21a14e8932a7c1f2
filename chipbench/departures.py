#!/usr/bin/env python3
"""Readings of a cell's correctness check beside ``calibrate.py``'s: the
reference computed in a lower precision, planted departures from the
architecture, and the harness's seeded weights changed.

  python3 chipbench/departures.py --workload stablelm-2-1.6b-6l.pair-k2 \\
      --variant ref_bf16:2 --variant rms_norm:2 --out departures.json

In one process, on the chip, at the cell's own sizes, each seed builds the
cell, runs its followed rounds and one window round, and compares with the
plain reference through ``harness.run_cell`` as a timed run does:

* ``program``: the program as the configuration states it;
* ``ref_bf16``: the sound program against the reference replayed with its
  parameters in bfloat16 (the nearest precision below the configuration's
  float32), where the program's own bfloat16-parameter path does not fit;
* ``rms_norm``: the program with RMSNorm in place of a LayerNorm (the
  scale kept, the mean and the bias left out);
* ``full_rotary``: the program with rope over the whole head;
* ``ln_bias0``: program and reference both, with every LayerNorm bias
  seeded at 0 where the harness seeds it at 1.

Each reading is printed as one JSON line and all are written to ``--out``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import fedref, harness  # noqa: E402

VARIANTS = ("program", "ref_bf16", "rms_norm", "full_rotary", "ln_bias0")
SEED_BASE = {v: 2**31 + 5000 + 100 * i for i, v in enumerate(VARIANTS)}
# the same seeds as ``program``: the two are read in pairs
SEED_BASE["ln_bias0"] = SEED_BASE["program"]


def rms_for_layer_norm(x, w, cfg):
    """RMSNorm with the norm's scale: a LayerNorm's mean and bias left
    out."""
    from repro.models import common, transformer
    scale, _ = transformer.norm_parts(w, cfg)
    return common.rms_norm(x, scale, cfg.norm_eps)


def zero_layer_norm_bias(init, arch):
    """The harness's host weights with each LayerNorm's bias set to 0."""
    import numpy as np
    from repro.models import transformer

    def zero(path, a):
        if not str(getattr(path[-1], "key", "")).startswith("ln"):
            return a
        scale, bias = transformer.norm_parts(a, arch)
        if bias is None:
            return a
        return np.asarray(transformer.norm_leaf(scale, np.zeros_like(bias)))

    return jax.tree_util.tree_map_with_path(zero, init)


@contextlib.contextmanager
def patched(module, name, value):
    real = getattr(module, name)
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, real)


def variant_context(variant: str):
    from repro.models import transformer
    if variant == "ref_bf16":
        follow = fedref.follow_rounds

        def bf16_follow(init, *a, **kw):
            return follow(jax.tree_util.tree_map(
                lambda x: jnp.asarray(x, jnp.bfloat16), init), *a, **kw)

        return patched(fedref, "follow_rounds", bf16_follow)
    if variant == "rms_norm":
        return patched(transformer, "norm", rms_for_layer_norm)
    if variant == "ln_bias0":
        make = harness.make_weights
        return patched(harness, "make_weights",
                       lambda arch, seed: zero_layer_norm_bias(
                           make(arch, seed), arch))
    return contextlib.nullcontext()


def variant_kwargs(variant: str):
    if variant == "full_rotary":
        return {"arch_overrides": {"partial_rotary_factor": 1.0}}
    return {}


def reading(cell, variant: str, seed: int) -> dict:
    t0 = time.perf_counter()
    with variant_context(variant):
        line = harness.run_cell(cell, seed, 0.0, False, t0,
                                **variant_kwargs(variant))
    return {"variant": variant, "seed": seed, "correct": line["correct"],
            "checks": {k: c["value"] for k, c in line["checks"].items()},
            "seconds": time.perf_counter() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--variant", action="append", default=[],
                    metavar="NAME:SEEDS",
                    help=f"one of {', '.join(VARIANTS)}, with its seed count")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = harness.resolve(args.workload)
    plan = []
    for spec in args.variant:
        name, _, count = spec.partition(":")
        if name not in VARIANTS:
            ap.error(f"unknown variant {name!r}")
        plan.append((name, int(count or 1)))
    readings = []
    for name, count in plan:
        for i in range(count):
            r = reading(cell, name, SEED_BASE[name] + i)
            readings.append(r)
            print(json.dumps(r), flush=True)
            # the drivers' compiled programs hold device memory until
            # collected; the next reading must find the chip empty
            gc.collect()
            jax.clear_caches()
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps({"workload": cell.name, "readings": readings},
                              indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
