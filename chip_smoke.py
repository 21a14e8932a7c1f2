#!/usr/bin/env python3
"""FedPairing round loop on the TPU at qwen1.5-0.5b's published widths.

The quickest proof that the system still starts on the chip.  Every
driver is built by ``repro.launch.fed_train.build_driver`` from the same
flags a user would pass: all 24 layers of qwen1.5-0.5b (d_model 1024, 16
heads, d_ff 2816, vocabulary 151,936 with tied embeddings), random
weights from seed 0, a seeded fleet whose Eq. (6) cuts are uneven,
participation 1.0 and synthetic LM batches of 2 x 512 tokens per client.

  python chip_smoke.py               # one chip: one pair, 2 rounds x 2 local
                                     # steps on the vmapped engine, then 1
                                     # round on the bucketed engine
  python chip_smoke.py --four-chips  # four chips: two pairs, 1 round on the
                                     # dist engine and on the vmapped engine
                                     # sharded over the 4 devices

What it checks: every round's losses are finite; on one chip the
vmapped and bucketed engines agree on round 1's cohort-mean loss for the
same plan (DESIGN.md §5); on four chips the global parameters after
round 1 agree between dist and sharded vmapped within the DESIGN.md §11
float32 tolerance.  The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
No TPU, a failed check or any exception exits non-zero without that line.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch import compile_cache, fed_train  # noqa: E402

ARCH = "qwen1.5-0.5b"
SEED = 0
BATCH, SEQ = 2, 512          # sequences x tokens per client per local step
LOCAL_STEPS = 2
# vmapped vs bucketed round-1 cohort-mean loss: the engines run the same
# bf16 matmuls in different groupings, so only accumulation order differs
LOSS_RTOL = 1e-3
# DESIGN.md §11: float32 reassociation contract for params across meshes
PARAM_RTOL, PARAM_ATOL = 2e-5, 2e-6


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phase: dist engine vs "
                         "the vmapped engine with --mesh-shape 4")
    return ap


def make_driver(model_flags, engine: str, clients: int, rounds: int,
                extra=(), cfg=None):
    """A driver from ``fed_train``'s own parser and builder."""
    args = fed_train.build_parser().parse_args([
        *model_flags, "--engine", engine, "--clients", str(clients),
        "--rounds", str(rounds), "--batches-per-round", str(LOCAL_STEPS),
        "--batch", str(BATCH), "--seq", str(SEQ), "--participation", "1.0",
        "--seed", str(SEED), *extra])
    return fed_train.build_driver(args, cfg)


def describe(cfg) -> None:
    published = get_config(ARCH)
    cut = ("none" if cfg.num_layers == published.num_layers else
           f"{published.num_layers - cfg.num_layers} whole layers")
    print(f"[chip] arch {cfg.name}: d_model {cfg.d_model}, "
          f"{cfg.num_heads} heads ({cfg.num_kv_heads} kv), d_ff {cfg.d_ff}, "
          f"vocab {cfg.vocab_size}, tied embeddings {cfg.tie_embeddings}, "
          f"compute {cfg.dtype}, params {cfg.param_dtype}; depth run "
          f"{cfg.num_layers} of {published.num_layers} layers "
          f"(depth cut: {cut})")
    print(f"[chip] {LOCAL_STEPS} local steps of {BATCH}x{SEQ} tokens per "
          f"client per round, seed {SEED}")


def run_rounds(driver, rounds: int, tag: str):
    """``rounds`` rounds from a fresh state, each timed to the device."""
    state = driver.init_state()
    for _ in range(rounds):
        t0 = time.perf_counter()
        state = driver.run_round(state)
        jax.block_until_ready(state.client_params)
        wall = time.perf_counter() - t0
        r = state.history[-1]
        if not math.isfinite(r.mean_loss):
            raise RuntimeError(f"{tag} round {r.round}: non-finite mean "
                               f"loss {r.mean_loss}")
        print(f"[chip] {tag} round {r.round}: pairs {list(r.pairs)} "
              f"lengths {list(r.lengths)} mean client loss "
              f"{r.mean_loss:.6f} wall {wall:.3f}s "
              f"cached_steps {r.cached_steps}")
    return state


def device_bytes(key: str):
    return [(d.memory_stats() or {}).get(key) for d in jax.devices()]


def one_chip(model_flags) -> None:
    driver = make_driver(model_flags, "vmapped", 2, 2)
    describe(driver.cfg)
    state = run_rounds(driver, 2, "vmapped")
    v0 = state.history[0]
    if v0.lengths[0] == v0.lengths[1]:
        raise RuntimeError(f"the seeded pair cut evenly {v0.lengths}; the "
                           f"smoke needs an uneven Eq. (6) cut")
    del driver, state
    gc.collect()
    driver = make_driver(model_flags, "bucketed", 2, 1)
    b0 = run_rounds(driver, 1, "bucketed").history[0]
    if (b0.pairs, b0.lengths) != (v0.pairs, v0.lengths):
        raise RuntimeError(f"engines ran different plans: vmapped "
                           f"{v0.pairs}/{v0.lengths}, bucketed "
                           f"{b0.pairs}/{b0.lengths}")
    rel = abs(b0.mean_loss - v0.mean_loss) / abs(v0.mean_loss)
    agree = rel <= LOSS_RTOL
    print(f"[chip] round 1 cohort-mean loss on plan {list(v0.lengths)}: "
          f"vmapped {v0.mean_loss:.6f}, bucketed {b0.mean_loss:.6f}, "
          f"relative difference {rel:.3e} (limit {LOSS_RTOL:g}): "
          f"{'agree' if agree else 'DISAGREE'}")
    if not agree:
        raise RuntimeError("vmapped and bucketed engines disagree")


def four_chips(model_flags) -> None:
    if len(jax.devices()) < 4:
        raise RuntimeError(f"--four-chips needs 4 devices, JAX sees "
                           f"{len(jax.devices())}")
    # the §11 tolerance is a float32 contract: same widths and depth, but
    # float32 compute and full-precision matmuls, so that the comparison
    # sees the engines' semantics rather than where each rounds to bf16
    cfg = get_config(ARCH).with_overrides(dtype="float32")
    runs = {}
    for tag, engine, extra in (("dist", "dist", ()),
                               ("vmapped/mesh-4", "vmapped",
                                ("--mesh-shape", "4"))):
        with jax.default_matmul_precision("highest"):
            driver = make_driver(model_flags, engine, 4, 1, extra, cfg)
            if not runs:
                describe(driver.cfg)
                print("[chip] matmul precision: highest")
            state = run_rounds(driver, 1, tag)
        print(f"[chip] {tag} bytes in use per device: "
              f"{device_bytes('bytes_in_use')}")
        runs[tag] = (state.history[0],
                     jax.device_get(driver.global_params(state)))
        del driver, state
        gc.collect()
    (rd, gd), (rv, gv) = runs.values()
    if (rd.pairs, rd.lengths) != (rv.pairs, rv.lengths):
        raise RuntimeError(f"engines ran different plans: dist "
                           f"{rd.pairs}/{rd.lengths}, vmapped "
                           f"{rv.pairs}/{rv.lengths}")
    worst_abs, worst_excess = 0.0, -np.inf
    for a, b in zip(jax.tree_util.tree_leaves(gd),
                    jax.tree_util.tree_leaves(gv)):
        diff = np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
        worst_abs = max(worst_abs, float(diff.max()))
        limit = PARAM_ATOL + PARAM_RTOL * np.abs(np.asarray(b, np.float64))
        worst_excess = max(worst_excess, float((diff - limit).max()))
    agree = worst_excess <= 0.0
    print(f"[chip] global params after round 1, dist vs vmapped/mesh-4: "
          f"max abs difference {worst_abs:.3e} (rtol {PARAM_RTOL:g}, atol "
          f"{PARAM_ATOL:g}): {'agree' if agree else 'DISAGREE'}")
    if not agree:
        raise RuntimeError("dist and sharded vmapped engines disagree")


def main() -> int:
    args = build_parser().parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees platform "
              f"{dev.platform!r}); this check runs only on the chip",
              file=sys.stderr)
        return 2
    print(f"[chip] compile cache: {compile_cache.enable_compile_cache()}")
    compiled = {"seconds": 0.0, "programs": 0, "cache_hits": 0}

    def on_duration(event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled["seconds"] += duration_secs
            compiled["programs"] += 1

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            compiled["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    model_flags = ["--arch", ARCH, "--published"]
    (four_chips if args.four_chips else one_chip)(model_flags)

    print(f"[chip] compilation: {compiled['programs']} programs, "
          f"{compiled['seconds']:.1f}s compiling or reading the cache, "
          f"{compiled['cache_hits']} persistent-cache hits")
    print(f"[chip] peak_bytes_in_use per device: "
          f"{device_bytes('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
