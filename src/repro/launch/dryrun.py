"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combo.

Proves the distribution config is coherent without hardware:
``jax.jit(step).lower(**specs).compile()`` must succeed on the single-pod
(16,16) mesh and the 2-pod (2,16,16) mesh for all 10 architectures x 4
input shapes, then reports memory_analysis / cost_analysis / collective
bytes for the roofline (EXPERIMENTS.md §Dry-run / §Roofline).

NOTE: ``main`` fabricates 512 host placeholder devices for this process
(``fabricate_host_devices``) before anything initializes jax's backend;
importing this module changes no device state.

Usage:
  python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out experiments/dryrun]
"""

import argparse
import json
import os
import time
import traceback

import jax

from repro import compat
from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config, get_shape
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_step
from repro.roofline import analysis


def fabricate_host_devices(count: int = 512) -> None:
    """Ask XLA for ``count`` placeholder CPU devices.  Only effective
    before the first computation initializes the CPU backend."""
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={count}").strip()


def supported(arch_id: str, shape_id: str) -> bool:
    """long_500k runs only for sub-quadratic decode (SSM/hybrid natively;
    attention archs via the sliding-window variant — all support it here)."""
    return True


def run_combo(arch_id: str, shape_id: str, multi_pod: bool,
              out_dir: str | None, fed: str = "") -> dict:
    cfg = get_config(arch_id)
    shape = get_shape(shape_id)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    chips = mesh.devices.size
    t0 = time.time()

    if fed:
        return _run_fed_combo(arch_id, cfg, shape, mesh, mesh_name, chips,
                              out_dir, static=(fed == "half"), t0=t0)

    # Pass 1 — scan-over-layers program: this is the deployable artifact;
    # its memory_analysis has realistic buffer reuse ("proves it fits").
    with jax.set_mesh(mesh):
        fn, example, in_shardings, out_shardings = build_step(
            cfg, shape, mesh, unroll=1)
        jitted = jax.jit(fn, in_shardings=in_shardings,
                         out_shardings=out_shardings)
        lowered = jitted.lower(*example)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    peak = getattr(mem, "temp_size_in_bytes", None)
    args_size = getattr(mem, "argument_size_in_bytes", 0) or 0
    out_size = getattr(mem, "output_size_in_bytes", 0) or 0

    # Pass 2 (single-pod only — the roofline table is single-pod): XLA's
    # cost analysis counts a while-body once, so scan-over-layers programs
    # undercount by ~L.  A full unroll is exact but compiles for ~10 min on
    # the deep configs, so we compile two SHALLOW fully-unrolled variants of
    # the same config and extrapolate linearly in depth — exact for
    # homogeneous stacks (identical layers; embed/head live in the
    # intercept).  For zamba2 the depth unit is one shared-block period
    # (rounding the 7th shared invocation into the slope, <2% error).
    if not multi_pod:
        cost, coll_kinds = _extrapolated_cost(cfg, shape, mesh)
        hlo = ""   # collectives already aggregated in coll_kinds
    else:
        cost = compat.cost_analysis(compiled)
        hlo = compiled.as_text()
        coll_kinds = None

    report = analysis.make_report(arch_id, shape, mesh_name, chips, cost, hlo,
                                  cfg, peak_mem=peak)
    if coll_kinds is not None:
        report.coll_by_kind = coll_kinds
        report.coll_bytes_per_device = float(sum(coll_kinds.values()))
    rec = report.to_dict()
    rec.update({
        "compile_seconds": round(time.time() - t0, 1),
        "temp_bytes_per_device": peak,
        "argument_bytes_per_device": args_size,
        "output_bytes_per_device": out_size,
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
    })

    print(f"[dryrun] {arch_id} x {shape_id} x mesh {mesh_name}: OK "
          f"({rec['compile_seconds']}s compile)")
    print(f"  memory_analysis: args={args_size/1e9:.2f}GB "
          f"temps={(peak or 0)/1e9:.2f}GB out={out_size/1e9:.2f}GB per device")
    print(f"  cost_analysis: flops={rec['hlo_flops']:.3e} "
          f"bytes={rec['hlo_bytes']:.3e}")
    print(f"  collectives: {rec['coll_by_kind']}")
    print(f"  roofline: compute={rec['t_compute_s']*1e3:.2f}ms "
          f"memory={rec['t_memory_s']*1e3:.2f}ms "
          f"collective={rec['t_collective_s']*1e3:.2f}ms "
          f"-> dominant {rec['dominant']}; useful-FLOP ratio "
          f"{rec['useful_flop_ratio']:.3f}")

    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, f"{arch_id}_{shape_id}_{mesh_name}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=2)
    jax.clear_caches()   # keep the 80-combo batch's memory flat
    return rec


def _extrapolated_cost(cfg, shape, mesh, d_pair=None):
    """Per-device (flops, bytes, collectives) extrapolated linearly in depth
    from two shallow fully-unrolled compiles of the same config."""
    if d_pair is None:
        if cfg.family.value == "hybrid":
            d_pair = (cfg.shared_attn_every, 2 * cfg.shared_attn_every)
        else:
            d_pair = (2, 4)
    d1, d2 = d_pair
    samples = []
    for d in (d1, d2):
        over = {"num_layers": d}
        if cfg.is_encdec:
            over["num_encoder_layers"] = d
        cfg_d = cfg.with_overrides(**over)
        with jax.set_mesh(mesh):
            fn, ex, ins, outs = build_step(cfg_d, shape, mesh, unroll=True)
            comp = jax.jit(fn, in_shardings=ins,
                           out_shardings=outs).lower(*ex).compile()
        c = compat.cost_analysis(comp)
        coll = analysis.collective_bytes(comp.as_text())
        samples.append((float(c.get("flops", 0.0)),
                        float(c.get("bytes accessed", 0.0)), coll))
        jax.clear_caches()
    scale = (cfg.num_layers - d1) / (d2 - d1)
    (f1, b1, k1), (f2, b2, k2) = samples
    cost = {"flops": f1 + (f2 - f1) * scale,
            "bytes accessed": b1 + (b2 - b1) * scale}
    coll = {k: int(max(k1[k] + (k2[k] - k1[k]) * scale, 0)) for k in k1}
    return cost, coll


def _run_fed_combo(arch_id, cfg, shape, mesh, mesh_name, chips, out_dir,
                   static, t0, ce_chunk=0, tag=""):
    """Dry-run the distributed FedPairing step (the paper's technique)."""
    from repro.launch.steps import build_fed_step

    with jax.set_mesh(mesh):
        fn, example, in_shardings, out_shardings = build_fed_step(
            cfg, shape, mesh, static_half_split=static, unroll=True,
            ce_chunk=ce_chunk)
        compiled = jax.jit(fn, in_shardings=in_shardings,
                           out_shardings=out_shardings).lower(
            *example).compile()
    mem = compiled.memory_analysis()
    cost = compat.cost_analysis(compiled)
    hlo = compiled.as_text()
    peak = getattr(mem, "temp_size_in_bytes", None)

    report = analysis.make_report(arch_id, shape, mesh_name, chips, cost, hlo,
                                  cfg, peak_mem=peak)
    rec = report.to_dict()
    variant = tag or ("fed_half" if static else "fed")
    rec.update({
        "variant": variant,
        "compile_seconds": round(time.time() - t0, 1),
        "temp_bytes_per_device": peak,
        # fed step: every client runs 2 full passes (bottom+top phases) of a
        # *fwd+bwd* step -> useful flops = 6·N·tokens x 2 phases baseline
        "model_flops_note": "fed step spans two gated passes per flow",
    })
    print(f"[dryrun] FED({variant}) {arch_id} x {shape.name} x {mesh_name}: "
          f"OK ({rec['compile_seconds']}s)")
    print(f"  flops/dev={cost.get('flops', 0):.3e} "
          f"coll={rec['coll_by_kind']}")
    print(f"  roofline: compute={rec['t_compute_s']*1e3:.2f}ms "
          f"memory={rec['t_memory_s']*1e3:.2f}ms "
          f"collective={rec['t_collective_s']*1e3:.2f}ms")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir,
                            f"{arch_id}_{shape.name}_{mesh_name}_{variant}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=2)
    jax.clear_caches()
    return rec


def main() -> None:
    fabricate_host_devices()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--fed", choices=["", "paper", "half"], default="",
                    help="dry-run the FedPairing step itself "
                         "(paper-faithful or static-half-split variant)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="all 10 archs x 4 shapes")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--continue-on-error", action="store_true")
    args = ap.parse_args()

    combos = []
    archs = ARCH_IDS if args.all or not args.arch else [args.arch]
    shapes = list(INPUT_SHAPES) if args.all or not args.shape else [args.shape]
    meshes = [args.multi_pod] if not args.both_meshes else [False, True]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                combos.append((a, s, mp))

    failures = []
    for a, s, mp in combos:
        try:
            run_combo(a, s, mp, args.out, fed=args.fed)
        except Exception as e:  # noqa: BLE001
            failures.append((a, s, mp, repr(e)))
            print(f"[dryrun] {a} x {s} x multi_pod={mp}: FAILED: {e}")
            if not args.continue_on_error:
                traceback.print_exc()
                raise SystemExit(1)
    if failures:
        print(f"[dryrun] {len(failures)} failures:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print(f"[dryrun] all {len(combos)} combos lowered + compiled OK")


if __name__ == "__main__":
    main()
