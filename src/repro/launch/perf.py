"""§Perf hillclimbing driver: compile a (arch x shape) combo under a named
variant, report the three roofline terms + memory, append to the perf log.

Variants:
  train:  baseline | seq_parallel | ce_chunk | sp+ce
  decode: baseline | flash_decode
  fed:    paper | half     (the FedPairing step itself)

  python -m repro.launch.perf --arch yi-6b --shape train_4k --variant seq_parallel
"""

import argparse
import json
import time

import jax

from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config, get_shape
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (build_fed_step, build_serve_step,
                                build_train_step)
from repro.launch.dryrun import _extrapolated_cost, fabricate_host_devices
from repro.roofline import analysis

TRAIN_VARIANTS = {
    "baseline": {},
    "seq_parallel": {"seq_parallel": True},
    "ce_chunk": {"ce_chunk": 512},
    "sp+ce": {"seq_parallel": True, "ce_chunk": 512},
    "moe_ep": {"moe_ep": True},
    "moe_ep+ce": {"moe_ep": True, "ce_chunk": 512},
    "moe_ep+sp": {"moe_ep": True, "seq_parallel": True},
    "microbatch4": {"microbatches": 4},
    "moe_ep+mb4": {"moe_ep": True, "microbatches": 4},
}
DECODE_VARIANTS = {
    "baseline": {},
    "flash_decode": {"flash_decode": True},
    "bf16_params": {"bf16_params": True},
    "flash+bf16": {"flash_decode": True, "bf16_params": True},
    "moe_ep": {"moe_ep": True},
}


def run(arch_id: str, shape_id: str, variant: str, out_dir: str) -> dict:
    cfg = get_config(arch_id)
    shape = get_shape(shape_id)
    mesh = make_production_mesh()
    chips = mesh.devices.size
    t0 = time.time()

    if variant in ("paper", "half", "half+ce"):     # fed step
        from repro.launch.dryrun import _run_fed_combo
        return _run_fed_combo(arch_id, cfg, shape, mesh, "16x16", chips,
                              out_dir, static=("half" in variant), t0=t0,
                              ce_chunk=512 if variant == "half+ce" else 0,
                              tag={"half+ce": "fed_half_ce"}.get(variant, ""))

    if shape.mode == "train":
        kw = TRAIN_VARIANTS[variant]
        builder = lambda c, unroll: build_train_step(  # noqa: E731
            c, shape, mesh, unroll=unroll, **kw)
    else:
        kw = DECODE_VARIANTS[variant]
        builder = lambda c, unroll: build_serve_step(  # noqa: E731
            c, shape, mesh, unroll=unroll, **kw)

    # memory pass (scan program)
    with jax.set_mesh(mesh):
        fn, ex, ins, outs = builder(cfg, 1)
        compiled = jax.jit(fn, in_shardings=ins,
                           out_shardings=outs).lower(*ex).compile()
    mem = compiled.memory_analysis()
    peak = getattr(mem, "temp_size_in_bytes", None)
    jax.clear_caches()

    # cost pass (depth-extrapolated unroll) — reuse dryrun machinery but
    # with the variant builder
    import repro.launch.dryrun as dr
    import repro.launch.steps as steps_mod
    orig = steps_mod.build_step

    def patched(cfg_, shape_, mesh_, *, unroll=1, **_kw):
        return builder(cfg_, unroll)

    steps_mod.build_step = patched
    dr.build_step = patched
    try:
        cost, coll = _extrapolated_cost(cfg, shape, mesh)
    finally:
        steps_mod.build_step = orig
        dr.build_step = orig

    report = analysis.make_report(arch_id, shape, "16x16", chips, cost, "",
                                  cfg, peak_mem=peak)
    report.coll_by_kind = coll
    report.coll_bytes_per_device = float(sum(coll.values()))
    rec = report.to_dict()
    rec.update({"variant": variant, "compile_seconds": round(time.time() - t0, 1),
                "temp_bytes_per_device": peak})
    print(f"[perf] {arch_id} x {shape_id} [{variant}]: "
          f"compute={rec['t_compute_s']*1e3:.1f}ms "
          f"memory={rec['t_memory_s']*1e3:.1f}ms "
          f"collective={rec['t_collective_s']*1e3:.1f}ms "
          f"temps={(peak or 0)/1e9:.1f}GB dominant={rec['dominant']}")
    print(f"       collectives: { {k: round(v/1e6) for k, v in coll.items()} } MB/dev")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir,
                               f"{arch_id}_{shape_id}_{variant}.json"),
                  "w") as f:
            json.dump(rec, f, indent=2)
    jax.clear_caches()
    return rec


def main() -> None:
    fabricate_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES), required=True)
    ap.add_argument("--variant", required=True)
    ap.add_argument("--out", default="experiments/perf")
    args = ap.parse_args()
    run(args.arch, args.shape, args.variant, args.out)


if __name__ == "__main__":
    main()
