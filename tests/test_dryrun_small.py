"""Dry-run machinery on a small fabricated mesh (subprocess keeps the main
test process at 1 device).  Exercises build_step + sharding rules +
roofline extraction for train / prefill / decode on a (4,2) mesh."""
import subprocess
import sys

import pytest

CODE = r"""
import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import jax.numpy as jnp
from repro import compat
from repro.configs import get_smoke_config
from repro.configs.base import InputShape
from repro.launch.steps import build_step
from repro.roofline import analysis

mesh = compat.make_mesh((4, 2), ("data", "model"))

for arch in ["tinyllama-1.1b", "deepseek-moe-16b", "rwkv6-1.6b",
             "zamba2-1.2b", "qwen2-vl-2b", "seamless-m4t-large-v2"]:
    cfg = get_smoke_config(arch).with_overrides(dtype="bfloat16")
    for shape in [InputShape("train", 64, 8, "train"),
                  InputShape("decode", 64, 8, "decode")]:
        with jax.set_mesh(mesh):
            fn, ex, ins, outs = build_step(cfg, shape, mesh, unroll=True)
            compiled = jax.jit(fn, in_shardings=ins,
                               out_shardings=outs).lower(*ex).compile()
        cost = compat.cost_analysis(compiled)
        coll = analysis.collective_bytes(compiled.as_text())
        assert cost.get("flops", 0) > 0, (arch, shape.name)
        print(f"OK {arch} {shape.name} flops={cost['flops']:.2e} "
              f"coll={sum(coll.values())}")
print("SMALL_DRYRUN_OK")
"""


@pytest.mark.slow
def test_small_mesh_dryrun_all_families():
    res = subprocess.run(
        [sys.executable, "-c", CODE], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root"},
        cwd="/root/repo", timeout=900)
    assert "SMALL_DRYRUN_OK" in res.stdout, res.stdout[-2000:] + res.stderr[-4000:]
