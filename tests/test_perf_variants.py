"""§Perf optimization variants — correctness vs their baselines.

Each variant must be numerically equivalent to the baseline semantics:
  * expert-parallel MoE dispatch (shard_map all_to_all) == capacity dispatch
  * flash-decode (seq-parallel cache attention)         == plain decode
  * chunked CE                                          == plain CE
  * fed static-half-split == masked split at L=W/2
"""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import registry


def test_chunked_ce_matches_plain():
    cfg = get_smoke_config("qwen1.5-0.5b")
    p = registry.init_params(cfg, jax.random.key(0))
    t = jax.random.randint(jax.random.key(1), (2, 32), 0, cfg.vocab_size)
    b = {"tokens": t, "labels": t}
    l1, _ = registry.loss_fn(p, b, cfg)
    l2, _ = registry.loss_fn(p, b, cfg, ce_chunk=8)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)


_SUBPROC = r"""
import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs import get_smoke_config
from repro.configs.base import InputShape
from repro.models import moe, registry
from repro.launch.steps import build_serve_step

from repro import compat
mesh = compat.make_mesh((4, 2), ("data", "model"))

# --- EP dispatch == capacity dispatch
cfg = get_smoke_config("deepseek-moe-16b").with_overrides(
    num_experts=4, expert_pad_to=4, moe_capacity_factor=8.0)
p = moe.moe_init(jax.random.key(0), None, cfg)
x = jax.random.normal(jax.random.key(1), (8, 16, cfg.d_model))
with jax.set_mesh(mesh):
    y0, a0 = jax.jit(lambda p, x: moe.moe_apply(p, x, cfg))(p, x)
    y1, a1 = jax.jit(lambda p, x: moe.moe_apply_ep(p, x, cfg, mesh,
                                                   ("data",)))(p, x)
np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), rtol=2e-5,
                           atol=2e-5)
np.testing.assert_allclose(float(a0), float(a1), rtol=1e-5)
print("EP_OK")

# --- flash decode == plain decode
cfg = get_smoke_config("tinyllama-1.1b")
shape = InputShape("decode", 64, 4, "decode")
outs = {}
for fd in (False, True):
    with jax.set_mesh(mesh):
        fn, ex, ins, osh = build_serve_step(cfg, shape, mesh, flash_decode=fd)
        jitted = jax.jit(fn, in_shardings=ins, out_shardings=osh)
        params = jax.device_put(registry.init_params(cfg, jax.random.key(0)),
                                ins[0])
        state = jax.device_put(
            registry.init_serve_state(
                registry.init_params(cfg, jax.random.key(0)), cfg,
                shape.global_batch, shape.seq_len), ins[2])
        toks = jax.device_put(
            jax.random.randint(jax.random.key(2), (shape.global_batch, 1), 0,
                               cfg.vocab_size), ins[1])
        logits, _ = jitted(params, toks, state)
        outs[fd] = np.asarray(logits, np.float32)
np.testing.assert_allclose(outs[True], outs[False], rtol=2e-4, atol=2e-4)
print("FLASH_DECODE_OK")
"""


@pytest.mark.slow
def test_ep_and_flash_decode_equivalence():
    res = subprocess.run(
        [sys.executable, "-c", _SUBPROC], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root"},
        cwd="/root/repo", timeout=900)
    assert "EP_OK" in res.stdout and "FLASH_DECODE_OK" in res.stdout, \
        res.stdout[-1500:] + res.stderr[-3000:]
