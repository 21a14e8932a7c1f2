"""Compile for a described TPU v5e chip, without one attached.

The TPU compiler refuses what interpret mode accepts — block shapes whose
last two dims are not (8, 128)-aligned or whole, programs that do not fit
the chip's memory — so the main path's kernels at real widths and the
vmapped / bucketed fed steps at qwen1.5-0.5b's published widths (and the
vmapped one and the in-place aggregation at stablelm-2-1.6b's, 6 of its
24 layers) are compiled here for a ``v5e:2x2`` topology.  Nothing runs:
these tests say that the chip's compiler accepts the programs, and what
they reserve, not what they compute; for the vmapped steps also that no
partner read was lowered to a gather loop.

The topology is described inside a fixture (never at import), so every
xdist worker collects the same tests and only the worker given this file
loads the TPU compiler.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import aggregation, fedbucket, fedpair, splitting
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd
from repro.kernels.wkv6 import wkv6
from repro.models import registry

# usable HBM of one v5e chip as the compiler reports it (15.75 GiB)
V5E_HBM_BYTES = int(15.75 * 2 ** 30)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    program compiled for it could be written but never read back here."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_wkv6_compiles_at_rwkv6_widths(one_chip):
    B, S, H, N = 1, 2048, 32, 64
    seq = _spec(one_chip, (B, S, H, N), jnp.bfloat16)
    hlo = _kernel_hlo(lambda r, k, v, lw, u: wkv6(r, k, v, lw, u, chunk=64),
                      seq, seq, seq, _spec(one_chip, (B, S, H, N)),
                      _spec(one_chip, (H, N)))
    assert "tpu_custom_call" in hlo


def test_ssd_compiles_at_zamba2_widths(one_chip):
    B, S, H, P, N = 1, 2048, 64, 64, 128
    hlo = _kernel_hlo(lambda x, a, b, c: ssd(x, a, b, c, chunk=64),
                      _spec(one_chip, (B, S, H, P), jnp.bfloat16),
                      _spec(one_chip, (B, S, H)),
                      _spec(one_chip, (B, S, N), jnp.bfloat16),
                      _spec(one_chip, (B, S, N), jnp.bfloat16))
    assert "tpu_custom_call" in hlo


def test_flash_attention_compiles_at_qwen_widths(one_chip):
    qkv = _spec(one_chip, (1, 2048, 16, 64), jnp.bfloat16)
    hlo = _kernel_hlo(lambda q, k, v: flash_attention(q, k, v), qkv, qkv, qkv)
    assert "tpu_custom_call" in hlo


QWEN = get_config("qwen1.5-0.5b")
N_CLIENTS, BATCH, SEQ = 2, 2, 512


def _fed_inputs(sharding, cfg=QWEN):
    g = jax.eval_shape(lambda k: registry.init_params(cfg, k),
                       jax.random.key(0))
    params = jax.tree_util.tree_map(
        lambda a: _spec(sharding, (N_CLIENTS,) + a.shape, a.dtype), g)
    batch = {k: _spec(sharding, (N_CLIENTS, BATCH, SEQ), jnp.int32)
             for k in ("tokens", "labels")}
    return g, params, batch


def _assert_fits(compiled):
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert 0 < peak <= V5E_HBM_BYTES, peak


def _gather_loops(compiled) -> int:
    """``while`` ops the TPU compiler made of a gather.  A traced-index
    partner read ``x[partner]`` becomes one such loop per leaf and use,
    each writing a whole copy of the leaf."""
    return sum(1 for line in compiled.as_text().splitlines()
               if re.search(r"\bwhile\(", line)
               and re.search(r'op_name="[^"]*/gather"', line))


def _assert_lean_fed_step(compiled, gather_step_peak):
    """No partner read left as a gather loop, and the compiled peak within
    1 % of the gather form's (its value here when the step read partners
    with ``x[partner]``)."""
    assert _gather_loops(compiled) == 0
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert peak <= 1.01 * gather_step_peak, peak


def test_vmapped_fed_step_compiles_at_qwen_published_widths(one_chip):
    g, params, batch = _fed_inputs(one_chip)
    step = fedpair.make_fed_step(
        lambda p, b: registry.loss_fn(p, b, QWEN)[0],
        splitting.split_plan(QWEN, g), QWEN.num_layers,
        fedpair.FedPairingConfig(lr=0.05 / N_CLIENTS),
        tied_head_dtype=QWEN.dtype)
    vec = lambda dtype: _spec(one_chip, (N_CLIENTS,), dtype)
    compiled = step.lower(params, batch, vec(jnp.int32), vec(jnp.int32),
                          vec(jnp.float32)).compile()
    _assert_fits(compiled)
    _assert_lean_fed_step(compiled, 13_941_535_744)


def test_bucketed_fed_step_compiles_at_qwen_published_widths(one_chip):
    _, params, batch = _fed_inputs(one_chip)
    step, _ = fedbucket.make_bucketed_fed_step(
        QWEN, np.array([1, 0]), np.array([9, 15]),
        np.array([0.5, 0.5], np.float32), fedbucket.FedBucketConfig(lr=0.05))
    _assert_fits(step.lower(params, batch).compile())


def test_vmapped_fed_step_compiles_at_stablelm2_widths(one_chip):
    """The benchmark's StableLM 2 cell: published widths at 6 layers,
    LayerNorm, partial rope, QKV bias and the untied 100k head on the
    partner."""
    cfg = get_config("stablelm-1.6b").with_overrides(num_layers=6)
    g, params, batch = _fed_inputs(one_chip, cfg)
    step = fedpair.make_fed_step(
        lambda p, b: registry.loss_fn(p, b, cfg)[0],
        splitting.split_plan(cfg, g), cfg.num_layers,
        fedpair.FedPairingConfig(lr=0.05 / N_CLIENTS))
    vec = lambda dtype: _spec(one_chip, (N_CLIENTS,), dtype)
    compiled = step.lower(params, batch, vec(jnp.int32), vec(jnp.int32),
                          vec(jnp.float32)).compile()
    _assert_fits(compiled)
    _assert_lean_fed_step(compiled, 14_473_285_120)


def test_in_place_aggregation_allocates_no_model_at_stablelm2_widths(
        one_chip):
    """The round's reduce and broadcast as one program written into the
    donated replicas: every output aliases an argument and the only
    temporary is one leaf's mean, so nothing model-sized is allocated
    while the last local step may still hold its reservation."""
    cfg = get_config("stablelm-1.6b").with_overrides(num_layers=6)
    g, params, _ = _fed_inputs(one_chip, cfg)
    w = _spec(one_chip, (N_CLIENTS,))
    m = aggregation._reduce_into_program(None).lower(
        w, params).compile().memory_analysis()
    # beyond the aliased buffers the outputs count a few hundred bytes
    # of the runtime's own (512 here), never a leaf
    assert m.alias_size_in_bytes > 0
    assert m.output_size_in_bytes - m.alias_size_in_bytes < 2 ** 20
    largest = max(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(g))
    # one leaf's mean, and 64 KiB of scratch here
    assert m.temp_size_in_bytes <= largest + 2 ** 20
