"""The StableLM 2 cell's check tells StableLM 2 from what the repository
ran under its name before: a sound run reads correct, and the program with
RMSNorm in place of LayerNorm, or with rope over the whole head, reads not
correct.

A tiny copy of the configuration file runs on the CPU with the chip check
skipped and float32 compute.  It keeps the published head size, 64, so rope
rotates 16 of each head's dimensions as at full size.  The sound run reads
near float32 round-off (loss ~1e-7, norm gaps ~1e-6 at this size), far
under the limits below; the departures read far above them.
"""
import json
import time

import jax
import numpy as np
import pytest

from chipbench import departures, harness, traffic
from repro.configs import NormKind, get_config
from repro.models import transformer

LIMITS = {"loss": 1e-4, "update1": 1e-3, "change": 1e-3}
CONFIG_FILE = harness.HERE / "configs" / "stablelm-2-1.6b-6l.json"


def tiny_cell():
    cfg = json.loads(CONFIG_FILE.read_text())
    cfg.update(hidden_size=256, intermediate_size=512, num_attention_heads=4,
               num_key_value_heads=4, num_hidden_layers=4, vocab_size=512,
               compute_dtype="float32")
    mix = dict(traffic.load_mix("pair-k2"), seq=16, corpus_tokens=20000)
    return harness.Cell(name="tiny-stablelm", chips=1, config=cfg, mix=mix,
                        limits=LIMITS, end_to_end=[
                            {"name": "setup_s", "unit": "s"}],
                        per_layer=[])


def run(cell, **kw):
    return harness.run_cell(cell, 2**31 + 91, 0.0, False, time.perf_counter(),
                            require_tpu=False, **kw)


def test_sound_run_is_correct():
    line = run(tiny_cell())
    assert line["correct"], line["checks"]
    assert line["checks"]["plan"] == {"value": 0, "limit": 0}


@pytest.mark.parametrize("departure", ["rms_norm", "full_rotary"])
def test_departure_from_stablelm2_is_not_correct(departure, monkeypatch):
    cell = tiny_cell()
    kw = {}
    if departure == "rms_norm":
        monkeypatch.setattr(transformer, "norm",
                            departures.rms_for_layer_norm)
    else:
        kw["arch_overrides"] = {"partial_rotary_factor": 1.0}
    line = run(cell, **kw)
    assert not line["correct"]
    worst = max(c["value"] / c["limit"] for k, c in line["checks"].items()
                if k != "plan")
    assert worst > 10, line["checks"]


def test_registry_config_states_what_the_harness_does_not_pass_on():
    cfg = json.loads(CONFIG_FILE.read_text())
    arch = harness.program_config(cfg)
    assert "partial_rotary_factor" not in harness.ARCH_FIELDS
    assert arch == get_config(cfg["program_arch"]).with_overrides(
        num_layers=cfg["num_hidden_layers"])
    assert arch.partial_rotary_factor == cfg["partial_rotary_factor"]
    assert arch.rotary_dim == 16
    assert "layer_norm_eps" in cfg and "rms_norm_eps" not in cfg
    assert arch.norm == NormKind.LAYER
    assert arch.norm_eps == cfg["layer_norm_eps"]
    assert arch.qkv_bias and not arch.tie_embeddings
    assert arch.rope_theta == cfg["rope_theta"]


def test_zero_layer_norm_bias_changes_only_the_biases():
    cell = tiny_cell()
    arch = harness.program_config(cell.config)
    init = harness.make_weights(arch, 7)
    zeroed = departures.zero_layer_norm_bias(init, arch)
    flat = jax.tree_util.tree_flatten_with_path(init)[0]
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(zeroed)):
        if str(path[-1].key).startswith("ln"):
            scale, bias = transformer.norm_parts(b, arch)
            np.testing.assert_array_equal(scale,
                                          transformer.norm_parts(a, arch)[0])
            np.testing.assert_array_equal(bias, 0.0)
        else:
            assert b is a


@pytest.mark.parametrize("variant", ["ref_bf16", "ln_bias0"])
def test_departure_readings_run_through_the_check(variant, monkeypatch):
    """The readings beside the limits: the reference in bfloat16 rounds the
    tiny cell's float32 updates away, so its norm numbers read as an
    unchanged state; zero LayerNorm biases leave a sound run correct."""
    monkeypatch.setattr(harness, "resolve", lambda name: tiny_cell())
    monkeypatch.setattr(harness, "require_chips",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "configure_jax", lambda: None)
    r = departures.reading(tiny_cell(), variant, 2**31 + 92)
    assert set(r["checks"]) == {"loss", "update1", "change", "plan"}
    if variant == "ln_bias0":
        assert r["correct"], r["checks"]
    else:
        assert not r["correct"]
        assert r["checks"]["update1"] > 0.5, r["checks"]
