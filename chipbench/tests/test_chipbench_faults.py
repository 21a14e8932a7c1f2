"""The check decides ``correct`` by the plain reference: a sound run passes,
and each fault a training cell can have, planted under the timed path,
comes out not correct.  So does the control, the program's own
lower-precision path (parameters held in bfloat16).

A tiny model of the qwen configuration's family runs on the CPU, with the
chip check skipped and float32 compute, so the sound run reads near
float32 round-off (loss ~1e-7, norm gaps ~1e-6 at this size) and the limits
below sit far from both it and the faults.
"""
import json
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import calibrate, harness, traffic

LIMITS = {"loss": 1e-4, "update1": 1e-3, "change": 1e-3}


def tiny_cell(lr=0.05):
    cfg = json.loads((harness.HERE / "configs" / "qwen1.5-0.5b.json")
                     .read_text())
    cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=4, num_hidden_layers=4, vocab_size=500,
               compute_dtype="float32")
    mix = dict(traffic.load_mix("pair-k2"), seq=16, corpus_tokens=20000,
               lr=lr)
    return harness.Cell(name="tiny", chips=1, config=cfg, mix=mix,
                        limits=LIMITS, end_to_end=[
                            {"name": "setup_s", "unit": "s"}],
                        per_layer=[])


def plant(variant, cell):
    if variant == "unchanged_state":
        def wrap(step):
            def planted(params, batch, plan, agg_w):
                kept = jax.tree_util.tree_map(jnp.copy, params)
                _, loss = step(params, batch, plan, agg_w)
                return kept, loss
            return planted
        return {"plant": {"step": wrap}}
    if variant == "half_batch":
        return calibrate.variant_kwargs(cell, "half_batch")
    if variant == "control":
        return calibrate.variant_kwargs(cell, "control")
    return {}


def run(cell, **kw):
    return harness.run_cell(cell, 2**31 + 77, 0.0, False, time.perf_counter(),
                            require_tpu=False, **kw)


def test_sound_run_is_correct():
    line = run(tiny_cell())
    assert line["correct"], line["checks"]
    assert line["checks"]["plan"] == {"value": 0, "limit": 0}
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("variant,number", [
    ("unchanged_state", "change"),
    ("half_batch", "update1"),
])
def test_planted_fault_is_not_correct(variant, number):
    cell = tiny_cell()
    line = run(cell, **plant(variant, cell))
    assert not line["correct"]
    assert line["checks"][number]["value"] > 10 * LIMITS[number]


def test_control_in_lower_precision_is_not_correct():
    # at this size a step of lr 0.05 moves weights by several bfloat16
    # steps; at 0.005 it is under one, as it is at the published widths
    cell = tiny_cell(lr=0.005)
    line = run(cell, **plant("control", cell))
    assert not line["correct"]
    assert line["checks"]["update1"]["value"] > 3 * LIMITS["update1"]
