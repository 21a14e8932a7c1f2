"""The peaks table and the FLOPs that utilization is counted in."""
import json
import pathlib

import pytest

from chipbench import peaks

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


# stablelm-2-1.6b's published sizes at 6 of its 24 layers (no cell yet)
STABLELM_6L = {"hidden_size": 2048, "intermediate_size": 5632,
               "num_attention_heads": 32, "num_key_value_heads": 32,
               "num_hidden_layers": 6, "vocab_size": 100352}


def _qwen():
    return json.loads((CONFIGS / "qwen1.5-0.5b.json").read_text())


@pytest.mark.parametrize("cfg,gflop", [
    # qwen1.5-0.5b: 24 x (4 x 1024^2 + 3 x 1024 x 2816) + 1024 x 151,936
    # = 463.86 M matmul parameters; 12 x 24 x 16 x 64 x 512 = 0.151 G
    (_qwen(), 2.934),
    # stablelm-2-1.6b at 6 layers: 6 x (4 x 2048^2 + 3 x 2048 x 5632)
    # + 2048 x 100,352 = 513.80 M; 12 x 6 x 32 x 64 x 512 = 0.0755 G
    (STABLELM_6L, 3.158),
], ids=["qwen1.5-0.5b", "stablelm-2-1.6b-6l"])
def test_flops_per_token_matches_hand_count(cfg, gflop):
    assert peaks.flops_per_token(cfg, 512) / 1e9 == pytest.approx(
        gflop, abs=5e-4)


def test_matmul_params_counts_the_tied_head_once():
    assert peaks.matmul_params(_qwen()) == 463_863_808


def test_v5e_peaks_are_the_published_ones():
    assert peaks.peak("TPU v5 lite") == 197e12
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9


def test_unknown_device_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("cpu")
