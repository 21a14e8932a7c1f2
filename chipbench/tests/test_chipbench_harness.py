"""The harness finds every cell's files by name, refuses a machine
without the chip, and prints the result line the contract asks for."""
import json
import re

import pytest

from chipbench import harness, run, traffic

BENCH = harness.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_to_its_files(name):
    cell = harness.resolve(name)
    assert cell.config["name"] in {c["name"] for c in BENCH["configs"]}
    assert set(traffic.MIX_KEYS) <= set(cell.mix)
    assert {"loss", "update1", "change"} <= set(cell.limits)
    ref = harness.load_module("references", cell.config["reference"])
    assert callable(ref.flow_loss) and callable(ref.config_view)
    for m in cell.per_layer:
        assert callable(harness.load_module("metrics", m["name"]).read)
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_config_file_runs_the_program_config_it_states(name):
    entry = {c["name"]: c for c in BENCH["configs"]}[name]
    cfg = json.loads((harness.ROOT / entry["file"]).read_text())
    assert cfg["reduced"] == entry["reduced"]
    arch = harness.program_config(cfg)
    assert arch.num_layers == cfg["num_hidden_layers"]
    assert arch.d_model == cfg["hidden_size"]
    assert arch.d_ff == cfg["intermediate_size"]
    assert arch.vocab_size == cfg["vocab_size"]
    assert arch.tie_embeddings == cfg["tie_word_embeddings"]
    assert (arch.dtype, arch.param_dtype) == (cfg["compute_dtype"],
                                              cfg["param_dtype"])


def test_benchmark_json_keeps_to_the_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    metric_names = [m["name"] for m in BENCH["end_to_end"]
                    + BENCH["per_layer"]]
    names = metric_names + CELLS + [c["name"] for c in BENCH["configs"]]
    assert all(NAME.match(n) for n in names)
    assert len(set(metric_names)) == len(metric_names)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert 1 <= BENCH["run_seconds"] <= 51


def test_runner_refuses_a_machine_without_the_chip(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "no TPU" in out.err


def test_result_line_has_only_the_contract_keys_with_checks_last():
    checks = {"loss": {"value": 1e-4, "limit": 1e-3}}
    line = harness.result_line(True, 3, 0, {}, {"platform": "tpu"}, checks)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    traced = harness.result_line(True, 3, 0, {}, {}, checks,
                                 {"device_ops": [], "idle_gaps": []})
    assert list(traced) == ["correct", "attempted", "failed", "metrics",
                            "device", "breakdown", "checks"]
    assert json.loads(json.dumps(traced)) == traced


def test_sub_seeds_take_any_whole_number_and_repeat():
    big = traffic.sub_seeds(2**40 + 3)
    assert big == traffic.sub_seeds(2**40 + 3)
    assert all(0 <= v < 2**31 for v in big.values())
    assert big != traffic.sub_seeds(2**40 + 4)


def test_token_stream_repeats_per_seed_and_keeps_its_sizes():
    mix = dict(traffic.load_mix("pair-k2"), corpus_tokens=4096, seq=16)
    a, b = (traffic.TokenStream(mix, 300, 9) for _ in range(2))
    x, y = a.next(), b.next()
    assert x["tokens"].shape == (2, 2, 16)
    assert (x["tokens"] == y["tokens"]).all()
    assert (x["labels"][..., :-1] == x["tokens"][..., 1:]).all()
    z = traffic.TokenStream(mix, 300, 10).next()
    assert z["tokens"].shape == x["tokens"].shape


def test_paper_lengths_follow_eq6():
    lengths = harness.paper_lengths(
        [0.5e9, 1.5e9], [1, 0], 24)
    assert list(lengths) == [6, 18]
