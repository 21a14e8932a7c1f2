#!/usr/bin/env bash
# Tier-1 gate: the full test suite plus the benchmark bit-rot guard
# (tiny fedstep + roundtime suites with JSON validation), so the round
# driver, the engines and the benchmarks can't rot independently.
#
#   bash scripts/test_smoke.sh
set -euo pipefail
cd "$(dirname "$0")/.."

# docs-consistency gate: every CLI flag documented in README.md must
# exist in the sim/fed_train/benchmarks/chip_smoke argparse definitions
# and vice versa — new flags can't ship undocumented, docs can't rot
# silently
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python - <<'PY'
import re

from benchmarks.run import build_parser as bench_parser
from chip_smoke import build_parser as chip_parser
from repro.launch.fed_train import build_parser as fed_parser
from repro.launch.sim import build_parser as sim_parser


def flags(parser):
    out = set()
    for action in parser._actions:
        out.update(s for s in action.option_strings if s.startswith("--"))
    out.discard("--help")
    return out


in_code = (flags(sim_parser()) | flags(fed_parser()) | flags(bench_parser())
           | flags(chip_parser()))
with open("README.md") as f:
    readme = f.read()
# long flags only; the lookahead rejects tokens that continue with '_'
# (e.g. XLA_FLAGS values are not CLI flags of ours)
in_docs = set(re.findall(r"--[a-z][a-z0-9-]*(?![a-z0-9_-])", readme))
undocumented = sorted(in_code - in_docs)
phantom = sorted(in_docs - in_code)
assert not undocumented, f"CLI flags missing from README.md: {undocumented}"
assert not phantom, f"README.md documents nonexistent flags: {phantom}"
print(f"docs-consistency: README.md <-> argparse OK "
      f"({len(in_code)} flags)")
PY

# marker-audit gate: every marker declared in pytest.ini must be
# exercised by at least one collected test — a renamed/retired suite
# can't leave a stage above silently selecting zero tests
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python - <<'PY'
import configparser
import sys

import pytest

cp = configparser.ConfigParser()
cp.read("pytest.ini")
declared = {line.split(":", 1)[0].strip()
            for line in cp["pytest"]["markers"].strip().splitlines()}


class _Audit:
    def __init__(self):
        self.seen = set()

    def pytest_collection_finish(self, session):
        for item in session.items:
            self.seen.update(m.name for m in item.iter_markers())


audit = _Audit()
rc = pytest.main(["--collect-only", "-q", "-p", "no:cacheprovider",
                  "--no-header", "-W", "ignore"], plugins=[audit])
assert rc == 0, f"test collection failed (exit {rc})"
unexercised = sorted(declared - audit.seen)
assert not unexercised, \
    f"pytest.ini declares markers no collected test carries: {unexercised}"
print(f"marker-audit: every declared marker exercised OK "
      f"({len(declared)} markers)")
PY

# planning + pairing suites first (fast, host-side): the RoundPlan and
# joint-matching invariants gate everything downstream — fail here before
# paying for the full suite
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -q -m planning
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -q -m pairing

# fault-tolerance suite (DESIGN.md §9): zero-cost contract, graceful
# degradation, checkpoint/resume exactness
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -q -m faults

# heterogeneous-workload suite (DESIGN.md §10): device classes, the
# all-equal-vector bit-identity contract, per-client shape validation
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -q -m het

# async pipelined-rounds suite (DESIGN.md §12): S=0 bit-identity to the
# synchronous driver, event-clock monotonicity, bounded-staleness
# aggregation, overlap planning, the batch_fn boundary contract
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -q -m async

# aggregation-policy / convergence suite (DESIGN.md §13): the scaffold
# vs mean non-IID regression, registry-mean bit-identity to the
# pre-registry loop, control-variate invariants
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -q -m convergence

# fleet-axis sharding suite (DESIGN.md §11): placement rules, mesh
# validation, the 1-device bit-identity contract, compat-shim dispatch
# (the slow fabricated-device property sweeps run in the full suite)
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -q -m "sharding and not slow"

# fabricated-8-device smoke: one sharded fedpairing round on an 8-way
# client-axis mesh must reproduce the unsharded single-device trace
# (structural fields exact, loss within the DESIGN.md §11 float32
# reassociation tolerance) — the whole tentpole in one round
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python - <<'PY'
import os, subprocess, sys

CODE = r"""
import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, jax
from repro.configs import get_smoke_config
from repro.core import latency, rounds
from repro.core.latency import ChannelModel
from repro.sharding.fleet import make_fleet_sharding

assert jax.device_count() == 8
cfg = get_smoke_config("tinyllama-1.1b")
def one_round(sharding):
    rc = rounds.RoundConfig(rounds=1, batches_per_round=1, seed=3)
    fleet = latency.make_fleet(n=8, seed=3)
    return rounds.RoundDriver(cfg, rc, fleet, chan=ChannelModel(),
                              sharding=sharding).run().history[0]
ref = dataclasses.asdict(one_round(None))
got = dataclasses.asdict(one_round(make_fleet_sharding()))
la, lb = ref.pop("mean_loss"), got.pop("mean_loss")
assert ref == got, (ref, got)
assert abs(la - lb) <= 1e-4 * max(1.0, abs(la)), (la, lb)
print("sharded-8dev smoke: trace OK (loss delta %.2e)" % abs(la - lb))
"""
env = dict(os.environ, PYTHONPATH="src",
           XLA_FLAGS="")  # the child sets its own device fabrication
res = subprocess.run([sys.executable, "-c", CODE], env=env)
sys.exit(res.returncode)
PY

PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q

bash scripts/bench_smoke.sh
