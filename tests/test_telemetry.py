"""In-program telemetry: off it records nothing and opens no profiler
annotation; on, the round driver's spans nest as the round runs them and
every blocking read of a device value to the host is counted, while the
trained parameters stay bit-identical."""
import collections

import jax
import numpy as np
import pytest

from repro import telemetry
from repro.configs import get_smoke_config
from repro.core import faults, latency, rounds

W = 4
CFG = get_smoke_config("tinyllama-1.1b").with_overrides(num_layers=W)


class Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``; keeps the names."""

    def __init__(self):
        self.names = []

    def __call__(self, name):
        self.names.append(name)
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def annotations(monkeypatch):
    fake = Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", fake)
    return fake


@pytest.fixture
def on():
    telemetry.reset()
    telemetry.enable()
    try:
        yield
    finally:
        telemetry.disable()
        telemetry.reset()


def _driver(algorithm="fedpairing", n=2, **kw):
    rc = rounds.RoundConfig(algorithm=algorithm, engine="vmapped", rounds=2,
                            batches_per_round=2, seed=0, **kw)
    return rounds.RoundDriver(CFG, rc, latency.make_fleet(n=n, seed=0))


def _spans(kind="span"):
    return [e for e in telemetry.events() if e.kind == kind]


def _parent(e, spans):
    """The innermost span strictly around ``e``."""
    around = [s for s in spans if s is not e and s.start <= e.start
              and e.end <= s.end]
    return min(around, key=lambda s: s.end - s.start, default=None)


def test_off_records_nothing_and_opens_no_annotation(annotations):
    telemetry.reset()
    with telemetry.span("round"):
        telemetry.count("host_sync")
    assert telemetry.events() == []
    assert annotations.names == []
    assert telemetry.span("a") is telemetry.span("b")


def test_on_spans_nest_and_counts_record(annotations, on):
    with telemetry.span("outer"):
        with telemetry.span("inner"):
            telemetry.count("host_sync", 2)
    assert annotations.names == ["repro.outer", "repro.inner"]
    inner, outer = _spans()
    assert (inner.name, outer.name) == ("inner", "outer")
    assert outer.start <= inner.start <= inner.end <= outer.end
    (c,) = _spans("count")
    assert (c.name, c.n) == ("host_sync", 2)
    assert inner.start <= c.start <= inner.end
    telemetry.reset()
    assert telemetry.events() == []


def test_a_span_closes_when_its_work_raises(on):
    with pytest.raises(ZeroDivisionError):
        with telemetry.span("plan"):
            1 / 0
    assert [e.name for e in _spans()] == ["plan"]


def test_fedpairing_round_spans_and_host_syncs(on):
    d = _driver()
    d.run()
    spans = _spans()
    round_spans = [s for s in spans if s.name == "round"]
    assert len(round_spans) == 2
    for r in round_spans:
        children = collections.Counter(
            s.name for s in spans if _parent(s, spans) is r)
        assert children == {"plan": 1, "batch": 2, "step": 2,
                            "loss_sync": 2, "clock": 1, "aggregate": 1,
                            "record": 1}
        for step in (s for s in spans
                     if s.name == "step" and _parent(s, spans) is r):
            assert sorted(s.name for s in spans
                          if _parent(s, spans) is step) == [
                "step.args", "step.dispatch"]
        # one read per local step's loss; the aggregation composes its
        # weights and checks the cohort on the host, so it reads nothing
        in_round = [c for c in _spans("count") if r.start <= c.start <= r.end]
        syncs = [_parent(c, spans).name for c in in_round
                 if c.name == "host_sync"]
        assert sorted(syncs) == ["loss_sync", "loss_sync"]
        # one jitted program in `aggregate`: the reduce and the broadcast,
        # written into the trained replicas
        programs = [_parent(c, spans).name for c in in_round
                    if c.name == "agg_program"]
        assert programs == ["aggregate"]


@pytest.mark.parametrize("algorithm,names", [
    ("fl", {"round", "batch", "step", "loss_sync", "aggregate"}),
    ("splitfed", {"round", "batch", "step", "loss_sync", "aggregate",
                  "broadcast"}),
    ("sl", {"round", "batch", "step", "loss_sync"}),
])
def test_baseline_rounds_have_their_spans(on, algorithm, names):
    _driver(algorithm).run(rounds=1)
    assert {s.name for s in _spans()} == names


def test_scaffold_broadcasts_inside_its_aggregate_span(on):
    _driver(agg_policy="scaffold").run(rounds=1)
    spans = _spans()
    (bcast,) = [s for s in spans if s.name == "broadcast"]
    assert _parent(bcast, spans).name == "aggregate"


def test_faulted_round_has_every_span(on):
    fc = faults.FaultConfig(straggler=0.5)
    _driver(n=4, faults=fc).run(rounds=1)
    assert {s.name for s in _spans()} >= {
        "round", "plan", "batch", "step", "step.args", "step.dispatch",
        "loss_sync", "clock", "aggregate", "record"}


def test_parameters_are_bit_identical_with_telemetry_on_and_off():
    def trained(enabled):
        telemetry.reset()
        if enabled:
            telemetry.enable()
        try:
            d = _driver()
            s = d.run()
            return s, d.global_params(s), len(telemetry.events())
        finally:
            telemetry.disable()
            telemetry.reset()

    s_off, g_off, n_off = trained(False)
    s_on, g_on, n_on = trained(True)
    assert n_off == 0 and n_on > 0
    assert s_on.history == s_off.history
    for a, b in zip(jax.tree_util.tree_leaves((s_on.client_params, g_on)),
                    jax.tree_util.tree_leaves((s_off.client_params, g_off))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
