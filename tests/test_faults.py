"""Fault-tolerant round execution (DESIGN.md §9) — the ISSUE-6 contracts.

* zero-cost: an all-zero ``FaultConfig`` leaves the driver trace
  bit-identical to ``faults=None`` (and the planner key unchanged),
* determinism: same seed + same FaultModel -> identical fault traces on
  every engine; checkpoint/resume reproduces the uninterrupted history
  exactly,
* degradation ladder: dropouts excluded via the aggregation mask (whose
  correctness is a property test), orphans re-paired or solo, all-fail
  rounds skipped cleanly, abort mode never beats graceful on the clock,
* guards: RoundConfig validation, empty-cohort no-op rounds, the
  non-finite-loss error naming round and clients.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.configs import get_smoke_config
from repro.core import aggregation, faults, latency, planning, rounds
from repro.hypothesis_compat import given, settings, strategies as st

pytestmark = pytest.mark.faults

W = 4
N = 4
CFG = get_smoke_config("tinyllama-1.1b").with_overrides(num_layers=W)
FLEET = latency.make_fleet(n=N, seed=0)
CHAN = latency.ChannelModel()
WORK = latency.WorkloadModel(num_layers=W)


def _driver(engine="vmapped", **kw):
    rc_kw = dict(algorithm="fedpairing", engine=engine, rounds=3,
                 batches_per_round=2, participation=1.0, drift_sigma_m=2.0,
                 donate=False, seed=0)
    rc_kw.update(kw)
    return rounds.RoundDriver(CFG, rounds.RoundConfig(**rc_kw), FLEET)


def _fc(**kw):
    base = dict(dropout=0.3, outage=0.3, straggler=0.3,
                deadline_factor=2.0, seed=7)
    base.update(kw)
    return faults.FaultConfig(**base)


def _tree_equal(a, b):
    for (path, x), (_, y) in zip(jax.tree_util.tree_flatten_with_path(a)[0],
                                 jax.tree_util.tree_flatten_with_path(b)[0]):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=str(path))


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------

class TestValidation:
    @pytest.mark.parametrize("kw", [
        dict(dropout=1.0), dict(dropout=-0.1), dict(dropout=(0.2, 1.5)),
        dict(straggler=1.5), dict(straggler_factor=0.5),
        dict(outage=1.0), dict(retries=-1), dict(backoff_s=-1.0),
        dict(deadline_factor=-0.5), dict(orphan="adopt"),
        dict(mode="retry"),
    ])
    def test_fault_config_rejects(self, kw):
        with pytest.raises(ValueError):
            faults.FaultConfig(**kw)

    def test_round_config_participation_bounds(self):
        for bad in (0.0, -0.2, 1.5):
            with pytest.raises(ValueError, match="participation"):
                rounds.RoundConfig(participation=bad)
        rounds.RoundConfig(participation=1.0)   # inclusive upper bound

    def test_round_config_batches_per_round(self):
        with pytest.raises(ValueError, match="batches_per_round"):
            rounds.RoundConfig(batches_per_round=0)

    def test_faults_require_fedpairing(self):
        with pytest.raises(ValueError, match="fedpairing"):
            rounds.RoundConfig(algorithm="fl", faults=_fc())
        # a disabled FaultConfig is fine anywhere
        rounds.RoundConfig(algorithm="fl", faults=faults.FaultConfig())
        with pytest.raises(ValueError, match="FaultConfig"):
            rounds.RoundConfig(faults={"dropout": 0.1})

    def test_enabled_and_randomized(self):
        assert not faults.FaultConfig().enabled
        assert faults.FaultConfig(deadline_factor=1.5).enabled
        assert not faults.FaultConfig(deadline_factor=1.5).randomized
        assert faults.FaultConfig(dropout=0.1).randomized
        assert faults.FaultConfig(dropout=(0.0, 0.2)).enabled


# ---------------------------------------------------------------------------
# zero-cost + determinism contracts
# ---------------------------------------------------------------------------

class TestZeroCost:
    def test_zero_fault_trace_bit_identical(self):
        s0 = _driver().run()
        sz = _driver(faults=faults.FaultConfig(seed=3)).run()
        assert s0.history == sz.history
        _tree_equal(s0.client_params, sz.client_params)

    @pytest.mark.parametrize("clock", [
        {}, dict(async_rounds=True, staleness_bound=1)],
        ids=["sync", "async"])
    @pytest.mark.parametrize("engine", ["vmapped", "bucketed"])
    def test_enabled_layer_realizing_nothing_is_fault_free(self, engine,
                                                           clock):
        """An ENABLED fault layer whose deadline nobody misses realizes
        no fault, and its trace and parameters equal ``faults=None``."""
        fc = faults.FaultConfig(deadline_factor=1e6)
        assert fc.enabled and not fc.randomized
        s0 = _driver(engine, **clock).run()
        sf = _driver(engine, faults=fc, **clock).run()
        assert s0.history == sf.history
        assert all(r.status == "ok" for r in sf.history)
        _tree_equal(s0.client_params, sf.client_params)

    @pytest.mark.parametrize("n", [2, 4, 7, 16])
    def test_clock_without_faults_is_the_fault_free_clock(self, n):
        """With no realized fault, ``faulted_clock`` gives the units,
        times, upload and round time of ``latency.round_clock_plan``
        exactly (odd n leaves one client solo)."""
        fleet = latency.make_fleet(n=n, seed=n)
        partner = np.arange(n)
        for i in range(0, n - 1, 2):
            partner[i], partner[i + 1] = i + 1, i
        plan = planning.build_round_plan(fleet, CHAN, partner, W,
                                         workload=WORK)
        units, times, upload_s = latency.round_clock_plan(plan, fleet, CHAN,
                                                          WORK)
        c = faults.faulted_clock(plan, fleet, CHAN, WORK,
                                 faults.no_faults(n), faults.FaultConfig())
        assert c.completed
        assert (c.late, c.link_failed) == ((), ())
        assert c.units == units
        assert c.times == tuple(times)
        assert c.upload_s == upload_s
        assert c.round_s == float(np.max(times)) + upload_s

    def test_fail_prob_none_when_rates_zero(self):
        m = faults.FaultModel(faults.FaultConfig(deadline_factor=2.0), N)
        assert m.fail_prob() is None
        m = faults.FaultModel(faults.FaultConfig(dropout=0.2), N)
        p = m.fail_prob()
        assert p is not None
        np.testing.assert_allclose(p, 0.2)
        m = faults.FaultModel(faults.FaultConfig(dropout=0.2, outage=0.5,
                                                 retries=1), N)
        assert np.all(m.fail_prob() > 0.2)   # exhausted-outage term adds

    def test_realization_stateless_and_deterministic(self):
        m = faults.FaultModel(_fc(), N, seed=0)
        act = np.ones(N, bool)
        pairs = ((0, 1), (2, 3))
        assert m.realize(5, act, pairs) == m.realize(5, act, pairs)
        # different rounds draw independently
        rfs = [m.realize(k, act, pairs) for k in range(20)]
        assert any(r.any_fault for r in rfs)
        assert len({r.dropped for r in rfs}) > 1


class TestCrossEngine:
    def test_vmapped_vs_bucketed_fault_traces(self):
        s_v = _driver("vmapped", faults=_fc()).run()
        s_b = _driver("bucketed", faults=_fc()).run()
        for r_v, r_b in zip(s_v.history, s_b.history):
            assert r_v.status == r_b.status
            assert r_v.failed == r_b.failed
            assert r_v.retries == r_b.retries
            assert r_v.pairs == r_b.pairs
            assert r_v.sim_round_s == pytest.approx(r_b.sim_round_s)

    @pytest.mark.skipif(len(jax.devices()) < N,
                        reason=f"dist engine needs {N} devices")
    def test_dist_fault_trace(self):
        s_v = _driver("vmapped", rounds=1, faults=_fc()).run()
        s_d = _driver("dist", rounds=1, faults=_fc()).run()
        for r_v, r_d in zip(s_v.history, s_d.history):
            assert r_v.status == r_d.status
            assert r_v.failed == r_d.failed
            assert r_v.pairs == r_d.pairs


# ---------------------------------------------------------------------------
# checkpoint / resume
# ---------------------------------------------------------------------------

class TestCheckpointResume:
    def test_resume_reproduces_uninterrupted_history(self, tmp_path):
        path = os.fspath(tmp_path / "ck.msgpack")
        d1 = _driver(faults=_fc())
        st1 = d1.init_state()
        for _ in range(2):
            st1 = d1.run_round(st1)
        d1.save_state(st1, path)
        d2 = _driver(faults=_fc())
        st2 = d2.load_state(path)
        assert st2.round == 2
        st2 = d2.run_round(st2)
        full = _driver(faults=_fc()).run()
        assert st2.history == full.history
        _tree_equal(st2.client_params, full.client_params)

    def test_resume_faultfree_and_adaptive_plan(self, tmp_path):
        path = os.fspath(tmp_path / "ck.msgpack")
        kw = dict(pair_policy="greedy-cost", replan_threshold=0.5)
        d1 = _driver(**kw)
        st1 = d1.run(rounds=1)
        d1.save_state(st1, path)
        d2 = _driver(**kw)
        st2 = d2.load_state(path)
        assert st2.plan == st1.plan      # adaptive anchor survives
        st2 = d2.run_round(st2)
        full = _driver(**kw).run(rounds=2)
        assert st2.history == full.history

    def test_mismatched_config_rejected(self, tmp_path):
        path = os.fspath(tmp_path / "ck.msgpack")
        d1 = _driver()
        d1.save_state(d1.init_state(), path)
        with pytest.raises(ValueError, match="seed"):
            _driver(seed=1).load_state(path)
        with pytest.raises(ValueError, match="batches_per_round"):
            _driver(batches_per_round=3).load_state(path)

    def test_nan_record_roundtrip(self, tmp_path):
        """Skipped rounds carry mean_loss = nan; the record must survive
        the msgpack round-trip and still compare equal."""
        path = os.fspath(tmp_path / "ck.msgpack")
        d = _driver(faults=_fc(dropout=(0.95,) * N, deadline_factor=0.0,
                               outage=0.0, straggler=0.0))
        st1 = d.run(rounds=2)
        assert any(r.status == "skipped" for r in st1.history)
        d.save_state(st1, path)
        st2 = _driver(faults=_fc(dropout=(0.95,) * N, deadline_factor=0.0,
                                 outage=0.0, straggler=0.0)).load_state(
            path, fast_forward=False)
        assert st2.history == st1.history


# ---------------------------------------------------------------------------
# degradation ladder
# ---------------------------------------------------------------------------

class TestDegradation:
    def test_skipped_round_keeps_params(self):
        fc = _fc(dropout=(0.95,) * N, outage=0.0, straggler=0.0,
                 deadline_factor=0.0)
        d = _driver(faults=fc)
        st = d.init_state()
        g0 = d.global_params(st)
        st = d.run_round(st)
        rec = st.history[-1]
        assert rec.status == "skipped"
        assert np.isnan(rec.mean_loss)
        assert rec.failed == tuple(range(N))
        _tree_equal(g0, d.global_params(st))

    @pytest.mark.parametrize("mode,status", [("graceful", "skipped"),
                                             ("abort", "aborted")])
    def test_lost_round_hands_back_the_same_buffers(self, mode, status):
        """A lost round trains nothing, so its next replicas are the very
        buffers it was given: no broadcast, no aggregation program."""
        fc = _fc(dropout=(0.95,) * N, outage=0.0, straggler=0.0,
                 deadline_factor=0.0, mode=mode)
        d = _driver(faults=fc, donate=True)
        st = d.init_state()
        before = jax.tree_util.tree_leaves(st.client_params)
        telemetry.reset()
        telemetry.enable()
        try:
            st = d.run_round(st)
            names = {e.name for e in telemetry.events()}
        finally:
            telemetry.disable()
            telemetry.reset()
        assert st.history[-1].status == status
        after = jax.tree_util.tree_leaves(st.client_params)
        assert len(after) == len(before)
        assert all(a is b for a, b in zip(after, before))
        assert not any(a.is_deleted() for a in after)
        assert "broadcast" not in names and "agg_program" not in names
        assert {"round", "plan", "clock", "batch", "record"} <= names

    def test_abort_round_keeps_params_and_pays_clock(self):
        graceful = _driver(faults=_fc()).run()
        abort = _driver(faults=_fc(mode="abort")).run()
        saw_abort = False
        for rg, ra in zip(graceful.history, abort.history):
            assert rg.sim_round_s <= ra.sim_round_s + 1e-9
            if ra.status == "aborted":
                saw_abort = True
                assert np.isnan(ra.mean_loss)
        assert saw_abort

    def test_degraded_round_excludes_failed_from_record(self):
        st = _driver(faults=_fc(seed=7)).run()
        degraded = [r for r in st.history if r.status == "degraded"]
        assert degraded, "seed 7 should produce a degraded round"
        for r in degraded:
            assert r.failed
            assert np.isfinite(r.mean_loss)
            surviving = set(r.cohort) - set(r.failed)
            for i, j in r.pairs:
                assert {i, j} <= set(r.cohort)

    @pytest.mark.parametrize("orphan", faults.ORPHAN_POLICIES)
    def test_orphan_policies(self, orphan):
        partner = np.array([1, 0, 3, 2, 5, 4])
        active = np.ones(6, bool)
        rf = faults.RoundFaults(dropped=(1, 2), slowdown=(1.0,) * 6,
                                outages=(), failed_links=())
        p2, a2 = faults.degrade_partner(partner, active, rf, orphan)
        assert not a2[1] and not a2[2]
        assert p2[1] == 1 and p2[2] == 2
        # the involution survives degradation
        assert all(p2[p2[i]] == i for i in range(6))
        if orphan == "repair":
            assert p2[0] == 3 and p2[3] == 0     # orphans re-paired
        else:
            assert p2[0] == 0 and p2[3] == 3     # solo fallback
        assert p2[4] == 5 and p2[5] == 4         # untouched pair survives

    def test_faulted_clock_graceful_le_abort(self):
        plan = planning.build_round_plan(FLEET, CHAN,
                                         np.array([1, 0, 3, 2]), W,
                                         workload=WORK)
        rf = faults.RoundFaults(dropped=(), slowdown=(1.0, 8.0, 1.0, 1.0),
                                outages=((0, 1, 2),), failed_links=())
        g = faults.faulted_clock(plan, FLEET, CHAN, WORK, rf,
                                 _fc(mode="graceful"))
        a = faults.faulted_clock(plan, FLEET, CHAN, WORK, rf,
                                 _fc(mode="abort"))
        assert g.round_s <= a.round_s + 1e-9
        assert g.deadline_s == a.deadline_s

    def test_dead_link_fails_pair(self):
        plan = planning.build_round_plan(FLEET, CHAN,
                                         np.array([1, 0, 3, 2]), W,
                                         workload=WORK)
        rf = faults.RoundFaults(dropped=(), slowdown=(1.0,) * N,
                                outages=(), failed_links=((0, 1),))
        c = faults.faulted_clock(plan, FLEET, CHAN, WORK, rf, _fc())
        assert c.link_failed == (0, 1)
        assert c.completed
        assert rf.retry_total(_fc().retries) == _fc().retries + 1


# ---------------------------------------------------------------------------
# properties (hypothesis_compat)
# ---------------------------------------------------------------------------

class TestProperties:
    @settings(max_examples=15, deadline=None)
    @given(mask=st.lists(st.booleans(), min_size=N, max_size=N),
           seed=st.integers(min_value=0, max_value=10 ** 6))
    def test_aggregation_mask_ignores_excluded(self, mask, seed):
        """Aggregating with an active mask must not read excluded
        clients' params — the mechanism degraded rounds rely on."""
        if not any(mask):
            return
        rng = np.random.default_rng(seed)
        params = {"w": jnp.asarray(rng.normal(size=(N, 3, 2)))}
        active = np.asarray(mask, bool)
        w = jnp.asarray(rng.uniform(1.0, 2.0, size=N), jnp.float32)
        for mode in ("paper", "fedavg"):
            g1 = aggregation.aggregate(params, w, mode,
                                       active=jnp.asarray(active))
            poisoned = {"w": params["w"].at[~active].set(jnp.nan)}
            g2 = aggregation.aggregate(poisoned, w, mode,
                                       active=jnp.asarray(active))
            np.testing.assert_array_equal(np.asarray(g1["w"]),
                                          np.asarray(g2["w"]))

    def test_aggregate_empty_cohort_raises(self):
        params = {"w": jnp.ones((N, 2))}
        with pytest.raises(ValueError, match="empty cohort"):
            aggregation.aggregate(params, jnp.ones(N), "paper",
                                  active=jnp.zeros(N, bool))

    @settings(max_examples=15, deadline=None)
    @given(fi=st.floats(min_value=0.0, max_value=0.6),
           fj=st.floats(min_value=0.0, max_value=0.6))
    def test_reliability_pricing_monotone_and_cut_invariant(self, fi, fj):
        """The expected-attempts multiplier raises every cut's price by
        the same factor — cost monotone in fail, argmin cut unchanged."""
        rate = float(FLEET.rates(CHAN)[0, 1])
        f0, f1 = float(FLEET.cpu_hz[0]), float(FLEET.cpu_hz[1])
        cuts = np.arange(1, W)
        base = np.array([planning.pair_cost(f0, f1, rate, WORK, int(c),
                                            W - int(c), 0.25, 0.25)
                         for c in cuts])
        priced = np.array([planning.pair_cost(f0, f1, rate, WORK, int(c),
                                              W - int(c), 0.25, 0.25,
                                              fail_i=fi, fail_j=fj)
                           for c in cuts])
        assert np.all(priced >= base - 1e-12)
        assert int(np.argmin(priced)) == int(np.argmin(base))
        mult = 1.0 / ((1.0 - fi) * (1.0 - fj))
        np.testing.assert_allclose(priced, base * mult, rtol=1e-12)

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=10 ** 6),
           drop=st.floats(min_value=0.0, max_value=0.6),
           out=st.floats(min_value=0.0, max_value=0.6))
    def test_clock_graceful_le_abort_property(self, seed, drop, out):
        """With a finite deadline, graceful never pays more than abort on
        the SAME fault realization (the bench invariant, as a property)."""
        cfg_g = faults.FaultConfig(dropout=drop, outage=out,
                                   deadline_factor=1.5, seed=seed)
        model = faults.FaultModel(cfg_g, N, seed=seed)
        plan = planning.build_round_plan(FLEET, CHAN,
                                         np.array([1, 0, 3, 2]), W,
                                         workload=WORK)
        rf = model.realize(0, np.ones(N, bool), plan.pairs)
        g = faults.faulted_clock(plan, FLEET, CHAN, WORK, rf, cfg_g)
        a = faults.faulted_clock(
            plan, FLEET, CHAN, WORK, rf,
            dataclasses.replace(cfg_g, mode="abort"))
        assert g.round_s <= a.round_s + 1e-9


# ---------------------------------------------------------------------------
# guards
# ---------------------------------------------------------------------------

class TestGuards:
    def test_empty_cohort_round_is_defined_noop(self):
        d = _driver(participation=0.05)
        st = d.init_state()
        g0 = d.global_params(st)
        st = d.run_round(st)
        rec = st.history[-1]
        assert rec.status == "empty"
        assert rec.cohort == () and rec.pairs == ()
        assert np.isnan(rec.mean_loss)
        assert rec.sim_round_s == 0.0
        _tree_equal(g0, d.global_params(st))
        st = d.run_round(st)             # the loop keeps going
        assert st.history[-1].status == "empty"

    def test_non_finite_loss_error_names_round_and_clients(self):
        losses = [np.array([0.5, np.nan, 0.7, 0.9]),
                  np.array([0.4, 0.6, 0.8, np.inf])]
        active = np.array([True, True, False, True])
        with pytest.raises(rounds.NonFiniteLossError) as ei:
            rounds._mean_active_loss(losses, active, round_idx=7)
        assert ei.value.round == 7
        assert ei.value.clients == (1, 3)
        assert "round 7" in str(ei.value)
        assert "[1, 3]" in str(ei.value)
        # without round_idx (no guard requested) the mean still computes
        assert np.isnan(rounds._mean_active_loss(losses, active))

    def test_record_nan_aware_equality(self):
        r = rounds.RoundRecord(round=0, cohort=(0,), pairs=(),
                               lengths=(W,), mean_loss=float("nan"),
                               sim_round_s=1.0, sim_total_s=1.0,
                               cached_steps=1)
        assert r == dataclasses.replace(r)
        assert r != dataclasses.replace(r, mean_loss=1.0)
        assert r != dataclasses.replace(r, status="skipped")
