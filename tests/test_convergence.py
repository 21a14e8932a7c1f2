"""Aggregation-policy registry + convergence tier (DESIGN.md §13).

Three regression layers over ``core.aggregation``'s policy registry and
the ``bench_convergence`` driver matrix:

* convergence — at the benchmark's fixed operating point (2-class
  Non-IID, partial participation, fixed seed) the ``scaffold`` policy's
  top1@rounds is at least the ``mean`` policy's: the variance-reduction
  claim, pinned small-scale.
* bit-identity — a driver configured with ``agg_policy="mean"``
  reproduces the PRE-registry round loop (manual drift -> cohort ->
  pairing -> fed steps -> ``aggregation.aggregate`` -> broadcast)
  bit-exactly, and the bench helpers import and yield finite metrics.
* invariants (via ``repro.hypothesis_compat``) — fresh variates make a
  scaffold step bit-identical to mean at full participation; an
  excluded client's variate can never move ``c_global`` (its replica
  row may be arbitrary garbage); the variate state survives
  ``save_state``/``load_state`` exactly; 1-device ``FleetSharding``
  composes bit-identically.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import (aggregation, fedpair, latency, pairing,
                        participation, planning, rounds, splitting)
from repro.hypothesis_compat import given, settings, strategies as st

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))          # repo root -> import benchmarks

pytestmark = pytest.mark.convergence


def _tree_equal(a, b) -> bool:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        bool(jnp.array_equal(x, y, equal_nan=True))
        for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# convergence: scaffold >= mean on 2-class Non-IID at fixed rounds
# ---------------------------------------------------------------------------

class TestScaffoldConvergence:
    ROUNDS = 10

    @pytest.fixture(scope="class")
    def noniid_curves(self):
        from benchmarks import bench_convergence as bc
        from repro.data import SyntheticImages, two_class_partition
        imgs, labels = SyntheticImages(num_samples=2400, image_size=8,
                                       noise=0.6, seed=0).generate()
        test = {"images": jnp.asarray(imgs[:400]),
                "labels": jnp.asarray(labels[:400])}
        shards = two_class_partition(labels, bc.N_CLIENTS, seed=0)
        out = {}
        for pol in ("mean", "scaffold"):
            drv = bc.make_matrix_driver(pol, shards, imgs, labels,
                                        rounds_n=self.ROUNDS)
            out[pol] = bc.driver_curve(drv, self.ROUNDS, test)
        return out

    def test_scaffold_at_least_mean_top1_at_rounds(self, noniid_curves):
        """The §13 claim at the benchmark's fixed seed: scaffold's
        climb-window top1@rounds >= mean's on the Non-IID partition
        (margin at this seed is ~0.06 — the assertion is >=, not
        strict, so float-visit noise cannot flake it)."""
        from benchmarks import bench_convergence as bc
        m = bc.curve_metrics(noniid_curves["mean"])
        s = bc.curve_metrics(noniid_curves["scaffold"])
        assert s["window_mean"] >= m["window_mean"], (
            f"scaffold window_mean {s['window_mean']} fell below mean's "
            f"{m['window_mean']} at the fixed benchmark seed")

    def test_bench_helpers_produce_finite_metrics(self, noniid_curves):
        """Satellite (c): the bench helpers are importable and every
        metric they derive is finite and a sane accuracy."""
        from benchmarks import bench_convergence as bc
        for curve in noniid_curves.values():
            assert len(curve) == self.ROUNDS
            met = bc.curve_metrics(curve)
            for v in met.values():
                assert np.isfinite(v) and 0.0 <= v <= 1.0
            assert met["top1_at_rounds"] >= met["window_mean"] - 1e-9 \
                or met["top1_at_rounds"] == max(curve)


# ---------------------------------------------------------------------------
# bit-identity: registry "mean" == the pre-registry round loop
# ---------------------------------------------------------------------------

class TestMeanMatchesPreRegistryLoop:
    W = 4
    N = 4
    ROUNDS = 3
    BPR = 2
    FRAC = 0.75
    DRIFT = 2.0
    LR = 0.05

    def _manual_run(self, cfg, fleet0, workload, batch_fn, loss_fn, g0):
        """The PRE-PR fedpairing loop, replayed verbatim: one rng in the
        driver's §5 order (drift -> cohort -> pair seed), weight-policy
        pairing, paper-mode fed steps, direct ``aggregation.aggregate``
        + ``broadcast`` — no registry anywhere."""
        from repro.core.latency import ChannelModel
        chan = ChannelModel()
        rng = np.random.default_rng(0)
        fleet = fleet0
        split = splitting.split_plan(cfg, g0)
        step = fedpair.make_fed_step(
            loss_fn, split, cfg.num_layers,
            fedpair.FedPairingConfig(lr=self.LR / self.N,
                                     overlap_boost=True,
                                     aggregation="paper", donate=False))
        params = fedpair.replicate(g0, self.N)
        losses = []
        policy = pairing.get_pairing_policy("fedpairing")
        for _ in range(self.ROUNDS):
            fleet = latency.drift_fleet(fleet, rng, self.DRIFT)
            cohort = participation.sample_cohort(self.N, self.FRAC, rng)
            pair_seed = int(rng.integers(2 ** 31))
            active = np.zeros(self.N, bool)
            active[cohort] = True
            ctx = pairing.PairingContext(
                num_layers=cfg.num_layers, workload=workload,
                split_policy="paper", seed=pair_seed)
            partner, _ = participation.cohort_partner(fleet, chan, cohort,
                                                      policy, ctx=ctx)
            plan = planning.build_round_plan(
                fleet, chan, partner, cfg.num_layers, policy="paper",
                workload=workload, active=active)
            agg_w = fedpair.pair_weights(fleet.data_sizes,
                                         plan.partner_array())
            for _ in range(self.BPR):
                params, m = step(
                    params, batch_fn(),
                    jnp.asarray(plan.partner_array(), jnp.int32),
                    jnp.asarray(plan.lengths_array(), jnp.int32),
                    jnp.asarray(agg_w, jnp.float32))
                losses.append(np.asarray(m["loss"]))
            g = aggregation.aggregate(
                params, jnp.asarray(fleet.data_sizes, jnp.float32),
                "paper", active=jnp.asarray(active))
            params = aggregation.broadcast(g, self.N)
        return g, losses

    def test_driver_mean_bit_identical_to_manual_loop(self):
        cfg = get_smoke_config("tinyllama-1.1b").with_overrides(
            num_layers=self.W)
        fleet = latency.make_fleet(n=self.N, seed=0)
        rc = rounds.RoundConfig(rounds=self.ROUNDS,
                                batches_per_round=self.BPR,
                                participation=self.FRAC,
                                drift_sigma_m=self.DRIFT, lr=self.LR,
                                agg_policy="mean", donate=False, seed=0)
        driver = rounds.RoundDriver(
            cfg, rc, fleet,
            batch_fn=rounds.make_lm_batch_fn(cfg, self.N, seed=0))
        state = driver.run()
        g_driver = driver.global_params(state)

        manual_batches = rounds.make_lm_batch_fn(cfg, self.N, seed=0)
        workload = driver.workload
        g_manual, _ = self._manual_run(cfg, fleet, workload,
                                       manual_batches, driver.loss_fn,
                                       driver._gparams)
        assert _tree_equal(g_driver, g_manual), (
            "registry 'mean' driver diverged from the pre-registry loop")

    def test_scaffold_first_round_bit_identical_to_mean(self):
        """Fresh variates skip the correction entirely — round 1 of a
        scaffold run IS round 1 of a mean run, at the bit level."""
        cfg = get_smoke_config("tinyllama-1.1b").with_overrides(
            num_layers=self.W)
        fleet = latency.make_fleet(n=self.N, seed=0)
        outs = {}
        for pol in ("mean", "scaffold"):
            rc = rounds.RoundConfig(rounds=1, batches_per_round=self.BPR,
                                    participation=self.FRAC, lr=self.LR,
                                    agg_policy=pol, donate=False, seed=0)
            d = rounds.RoundDriver(
                cfg, rc, fleet,
                batch_fn=rounds.make_lm_batch_fn(cfg, self.N, seed=0))
            outs[pol] = d.global_params(d.run())
        assert _tree_equal(outs["mean"], outs["scaffold"])


# ---------------------------------------------------------------------------
# aggregation invariants (property suite)
# ---------------------------------------------------------------------------

def _random_stack(rng, n, extra_leaf=True):
    tree = {"w": jnp.asarray(rng.normal(size=(n, 3, 2)), jnp.float32)}
    if extra_leaf:
        tree["b"] = jnp.asarray(rng.normal(size=(n, 4)), jnp.float32)
    return tree


def _row0(tree):
    return jax.tree_util.tree_map(lambda a: a[0], tree)


def _pair_ctx(rng, tree, n, w_layers=6, lr=0.1, steps=3):
    """A complementary-cut pairing context over a random adjacent-swap
    matching (odd n leaves the last client solo)."""
    partner = np.arange(n)
    for i in range(0, n - 1, 2):
        partner[i], partner[i + 1] = i + 1, i
    lengths = np.where(partner == np.arange(n), w_layers,
                       rng.integers(1, w_layers, size=n))
    lengths = np.where((partner != np.arange(n))
                       & (np.arange(n) > partner),
                       w_layers - lengths[partner], lengths)
    return aggregation.AggContext(
        prev_global=_row0(tree), partner=partner,
        lengths=lengths.astype(np.float64), num_layers=w_layers,
        lr=lr, steps=steps)


class TestAggregationInvariants:

    @settings(max_examples=12, deadline=None)
    @given(seed=st.integers(0, 2 ** 16),
           n=st.sampled_from((2, 4, 5)),
           mode=st.sampled_from(("paper", "fedavg")),
           stale=st.booleans())
    def test_fresh_scaffold_bit_identical_to_mean(self, seed, n, mode,
                                                  stale):
        """Full participation + zero (fresh) variates: the scaffold step
        IS the mean step, bitwise — correction skipped, not rounded."""
        rng = np.random.default_rng(seed)
        tree = _random_stack(rng, n)
        agg_w = jnp.asarray(rng.uniform(0.5, 2.0, n), jnp.float32)
        staleness = (jnp.asarray(rng.integers(0, 3, n), jnp.int32)
                     if stale else None)
        pol = aggregation.ScaffoldAggregation()
        state = pol.init_state(_row0(tree), n)
        g_m = aggregation.aggregate(tree, agg_w, mode,
                                    staleness=staleness)
        replicas, new_state = pol.apply(tree, agg_w, mode,
                                        staleness=staleness, state=state,
                                        ctx=_pair_ctx(rng, tree, n))
        assert _tree_equal(_row0(replicas), g_m)
        assert new_state.applied     # correction arms for the NEXT round

    @settings(max_examples=12, deadline=None)
    @given(seed=st.integers(0, 2 ** 16), n=st.sampled_from((4, 6)),
           mode=st.sampled_from(("paper", "fedavg")),
           stale=st.booleans())
    def test_excluded_variate_never_moves_c_global(self, seed, n, mode,
                                                   stale):
        """Cohort-mask x staleness x zero-weight hard-mask composition:
        an excluded client's replica may be NaN garbage and its variate
        arbitrary — neither may touch the global step, ``c_global``, or
        any included client's new variate.  Checked by independence:
        rewriting the excluded rows with different garbage must change
        NOTHING downstream, bitwise."""
        rng = np.random.default_rng(seed)
        tree = _random_stack(rng, n)
        agg_w = jnp.asarray(rng.uniform(0.5, 2.0, n), jnp.float32)
        active = np.ones(n, bool)
        excluded = rng.choice(n, size=max(1, n // 3), replace=False)
        active[excluded] = False
        staleness = (jnp.asarray(rng.integers(0, 3, n), jnp.int32)
                     if stale else None)
        ctx = _pair_ctx(rng, tree, n)
        pol = aggregation.ScaffoldAggregation()

        def armed_state(poison):
            c_local = jax.tree_util.tree_map(
                lambda a: jnp.asarray(
                    rng_fixed.normal(size=a.shape), a.dtype), tree)
            mask = jnp.zeros(n, bool).at[jnp.asarray(excluded)].set(True)
            c_local = jax.tree_util.tree_map(
                lambda a: jnp.where(
                    mask.reshape((-1,) + (1,) * (a.ndim - 1)),
                    jnp.asarray(poison, a.dtype), a), c_local)
            return aggregation.ScaffoldState(
                c_global=jax.tree_util.tree_map(
                    lambda a: jnp.asarray(rng_fixed.normal(size=a.shape),
                                          a.dtype), _row0(tree)),
                c_local=c_local, applied=True)

        def poisoned_params(poison):
            mask = jnp.zeros(n, bool).at[jnp.asarray(excluded)].set(True)
            return jax.tree_util.tree_map(
                lambda a: jnp.where(
                    mask.reshape((-1,) + (1,) * (a.ndim - 1)),
                    jnp.asarray(poison, a.dtype), a), tree)

        outs = []
        for poison in (float("nan"), 1e30):
            rng_fixed = np.random.default_rng(seed + 1)   # same variates
            g, st2 = pol.apply(poisoned_params(poison), agg_w, mode,
                               active=jnp.asarray(active),
                               staleness=staleness,
                               state=armed_state(poison), ctx=ctx,
                               round_idx=0)
            for leaf in jax.tree_util.tree_leaves(g):
                assert bool(jnp.isfinite(leaf).all())
            for leaf in jax.tree_util.tree_leaves(st2.c_global):
                assert bool(jnp.isfinite(leaf).all())
            incl = np.flatnonzero(active)
            outs.append((_row0(g),
                         st2.c_global,
                         jax.tree_util.tree_map(lambda a: a[incl],
                                                st2.c_local)))
        (g1, cg1, cl1), (g2, cg2, cl2) = outs
        assert _tree_equal(g1, g2)
        assert _tree_equal(cg1, cg2)
        assert _tree_equal(cl1, cl2)

    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(0, 2 ** 16), n=st.sampled_from((3, 4)))
    def test_excluded_variate_rows_stay_put(self, seed, n):
        """An excluded client keeps its variate verbatim (it did not
        train; nothing to refresh)."""
        rng = np.random.default_rng(seed)
        tree = _random_stack(rng, n, extra_leaf=False)
        active = np.ones(n, bool)
        active[int(rng.integers(n))] = False
        pol = aggregation.ScaffoldAggregation()
        state = pol.init_state(_row0(tree), n)
        # arm with one full-participation step so variates are nonzero
        _, state = pol.apply(tree, jnp.ones(n), "paper", state=state,
                             ctx=_pair_ctx(rng, tree, n))
        tree2 = _random_stack(rng, n, extra_leaf=False)
        _, st2 = pol.apply(tree2, jnp.ones(n), "paper",
                           active=jnp.asarray(active), state=state,
                           ctx=_pair_ctx(rng, tree2, n))
        out = np.flatnonzero(~active)
        assert _tree_equal(
            jax.tree_util.tree_map(lambda a: a[out], st2.c_local),
            jax.tree_util.tree_map(lambda a: a[out], state.c_local))

    def test_variate_state_survives_checkpoint_roundtrip(self, tmp_path):
        """save_state/load_state round-trips the scaffold state EXACTLY
        (c_global, c_local, applied), and the resumed driver continues
        bit-identically to the uninterrupted one."""
        cfg = get_smoke_config("tinyllama-1.1b").with_overrides(
            num_layers=4)
        fleet = latency.make_fleet(n=4, seed=0)
        rc = rounds.RoundConfig(rounds=4, batches_per_round=2,
                                participation=0.5, agg_policy="scaffold",
                                donate=False, seed=0)
        d1 = rounds.RoundDriver(
            cfg, rc, fleet,
            batch_fn=rounds.make_lm_batch_fn(cfg, 4, seed=0))
        s = d1.init_state()
        for _ in range(2):
            s = d1.run_round(s)
        path = str(tmp_path / "scaffold.ckpt")
        d1.save_state(s, path)
        d2 = rounds.RoundDriver(
            cfg, rc, fleet,
            batch_fn=rounds.make_lm_batch_fn(cfg, 4, seed=0))
        s2 = d2.load_state(path)
        assert s2.agg.applied == s.agg.applied
        assert _tree_equal(s2.agg.c_global, s.agg.c_global)
        assert _tree_equal(s2.agg.c_local, s.agg.c_local)
        s, s2 = d1.run_round(s), d2.run_round(s2)
        assert s.history[-1] == s2.history[-1]
        assert _tree_equal(s.client_params, s2.client_params)
        assert _tree_equal(s.agg.c_global, s2.agg.c_global)

    def test_mean_driver_rejects_scaffold_checkpoint(self, tmp_path):
        cfg = get_smoke_config("tinyllama-1.1b").with_overrides(
            num_layers=4)
        fleet = latency.make_fleet(n=4, seed=0)
        rc = rounds.RoundConfig(rounds=2, batches_per_round=2,
                                agg_policy="scaffold", seed=0)
        d1 = rounds.RoundDriver(
            cfg, rc, fleet,
            batch_fn=rounds.make_lm_batch_fn(cfg, 4, seed=0))
        path = str(tmp_path / "scaffold.ckpt")
        d1.save_state(d1.run_round(d1.init_state()), path)
        d2 = rounds.RoundDriver(
            cfg, dataclasses.replace(rc, agg_policy="mean"), fleet,
            batch_fn=rounds.make_lm_batch_fn(cfg, 4, seed=0))
        with pytest.raises(ValueError, match="agg_policy"):
            d2.load_state(path)

    def test_one_device_sharding_composes_bit_identically(self):
        """FleetSharding on 1 device is a placement no-op (the §11
        contract) — including for the scaffold variate trees."""
        from repro.sharding.fleet import make_fleet_sharding
        cfg = get_smoke_config("tinyllama-1.1b").with_overrides(
            num_layers=4)
        fleet = latency.make_fleet(n=4, seed=0)
        outs = {}
        for shard in (None, make_fleet_sharding(1)):
            rc = rounds.RoundConfig(rounds=2, batches_per_round=2,
                                    participation=0.5,
                                    agg_policy="scaffold",
                                    donate=False, seed=0)
            d = rounds.RoundDriver(
                cfg, rc, fleet,
                batch_fn=rounds.make_lm_batch_fn(cfg, 4, seed=0),
                sharding=shard)
            s = d.run()
            outs[shard is None] = s
        assert _tree_equal(outs[True].client_params,
                           outs[False].client_params)
        assert _tree_equal(outs[True].agg.c_global,
                           outs[False].agg.c_global)
        assert _tree_equal(outs[True].agg.c_local,
                           outs[False].agg.c_local)

    def test_unknown_policy_raises_at_config_time(self):
        with pytest.raises(ValueError, match="aggregation policy"):
            rounds.RoundConfig(agg_policy="fedprox")

    def test_stateful_policy_rejected_on_relay_algorithms(self):
        for alg in ("sl", "splitfed"):
            with pytest.raises(ValueError, match="stateful aggregation"):
                rounds.RoundConfig(algorithm=alg, agg_policy="scaffold")
