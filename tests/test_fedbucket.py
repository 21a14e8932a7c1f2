"""Length-bucketed split execution — plan invariants and bucketed-vs-dense
equivalence (identical losses/params across IID and heterogeneous length
assignments, odd-N self-pairs, overlap_boost on/off, granularities)."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import fedbucket, fedpair, splitting
from repro.models import registry

W = 4
CFG = get_smoke_config("tinyllama-1.1b").with_overrides(num_layers=W)


@functools.lru_cache(maxsize=None)
def _gparams():
    return registry.init_params(CFG, jax.random.key(0))


def _setup(n, seed=0):
    cp = fedpair.replicate(_gparams(), n)
    key = jax.random.key(seed + 1)
    batch = {"tokens": jax.random.randint(key, (n, 2, 16), 0,
                                          CFG.vocab_size)}
    batch["labels"] = batch["tokens"]
    return cp, batch


def _tree_allclose(a, b, rtol=2e-5, atol=2e-6):
    for (path, x), (_, y) in zip(jax.tree_util.tree_flatten_with_path(a)[0],
                                 jax.tree_util.tree_flatten_with_path(b)[0]):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=rtol,
                                   atol=atol, err_msg=str(path))


# ---------------------------------------------------------------------------
# planning
# ---------------------------------------------------------------------------

class TestBucketPlan:
    def test_exact_plan_matches_protocol(self):
        plan = fedbucket.plan_buckets([1, 3, 2, 2], [1, 0, 3, 2], W)
        assert plan.scanned_blocks == plan.protocol_blocks
        # every client appears exactly once per phase
        for phase in (plan.bottom, plan.top):
            seen = sorted(c for g in phase for c in g.clients)
            assert seen == [0, 1, 2, 3]

    def test_granularity_rounds_up_bottom_down_top(self):
        plan = fedbucket.plan_buckets([1, 3], [1, 0], W, granularity=2)
        assert {g.hi for g in plan.bottom} == {2, 4}
        assert {g.lo for g in plan.top} == {0, 2}
        assert plan.scanned_blocks >= plan.protocol_blocks

    def test_full_granularity_degenerates_to_dense(self):
        plan = fedbucket.plan_buckets([1, 3], [1, 0], W, granularity=W)
        assert plan.scanned_blocks == plan.dense_blocks

    def test_self_pair_gets_empty_top_range(self):
        plan = fedbucket.plan_buckets([2, 2, W], [1, 0, 2], W)
        tops = {c: g for g in plan.top for c in g.clients}
        assert tops[2].n_layers == 0
        assert plan.protocol_blocks == 2 + 2 + W + (W - 2) + (W - 2) + 0

    def test_compile_bound_is_shape_count_not_fleet_size(self):
        n = 32
        partner = np.array([i ^ 1 for i in range(n)])
        lengths = np.array([1 if i % 2 == 0 else W - 1 for i in range(n)])
        plan = fedbucket.plan_buckets(lengths, partner, W)
        assert plan.num_compiled_shapes <= 4

    def test_fleet_phase_ranges_envelope(self):
        hi, lo = fedbucket.fleet_phase_ranges([1, 3, 2, 2], [1, 0, 3, 2], W)
        assert (hi, lo) == (3, 1)
        hi, lo = fedbucket.fleet_phase_ranges([2, 2], [1, 0], W)
        assert (hi, lo) == (2, 2)      # homogeneous -> static half split

    def test_rejects_out_of_range_lengths(self):
        with pytest.raises(ValueError):
            fedbucket.plan_buckets([0, 4], [1, 0], W)


# ---------------------------------------------------------------------------
# bucketed == dense-masked execution
# ---------------------------------------------------------------------------

CASES = [
    # (name, partner, lengths)
    ("iid", [1, 0, 3, 2], [2, 2, 2, 2]),
    ("heterogeneous", [1, 0, 3, 2], [1, 3, 3, 1]),
    ("odd_n_self_pair", [1, 0, 2], [2, 2, W]),
]


@pytest.mark.parametrize("boost", [True, False])
@pytest.mark.parametrize("name,partner,lengths", CASES)
def test_bucketed_matches_dense(name, partner, lengths, boost):
    n = len(partner)
    cp, batch = _setup(n)
    agg_w = fedpair.pair_weights(np.arange(1.0, n + 1), np.asarray(partner))
    step_d, _ = fedbucket.make_bucketed_fed_step(
        CFG, partner, lengths, agg_w,
        fedbucket.FedBucketConfig(dense=True, overlap_boost=boost,
                                  donate=False))
    step_b, plan = fedbucket.make_bucketed_fed_step(
        CFG, partner, lengths, agg_w,
        fedbucket.FedBucketConfig(overlap_boost=boost, donate=False))
    new_d, m_d = step_d(cp, batch)
    new_b, m_b = step_b(cp, batch)
    np.testing.assert_allclose(np.asarray(m_d["loss"]),
                               np.asarray(m_b["loss"]), rtol=1e-5, atol=1e-6)
    _tree_allclose(new_d, new_b)
    assert plan.scanned_blocks <= plan.dense_blocks


@pytest.mark.parametrize("gran", [2, 3, W])
def test_granularity_rounding_preserves_semantics(gran):
    partner, lengths = [1, 0, 3, 2], [1, 3, 3, 1]
    cp, batch = _setup(4)
    agg_w = fedpair.pair_weights(np.ones(4), np.asarray(partner))
    step_d, _ = fedbucket.make_bucketed_fed_step(
        CFG, partner, lengths, agg_w,
        fedbucket.FedBucketConfig(dense=True, donate=False))
    step_b, _ = fedbucket.make_bucketed_fed_step(
        CFG, partner, lengths, agg_w,
        fedbucket.FedBucketConfig(bucket_granularity=gran, donate=False))
    new_d, _ = step_d(cp, batch)
    new_b, _ = step_b(cp, batch)
    _tree_allclose(new_d, new_b)


@pytest.mark.parametrize("aggregation", ["paper", "fedavg"])
def test_bucketed_matches_vmapped_mix_core(aggregation):
    """Cross-engine: bucketed == the functional parameter-mix core (up to
    the dist-style 1/N loss normalization), in both aggregation modes."""
    n = 4
    partner, lengths = np.array([1, 0, 3, 2]), np.array([1, 3, 2, 2])
    cp, batch = _setup(n)
    agg_w = fedpair.pair_weights(np.arange(1.0, n + 1), partner)
    step_b, _ = fedbucket.make_bucketed_fed_step(
        CFG, partner, lengths, agg_w,
        fedbucket.FedBucketConfig(lr=0.1, aggregation=aggregation,
                                  donate=False))
    new_b, _ = step_b(cp, batch)

    plan = splitting.split_plan(CFG, _gparams())
    step_v = fedpair.make_fed_step(
        lambda p, b: registry.loss_fn(p, b, CFG)[0], plan, W,
        fedpair.FedPairingConfig(lr=0.1 / n, aggregation=aggregation,
                                 donate=False))
    new_v, _ = step_v(cp, batch, jnp.asarray(partner), jnp.asarray(lengths),
                      jnp.asarray(agg_w))
    _tree_allclose(new_v, new_b, rtol=5e-4, atol=5e-5)


@pytest.mark.parametrize("aggregation", ["paper", "fedavg"])
def test_bucketed_matches_vmapped_mix_core_with_tied_embeddings(aggregation):
    """With a tied head the flow's head runs on the partner's embedding
    matrix in both engines (``fedpair.make_fed_step(tied_head=True)``)."""
    cfg = get_smoke_config("qwen1.5-0.5b").with_overrides(num_layers=W)
    assert cfg.tie_embeddings
    n = 4
    partner, lengths = np.array([1, 0, 3, 2]), np.array([1, 3, 2, 2])
    g = registry.init_params(cfg, jax.random.key(0))
    cp = fedpair.replicate(g, n)
    tokens = jax.random.randint(jax.random.key(1), (n, 2, 16), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    agg_w = fedpair.pair_weights(np.arange(1.0, n + 1), partner)
    step_b, _ = fedbucket.make_bucketed_fed_step(
        cfg, partner, lengths, agg_w,
        fedbucket.FedBucketConfig(lr=0.1, aggregation=aggregation,
                                  donate=False))
    new_b, _ = step_b(cp, batch)
    step_v = fedpair.make_fed_step(
        lambda p, b: registry.loss_fn(p, b, cfg)[0],
        splitting.split_plan(cfg, g), W,
        fedpair.FedPairingConfig(lr=0.1 / n, aggregation=aggregation,
                                 donate=False), tied_head=True)
    new_v, _ = step_v(cp, batch, jnp.asarray(partner), jnp.asarray(lengths),
                      jnp.asarray(agg_w))
    _tree_allclose(new_v, new_b, rtol=5e-4, atol=5e-5)


def test_step_donates_client_params():
    cp, batch = _setup(2)
    step, _ = fedbucket.make_bucketed_fed_step(
        CFG, [1, 0], [2, 2], np.array([0.5, 0.5]),
        fedbucket.FedBucketConfig())
    new, _ = step(cp, batch)
    leaf = jax.tree_util.tree_leaves(cp)[0]
    with pytest.raises(RuntimeError):
        _ = np.asarray(leaf)


def test_dist_core_rejects_uncovering_split_ranges():
    """An SPMD envelope that skips some client's owned blocks must refuse
    to build rather than silently truncate the protocol."""
    from repro.core import fedpair_dist
    lengths = np.array([3, 1])
    masks = np.stack([np.arange(W) < l for l in lengths]).astype(np.float32)
    dcfg = fedpair_dist.FedDistConfig(split_ranges=(2, 2))   # max L_i = 3
    with pytest.raises(ValueError, match="do not cover"):
        fedpair_dist.make_dist_fed_step(CFG, None, [(0, 1), (1, 0)],
                                        np.array([0.5, 0.5]), masks, dcfg)


# ---------------------------------------------------------------------------
# chunked CE divisor selection
# ---------------------------------------------------------------------------

class TestCeChunk:
    def test_picks_largest_divisor_leq_chunk(self):
        assert fedbucket.ce_chunk_size(64, 48) == 32
        assert fedbucket.ce_chunk_size(64, 16) == 16
        assert fedbucket.ce_chunk_size(8, 64) == 8

    def test_rejects_degenerate_divisor(self):
        with pytest.raises(ValueError):        # prime S -> best divisor 1
            fedbucket.ce_chunk_size(61, 16)

    def test_chunked_matches_unchunked_loss(self):
        cp, batch = _setup(2)
        agg_w = np.array([0.5, 0.5], np.float32)
        kw = dict(donate=False)
        s0, _ = fedbucket.make_bucketed_fed_step(
            CFG, [1, 0], [2, 2], agg_w, fedbucket.FedBucketConfig(**kw))
        s1, _ = fedbucket.make_bucketed_fed_step(
            CFG, [1, 0], [2, 2], agg_w,
            fedbucket.FedBucketConfig(ce_chunk=8, **kw))
        _, m0 = s0(cp, batch)
        _, m1 = s1(cp, batch)
        np.testing.assert_allclose(np.asarray(m0["loss"]),
                                   np.asarray(m1["loss"]), rtol=1e-5,
                                   atol=1e-6)
