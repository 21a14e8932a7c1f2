"""The round's aggregation as jitted programs: the weights are composed
on the host, the reduce and the broadcast each run as one program over
the whole tree, or together as one program written into the donated
replicas (``aggregate_into``), and the results equal the per-leaf eager
reduction they replace (kept below as the reference)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.core import aggregation, fedpair

N = 4


def _eager_weights(agg_w, mode, active, staleness):
    """The pre-jit weight composition: device arrays, device sum."""
    if staleness is not None and not bool(jnp.any(staleness)):
        staleness = None
    if mode == "paper":
        if active is None and staleness is None:
            return None
        w = (jnp.ones_like(jnp.asarray(staleness, jnp.float32))
             if active is None else jnp.asarray(active, jnp.float32))
    else:
        w = jnp.asarray(agg_w, jnp.float32)
        if active is not None:
            w = w * jnp.asarray(active, jnp.float32)
    if staleness is not None:
        w = w / (1.0 + jnp.asarray(staleness, jnp.float32))
    return w / jnp.sum(w)


def _eager_aggregate(tree, agg_w, mode, active, staleness):
    """The pre-jit reduction: per leaf, eager, a tensordot over clients."""
    w = _eager_weights(agg_w, mode, active, staleness)
    if w is None:
        return jax.tree_util.tree_map(lambda a: jnp.mean(a, axis=0), tree)

    def wmean(a):
        keep = (w > 0).reshape((-1,) + (1,) * (a.ndim - 1))
        masked = jnp.where(keep, a, jnp.zeros((), a.dtype))
        return jnp.tensordot(w.astype(a.dtype), masked, axes=(0, 0))

    return jax.tree_util.tree_map(wmean, tree)


def _tree(seed=0, n=N):
    rng = np.random.default_rng(seed)
    return {"embed": jnp.asarray(rng.normal(size=(n, 6, 8)), jnp.float32),
            "blocks": {"w": jnp.asarray(rng.normal(size=(n, 3, 8, 8)),
                                        jnp.float32),
                       "b": jnp.asarray(rng.normal(size=(n, 3, 8)),
                                        jnp.float32)},
            "norm": jnp.asarray(rng.normal(size=(n,)), jnp.float32)}


AGG_W = np.array([0.1, 0.4, 0.2, 0.3])
MASKS = {"none": None, "partial": np.array([True, False, True, True])}
STALENESS = {"none": None, "zero": np.zeros(N, np.int32),
             "positive": np.array([0, 2, 1, 3], np.int32)}


@pytest.mark.parametrize("stale", sorted(STALENESS))
@pytest.mark.parametrize("mask", sorted(MASKS))
@pytest.mark.parametrize("mode", ["paper", "fedavg"])
def test_reduce_matches_the_eager_reduction(mode, mask, stale):
    tree = _tree()
    active, staleness = MASKS[mask], STALENESS[stale]
    dev = (jnp.asarray(AGG_W), mode,
           None if active is None else jnp.asarray(active),
           None if staleness is None else jnp.asarray(staleness))
    got = aggregation.aggregate(tree, AGG_W, mode, active=active,
                                staleness=staleness)
    want = _eager_aggregate(tree, *dev)
    # the normalized weights are the eager ones, bit for bit
    w_new = aggregation.aggregation_weights(AGG_W, N, mode, active=active,
                                            staleness=staleness)
    w_old = _eager_weights(*dev)
    if w_old is None:                   # the plain-mean path: same bits
        np.testing.assert_array_equal(w_new, np.full(N, 1.0 / N, np.float32))
        for g, r in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(r))
        return
    assert w_new.dtype == np.float32
    np.testing.assert_array_equal(w_new, np.asarray(w_old))
    # the weighted sum: the eager dot and the explicit multiply-and-sum
    # order and round an N-term float32 sum differently, so each lies
    # within N·eps·Σ|w_i a_i| of the exact sum (a bf16 pass would miss
    # this by about 2^16)
    eps = np.finfo(np.float32).eps
    w64 = w_new.astype(np.float64)
    for g, r, a in zip(jax.tree_util.tree_leaves(got),
                       jax.tree_util.tree_leaves(want),
                       jax.tree_util.tree_leaves(tree)):
        assert g.dtype == r.dtype == jnp.float32
        terms = w64.reshape((-1,) + (1,) * (a.ndim - 1)) \
            * np.asarray(a, np.float64)
        exact, bound = terms.sum(0), N * eps * np.abs(terms).sum(0)
        assert (np.abs(np.asarray(g, np.float64) - exact) <= bound).all()
        assert (np.abs(np.asarray(r, np.float64) - exact) <= bound).all()


@pytest.mark.parametrize("how", ["mask", "staleness-and-mask"])
@pytest.mark.parametrize("mode", ["paper", "fedavg"])
def test_nan_in_an_excluded_replica_never_reaches_the_global_model(mode,
                                                                   how):
    tree = _tree(2)
    poisoned = jax.tree_util.tree_map(lambda a: a.at[1].set(jnp.nan), tree)
    active = MASKS["partial"]              # client 1 excluded
    staleness = STALENESS["positive"] if how != "mask" else None
    clean = aggregation.aggregate(tree, AGG_W, mode, active=active,
                                  staleness=staleness)
    got = aggregation.aggregate(poisoned, AGG_W, mode, active=active,
                                staleness=staleness)
    for g, c in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(clean)):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_array_equal(np.asarray(g), np.asarray(c))


def test_reduce_compiles_once_across_masks_modes_and_staleness():
    tree = _tree(3, n=3)        # a shape no other test reduces
    before = aggregation._masked_weighted_mean._cache_size()
    for mode, active, staleness in [
            ("fedavg", None, None),
            ("paper", np.array([True, True, False]), None),
            ("fedavg", np.array([False, True, True]), None),
            ("paper", None, np.array([1, 0, 2])),
            ("fedavg", np.array([True, False, True]), np.array([0, 3, 1]))]:
        aggregation.aggregate(tree, np.array([1.0, 2.0, 3.0]), mode,
                              active=active, staleness=staleness)
    assert aggregation._masked_weighted_mean._cache_size() == before + 1


@pytest.fixture
def on():
    telemetry.reset()
    telemetry.enable()
    try:
        yield
    finally:
        telemetry.disable()
        telemetry.reset()


def _counts(name):
    return sum(e.n for e in telemetry.events()
               if e.kind == "count" and e.name == name)


def test_empty_cohort_names_the_round_with_no_device_read(on):
    with jax.transfer_guard_device_to_host("disallow"):
        with pytest.raises(aggregation.EmptyCohortError,
                           match="round 5") as ei:
            aggregation.aggregate(_tree(), AGG_W, "fedavg",
                                  active=np.zeros(N, bool),
                                  staleness=STALENESS["positive"],
                                  round_idx=5)
    assert ei.value.round == 5
    assert _counts("host_sync") == 0
    assert _counts("agg_program") == 0


def test_host_weights_dispatch_one_program_with_no_device_read(on):
    tree = _tree(4)
    with jax.transfer_guard_device_to_host("disallow"):
        g = aggregation.aggregate(tree, AGG_W, "fedavg",
                                  active=MASKS["partial"],
                                  staleness=STALENESS["positive"])
        out = aggregation.broadcast(g, N)
    assert (_counts("host_sync"), _counts("agg_program")) == (0, 2)
    jax.block_until_ready(out)


def test_device_weights_still_work_for_one_counted_read(on):
    tree = _tree(5)
    got = aggregation.aggregate(tree, jnp.asarray(AGG_W), "fedavg",
                                active=jnp.asarray(MASKS["partial"]),
                                staleness=jnp.asarray(STALENESS["positive"]))
    want = aggregation.aggregate(tree, AGG_W, "fedavg",
                                 active=MASKS["partial"],
                                 staleness=STALENESS["positive"])
    assert _counts("host_sync") == 1
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def test_broadcast_matches_the_eager_replicas_and_their_placement():
    g = jax.tree_util.tree_map(lambda a: a[0], _tree(6))
    out = aggregation.broadcast(g, N)
    ref = fedpair.replicate(g, N)
    for o, r in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_array_equal(np.asarray(o), np.asarray(r))
        # what the engine step saw from the eager broadcast: the same
        # placement and committedness keep its dispatch fast path
        assert o.sharding == r.sharding
        assert o.committed == r.committed


def test_broadcast_into_spent_replicas_reuses_their_buffers():
    spent = _tree(7)
    g = aggregation.aggregate(spent, AGG_W, "fedavg")
    buffers = [a.unsafe_buffer_pointer()
               for a in jax.tree_util.tree_leaves(spent)]
    out = aggregation.broadcast(g, N, into=spent)
    assert [a.unsafe_buffer_pointer()
            for a in jax.tree_util.tree_leaves(out)] == buffers
    assert all(a.is_deleted() for a in jax.tree_util.tree_leaves(spent))
    ref = aggregation.broadcast(g, N)
    for o, r in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_array_equal(np.asarray(o), np.asarray(r))
        assert (o.sharding, o.committed) == (r.sharding, r.committed)


def _copy(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


@pytest.mark.parametrize("stale", sorted(STALENESS))
@pytest.mark.parametrize("mask", sorted(MASKS))
@pytest.mark.parametrize("mode", ["paper", "fedavg"])
def test_aggregate_into_is_the_broadcast_of_the_reduce(mode, mask, stale):
    tree = _tree(8)
    kw = dict(active=MASKS[mask], staleness=STALENESS[stale])
    want = aggregation.broadcast(
        aggregation.aggregate(_copy(tree), AGG_W, mode, **kw), N)
    got = aggregation.aggregate_into(tree, AGG_W, mode, **kw)
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))
        assert (g.sharding, g.committed) == (r.sharding, r.committed)


def test_aggregate_into_writes_into_the_donated_replicas(on):
    tree = _tree(9)
    buffers = [a.unsafe_buffer_pointer()
               for a in jax.tree_util.tree_leaves(tree)]
    with jax.transfer_guard_device_to_host("disallow"):
        out = aggregation.aggregate_into(tree, AGG_W, "fedavg",
                                         active=MASKS["partial"],
                                         staleness=STALENESS["positive"])
    # one program, no device read, nothing allocated for the model
    assert (_counts("host_sync"), _counts("agg_program")) == (0, 1)
    assert [a.unsafe_buffer_pointer()
            for a in jax.tree_util.tree_leaves(out)] == buffers
    assert all(a.is_deleted() for a in jax.tree_util.tree_leaves(tree))


def test_aggregate_into_refuses_an_empty_cohort_before_donating(on):
    tree = _tree(10)
    with pytest.raises(aggregation.EmptyCohortError, match="round 3"):
        aggregation.aggregate_into(tree, AGG_W, "fedavg",
                                   active=np.zeros(N, bool), round_idx=3)
    assert _counts("agg_program") == 0
    assert not any(a.is_deleted() for a in jax.tree_util.tree_leaves(tree))


@pytest.mark.parametrize("mode", ["paper", "fedavg"])
def test_nan_in_an_excluded_replica_never_reaches_the_replicas(mode):
    tree = _tree(11)
    poisoned = jax.tree_util.tree_map(lambda a: a.at[1].set(jnp.nan), tree)
    clean = aggregation.aggregate_into(tree, AGG_W, mode,
                                       active=MASKS["partial"])
    got = aggregation.aggregate_into(poisoned, AGG_W, mode,
                                     active=MASKS["partial"])
    for g, c in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(clean)):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_array_equal(np.asarray(g), np.asarray(c))


@pytest.mark.parametrize("spec", ["mean", "scaffold"])
def test_policy_apply_returns_its_global_model_broadcast(spec):
    """``apply`` returns ``broadcast(g, N)`` of the policy's own global
    model ``g``, written into the donated trained replicas: the plain
    ``aggregate`` for ``mean``; for ``scaffold`` (its correction armed by
    one earlier round) whatever row 0 holds, so every row must equal it."""
    pol = aggregation.get_aggregation_policy(spec)
    tree = _tree(12)
    g0 = jax.tree_util.tree_map(lambda a: a[0] * 0.5, tree)
    ctx = aggregation.AggContext(
        prev_global=g0, partner=np.array([1, 0, 3, 2]),
        lengths=np.array([1, 2, 1, 2]), num_layers=3, lr=0.1, steps=2)
    kw = dict(active=MASKS["partial"], ctx=ctx, round_idx=0)
    state = pol.init_state(g0, N)
    if pol.stateful:
        _, state = pol.apply(_copy(tree), AGG_W, "fedavg", state=state, **kw)
    mean = aggregation.aggregate(tree, AGG_W, "fedavg",
                                 active=MASKS["partial"])
    got, _ = pol.apply(tree, AGG_W, "fedavg", state=state, **kw)
    assert all(a.is_deleted() for a in jax.tree_util.tree_leaves(tree))
    g = mean if spec == "mean" else jax.tree_util.tree_map(
        lambda a: a[0], got)
    if pol.stateful:        # the armed correction moved it off the mean
        assert not all(np.array_equal(a, b) for a, b in zip(
            jax.tree_util.tree_leaves(g), jax.tree_util.tree_leaves(mean)))
    want = aggregation.broadcast(g, N)
    for o, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(o), np.asarray(r))


FOUR_DEVICE_CODE = r"""
import jax
import jax.numpy as jnp
import numpy as np
from repro.core import aggregation
from repro.sharding.fleet import make_fleet_sharding

assert jax.device_count() == 4
sh = make_fleet_sharding()
rng = np.random.default_rng(0)
g = {"w": jnp.asarray(rng.normal(size=(3, 5)), jnp.float32),
     "b": jnp.asarray(rng.normal(size=(5,)), jnp.float32)}
for n in (4, 8):
    out = aggregation.broadcast(g, n, sharding=sh)
    ref = sh.place(jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), g))
    for o, r in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(ref)):
        assert o.sharding == r.sharding, (o.sharding, r.sharding)
        assert o.sharding.spec == jax.sharding.PartitionSpec("data")
        assert o.committed == r.committed
        assert len(o.addressable_shards) == 4
        assert o.addressable_shards[0].data.shape[0] == n // 4
        np.testing.assert_array_equal(np.asarray(o), np.asarray(r))
    back = aggregation.aggregate(out, np.ones(n), "fedavg",
                                 active=np.arange(n) % 2 == 0)
    for b, a in zip(jax.tree_util.tree_leaves(back),
                    jax.tree_util.tree_leaves(g)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-6)
    # written into the spent sharded replicas: same shards, same buffers
    buffers = [s.data.unsafe_buffer_pointer()
               for a in jax.tree_util.tree_leaves(out)
               for s in a.addressable_shards]
    again = aggregation.broadcast(back, n, sharding=sh, into=out)
    assert [s.data.unsafe_buffer_pointer()
            for a in jax.tree_util.tree_leaves(again)
            for s in a.addressable_shards] == buffers
    for a, r in zip(jax.tree_util.tree_leaves(again),
                    jax.tree_util.tree_leaves(ref)):
        assert a.sharding == r.sharding
    # the reduce and the broadcast in one program: the same shards and
    # buffers, every row the masked mean
    mean = aggregation.aggregate(again, np.ones(n), "fedavg",
                                 active=np.arange(n) % 2 == 0)
    into = aggregation.aggregate_into(again, np.ones(n), "fedavg",
                                      active=np.arange(n) % 2 == 0,
                                      sharding=sh)
    assert [s.data.unsafe_buffer_pointer()
            for a in jax.tree_util.tree_leaves(into)
            for s in a.addressable_shards] == buffers
    for a, r, m in zip(jax.tree_util.tree_leaves(into),
                       jax.tree_util.tree_leaves(ref),
                       jax.tree_util.tree_leaves(mean)):
        assert a.sharding == r.sharding
        np.testing.assert_array_equal(
            np.asarray(a), np.broadcast_to(np.asarray(m)[None], a.shape))
print("ok")
"""


def test_broadcast_places_dim0_over_the_fleet_axis_as_place_did():
    # four fabricated devices in a child interpreter: dim 0 split 4 ways
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                       "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", FOUR_DEVICE_CODE], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-3000:]
    assert res.stdout.strip().endswith("ok")
