"""FedPairing step semantics — vs a hand-written per-client reference,
degenerate cases, overlap boost, and round-level convergence."""
import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import aggregation, fedpair, splitting
from repro.models import registry, vision

CFG = vision.VisionConfig(num_layers=4, width=16, image_size=4, num_classes=3)
LOSS = functools.partial(vision.vision_loss, cfg=CFG)


def _loss(p, b):
    return LOSS(p, b)


def _clients(n, seed=0):
    key = jax.random.key(seed)
    g = vision.vision_init(CFG, key)
    return g, fedpair.replicate(g, n)


def _batches(n, bs=8, seed=0):
    rng = np.random.default_rng(seed)
    imgs = rng.normal(size=(n, bs, 4, 4, 3)).astype(np.float32)
    labels = rng.integers(0, 3, size=(n, bs))
    return {"images": jnp.asarray(imgs), "labels": jnp.asarray(labels)}


def _reference_step(gparams, cparams, batches, partner, lengths, agg_w,
                    lr, boost):
    """Slow per-client loop implementing Eq. (1)/(2)/(7) directly."""
    plan = splitting.split_plan(CFG, gparams)
    n = len(partner)
    W = CFG.num_layers
    per_client_g_own, per_client_g_out = [], []
    for i in range(n):
        mask = splitting.layer_mask(jnp.asarray(int(lengths[i])), W)
        own = jax.tree_util.tree_map(lambda a: a[i], cparams)
        part = jax.tree_util.tree_map(lambda a: a[partner[i]], cparams)
        mix = splitting.mix_params(own, part, plan, mask)
        batch = {k: v[i] for k, v in batches.items()}
        g = jax.grad(_loss)(mix, batch)
        go, gp = splitting.route_gradients(g, plan, mask)
        per_client_g_own.append(go)
        per_client_g_out.append(gp)

    new = []
    for i in range(n):
        j = int(partner[i])
        mask_i = splitting.layer_mask(jnp.asarray(int(lengths[i])), W)
        mask_j = splitting.layer_mask(jnp.asarray(int(lengths[j])), W)
        factor = splitting.overlap_factor(mask_i, mask_j, boost)

        def upd(p, go, gi, label, factor=factor, i=i, j=j):
            u = agg_w[i] * go + agg_w[j] * gi
            if label == "stack":
                u = u * factor.reshape((-1,) + (1,) * (u.ndim - 1))
            return p - lr * u

        own = jax.tree_util.tree_map(lambda a: a[i], cparams)
        new.append(jax.tree_util.tree_map(
            upd, own, per_client_g_own[i], per_client_g_out[j], plan))
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *new)


@pytest.mark.parametrize("boost", [True, False])
@pytest.mark.parametrize("lengths", [[1, 3], [2, 2], [3, 1]])
def test_step_matches_reference(lengths, boost):
    g, cp = _clients(2)
    partner = np.array([1, 0])
    agg_w = np.array([0.3, 0.7], np.float32)
    batches = _batches(2)
    plan = splitting.split_plan(CFG, g)
    fcfg = fedpair.FedPairingConfig(lr=0.1, overlap_boost=boost)
    step = fedpair.make_fed_step(_loss, plan, CFG.num_layers, fcfg)
    # reference first: the jitted step donates (consumes) cp's buffers
    want = _reference_step(g, cp, batches, partner, np.asarray(lengths),
                           agg_w, 0.1, boost)
    got, _ = step(cp, batches, jnp.asarray(partner), jnp.asarray(lengths),
                  jnp.asarray(agg_w))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


def _gather_step(loss_fn, plan, num_layers, lr):
    """The vmapped step of a tied-head model with every partner read a
    traced-index gather ``x[partner]`` and the tied head selected in the
    parameter dtype: the form the static row slices replaced, kept as
    their reference."""

    def bmask(masks, a):
        return masks.astype(a.dtype).reshape(masks.shape + (1,) * (a.ndim - 2))

    @jax.jit
    def step(cp, batches, partner, lengths, agg_w):
        n = partner.shape[0]
        masks = jax.vmap(splitting.layer_mask, in_axes=(0, None))(
            lengths, num_layers)
        masks_p = masks[partner]

        def mix(a, label):
            if label == "bottom":
                return a
            if label == "top":
                return a[partner]
            m = bmask(masks, a)
            return a * m + a[partner] * (1.0 - m)

        mixed = dict(jax.tree_util.tree_map(mix, cp, plan),
                     tied_head=cp["embed"][partner])
        losses, g = jax.vmap(jax.value_and_grad(loss_fn))(mixed, batches)
        g = dict(g)
        g_head = g.pop("tied_head")
        a_in = agg_w[partner]
        factor = jax.vmap(splitting.overlap_factor, in_axes=(0, 0, None))(
            masks, masks_p, True)

        def apply(p, gl, label):
            b = (n,) + (1,) * (gl.ndim - 1)
            if label == "bottom":
                u = agg_w.reshape(b) * gl
            elif label == "top":
                u = a_in.reshape(b) * gl[partner]
            else:
                u = (agg_w.reshape(b) * (gl * bmask(masks, gl))
                     + a_in.reshape(b) * (gl[partner]
                                          * (1.0 - bmask(masks_p, gl))))
                u = u * bmask(factor, gl).astype(u.dtype)
            return p - lr * u

        new = jax.tree_util.tree_map(apply, cp, g, plan)
        b = (n,) + (1,) * (g_head.ndim - 1)
        new["embed"] = new["embed"] - lr * (a_in.reshape(b) * g_head[partner])
        return new, losses

    return step


def _unequal_to_gather_step(partner, lengths, dtype):
    """Run the step and its gather reference on one tied-head LM; return
    the names of the outputs that differ in any bit or in dtype."""
    w = 4
    cfg = get_smoke_config("qwen1.5-0.5b").with_overrides(
        num_layers=w, dtype=dtype)
    assert cfg.tie_embeddings
    n = len(partner)
    g = registry.init_params(cfg, jax.random.key(0))
    cp = fedpair.replicate(g, n)
    tokens = jax.random.randint(jax.random.key(1), (n, 2, 16), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    agg_w = jnp.asarray(fedpair.pair_weights(np.arange(1.0, n + 1),
                                             np.asarray(partner)))
    args = (batch, jnp.asarray(partner, jnp.int32),
            jnp.asarray(lengths, jnp.int32), agg_w)
    loss = lambda p, b: registry.loss_fn(p, b, cfg)[0]
    plan = splitting.split_plan(cfg, g)
    want, want_loss = _gather_step(loss, plan, w, 0.1)(cp, *args)
    got, got_m = fedpair.make_fed_step(
        loss, plan, w, fedpair.FedPairingConfig(lr=0.1, donate=False),
        tied_head_dtype=cfg.dtype)(cp, *args)
    pairs = [("loss", got_m["loss"], want_loss)] + [
        (jax.tree_util.keystr(path), a, b) for (path, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(got)[0],
            jax.tree_util.tree_flatten_with_path(want)[0])]
    return [name for name, a, b in pairs
            if a.dtype != b.dtype
            or not np.array_equal(np.asarray(a), np.asarray(b))]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("partner, lengths", [
    ([1, 0], [3, 2]), ([1, 0], [1, 3]),                    # one pair
    ([1, 0, 2], [3, 2, 4]), ([1, 0, 2], [1, 3, 4]),        # + a self-pair
    ([1, 0, 3, 2], [3, 2, 2, 3]), ([1, 0, 3, 2], [1, 3, 3, 1]),
], ids=["n2-overlap", "n2-uneven", "n3-overlap", "n3-uneven",
        "n4-overlap", "n4-uneven"])
def test_static_row_slices_equal_the_gather_step(partner, lengths, dtype):
    """The partner's rows read as static slices, and the tied head
    selected after its cast to a bfloat16 compute dtype, give the same
    bits as the gather form: the rows are copied, not recomputed.

    XLA's CPU backend contracts a multiply and an add into one FMA as its
    fusions happen to fall, and the two forms fuse differently, so the
    comparison runs in a child interpreter whose instruction set stops at
    AVX (no FMA): there both compile to the rounding their operations
    state."""
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                       "src")
    flags = os.environ.get("XLA_FLAGS", "") + " --xla_cpu_max_isa=AVX"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src,
               XLA_FLAGS=flags.strip())
    res = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         json.dumps([partner, lengths, dtype])],
        env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-3000:]
    assert json.loads(res.stdout.splitlines()[-1]) == []


def _partner_rows_form(n, devices):
    """Lower ``fedpair._partner_rows`` for N clients whose axis is split
    over ``devices``; return the form it took (a gather, or the one-row
    slices) and whether its rows equal ``x[partner]``."""
    from jax.sharding import NamedSharding, PartitionSpec
    assert jax.device_count() >= devices
    x = jnp.arange(n * 6, dtype=jnp.float32).reshape(n, 2, 3)
    if devices > 1:
        mesh = jax.make_mesh((devices,), ("data",), axis_types=(
            jax.sharding.AxisType.Auto,))
        x = jax.device_put(x, NamedSharding(mesh, PartitionSpec("data")))
    partner = jnp.asarray(np.arange(n)[::-1].copy(), jnp.int32)
    rows = jax.jit(fedpair._partner_rows)
    text = rows.lower(x, partner).as_text()
    form = "gather" if "gather" in text else "slices"
    equal = np.array_equal(np.asarray(rows(x, partner)),
                           np.asarray(x)[np.asarray(partner)])
    return [form, bool(equal)]


@pytest.mark.parametrize("n, devices, form", [
    (fedpair.SLICE_MAX_CLIENTS, 1, "slices"),
    (fedpair.SLICE_MAX_CLIENTS + 1, 1, "gather"),
    (4, 2, "gather"),
], ids=["at-the-bound", "above-the-bound", "client-axis-on-a-mesh"])
def test_partner_rows_form_follows_client_count_and_mesh(n, devices, form):
    """Up to ``SLICE_MAX_CLIENTS`` clients on one device the partner's rows
    are one-row slices; above it, or with the client axis split over a
    mesh, the gather is kept.  Both give ``x[partner]``.  The mesh case
    runs in a child interpreter with fabricated CPU devices."""
    if devices == 1:
        assert _partner_rows_form(n, devices) == [form, True]
        return
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                       "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    res = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         json.dumps(["rows", n, devices])],
        env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-3000:]
    assert json.loads(res.stdout.splitlines()[-1]) == [form, True]


def test_self_paired_client_is_local_sgd():
    """partner == self must reduce to plain (a_i-weighted) SGD."""
    g, cp = _clients(1)
    partner = jnp.asarray([0])
    lengths = jnp.asarray([CFG.num_layers])
    agg_w = jnp.asarray([1.0])
    batches = _batches(1)
    plan = splitting.split_plan(CFG, g)
    step = fedpair.make_fed_step(_loss, plan, CFG.num_layers,
                                 fedpair.FedPairingConfig(lr=0.1))
    got, _ = step(cp, batches, partner, lengths, agg_w)

    batch0 = {k: v[0] for k, v in batches.items()}
    grads = jax.grad(_loss)(g, batch0)
    want = jax.tree_util.tree_map(lambda p, gr: p - 0.1 * gr, g, grads)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a)[0], np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


def test_overlap_boost_changes_only_overlapping_layers():
    g, cp = _clients(2, seed=3)
    _, cp2 = _clients(2, seed=3)    # each step donates its input replicas
    partner = jnp.asarray([1, 0])
    lengths = jnp.asarray([3, 1])   # overlap on client 0 layers [1, 3)
    agg_w = jnp.asarray([0.5, 0.5])
    batches = _batches(2, seed=3)
    plan = splitting.split_plan(CFG, g)
    p_on, _ = fedpair.make_fed_step(
        _loss, plan, CFG.num_layers,
        fedpair.FedPairingConfig(lr=0.1, overlap_boost=True))(
        cp, batches, partner, lengths, agg_w)
    p_off, _ = fedpair.make_fed_step(
        _loss, plan, CFG.num_layers,
        fedpair.FedPairingConfig(lr=0.1, overlap_boost=False))(
        cp2, batches, partner, lengths, agg_w)
    dw = np.asarray(p_on["blocks"]["w1"] - p_off["blocks"]["w1"])  # (2,W,...)
    per_layer = np.abs(dw).sum(axis=(2, 3))
    # client 0: layers 1,2 overlapping -> differ; 0,3 identical
    assert per_layer[0, 0] == 0 and per_layer[0, 3] == 0
    assert per_layer[0, 1] > 0 and per_layer[0, 2] > 0
    # client 1 (L=1, partner L=3): no overlap
    assert np.all(per_layer[1] == 0)
    # embed/head are not stack-labeled -> unchanged by the boost
    assert np.all(np.asarray(p_on["embed"]) == np.asarray(p_off["embed"]))


def test_round_training_reduces_loss_and_aggregates():
    n = 4
    g, cp = _clients(n, seed=1)
    partner = np.array([1, 0, 3, 2])
    lengths = np.array([2, 2, 1, 3])
    agg_w = np.full(n, 1.0 / n, np.float32)
    plan = splitting.split_plan(CFG, g)
    step = fedpair.make_fed_step(_loss, plan, CFG.num_layers,
                                 fedpair.FedPairingConfig(lr=0.1))

    rng = np.random.default_rng(0)

    def it():
        while True:
            imgs = rng.normal(size=(n, 16, 4, 4, 3)).astype(np.float32)
            labels = rng.integers(0, 3, size=(n, 16))
            imgs += labels[..., None, None, None] * 0.5
            yield {"images": jnp.asarray(imgs), "labels": jnp.asarray(labels)}

    gen = it()
    losses = []
    for _ in range(3):
        cp, l = fedpair.run_round(step, cp, gen, partner, lengths, agg_w, 8)
        losses.append(float(l.mean()))
        gl = aggregation.aggregate(cp, jnp.asarray(agg_w), "paper")
        cp = aggregation.broadcast(gl, n)
    assert losses[-1] < losses[0]
    # after broadcast every client replica is identical
    for leaf in jax.tree_util.tree_leaves(cp):
        np.testing.assert_allclose(np.asarray(leaf[0]), np.asarray(leaf[-1]))


if __name__ == "__main__":
    # the child of test_static_row_slices_equal_the_gather_step, or of
    # test_partner_rows_form_follows_client_count_and_mesh on a mesh
    args = json.loads(sys.argv[1])
    if args[0] == "rows":
        print(json.dumps(_partner_rows_form(*args[1:])))
    else:
        print(json.dumps(_unequal_to_gather_step(*args)))
