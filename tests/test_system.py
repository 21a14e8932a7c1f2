"""End-to-end system behaviour: the four training algorithms (FedPairing,
vanilla FL, vanilla SL, SplitFed) run on the same federated image task;
FedPairing + dist-engine equivalence; full-pipeline integration."""
import functools
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.core import (aggregation, baselines, fedpair, latency, pairing,
                        splitting)
from repro.data import FederatedBatcher, SyntheticImages, iid_partition
from repro.models import vision

CFG = vision.VisionConfig(num_layers=4, width=32, image_size=8)
LOSS = functools.partial(vision.vision_loss, cfg=CFG)
N = 6


def _loss(p, b):
    return LOSS(p, b)


@pytest.fixture(scope="module")
def task():
    imgs, labels = SyntheticImages(num_samples=1200, image_size=8,
                                   noise=0.5, seed=0).generate()
    shards = iid_partition(labels, N, seed=0)
    batcher = FederatedBatcher(imgs, labels, shards, batch_size=16, seed=0)
    test = {"images": jnp.asarray(imgs[:256]),
            "labels": jnp.asarray(labels[:256])}
    return batcher, test


def _accuracy(params, test):
    return float(vision.vision_accuracy(params, test, CFG))


def _jb(batch):
    return {"images": jnp.asarray(batch["images"]),
            "labels": jnp.asarray(batch["labels"])}


def test_fedpairing_end_to_end_learns(task):
    batcher, test = task
    fleet = latency.make_fleet(n=N, seed=0)
    chan = latency.ChannelModel()
    pairs = pairing.fedpairing_pairing(fleet, chan)
    partner = pairing.partner_permutation(pairs, N)
    lengths = splitting.propagation_lengths(fleet.cpu_hz, partner,
                                            CFG.num_layers)
    agg_w = fedpair.pair_weights(fleet.data_sizes, partner)

    key = jax.random.key(0)
    g = vision.vision_init(CFG, key)
    plan = splitting.split_plan(CFG, g)
    cp = fedpair.replicate(g, N)
    step = fedpair.make_fed_step(_loss, plan, CFG.num_layers,
                                 fedpair.FedPairingConfig(lr=0.1))
    acc0 = _accuracy(g, test)
    gen = iter(lambda: _jb(next(batcher)), None)
    for _ in range(4):
        cp, _ = fedpair.run_round(step, cp, gen, partner, lengths, agg_w, 10)
        g = aggregation.aggregate(cp, jnp.full((N,), 1.0 / N), "paper")
        cp = aggregation.broadcast(g, N)
    acc1 = _accuracy(g, test)
    assert acc1 > max(acc0 + 0.15, 0.25), (acc0, acc1)


def test_all_baselines_learn(task):
    batcher, test = task
    key = jax.random.key(1)
    g0 = vision.vision_init(CFG, key)
    plan = splitting.split_plan(CFG, g0)
    agg_w = jnp.full((N,), 1.0 / N)

    # vanilla FL
    cp = fedpair.replicate(g0, N)
    fl = baselines.make_fl_step(_loss, lr=0.1)
    for _ in range(3):
        cp, _ = baselines.fl_round(fl, cp, iter(lambda: _jb(next(batcher)),
                                                None), 10)
        g = aggregation.aggregate(cp, agg_w, "fedavg")
        cp = aggregation.broadcast(g, N)
    assert _accuracy(g, test) > 0.25

    # vanilla SL (sequential relay)
    sl = baselines.make_sl_step(_loss, plan, CFG.num_layers, cut=2, lr=0.1)
    client_p = server_p = g0

    def per_client(i):
        return [{k: v[i] for k, v in _jb(next(batcher)).items()}
                for _ in range(5)]

    for _ in range(3):
        client_p, server_p, _ = baselines.sl_round(sl, client_p,
                                                   per_client, N)
    mask = splitting.layer_mask(jnp.asarray(2), CFG.num_layers)
    merged = splitting.mix_params(client_p, server_p, plan, mask)
    assert _accuracy(merged, test) > 0.25

    # SplitFed
    cp = fedpair.replicate(g0, N)
    server_p = g0
    sf = baselines.make_splitfed_step(_loss, plan, CFG.num_layers, cut=2,
                                      lr=0.1)
    for _ in range(3):
        cp, server_p, _ = baselines.splitfed_round(
            sf, cp, server_p, iter(lambda: _jb(next(batcher)), None), 10,
            agg_w)
    merged = splitting.mix_params(
        jax.tree_util.tree_map(lambda a: a[0], cp), server_p, plan, mask)
    assert _accuracy(merged, test) > 0.25


def test_dist_engine_matches_vmapped_semantics():
    """shard_map+ppermute engine == vmapped mix-params engine (up to the
    1/N loss normalization).  Runs in a subprocess with 4 fabricated
    devices so this process's device count stays 1."""
    code = r"""
import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import functools
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.core import fedpair, fedpair_dist, splitting
from repro.models import registry

cfg = get_smoke_config("tinyllama-1.1b")
n = 4
partner = np.array([1, 0, 3, 2])
lengths = np.array([1, 1, 1, 1])
agg_w = np.array([0.1, 0.2, 0.3, 0.4], np.float32)
masks = np.stack([np.arange(cfg.num_layers) < l for l in lengths]).astype(np.float32)

key = jax.random.key(0)
g = registry.init_params(cfg, key)
cp = fedpair.replicate(g, n)
B, S = 2, 16
batch = {"tokens": jax.random.randint(key, (n, B, S), 0, cfg.vocab_size)}
batch["labels"] = batch["tokens"]

# vmapped engine
plan = splitting.split_plan(cfg, g)
step_v = fedpair.make_fed_step(
    lambda p, b: registry.loss_fn(p, b, cfg)[0], plan, cfg.num_layers,
    # dist normalizes loss by 1/N; donate=False keeps cp for the dist engine
    fedpair.FedPairingConfig(lr=0.1 / n, donate=False))
new_v, _ = step_v(cp, batch, jnp.asarray(partner), jnp.asarray(lengths),
                  jnp.asarray(agg_w))

# dist engine
from repro import compat
mesh = compat.make_mesh((4,), ("data",))
dcfg = fedpair_dist.FedDistConfig(lr=0.1)
with jax.set_mesh(mesh):
    step_d = fedpair_dist.make_dist_fed_step(
        cfg, mesh, fedpair_dist.pairs_to_ppermute(partner), agg_w, masks, dcfg)
    new_d, _ = step_d(cp, batch)

for (pa, a), (pb, b) in zip(
        jax.tree_util.tree_flatten_with_path(new_v)[0],
        jax.tree_util.tree_flatten_with_path(new_d)[0]):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4,
                               atol=5e-5, err_msg=str(pa))
print("DIST_EQUIV_OK")
"""
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                                          "HOME": "/root"}, cwd="/root/repo",
                         timeout=600)
    assert "DIST_EQUIV_OK" in res.stdout, res.stdout + res.stderr
