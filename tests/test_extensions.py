"""Framework extensions: LR schedules, partial participation, gradient
accumulation."""
import functools
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.core import fedpair, latency, participation, splitting
from repro.core.latency import ChannelModel
from repro.models import vision
from repro.optim import adamw, sgd
from repro.optim.schedules import (constant, cosine_decay, linear_warmup,
                                   scheduled)


class TestSchedules:
    def test_constant(self):
        s = constant(0.1)
        np.testing.assert_allclose(float(s(jnp.asarray(0))), 0.1, rtol=1e-6)
        np.testing.assert_allclose(float(s(jnp.asarray(1000))), 0.1, rtol=1e-6)

    def test_warmup_ramps(self):
        s = linear_warmup(1.0, 10)
        assert float(s(jnp.asarray(0))) == 0.0
        assert abs(float(s(jnp.asarray(5))) - 0.5) < 1e-6
        assert float(s(jnp.asarray(100))) == 1.0

    def test_cosine_endpoints(self):
        s = cosine_decay(1.0, total_steps=100, warmup_steps=10,
                         final_fraction=0.1)
        assert float(s(jnp.asarray(10))) > 0.95
        np.testing.assert_allclose(float(s(jnp.asarray(100))), 0.1, rtol=1e-5)

    def test_scheduled_sgd_matches_manual(self):
        opt = scheduled(lambda lr: sgd(lr), linear_warmup(1.0, 2))
        p = {"w": jnp.asarray([1.0])}
        st = opt.init(p)
        g = {"w": jnp.asarray([1.0])}
        u0, st = opt.update(g, st, p)       # step 0: lr 0
        u1, st = opt.update(g, st, p)       # step 1: lr 0.5
        u2, st = opt.update(g, st, p)       # step 2: lr 1.0
        np.testing.assert_allclose(np.asarray(u0["w"]), [0.0])
        np.testing.assert_allclose(np.asarray(u1["w"]), [-0.5])
        np.testing.assert_allclose(np.asarray(u2["w"]), [-1.0])

    def test_scheduled_adamw_bounded(self):
        opt = scheduled(lambda lr: adamw(lr), cosine_decay(0.1, 50))
        p = {"w": jnp.asarray([5.0])}
        st = opt.init(p)
        for _ in range(50):
            u, st = opt.update({"w": 2 * p["w"]}, st, p)
            p = jax.tree_util.tree_map(lambda a, b: a + b, p, u)
        assert float(jnp.abs(p["w"])[0]) < 5.0


class TestParticipation:
    def test_cohort_size_and_bounds(self):
        rng = np.random.default_rng(0)
        c = participation.sample_cohort(20, 0.4, rng)
        assert len(c) == 8 and len(np.unique(c)) == 8
        assert c.min() >= 0 and c.max() < 20

    def test_cohort_pairing_structure(self):
        fleet = latency.make_fleet(n=12, seed=0)
        rng = np.random.default_rng(1)
        cohort = participation.sample_cohort(12, 0.5, rng)
        partner, lengths, active = participation.cohort_pairing(
            fleet, ChannelModel(), cohort, num_layers=8)
        assert np.array_equal(partner[partner], np.arange(12))
        # non-participants are self-paired with the full stack
        for i in range(12):
            if not active[i]:
                assert partner[i] == i and lengths[i] == 8
        # participants pair within the cohort
        for i in cohort:
            assert partner[i] in cohort

    def test_fed_round_with_partial_participation(self):
        """Self-paired inactive clients degrade to local SGD — a cohort
        round must still be a valid step for everyone."""
        cfg = vision.VisionConfig(num_layers=4, width=16, image_size=4)
        loss = functools.partial(vision.vision_loss, cfg=cfg)
        fleet = latency.make_fleet(n=6, seed=0)
        rng = np.random.default_rng(2)
        cohort = participation.sample_cohort(6, 0.5, rng)
        partner, lengths, active = participation.cohort_pairing(
            fleet, ChannelModel(), cohort, cfg.num_layers)
        g = vision.vision_init(cfg, jax.random.key(0))
        plan = splitting.split_plan(cfg, g)
        cp = fedpair.replicate(g, 6)
        pw = fedpair.pair_weights(fleet.data_sizes, partner)
        # inactive clients get weight 0 -> frozen this round
        pw = np.where(active, pw, 0.0).astype(np.float32)
        # donate=False: the pre-step replicas are compared against below
        step = fedpair.make_fed_step(lambda p, b: loss(p, b), plan,
                                     cfg.num_layers,
                                     fedpair.FedPairingConfig(lr=0.1,
                                                              donate=False))
        imgs = jnp.asarray(np.random.default_rng(3).normal(
            size=(6, 8, 4, 4, 3)), jnp.float32)
        labels = jnp.asarray(np.random.default_rng(3).integers(
            0, 10, (6, 8)))
        new, _ = step(cp, {"images": imgs, "labels": labels},
                      jnp.asarray(partner), jnp.asarray(lengths),
                      jnp.asarray(pw))
        moved = np.asarray(jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(
                lambda a, b: jnp.sum(jnp.abs(a - b), axis=tuple(
                    range(1, a.ndim))), new, cp))[0])
        for i in range(6):
            if active[i]:
                assert moved[i] > 0
            else:
                assert moved[i] == 0


@pytest.mark.slow
def test_gradient_accumulation_matches_monolithic():
    code = r"""
import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.configs.base import InputShape
from repro.launch.steps import build_train_step
import repro.models.registry as R
from repro.optim import adamw

from repro import compat
mesh = compat.make_mesh((4, 2), ("data", "model"))
cfg = get_smoke_config("tinyllama-1.1b")
shape = InputShape("train", 32, 8, "train")
key = jax.random.key(0)
outs = {}
for mb in (1, 4):
    with jax.set_mesh(mesh):
        fn, ex, ins, osh = build_train_step(cfg, shape, mesh, microbatches=mb)
        jitted = jax.jit(fn, in_shardings=ins, out_shardings=osh)
        params = jax.device_put(R.init_params(cfg, key), ins[0])
        opt = adamw(3e-4)
        opt_state = jax.device_put(opt.init(R.init_params(cfg, key)), ins[1])
        toks = jax.random.randint(key, (8, 32), 0, cfg.vocab_size)
        batch = jax.device_put({"tokens": toks, "labels": toks}, ins[2])
        new_p, _, m = jitted(params, opt_state, batch)
        outs[mb] = new_p
for a, b in zip(jax.tree_util.tree_leaves(outs[1]),
                jax.tree_util.tree_leaves(outs[4])):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4,
                               atol=5e-6)
print("ACCUM_OK")
"""
    res = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root"},
        cwd="/root/repo", timeout=900)
    assert "ACCUM_OK" in res.stdout, res.stdout[-1500:] + res.stderr[-3000:]
