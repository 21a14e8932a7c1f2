"""StableLM 2 as published, at the smoke size of ``stablelm_1p6b``: the
program's loss and gradients agree with the plain reference
(``chipbench/references/stablelm2_lm.py``) on seeded random weights,
LayerNorm biases included; cached decode agrees with the full forward;
partial rope leaves the unrotated dimensions alone; and the RMSNorm /
whole-head-rope path that qwen runs is op for op what it was."""
import importlib.util
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import NormKind, get_config, get_smoke_config
from repro.models import common, registry, transformer

ROOT = pathlib.Path(__file__).resolve().parents[1]
CFG = get_smoke_config("stablelm-1.6b")
B, S = 2, 16


def _reference():
    path = ROOT / "chipbench" / "references" / "stablelm2_lm.py"
    spec = importlib.util.spec_from_file_location("stablelm2_lm", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
REF_CFG = {"num_attention_heads": CFG.num_heads,
           "num_key_value_heads": CFG.num_kv_heads,
           "vocab_size": CFG.vocab_size, "rope_theta": CFG.rope_theta,
           "partial_rotary_factor": CFG.partial_rotary_factor,
           "eps": CFG.norm_eps}


def _random_params(cfg, seed):
    """Every leaf random, so a LayerNorm's bias and a scale far from 1 both
    count: projections ~ N(0, 1/fan_in), everything else ~ 1 + N(0, 0.2)
    for norms and N(0, 0.1) for embeddings and biases."""
    shapes = jax.eval_shape(lambda: registry.init_params(cfg,
                                                         jax.random.key(0)))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (path, s) in enumerate(flat):
        name = str(getattr(path[-1], "key", path[-1]))
        k = jax.random.fold_in(jax.random.key(seed), i)
        z = jax.random.normal(k, s.shape, jnp.float32)
        if name.startswith("ln"):
            v = 1.0 + 0.2 * z
        elif name in ("embed", "bq", "bk", "bv"):
            v = 0.1 * z
        else:
            v = z / math.sqrt(s.shape[-2])
        out.append(v)
    return jax.tree_util.tree_unflatten(treedef, out)


def _flow_model(params):
    return {"embed": params["embed"], "ln_f": params["ln_f"],
            "head": params["unembed"], "blocks": params["blocks"]}


def _batch(seed=1):
    toks = jax.random.randint(jax.random.key(seed), (B, S + 1), 0,
                              CFG.vocab_size)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def test_smoke_config_is_stablelm2_as_published():
    full = get_config("stablelm-1.6b")
    assert full.norm == NormKind.LAYER and full.norm_eps == 1e-5
    assert full.partial_rotary_factor == 0.25 and full.rotary_dim == 16
    assert full.qkv_bias and not full.tie_embeddings
    assert full.rope_theta == 10000.0
    assert CFG.resolved_head_dim == 64 and CFG.rotary_dim == 16


def test_param_count_includes_layer_norm_biases():
    # embedding + untied head, then per layer 4 d^2 + 3 d (QKV bias)
    # + 3 d ff + 2 LayerNorms of (scale, bias), then the final LayerNorm
    d, ff, v = 2048, 5632, 100352
    layer = 4 * d * d + 3 * d + 3 * d * ff + 2 * 2 * d
    assert get_config("stablelm-1.6b").param_count() == (
        2 * v * d + 24 * layer + 2 * d)
    p = registry.init_params(CFG, jax.random.key(0))
    assert p["ln_f"].shape == (2, CFG.d_model)
    assert p["blocks"]["ln_attn"].shape == (CFG.num_layers, 2, CFG.d_model)
    scale, bias = transformer.norm_parts(p["ln_f"], CFG)
    np.testing.assert_array_equal(scale, 1.0)
    np.testing.assert_array_equal(bias, 0.0)


def test_loss_and_gradients_match_the_reference():
    params = _random_params(CFG, 3)
    batch = _batch()

    def program(p):
        return registry.loss_fn(p, batch, CFG)[0]

    def reference(fp):
        return REF.flow_loss(fp, batch["tokens"], batch["labels"], REF_CFG,
                             ce_rows=8)

    with jax.default_matmul_precision("highest"):
        loss_p, g_p = jax.jit(jax.value_and_grad(program))(params)
        loss_r, g_r = jax.jit(jax.value_and_grad(reference))(
            _flow_model(params))
    # both in float32 with exact products: the two differ only in the
    # order of sums (chunked cross-entropy, fused vs plain LayerNorm), a
    # few float32 ulps of a loss near 7
    np.testing.assert_allclose(float(loss_p), float(loss_r), rtol=2e-6)
    g_p = _flow_model(g_p)
    for (path, a), (_, b) in zip(jax.tree_util.tree_flatten_with_path(g_p)[0],
                                 jax.tree_util.tree_flatten_with_path(g_r)[0]):
        a, b = np.asarray(a), np.asarray(b)
        # per leaf against its own scale: a gradient sums B*S = 32 tokens'
        # terms, each carrying float32 round-off of order 1e-7 relative
        scale = np.max(np.abs(b))
        assert scale > 0, path
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-5 * scale,
                                   err_msg=jax.tree_util.keystr(path))


def test_prefill_then_decode_matches_the_full_forward():
    params = _random_params(CFG, 4)
    toks = _batch(5)["tokens"]
    full, _ = registry.forward_logits(params, {"tokens": toks}, CFG)

    # the reference's full forward, to the logits
    fp = _flow_model(params)
    x = fp["embed"][toks]
    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(lambda h, p_l: (REF.block(p_l, h, REF_CFG), None),
                            x, fp["blocks"])
        ref = REF.layer_norm(x, fp["ln_f"], REF_CFG["eps"]) @ fp["head"]
    # float32 on both sides, sums in different orders: logits of order 1
    np.testing.assert_allclose(np.asarray(full), np.asarray(ref), rtol=1e-4,
                               atol=1e-4)

    prompt = S // 2
    spec = registry.cache_spec_for(CFG, S, False)
    state = registry.init_serve_state(params, CFG, B, S)
    step = jax.jit(lambda t, st: registry.serve_step(params, t, st, CFG,
                                                     spec))
    outs = []
    for t in range(S):              # the prompt fills the cache, then decode
        lg, state = step(toks[:, t:t + 1], state)
        outs.append(lg)
    dec = jnp.concatenate(outs, axis=1)
    assert int(state["index"]) == S
    # cached attention sums the same products in another order
    np.testing.assert_allclose(np.asarray(dec[:, prompt:]),
                               np.asarray(full[:, prompt:]), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(dec[:, :prompt]),
                               np.asarray(full[:, :prompt]), rtol=1e-4,
                               atol=1e-4)


def test_partial_rope_rotates_only_the_first_rotary_dims():
    hd, rot = CFG.resolved_head_dim, CFG.rotary_dim
    x = jax.random.normal(jax.random.key(6), (B, S, CFG.num_heads, hd))
    cos, sin = transformer.rope_tables(CFG, jnp.arange(S)[None, :])
    assert cos.shape == (1, S, rot // 2)
    # frequencies over the 16 rotated dimensions, theta^(-2i/16)
    inv = CFG.rope_theta ** (-np.arange(0, rot, 2) / rot)
    np.testing.assert_allclose(np.asarray(cos[0, 3]), np.cos(3 * inv),
                               rtol=1e-6)
    y = common.apply_rope(x, cos[:, :, None, :], sin[:, :, None, :])
    np.testing.assert_array_equal(np.asarray(y[..., rot:]),
                                  np.asarray(x[..., rot:]))
    np.testing.assert_array_equal(np.asarray(y[:, 0]), np.asarray(x[:, 0]))
    assert not np.allclose(np.asarray(y[:, 1:, :, :rot]),
                           np.asarray(x[:, 1:, :, :rot]))


def _parent_apply_rope(x, cos, sin):
    """The whole-head rope as it was before partial rotary existed."""
    d_half = x.shape[-1] // 2
    x1, x2 = x[..., :d_half], x[..., d_half:]
    cos = cos.astype(x.dtype)
    sin = sin.astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@pytest.mark.parametrize("arch_id", ["qwen1.5-0.5b", "tinyllama-1.1b"])
def test_rms_full_rotary_program_is_unchanged(arch_id, monkeypatch):
    """The norm and rope helpers add nothing to an RMSNorm, whole-head rope
    configuration: its jaxpr equals the one built from the plain
    ``common.rms_norm`` / ``rope_cos_sin(head_dim)`` / whole-head rope
    calls, and its loss and gradients are bit-identical."""
    cfg = get_smoke_config(arch_id)
    assert cfg.norm == NormKind.RMS and cfg.rotary_dim == cfg.resolved_head_dim
    params = _random_params(cfg, 7)
    toks = jax.random.randint(jax.random.key(8), (B, S), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}

    def loss(p):
        return registry.loss_fn(p, batch, cfg)[0]

    now = jax.make_jaxpr(jax.value_and_grad(loss))(params)
    now_val = jax.jit(jax.value_and_grad(loss))(params)
    monkeypatch.setattr(transformer, "norm",
                        lambda x, w, c: common.rms_norm(x, w, c.norm_eps))
    monkeypatch.setattr(transformer, "rope_tables",
                        lambda c, pos: common.rope_cos_sin(
                            pos, c.resolved_head_dim, c.rope_theta))
    monkeypatch.setattr(common, "apply_rope", _parent_apply_rope)
    before = jax.make_jaxpr(jax.value_and_grad(loss))(params)
    before_val = jax.jit(jax.value_and_grad(loss))(params)
    assert str(now) == str(before)
    for a, b in zip(jax.tree_util.tree_leaves(now_val),
                    jax.tree_util.tree_leaves(before_val)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
