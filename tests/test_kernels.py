"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps in interpret
mode (deliverable c: per-kernel allclose against ref.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd
from repro.kernels.wkv6 import wkv6

KEY = jax.random.key(0)


def _tol(dtype):
    # fp32: accumulation-order noise across chunked vs sequential scans
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-4, atol=1e-4)


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "B,S,Hq,Hkv,d,causal,window,bq,bk",
        [
            (2, 128, 4, 2, 64, True, 0, 64, 64),
            (1, 256, 4, 4, 64, True, 64, 64, 64),
            (2, 128, 8, 2, 128, False, 0, 64, 64),
            (1, 128, 2, 1, 64, True, 0, 128, 32),
            (2, 64, 4, 4, 32, True, 16, 32, 32),
        ])
    def test_vs_oracle(self, B, S, Hq, Hkv, d, causal, window, bq, bk, dtype):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (B, S, Hq, d), dtype)
        k = jax.random.normal(ks[1], (B, S, Hkv, d), dtype)
        v = jax.random.normal(ks[2], (B, S, Hkv, d), dtype)
        out = flash_attention(q, k, v, causal=causal, sliding_window=window,
                              block_q=bq, block_k=bk, interpret=True)
        want = ref.attention_ref(q, k, v, causal=causal, sliding_window=window)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32), **_tol(dtype))

    def test_dispatch_interpret_path(self):
        q = jax.random.normal(KEY, (1, 64, 2, 32))
        k = jax.random.normal(KEY, (1, 64, 2, 32))
        v = jax.random.normal(KEY, (1, 64, 2, 32))
        a = ops.flash_attention(q, k, v, impl="pallas_interpret")
        b = ops.flash_attention(q, k, v, impl="xla")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=2e-5)


class TestSSD:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "B,S,H,P,N,chunk",
        [
            (2, 128, 4, 64, 32, 32),
            (1, 256, 2, 32, 64, 64),
            (2, 64, 8, 64, 16, 16),
            (1, 96, 2, 32, 32, 32),   # chunk divides S=96 after fit (32)
        ])
    def test_vs_naive(self, B, S, H, P, N, chunk, dtype):
        ks = jax.random.split(KEY, 4)
        x = jax.random.normal(ks[0], (B, S, H, P), dtype)
        ld = -jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
        b = (jax.random.normal(ks[2], (B, S, N)) * 0.5).astype(dtype)
        c = (jax.random.normal(ks[3], (B, S, N)) * 0.5).astype(dtype)
        y_k, s_k = ssd(x, ld, b, c, chunk=chunk, interpret=True)
        y_r, s_r = ref.ssd_naive(x, ld, b, c)
        np.testing.assert_allclose(np.asarray(y_k, np.float32),
                                   np.asarray(y_r, np.float32), **_tol(dtype))
        np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r, np.float32),
                                   rtol=1e-2 if dtype == jnp.bfloat16 else 1e-4,
                                   atol=1e-2 if dtype == jnp.bfloat16 else 1e-4)

    def test_chunked_ref_matches_naive(self):
        ks = jax.random.split(KEY, 4)
        x = jax.random.normal(ks[0], (2, 64, 2, 16))
        ld = -jax.nn.softplus(jax.random.normal(ks[1], (2, 64, 2)))
        b = jax.random.normal(ks[2], (2, 64, 8)) * 0.5
        c = jax.random.normal(ks[3], (2, 64, 8)) * 0.5
        y_c, s_c = ref.ssd_chunked_ref(x, ld, b, c, chunk=16)
        y_n, s_n = ref.ssd_naive(x, ld, b, c)
        np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_n),
                                   rtol=2e-5, atol=2e-5)

    def test_initial_state_carried(self):
        """Splitting a sequence in two chunks through initial_state must equal
        one full pass (the serving/training parity the models rely on)."""
        ks = jax.random.split(KEY, 4)
        B, S, H, P, N = 1, 64, 2, 16, 8
        x = jax.random.normal(ks[0], (B, S, H, P))
        ld = -jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
        b = jax.random.normal(ks[2], (B, S, N)) * 0.5
        c = jax.random.normal(ks[3], (B, S, N)) * 0.5
        y_full, s_full = ref.ssd_chunked_ref(x, ld, b, c, chunk=16)
        y1, s1 = ref.ssd_chunked_ref(x[:, :32], ld[:, :32], b[:, :32],
                                     c[:, :32], chunk=16)
        y2, s2 = ref.ssd_chunked_ref(x[:, 32:], ld[:, 32:], b[:, 32:],
                                     c[:, 32:], chunk=16, initial_state=s1)
        np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                                   np.asarray(y_full), rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                                   rtol=2e-5, atol=2e-5)


class TestWKV6:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "B,S,H,N,M,chunk,decay_scale",
        [
            (2, 128, 4, 32, 32, 32, 1.0),
            (1, 256, 2, 64, 64, 64, 1.0),
            (2, 64, 4, 32, 32, 16, 8.0),    # aggressive decay: no overflow
            (1, 64, 2, 16, 16, 64, 1.0),    # chunk > S -> fit_chunk
        ])
    def test_vs_naive(self, B, S, H, N, M, chunk, decay_scale, dtype):
        ks = jax.random.split(KEY, 5)
        r = (jax.random.normal(ks[0], (B, S, H, N)) * 0.5).astype(dtype)
        k = (jax.random.normal(ks[1], (B, S, H, N)) * 0.5).astype(dtype)
        v = (jax.random.normal(ks[2], (B, S, H, M)) * 0.5).astype(dtype)
        lw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, N))) * decay_scale
        u = jax.random.normal(ks[4], (H, N)) * 0.5
        y_k, s_k = wkv6(r, k, v, lw, u, chunk=chunk, interpret=True)
        y_n, s_n = ref.wkv6_naive(r, k, v, lw, u)
        np.testing.assert_allclose(np.asarray(y_k, np.float32),
                                   np.asarray(y_n, np.float32), **_tol(dtype))

    def test_chunked_ref_matches_naive(self):
        ks = jax.random.split(KEY, 5)
        r = jax.random.normal(ks[0], (1, 48, 2, 16)) * 0.5
        k = jax.random.normal(ks[1], (1, 48, 2, 16)) * 0.5
        v = jax.random.normal(ks[2], (1, 48, 2, 16)) * 0.5
        lw = -jnp.exp(jax.random.normal(ks[3], (1, 48, 2, 16)))
        u = jax.random.normal(ks[4], (2, 16)) * 0.5
        y_c, _ = ref.wkv6_chunked_ref(r, k, v, lw, u, chunk=16)
        y_n, _ = ref.wkv6_naive(r, k, v, lw, u)
        np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_n),
                                   rtol=2e-5, atol=2e-5)

    def test_decode_step_matches_naive_tail(self):
        ks = jax.random.split(KEY, 5)
        B, S, H, N, M = 1, 8, 2, 8, 8
        r = jax.random.normal(ks[0], (B, S, H, N)) * 0.5
        k = jax.random.normal(ks[1], (B, S, H, N)) * 0.5
        v = jax.random.normal(ks[2], (B, S, H, M)) * 0.5
        lw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, N)))
        u = jax.random.normal(ks[4], (H, N)) * 0.5
        y_n, _ = ref.wkv6_naive(r, k, v, lw, u)
        state = jnp.zeros((B, H, N, M))
        outs = []
        for t in range(S):
            o, state = ref.wkv6_decode_step(state, r[:, t], k[:, t], v[:, t],
                                            lw[:, t], u)
            outs.append(o)
        np.testing.assert_allclose(np.asarray(jnp.stack(outs, 1)),
                                   np.asarray(y_n), rtol=2e-5, atol=2e-5)


class TestDispatch:
    def test_default_impl_cpu_is_xla(self):
        assert ops.resolve_impl(None) == "xla"

    def test_set_default_impl_roundtrip(self):
        ops.set_default_impl("pallas_interpret")
        try:
            assert ops.resolve_impl(None) == "pallas_interpret"
        finally:
            ops.set_default_impl(None)

    def test_invalid_impl_rejected(self):
        with pytest.raises(ValueError):
            ops.set_default_impl("cuda")


class TestForwardOnly:
    """The Pallas kernels have no backward pass yet: differentiating
    through one must name it, never fall back to the reference."""

    CASES = {
        "flash_attention": lambda x: ops.flash_attention(
            x, x, x, impl="pallas_interpret"),
        "ssd": lambda x: ops.ssd(
            x, -jnp.ones(x.shape[:3]), x[:, :, 0], x[:, :, 0], chunk=16,
            impl="pallas_interpret")[0],
        "wkv6": lambda x: ops.wkv6(
            x, x, x, -jnp.abs(x), x[0, 0], impl="pallas_interpret")[0],
    }

    @pytest.mark.parametrize("name", sorted(CASES))
    def test_grad_names_the_missing_backward(self, name):
        x = jax.random.normal(KEY, (1, 64, 2, 16)) * 0.1
        fwd = self.CASES[name]
        assert fwd(x).shape[:2] == (1, 64)          # forward still runs
        with pytest.raises(NotImplementedError, match=name):
            jax.grad(lambda a: fwd(a).sum())(x)


class TestWKV6EffectiveChunk:
    """The Pallas wkv6 kernel coerces sub-64 chunk requests up to its
    minimum sequence tile; the coercion is explicit and queryable."""

    def test_xla_honors_requested_chunk(self):
        assert ops.wkv6_effective_chunk(16, "xla") == 16

    @pytest.mark.parametrize("impl", ["pallas", "pallas_interpret"])
    def test_kernel_paths_coerce_small_chunks_up(self, impl):
        assert ops.wkv6_effective_chunk(16, impl) == ops.WKV6_MIN_KERNEL_CHUNK
        assert ops.wkv6_effective_chunk(128, impl) == 128

    def test_coercion_is_semantically_safe(self):
        """chunk is a pure memory/latency knob: results are invariant, so
        coercing 16 -> 64 only changes the tiling."""
        rng = np.random.default_rng(0)
        B, S, H, N, M = 1, 64, 2, 8, 8
        r, k, v = (jnp.asarray(rng.normal(size=(B, S, H, N)), jnp.float32)
                   for _ in range(3))
        v = jnp.asarray(rng.normal(size=(B, S, H, M)), jnp.float32)
        lw = -jnp.exp(jnp.asarray(rng.normal(size=(B, S, H, N)), jnp.float32))
        u = jnp.asarray(rng.normal(size=(H, N)), jnp.float32)
        y16, s16 = ops.wkv6(r, k, v, lw, u, chunk=16, impl="xla")
        y64, s64 = ops.wkv6(r, k, v, lw, u,
                            chunk=ops.wkv6_effective_chunk(16, "pallas"),
                            impl="xla")
        np.testing.assert_allclose(np.asarray(y16), np.asarray(y64),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(s16), np.asarray(s64),
                                   rtol=2e-5, atol=2e-5)
