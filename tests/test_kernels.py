"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle,
swept over shapes and dtypes per the deliverable spec."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.mark.parametrize("backend,interpret,want", [
    ("tpu", None, False), ("cpu", None, True), ("gpu", None, RuntimeError),
    ("tpu", True, RuntimeError), ("gpu", True, RuntimeError),
    ("cpu", True, True), ("tpu", False, False), ("cpu", False, False),
])
def test_resolve_interpret_only_on_cpu(monkeypatch, backend, interpret,
                                       want):
    """Interpret mode is granted on the CPU backend only: autodetect
    compiles on TPU and refuses any other backend, and an explicit
    ``interpret=True`` off the CPU is refused too."""
    from repro.kernels import backend as kb

    monkeypatch.setattr(kb.jax, "default_backend", lambda: backend)
    if want is RuntimeError:
        with pytest.raises(RuntimeError):
            kb.resolve_interpret(interpret)
    else:
        assert kb.resolve_interpret(interpret) is want


# ---------------------------------------------------------------------------
# p2m_conv
# ---------------------------------------------------------------------------


class TestP2MConvKernel:
    @pytest.mark.parametrize("hw,cin,cout,t,nsub", [
        (8, 2, 4, 2, 3),
        (12, 2, 8, 1, 4),
        (16, 3, 5, 2, 2),
    ])
    def test_kernel_matches_scan_oracle(self, hw, cin, cout, t, nsub):
        from repro.core.p2m_layer import P2MConfig, p2m_init, p2m_forward_scan
        from repro.kernels.p2m_conv import ops

        cfg = P2MConfig(in_channels=cin, out_channels=cout, t_intg_ms=10.0,
                        n_sub=nsub)
        key = jax.random.PRNGKey(42)
        params = p2m_init(key, cfg)
        ev = jax.random.poisson(jax.random.fold_in(key, 1), 0.3,
                                (2, t, nsub, hw, hw, cin)).astype(jnp.float32)
        s_ref, v_ref = p2m_forward_scan(params, ev, cfg)
        s_k, v_k = ops.p2m_conv(params, ev, cfg)
        np.testing.assert_allclose(np.asarray(v_k), np.asarray(v_ref),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(s_k), np.asarray(s_ref))

    def test_kernel_matches_jnp_ref(self):
        """ops(use_ref=True) — the pure-jnp ref path — agrees with Pallas."""
        from repro.core.p2m_layer import P2MConfig, p2m_init
        from repro.kernels.p2m_conv import ops

        cfg = P2MConfig(out_channels=6, n_sub=3)
        key = jax.random.PRNGKey(0)
        params = p2m_init(key, cfg)
        ev = jax.random.poisson(key, 0.5, (1, 2, 3, 10, 10, 2)).astype(jnp.float32)
        s_k, v_k = ops.p2m_conv(params, ev, cfg)
        s_r, v_r = ops.p2m_conv(params, ev, cfg, use_ref=True)
        np.testing.assert_allclose(np.asarray(v_k), np.asarray(v_r),
                                   rtol=1e-5, atol=1e-6)

    def test_nonsquare_tile_padding(self):
        """P not a multiple of block_p exercises the pad/crop path."""
        from repro.core.p2m_layer import P2MConfig, p2m_init, p2m_forward_scan
        from repro.kernels.p2m_conv.p2m_conv import p2m_conv_pallas
        from repro.kernels.p2m_conv.ops import _prepare

        cfg = P2MConfig(out_channels=4, n_sub=2)
        params = p2m_init(jax.random.PRNGKey(1), cfg)
        ev = jnp.ones((1, 1, 2, 7, 9, 2))
        patches, w2, v_inf, decay, theta, params2, consts, dims = _prepare(
            params, ev, cfg)
        s, v = p2m_conv_pallas(patches, w2, v_inf, decay, theta,
                               params2["pv_gain"], params2["pv_offset"],
                               block_p=16, **consts)
        s_ref, v_ref = p2m_forward_scan(params, ev, cfg)
        B, T, Ho, Wo = dims
        v_cropped = v[:, :B * Ho * Wo].reshape(T, B, Ho, Wo, 4)
        np.testing.assert_allclose(np.asarray(jnp.moveaxis(v_cropped, 0, 1)),
                                   np.asarray(v_ref), rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# lif
# ---------------------------------------------------------------------------


class TestLIFKernel:
    @pytest.mark.parametrize("t,n", [(4, 32), (16, 100), (7, 513)])
    @pytest.mark.parametrize("soft", [True, False])
    def test_matches_ref(self, t, n, soft):
        from repro.kernels.lif.lif import lif_pallas
        from repro.kernels.lif.ref import lif_ref

        x = jax.random.normal(jax.random.PRNGKey(0), (t, n)) * 2.0
        out_k = lif_pallas(x, soft_reset=soft, block_n=64)
        out_r = lif_ref(x, soft_reset=soft)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   atol=1e-6)

    def test_matches_snn_lif(self):
        """Kernel agrees with the training-path LIF in core/snn.py."""
        from repro.core.snn import LIFConfig, lif_over_time
        from repro.kernels.lif.lif import lif_pallas

        x = jax.random.normal(jax.random.PRNGKey(3), (6, 4, 10)) * 1.5
        ref = lif_over_time(x, LIFConfig())
        k = lif_pallas(x.reshape(6, 40)).reshape(6, 4, 10)
        np.testing.assert_allclose(np.asarray(k), np.asarray(ref), atol=1e-6)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


class TestFlashAttention:
    @pytest.mark.parametrize("sq,skv,d,causal", [
        (64, 64, 16, True),
        (32, 128, 32, False),
        (100, 100, 16, True),     # non-multiple of block → pad path
        (1, 96, 16, False),       # decode-like
    ])
    def test_matches_ref(self, sq, skv, d, causal):
        from repro.kernels.flash_attention.flash_attention import (
            flash_attention_pallas)
        from repro.kernels.flash_attention.ref import attention_ref

        k = jax.random.PRNGKey(0)
        q = jax.random.normal(k, (2, sq, d))
        kk = jax.random.normal(jax.random.fold_in(k, 1), (2, skv, d))
        v = jax.random.normal(jax.random.fold_in(k, 2), (2, skv, d))
        if causal and sq != skv:
            pytest.skip("causal requires sq == skv in this ref")
        o_k = flash_attention_pallas(q, kk, v, causal=causal, block_q=32,
                                     block_k=32)
        o_r = attention_ref(q, kk, v, causal=causal)
        np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                                   rtol=2e-3, atol=2e-3)

    def test_kv_len_masking(self):
        from repro.kernels.flash_attention.flash_attention import (
            flash_attention_pallas)
        from repro.kernels.flash_attention.ref import attention_ref

        k = jax.random.PRNGKey(1)
        q = jax.random.normal(k, (1, 1, 16))
        kk = jax.random.normal(jax.random.fold_in(k, 1), (1, 64, 16))
        v = jax.random.normal(jax.random.fold_in(k, 2), (1, 64, 16))
        o_k = flash_attention_pallas(q, kk, v, causal=False, kv_len=40,
                                     block_k=32)
        o_r = attention_ref(q, kk[:, :40], v[:, :40], causal=False)
        np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                                   rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# ssd
# ---------------------------------------------------------------------------


def _ssd_inputs(key, b, s, h, p, g, n):
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, g, n))
    C = jax.random.normal(ks[4], (b, s, g, n))
    return x, dt, A, B, C


class TestSSDKernel:
    @pytest.mark.parametrize("b,s,h,p,g,n,chunk", [
        (1, 64, 2, 8, 1, 8, 16),
        (2, 96, 4, 16, 2, 8, 32),
        (1, 50, 2, 8, 2, 4, 16),    # pad path
        (2, 32, 8, 8, 8, 8, 32),    # g == h (no grouping)
    ])
    def test_matches_sequential_ref(self, b, s, h, p, g, n, chunk):
        from repro.kernels.ssd.ref import ssd_ref
        from repro.kernels.ssd.ssd import ssd_pallas

        x, dt, A, B, C = _ssd_inputs(jax.random.PRNGKey(7), b, s, h, p, g, n)
        y_k, st_k = ssd_pallas(x, dt, A, B, C, chunk=chunk)
        y_r, st_r = ssd_ref(x, dt, A, B, C)
        np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                                   rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(np.asarray(st_k), np.asarray(st_r),
                                   rtol=1e-3, atol=1e-4)

    def test_chunked_jnp_matches_ref(self):
        """nn/ssm.ssd_chunked (the training path) vs the sequential oracle."""
        from repro.kernels.ssd.ref import ssd_ref
        from repro.nn.ssm import ssd_chunked

        x, dt, A, B, C = _ssd_inputs(jax.random.PRNGKey(9), 2, 64, 4, 8, 2, 8)
        y_c, st_c = ssd_chunked(x, dt, A, B, C, chunk=16)
        y_r, st_r = ssd_ref(x, dt, A, B, C)
        np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_r),
                                   rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(np.asarray(st_c), np.asarray(st_r),
                                   rtol=1e-3, atol=1e-4)

    def test_trainable_grad_path(self):
        from repro.kernels.ssd.ops import ssd_trainable

        x, dt, A, B, C = _ssd_inputs(jax.random.PRNGKey(11), 1, 32, 2, 8, 1, 4)
        def loss(x):
            return jnp.sum(ssd_trainable(x, dt, A, B, C) ** 2)
        g = jax.grad(loss)(x)
        assert g.shape == x.shape
        assert bool(jnp.all(jnp.isfinite(g)))
