"""Fused streaming-fold kernel (repro.kernels.stream_fold) tests.

The load-bearing contract: the deposit-mode kernel folds exactly what the
``lax.scan`` oracle (``ref.stream_fold_ref``) folds — the same per-sub-slot
deposits, the same elementwise ``x·a + c`` — and equals it bit for bit on
every shape, including lane/tile padding edges, empty (gap-decay) chunks,
and inactive capacity-padding lanes. Against the XLA serving fold, which
computes ``x·a + conv·dv_unit`` as one fused expression, the only freedom
left is where the compiler contracts a multiply and an add into one
rounding (fused multiply-add), so the two agree to ``FOLD_ATOL`` — a few
ulp of the charge — and layer-1 spike maps stay identical, which is what
lets ``StreamEngine(use_kernel=True)`` inherit the streaming≡offline
parity contract; tests/test_streaming.py re-runs its parity grid through
the kernel on top of this suite."""
from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from repro.core.leakage import CircuitConfig, LeakageConfig  # noqa: E402
from repro.core.p2m_layer import _conv  # noqa: E402
from repro.kernels.stream_fold import ops, ref  # noqa: E402
from repro.kernels.stream_fold.stream_fold import (  # noqa: E402
    stream_fold_mac_pallas, stream_fold_pallas,
)
from repro.stream import accumulator, deploy as deploy_mod  # noqa: E402

HW = 16
# kernel fold vs the conv-fused XLA fold, absolute on a charge of |x| < 0.5
# V: the multiply-add contraction choice moves each sub-slot's sum by at
# most an ulp or two (2.2e-8 to 3.0e-8 seen on the CPU for 4 sub-slots)
FOLD_ATOL = 1e-7


def _fold_inputs(key, S, N, F):
    ks = jax.random.split(key, 3)
    x0 = jax.random.normal(ks[0], (N, F)) * 0.05
    dep = jax.random.normal(ks[1], (S, N, F)) * 0.01
    a = jnp.exp(-jax.random.uniform(ks[2], (F,)))
    return x0, dep, a


# ---------------------------------------------------------------------------
# kernel level
# ---------------------------------------------------------------------------

class TestFoldKernel:
    @pytest.mark.parametrize("S,N,F,block_n", [
        (1, 8, 3, 256),      # single sub-slot, tiny shapes
        (3, 37, 5, 16),      # N not a multiple of block_n → grid padding
        (6, 64, 8, 64),      # exact tiling
        (4, 5, 1, 2),        # single filter lane
    ])
    def test_bit_exact_vs_scan(self, S, N, F, block_n):
        x0, dep, a = _fold_inputs(jax.random.PRNGKey(S * 1000 + N), S, N, F)
        out = stream_fold_pallas(x0, dep, a, block_n=block_n)
        want = ref.stream_fold_ref(x0, dep, a)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))

    def test_bit_exact_under_jit(self):
        x0, dep, a = _fold_inputs(jax.random.PRNGKey(0), 4, 50, 8)
        out = jax.jit(lambda *t: stream_fold_pallas(*t, block_n=32))(
            x0, dep, a)
        want = ref.stream_fold_ref(x0, dep, a)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))

    def test_zero_deposits_pure_gap_decay(self):
        """An all-empty chunk is S multiplies by the decay: exactly the
        scan's answer, and (to float tolerance) x0·a^S."""
        S, N, F = 5, 20, 6
        x0, _, a = _fold_inputs(jax.random.PRNGKey(1), S, N, F)
        dep = jnp.zeros((S, N, F))
        out = stream_fold_pallas(x0, dep, a, block_n=8)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(ref.stream_fold_ref(x0, dep, a)))
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(x0 * a ** S), rtol=1e-6)

    def test_mac_variant_close(self):
        S, N, K, F = 3, 40, 18, 8
        key = jax.random.PRNGKey(2)
        ks = jax.random.split(key, 4)
        x0 = jax.random.normal(ks[0], (N, F)) * 0.05
        patches = jax.random.poisson(ks[1], 0.4, (S, N, K)).astype(
            jnp.float32)
        w = jax.random.normal(ks[2], (K, F)) * 0.1
        a = jnp.exp(-jax.random.uniform(ks[3], (F,)))
        out = stream_fold_mac_pallas(x0, patches, w, a, dv_unit=0.01,
                                     block_n=16)
        want = ref.stream_fold_mac_ref(x0, patches, w, a, dv_unit=0.01)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# serving-shaped wrapper (ops.fold_chunk)
# ---------------------------------------------------------------------------

def _chunk_inputs(key, B, S, hw, F, k=3):
    ks = jax.random.split(key, 4)
    frames = jax.random.poisson(ks[0], 0.3, (B, S, hw, hw, 2)).astype(
        jnp.float32)
    w_q = jax.random.normal(ks[1], (k, k, 2, F)) * 0.1
    a = jnp.exp(-jax.random.uniform(ks[2], (F,)))
    return frames, w_q, a, ks[3]


def _scan_fold(x, frames, w_q, a, stride, dv_unit):
    def sub(x, ev):
        return x * a + _conv(ev, w_q, stride) * dv_unit, None
    x, _ = lax.scan(sub, x, jnp.moveaxis(frames, 1, 0))
    return x


class TestFoldChunk:
    @pytest.mark.parametrize("stride", [1, 2])
    def test_deposit_bit_exact_vs_scan(self, stride):
        """Bit-exact with the scan over the same deposits (the kernel's
        oracle); within ``FOLD_ATOL`` of the conv-fused serving scan."""
        B, S, F = 3, 4, 8
        frames, w_q, a, kx = _chunk_inputs(jax.random.PRNGKey(3), B, S,
                                           HW, F)
        ho = HW // stride
        x0 = jax.random.normal(kx, (B, ho, ho, F)) * 0.05
        out = ops.fold_chunk(x0, frames, w_q, a, stride=stride,
                             dv_unit=0.01)
        oracle = ops.fold_chunk(x0, frames, w_q, a, stride=stride,
                                dv_unit=0.01, use_ref=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(oracle))
        want = _scan_fold(x0, frames, w_q, a, stride, 0.01)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=0, atol=FOLD_ATOL)

    def test_empty_chunk_gap_decay(self):
        B, S, F = 2, 6, 8
        _, w_q, a, kx = _chunk_inputs(jax.random.PRNGKey(4), B, S, HW, F)
        frames = jnp.zeros((B, S, HW, HW, 2))
        x0 = jax.random.normal(kx, (B, HW, HW, F)) * 0.05
        out = ops.fold_chunk(x0, frames, w_q, a, stride=1, dv_unit=0.01)
        want = _scan_fold(x0, frames, w_q, a, 1, 0.01)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))

    def test_mac_close_to_deposit(self):
        B, S, F = 2, 3, 8
        frames, w_q, a, kx = _chunk_inputs(jax.random.PRNGKey(5), B, S,
                                           HW, F)
        x0 = jax.random.normal(kx, (B, HW, HW, F)) * 0.05
        dep = ops.fold_chunk(x0, frames, w_q, a, stride=1, dv_unit=0.01)
        mac = ops.fold_chunk(x0, frames, w_q, a, stride=1, dv_unit=0.01,
                             mode="mac")
        np.testing.assert_allclose(np.asarray(mac), np.asarray(dep),
                                   rtol=1e-5, atol=1e-7)

    def test_unknown_mode_raises(self):
        frames, w_q, a, kx = _chunk_inputs(jax.random.PRNGKey(6), 1, 2,
                                           HW, 8)
        x0 = jnp.zeros((1, HW, HW, 8))
        with pytest.raises(ValueError, match="unknown stream_fold mode"):
            ops.fold_chunk(x0, frames, w_q, a, stride=1, dv_unit=0.01,
                           mode="conv")


# ---------------------------------------------------------------------------
# accumulator wiring (use_kernel switch) + offline telescope
# ---------------------------------------------------------------------------

def _deployment(circuit, t_intg_ms):
    from repro.core.codesign import P2MModelConfig
    from repro.core.p2m_layer import P2MConfig
    from repro.core.snn import SpikingCNNConfig

    model = P2MModelConfig(
        p2m=P2MConfig(out_channels=8, n_sub=2, t_intg_ms=t_intg_ms,
                      leak=LeakageConfig(circuit=circuit)),
        backbone=SpikingCNNConfig(channels=(8, 16), input_hw=(HW, HW),
                                  fc_hidden=32, n_classes=5,
                                  first_layer_external=True),
        coarse_window_ms=1000.0)
    return deploy_mod.fresh_deployment(model, seed=0)


class TestAccumulatorWiring:
    def test_fold_bit_exact_and_inactive_lanes_kept(self):
        """make_stream_fns(use_kernel=True).fold ≡ the scan fold bitwise,
        and inactive (capacity-padding) lanes keep their old state on
        both paths."""
        dep = _deployment(CircuitConfig.NULLIFIED, 250.0)
        n_sub = dep.model_cfg.p2m.n_sub
        capacity = 3
        fns_scan = accumulator.make_stream_fns(dep, capacity=capacity)
        fns_kern = accumulator.make_stream_fns(dep, capacity=capacity,
                                               use_kernel=True)
        key = jax.random.PRNGKey(7)
        frames = jax.random.poisson(key, 0.3,
                                    (capacity, n_sub, HW, HW, 2)).astype(
                                        jnp.float32)
        state = fns_scan.init_state()
        state["x"] = jax.random.normal(jax.random.fold_in(key, 1),
                                       state["x"].shape) * 0.05
        active = jnp.asarray([True, False, True])
        s_scan = fns_scan.fold(dict(state), frames, active)
        s_kern = fns_kern.fold(dict(state), frames, active)
        np.testing.assert_array_equal(np.asarray(s_kern["x"]),
                                      np.asarray(s_scan["x"]))
        # the masked (inactive) lane is untouched on both paths
        np.testing.assert_array_equal(np.asarray(s_kern["x"][1]),
                                      np.asarray(state["x"][1]))

    @pytest.mark.parametrize("circuit", [CircuitConfig.BASIC,
                                         CircuitConfig.NULLIFIED])
    @pytest.mark.parametrize("t_intg_ms", [100.0, 250.0])
    def test_telescope_matches_offline_curvefit(self, circuit, t_intg_ms):
        """Driving one coarse window through the KERNEL fold + readout
        reproduces the offline curve-fit forward: spike maps bit-equal,
        logits to 1e-5 — the telescoping identity survives the fusion,
        across 2 T_INTG × 2 circuits."""
        dep = _deployment(circuit, t_intg_ms)
        n_sub = dep.model_cfg.p2m.n_sub
        group = dep.model_cfg.coarsen_group()
        n_slots = group                       # exactly one coarse window
        frames = jax.random.poisson(
            jax.random.PRNGKey(int(t_intg_ms)), 0.3,
            (n_slots, n_sub, HW, HW, 2)).astype(jnp.float32)
        off = deploy_mod.offline_forward(dep, frames[None])

        fns = accumulator.make_stream_fns(dep, capacity=2,
                                          use_kernel=True)
        state = fns.init_state()
        active = jnp.asarray([True, False])
        spikes = []
        for t in range(n_slots):
            fr = jnp.concatenate(
                [frames[t][None], jnp.zeros((1, n_sub, HW, HW, 2))])
            state = fns.fold(state, fr, active)
            cm = jnp.asarray([(t + 1) % group == 0, False])
            state, out = fns.readout(state, active, cm)
            spikes.append(np.asarray(out["spikes"][0]))
        np.testing.assert_array_equal(np.stack(spikes),
                                      np.asarray(off["spikes"][0]))
        logits = np.asarray(state["logits"][0]) / int(state["n_coarse"][0])
        np.testing.assert_allclose(logits, np.asarray(off["logits"][0]),
                                   rtol=1e-5, atol=1e-6)
