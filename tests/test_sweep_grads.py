"""Gradient-correctness suite for the unfrozen phase-2 protocol.

Three layers of checks on the differentiable seam the unfrozen protocol
trains through (all on the CPU/interpret-friendly curvefit path):

  * finite-difference validation of ``jax.grad`` through
    ``p2m_forward_curvefit_stacked`` w.r.t. the layer-1 weights, per
    circuit config — including config (a), whose leak linearization
    (v_inf, tau) is itself a function of the kernel;
  * the frozen protocol's layer-1 gradients are EXACTLY zero (the
    ``stop_gradient`` contract the paper's §3 protocol relies on);
  * the grouped (per-config-params) forward matches the shared-params
    stacked forward when every config holds the same weights, and its
    gradients are per-config independent.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import enable_x64

from repro.core import codesign, snn
from repro.core import sweep as engine
from repro.core import leakage, p2m_layer
from repro.core.analog import AnalogConfig
from repro.core.codesign import P2MModelConfig
from repro.core.leakage import CircuitConfig, LeakageConfig
from repro.core.p2m_layer import P2MConfig, p2m_init
from repro.core.snn import SpikingCNNConfig
from repro.data import events as ev_mod

CIRCUITS = (CircuitConfig.BASIC, CircuitConfig.SWITCH,
            CircuitConfig.NULLIFIED)


def _setup(analog: AnalogConfig | None = None, key: int = 0):
    kw = dict(out_channels=4, t_intg_ms=10.0, n_sub=3)
    if analog is not None:
        kw["analog"] = analog
    cfg = P2MConfig(**kw)
    params = p2m_init(jax.random.PRNGKey(key), cfg)
    ev = jax.random.poisson(jax.random.PRNGKey(key + 1), 0.4,
                            (1, 2, cfg.n_sub, 8, 8, 2)).astype(
                                params["w"].dtype)
    return cfg, params, ev


class TestFiniteDifference:
    """``jax.grad`` through the stacked curvefit forward must match a
    central finite difference of a v_pre readout (rtol ≤ 1e-3).

    Two deliberate choices make FD meaningful: the readout is the
    pre-comparator voltage (the spike comparator is a step function — its
    surrogate gradient is exactly what FD must NOT see), and the weight
    quantizer runs at a very fine step (the straight-through estimator's
    analytic gradient is quantizer-independent, but FD of a coarse
    staircase measures the steps, not the slope). float64 keeps the FD
    truncation/roundoff error far below the tolerance.
    """

    @pytest.mark.parametrize("circuit", CIRCUITS, ids=lambda c: c.value)
    def test_grad_matches_fd_per_circuit(self, circuit):
        with enable_x64():
            cfg, params, ev = _setup(AnalogConfig(weight_levels=1 << 40))
            leak_cfgs = (LeakageConfig(circuit=circuit),)
            kc, kd = jax.random.split(jax.random.PRNGKey(42))

            _, v0 = p2m_layer.p2m_forward_curvefit_stacked(params, ev, cfg,
                                                           leak_cfgs)
            cot = jax.random.normal(kc, v0.shape)

            def scalar(w):
                p = {**params, "w": w}
                _, v = p2m_layer.p2m_forward_curvefit_stacked(p, ev, cfg,
                                                              leak_cfgs)
                return jnp.vdot(v, cot)

            w0 = params["w"]
            g = jax.grad(scalar)(w0)
            assert np.isfinite(np.asarray(g)).all()

            d = jax.random.normal(kd, w0.shape)
            d = d / jnp.linalg.norm(d)
            eps = 1e-3
            fd = (scalar(w0 + eps * d) - scalar(w0 - eps * d)) / (2 * eps)
            analytic = jnp.vdot(g, d)
            assert float(jnp.abs(fd)) > 1e-6, "degenerate FD probe"
            np.testing.assert_allclose(float(analytic), float(fd), rtol=1e-3)

    def test_basic_grad_flows_through_leak_linearization(self):
        """Config (a)'s v_inf/tau depend on the kernel: the gradient must
        differ from one with the leak params detached — i.e. the unfrozen
        protocol really trains through the re-linearized leak."""
        cfg, params, ev = _setup()
        leak_cfgs = (LeakageConfig(circuit=CircuitConfig.BASIC),)
        co = leakage.leak_coeffs(leak_cfgs[0])

        def v_sum(w, detach_leak):
            p = {**params, "w": w}
            w_q = p2m_layer.effective_weights(p, cfg)
            lk = leakage.leak_params_from_coeffs(w_q, co)
            if detach_leak:
                lk = jax.tree.map(jax.lax.stop_gradient, lk)
            return jnp.sum(p2m_layer._curvefit_from_lk(p, ev, cfg, w_q, lk))

        g_full = jax.grad(lambda w: v_sum(w, False))(params["w"])
        g_detached = jax.grad(lambda w: v_sum(w, True))(params["w"])
        assert float(jnp.max(jnp.abs(g_full - g_detached))) > 1e-7


def _mini_model():
    model = P2MModelConfig(
        p2m=P2MConfig(out_channels=8, n_sub=2, t_intg_ms=60.0),
        backbone=SpikingCNNConfig(channels=(8, 8, 8, 8), input_hw=(16, 16),
                                  fc_hidden=16, n_classes=5,
                                  first_layer_external=True),
        coarse_window_ms=120.0)
    data = ev_mod.EventStreamConfig(name="gesture", height=16, width=16,
                                    n_classes=5, duration_ms=240.0)
    return model, data


class TestFrozenProtocolGrads:
    def test_frozen_loss_layer1_grads_exactly_zero(self):
        """The frozen phase-2 loss (stacked layer-1 forward outside the
        gradient, stop_gradient on the coarse spikes) must give EXACTLY
        zero layer-1 gradients — not merely small ones."""
        model, data = _mini_model()
        leak_cfgs = engine.expand_leak_configs(engine.SweepGrid(),
                                               model.p2m.leak)
        G = len(leak_cfgs)
        key = jax.random.PRNGKey(0)
        params, state = codesign.model_init(key, model)
        bb_s = engine._stack_tree(params["backbone"], G)
        state_s = engine._stack_tree(state, G)
        ev, labels = ev_mod.sample_batch(key, data, 2, model.p2m.t_intg_ms,
                                         n_sub=model.p2m.n_sub)

        def frozen_loss(p2m_params):
            coarse_s, _ = engine._layer1_coarse(p2m_params, ev, model,
                                                leak_cfgs)
            coarse_s = jax.lax.stop_gradient(coarse_s)

            def per_cfg(bb_p, st, coarse):
                logits, _, _ = snn.spiking_cnn_apply(
                    bb_p, st, coarse, model.backbone, train=True)
                return snn.cross_entropy(logits, labels)

            return jnp.sum(jax.vmap(per_cfg)(bb_s, state_s, coarse_s))

        g = jax.grad(frozen_loss)(params["p2m"])
        for leaf in jax.tree.leaves(g):
            np.testing.assert_array_equal(np.asarray(leaf), 0.0)

    def test_unfrozen_loss_layer1_grads_nonzero_and_finite(self):
        """The unfrozen counterpart (no stop_gradient, per-config leak
        re-linearization) must produce finite, nonzero layer-1 grads for
        every circuit config."""
        model, data = _mini_model()
        leak_cfgs = engine.expand_leak_configs(engine.SweepGrid(),
                                               model.p2m.leak)
        coeffs_s = leakage.stacked_leak_coeffs(leak_cfgs)
        G = len(leak_cfgs)
        key = jax.random.PRNGKey(0)
        params, state = codesign.model_init(key, model)
        bb_s = engine._stack_tree(params["backbone"], G)
        state_s = engine._stack_tree(state, G)
        p2m_s = p2m_layer.stack_p2m_params(params["p2m"], G)
        ev, labels = ev_mod.sample_batch(key, data, 2, model.p2m.t_intg_ms,
                                         n_sub=model.p2m.n_sub)

        def unfrozen_loss(p2m_params_s):
            def per_cfg(p2m_p, bb_p, st, co):
                coarse, _ = engine._layer1_coarse_one(p2m_p, ev, model, co)
                logits, _, _ = snn.spiking_cnn_apply(
                    bb_p, st, coarse, model.backbone, train=True)
                return snn.cross_entropy(logits, labels)

            return jnp.sum(jax.vmap(per_cfg)(p2m_params_s, bb_s, state_s,
                                             coeffs_s))

        g = jax.grad(unfrozen_loss)(p2m_s)
        assert np.isfinite(np.asarray(g["w"])).all()
        for i in range(G):
            assert float(jnp.max(jnp.abs(g["w"][i]))) > 0.0, \
                f"zero layer-1 grad for config {leak_cfgs[i].circuit.value}"


class TestGroupedForward:
    def test_grouped_matches_stacked_with_shared_params(self):
        cfg, params, ev = _setup()
        leak_cfgs = tuple(LeakageConfig(circuit=c) for c in CIRCUITS)
        s0, v0 = p2m_layer.p2m_forward_curvefit_stacked(params, ev, cfg,
                                                        leak_cfgs)
        p_s = p2m_layer.stack_p2m_params(params, len(leak_cfgs))
        s1, v1 = p2m_layer.p2m_forward_curvefit_grouped(p_s, ev, cfg,
                                                        leak_cfgs)
        np.testing.assert_allclose(np.asarray(v0), np.asarray(v1),
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))

    def test_grouped_grads_per_config_independent(self):
        """Config g's output depends only on params slice g: the gradient
        of a single config's readout must vanish on every other slice."""
        cfg, params, ev = _setup()
        leak_cfgs = tuple(LeakageConfig(circuit=c) for c in CIRCUITS)
        p_s = p2m_layer.stack_p2m_params(params, len(leak_cfgs))

        def one_cfg_readout(p_s):
            _, v = p2m_layer.p2m_forward_curvefit_grouped(p_s, ev, cfg,
                                                          leak_cfgs)
            return jnp.sum(v[0] ** 2)

        g = jax.grad(one_cfg_readout)(p_s)
        assert float(jnp.max(jnp.abs(g["w"][0]))) > 0.0
        np.testing.assert_array_equal(np.asarray(g["w"][1:]), 0.0)

    def test_grouped_leak_params_match_per_config(self):
        w_s = jax.random.normal(jax.random.PRNGKey(3), (3, 3, 3, 2, 6))
        cfgs = leakage.paper_circuits()
        lk = leakage.grouped_leak_params(w_s, cfgs)
        for i, c in enumerate(cfgs):
            ref = leakage.kernel_leak_params(w_s[i], c)
            np.testing.assert_array_equal(np.asarray(lk.v_inf[i]),
                                          np.asarray(ref.v_inf))
            np.testing.assert_array_equal(np.asarray(lk.tau_ms[i]),
                                          np.asarray(ref.tau_ms))


class TestJointOptimizerLRSplit:
    """The unfrozen joint update's per-group optimizer: lr_p2m=None must be
    a pure refactor of the single-optimizer update, and a split LR must
    move ONLY the layer-1 leaf group differently."""

    def _joint(self):
        from repro.optim import adamw
        key = jax.random.PRNGKey(7)
        joint = {"p2m": {"w": jax.random.normal(key, (3, 3, 2, 4))},
                 "backbone": {"w": jax.random.normal(
                     jax.random.fold_in(key, 1), (8, 8))}}
        grads = jax.tree.map(jnp.ones_like, joint)
        return adamw, joint, grads

    def test_equal_lrs_match_single_optimizer(self):
        adamw, joint, grads = self._joint()
        single = adamw(2e-3)
        split = engine.joint_optimizer(adamw(2e-3), adamw(2e-3))
        up_1, _ = single.update(grads, single.init(joint), joint)
        up_2, _ = split.update(grads, split.init(joint), joint)
        for grp in ("p2m", "backbone"):
            np.testing.assert_array_equal(np.asarray(up_1[grp]["w"]),
                                          np.asarray(up_2[grp]["w"]))

    def test_split_lr_moves_only_layer1(self):
        adamw, joint, grads = self._joint()
        ref = engine.joint_optimizer(adamw(2e-3), adamw(2e-3))
        split = engine.joint_optimizer(adamw(2e-3), adamw(1e-4))
        up_r, _ = ref.update(grads, ref.init(joint), joint)
        up_s, _ = split.update(grads, split.init(joint), joint)
        np.testing.assert_array_equal(np.asarray(up_r["backbone"]["w"]),
                                      np.asarray(up_s["backbone"]["w"]))
        assert float(jnp.max(jnp.abs(up_r["p2m"]["w"] - up_s["p2m"]["w"]))) \
            > 0.0

    def test_run_grid_lr_p2m_changes_learned_layer1(self):
        """End-to-end: the same unfrozen fast cell with a different layer-1
        LR must produce different learned-kernel retention for the
        kernel-dependent circuit (a) — the lr_p2m knob actually reaches
        the in-pixel weights."""
        from dataclasses import replace as dc_replace

        from repro.core.codesign import SweepConfig
        from repro.data import events as events_mod

        model = P2MModelConfig(
            p2m=P2MConfig(out_channels=8, n_sub=2, t_intg_ms=120.0),
            backbone=SpikingCNNConfig(channels=(8, 8, 8, 8),
                                      input_hw=(16, 16), fc_hidden=16,
                                      n_classes=5,
                                      first_layer_external=True),
            coarse_window_ms=120.0)
        data = ev_mod.EventStreamConfig(name="gesture", height=16, width=16,
                                        n_classes=5, duration_ms=240.0)
        grid = engine.SweepGrid(circuits=(CircuitConfig.BASIC,),
                                t_intg_grid_ms=(120.0,))
        sweep_cfg = SweepConfig(batch_size=2, pretrain_steps=2,
                                finetune_steps=3, eval_batches=1)
        rec = {}
        for lr_p2m in (None, 0.2):
            sw = dc_replace(sweep_cfg, lr_p2m=lr_p2m)
            res = engine.run_grid(data, model, sw, grid,
                                  log=lambda *_: None, protocol="unfrozen")
            rec[lr_p2m] = res.records[0]["retention_err_v"]
        assert rec[None] != rec[0.2]
