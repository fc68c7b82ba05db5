"""Spikformer behind the P²M in-pixel layer (repro.core.spikformer, the
backbone seam repro.core.backbone) at a CPU size: 64×64 sensor, D = 32,
4 heads, depth 2, 16 tokens, on seeded random weights that the plain
reference (bench/references/p2m_spikformer.py) makes.

* stepping T coarse frames equals the batched forward;
* the program's offline forward equals the plain reference;
* the engine's served logits and layer-1 spikes equal the offline
  forward, as tests/test_streaming.py pins for the CNN;
* the seam: config dicts round-trip for both kinds, a dict without
  ``kind`` is the CNN, adaptation refuses Spikformer.
"""
from __future__ import annotations

import copy
import json
import sys
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.configs import p2m_dvs, p2m_spikformer  # noqa: E402
from repro.core import backbone, codesign, spikformer  # noqa: E402
from repro.core.snn import SpikingCNNConfig  # noqa: E402
from repro.core.spikformer import SpikformerConfig  # noqa: E402
from repro.stream import deploy as deploy_mod  # noqa: E402
from repro.stream.adapt import AdaptConfig  # noqa: E402
from repro.stream.engine import StreamEngine  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import catalog, compare, run  # noqa: E402
from bench.traffic import generator  # noqa: E402

HW, D, HEADS = 64, 32, 4
CONFIG = "p2m_spikformer_gesture128"


def _small_config() -> dict:
    """The benchmark's configuration cut to the CPU size: 300 ms
    streams, three 100 ms backbone steps each."""
    cfg = copy.deepcopy(catalog.config(CONFIG))
    m = cfg["model"]
    m["backbone"].update(embed_dim=D, heads=HEADS, input_hw=[HW, HW])
    m["p2m"]["out_channels"] = D // 8
    cfg["stream"].update(height=HW, width=HW, duration_ms=300.0)
    return cfg


@pytest.fixture(scope="module")
def setup():
    """Reference-made weights, a pool of 4 streams, the program's
    deployment holding the weights, and the reference's answers."""
    cfg = _small_config()
    ref_mod, weights, bn_state, pool = run.make_inputs(
        cfg, {"pool": 4}, 2 ** 31 + 11)
    dep = run.make_deployment(cfg, weights, bn_state)
    ref = jax.device_get(compare.reference_forward(ref_mod, cfg)(
        weights, bn_state, pool.counts))
    return cfg, dep, pool, ref


def _frames(dep, pool) -> jnp.ndarray:
    """The pool's sub-slot counts as the offline forward takes them:
    [B, windows, n_sub, H, W, 2]."""
    n_sub = dep.model_cfg.p2m.n_sub
    c = np.asarray(pool.counts, np.float32)
    return jnp.asarray(c.reshape((c.shape[0], -1, n_sub) + c.shape[2:]))


def test_every_lif_layer_fires(setup):
    _, _, _, ref = setup
    # otherwise the comparisons below would cover no decision there
    assert (ref["backbone_spikes"].sum(axis=0) > 0).all()
    assert (ref["layer1_spikes"] > 0).all()


def test_stream_steps_equal_the_batched_forward(setup):
    _, dep, pool, _ = setup
    cfg = dep.model_cfg.backbone
    off = deploy_mod.offline_forward(dep, _frames(dep, pool))
    coarse = off["coarse"]
    params, state = dep.params["backbone"], dep.bn_state
    logits, _, aux = spikformer.apply(params, state, coarse, cfg,
                                      train=False)
    mem = spikformer.stream_init(cfg, coarse.shape[0])
    assert set(aux) == {f"spikes/{n}" for n in mem}
    acc = 0.0
    for t in range(coarse.shape[1]):
        lt, mem = spikformer.stream_step(params, state, mem, coarse[:, t],
                                         cfg)
        acc = acc + lt
    # the same float32 ops over other batch shapes: rounding only, and
    # no LIF decision moves at this seed
    np.testing.assert_allclose(np.asarray(acc / coarse.shape[1]),
                               np.asarray(logits), rtol=1e-5, atol=1e-5)


def test_membranes_are_the_published_count():
    mem = spikformer.stream_init(p2m_spikformer.CONFIG.backbone, 1)
    # SPS 475,136 floats + two encoder blocks of 163,840
    assert sum(v.size for v in mem.values()) == 475_136 + 327_680


def test_offline_forward_equals_the_plain_reference(setup):
    _, dep, pool, ref = setup
    off = deploy_mod.offline_forward(dep, _frames(dep, pool))
    pooled = np.asarray(off["pooled"]).sum(axis=(1, 2, 3, 4))
    # layer-1 decisions: within the reference's near-threshold count
    # (float32 rounding of the charge may decide those either way)
    assert (np.abs(pooled - ref["layer1_spikes"])
            <= ref["layer1_near"]).all()
    # the backbone: float32 on both sides, in another order of sums; a
    # relative 1e-4 is rounding, a flipped LIF decision moves a logit by
    # about a tenth of its range
    got = np.asarray(off["logits"])
    scale = np.abs(ref["logits"]).max(axis=1, keepdims=True)
    assert (np.abs(got - ref["logits"]) / scale).max() < 1e-4


@pytest.mark.parametrize("capacity", [2, 3])
def test_served_equals_the_offline_forward(setup, capacity):
    _, dep, pool, _ = setup
    off = deploy_mod.offline_forward(dep, _frames(dep, pool))
    engine = StreamEngine(dep, capacity=capacity)
    report = engine.serve(generator.ReplaySource(pool), pool.size, seed=0)
    assert len(report.results) == pool.size
    pooled = np.asarray(off["pooled"]).sum(axis=(1, 2, 3, 4))
    for r in report.results:
        # the label is the pool index the stream replays
        assert r.n_layer1_spikes == pooled[r.label]
        assert r.n_coarse_frames == 3
        # the engine's fold telescopes the offline curve fit (charge to a
        # few ulp); the backbone is the same step over another batch
        np.testing.assert_allclose(np.asarray(r.logits),
                                   np.asarray(off["logits"][r.label]),
                                   rtol=1e-5, atol=1e-5)


def test_registry_serving_equals_single_serving(setup):
    """make_stream_fns(registry=True) steps the backbone through the same seam:
    one Spikformer entry in a registry serves what the deployment alone
    serves."""
    from repro.stream.registry import Registry

    _, dep, pool, _ = setup
    single = StreamEngine(dep, capacity=2).serve(
        generator.ReplaySource(pool), 2, seed=0)
    reg = Registry()
    reg.register("spikformer", dep)
    multi = StreamEngine(reg, capacity=2).serve(
        generator.ReplaySource(pool), 2, seed=0)
    assert [(r.label, r.prediction, r.n_layer1_spikes)
            for r in multi.results] == \
        [(r.label, r.prediction, r.n_layer1_spikes) for r in single.results]
    for a, b in zip(multi.results, single.results):
        np.testing.assert_allclose(a.logits, b.logits, rtol=1e-6, atol=1e-6)


def test_config_round_trips_for_both_kinds():
    for model in (p2m_dvs.CONFIG, p2m_spikformer.CONFIG):
        d = json.loads(json.dumps(deploy_mod.model_config_to_dict(model)))
        assert deploy_mod.model_config_from_dict(d) == model
    d = deploy_mod.model_config_to_dict(p2m_spikformer.CONFIG)
    assert d["backbone"]["kind"] == "spikformer"
    assert isinstance(deploy_mod.model_config_from_dict(d).backbone,
                      SpikformerConfig)


def test_a_dict_without_kind_is_the_cnn(tmp_path):
    cnn = deploy_mod.model_config_to_dict(p2m_dvs.CONFIG)
    assert "kind" not in cnn["backbone"]
    for name in ("p2m_gesture128", "p2m_nmnist34"):
        model = deploy_mod.model_config_from_dict(
            catalog.config(name)["model"])
        assert isinstance(model.backbone, SpikingCNNConfig)
        assert model.backbone.kind == "cnn"
    # a saved deployment of the CNN loads as it was written
    model, _ = p2m_dvs.reduced(hw=16, channels=(4, 8, 8, 8), fc=16)
    dep = deploy_mod.fresh_deployment(model, seed=0)
    deploy_mod.save_deployment(tmp_path / "ckpt", dep)
    loaded = deploy_mod.load_deployment(tmp_path / "ckpt")
    assert loaded.model_cfg == model
    assert isinstance(loaded.model_cfg.backbone, SpikingCNNConfig)


def test_unknown_kind_is_refused():
    d = deploy_mod.model_config_to_dict(p2m_spikformer.CONFIG)
    d["backbone"]["kind"] = "mamba"
    with pytest.raises(ValueError, match="unknown backbone kind"):
        deploy_mod.model_config_from_dict(d)


def test_the_in_pixel_layer_must_be_sps_stage_0():
    from dataclasses import replace

    model = p2m_spikformer.CONFIG
    with pytest.raises(ValueError, match="SPS stage 0"):
        replace(model, p2m=replace(model.p2m, out_channels=16))


def test_the_seam_keeps_the_cnn_functions():
    from repro.core import snn

    cnn = backbone.KINDS["cnn"]
    assert (cnn.init, cnn.apply, cnn.stream_init, cnn.stream_step) == (
        snn.spiking_cnn_init, snn.spiking_cnn_apply,
        snn.spiking_cnn_stream_init, snn.spiking_cnn_stream_step)
    model, _ = p2m_spikformer.reduced()
    params, state = codesign.model_init(jax.random.PRNGKey(0), model)
    assert set(params["backbone"]) >= {"sps1", "rpe", "b1_mlp2", "head"}


def test_adaptation_refuses_spikformer(setup):
    _, dep, _, _ = setup
    with pytest.raises(ValueError, match="'spikformer' backbone cannot "
                                         "adapt"):
        StreamEngine(dep, capacity=2, adapt=AdaptConfig())
