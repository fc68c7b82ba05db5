"""Per-lane online adaptation (repro.stream.adapt) — the four contracts
that make it safe to ship:

1. **Adaptation-off parity** — an adapting engine that cannot learn
   (zero learning rates, or no labeled streams) serves bit-identically
   to the frozen PR 9 engine: same predictions, same logits to the last
   ulp, same ledger. The relinearized per-lane numerics seam must not
   perturb the frozen forward. Checked on one device here and on a
   forced 8-device lane mesh in the subprocess test.
2. **Lane isolation** — a lane's updates never touch another lane:
   unlabeled lanes keep exactly-zero deltas AND their streams' logits
   stay bit-equal to the frozen serve even while neighbouring lanes
   learn.
3. **Delta round trip** — harvest → save_adapt_delta → load_adapt_delta
   → apply_adapt_delta reproduces exactly what the lane served, and the
   load refuses tampered stamps, wrong bases, and stale base uids.
4. **Kernel guard** — the fused stream_fold kernel has no VJP, so
   adaptation with use_kernel=True must fail loudly at construction.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import p2m_layer  # noqa: E402
from repro.core.codesign import P2MModelConfig  # noqa: E402
from repro.core.leakage import CircuitConfig, LeakageConfig  # noqa: E402
from repro.core.p2m_layer import P2MConfig  # noqa: E402
from repro.core.snn import SpikingCNNConfig  # noqa: E402
from repro.data import sources  # noqa: E402
from repro.stream import deploy as deploy_mod  # noqa: E402
from repro.stream.adapt import AdaptConfig, make_adapt_fns  # noqa: E402
from repro.stream.engine import StreamEngine  # noqa: E402
from repro.stream.registry import Registry, compat_key  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
HW = 16


def _dep(seed=0, coarse_ms=200.0):
    src = sources.resolve_dataset("synthetic-gesture", hw=HW,
                                  duration_ms=400.0)
    model = P2MModelConfig(
        p2m=P2MConfig(out_channels=8, n_sub=2, t_intg_ms=100.0,
                      leak=LeakageConfig(circuit=CircuitConfig.NULLIFIED)),
        backbone=SpikingCNNConfig(channels=(8, 16), input_hw=(HW, HW),
                                  fc_hidden=32, n_classes=src.n_classes,
                                  first_layer_external=True),
        coarse_window_ms=coarse_ms)
    return src, deploy_mod.fresh_deployment(model, seed=seed)


class _MaskLabels:
    """Source wrapper hiding the label (-> -1) of every stream whose
    open order is NOT in ``labeled`` — so only chosen lanes can learn."""

    def __init__(self, src, labeled):
        self._src = src
        self._labeled = set(labeled)
        self._i = 0
        for attr in ("name", "height", "width", "n_classes", "duration_ms",
                     "sensor_hw"):
            setattr(self, attr, getattr(src, attr))

    def n_slots(self, t_intg_ms):
        return self._src.n_slots(t_intg_ms)

    def iter_event_chunks(self, key, *, chunk_us, slot_us=None):
        label, chunks = self._src.iter_event_chunks(key, chunk_us=chunk_us,
                                                    slot_us=slot_us)
        keep = self._i in self._labeled
        self._i += 1
        return (label if keep else -1), chunks


def _assert_bitexact(ref, got, *, check_labels=True):
    key = lambda r: r.stream_id  # noqa: E731
    assert len(ref.results) == len(got.results)
    for a, b in zip(sorted(ref.results, key=key),
                    sorted(got.results, key=key)):
        if check_labels:
            assert a.label == b.label
        assert a.prediction == b.prediction, a.stream_id
        assert a.n_events == b.n_events
        assert a.n_readouts == b.n_readouts
        np.testing.assert_array_equal(np.asarray(a.logits),
                                      np.asarray(b.logits))
    for k in ("n_offered", "n_admitted", "total_events", "total_readouts",
              "total_layer1_spikes"):
        assert getattr(ref, k) == getattr(got, k), k


# ---------------------------------------------------------------------------
# config + kernel guard
# ---------------------------------------------------------------------------

class TestAdaptConfig:
    def test_rejects_unknown_rule(self):
        with pytest.raises(ValueError, match="rule"):
            AdaptConfig(rule="hebbian-ish")

    def test_rejects_negative_lr(self):
        with pytest.raises(ValueError, match="learning rates"):
            AdaptConfig(lr_w=-1e-3)

    def test_rejects_nonpositive_clip(self):
        with pytest.raises(ValueError, match="clips"):
            AdaptConfig(clip_w=0.0)


class TestKernelGuard:
    """kernels/stream_fold carries no VJP: adaptation needs gradients
    through the fold, so use_kernel=True must fail at construction, not
    silently serve without learning (or crash mid-stream)."""

    def test_make_adapt_fns_raises(self):
        _, dep = _dep()
        with pytest.raises(ValueError, match="use_kernel"):
            make_adapt_fns(dep, capacity=2, adapt=AdaptConfig(),
                           use_kernel=True)

    def test_engine_raises(self):
        _, dep = _dep()
        with pytest.raises(ValueError, match="use_kernel"):
            StreamEngine(dep, capacity=2, use_kernel=True,
                         adapt=AdaptConfig())

    def test_kernel_without_adapt_still_allowed(self):
        _, dep = _dep()
        eng = StreamEngine(dep, capacity=2, use_kernel=True)
        assert eng.adapt is None


# ---------------------------------------------------------------------------
# adaptation-off bit-identity (single device; the mesh variant is the
# subprocess test at the bottom)
# ---------------------------------------------------------------------------

class TestAdaptOffParity:
    @pytest.mark.parametrize("served", ["deployment", "registry"])
    def test_zero_lr_bit_identical_to_frozen(self, served):
        """lr_w = lr_theta = 0: updates fire but deltas stay exactly 0,
        so the relinearized per-lane forward must reproduce the frozen
        engine's logits bit-for-bit — serving one deployment, or a
        two-entry registry whose lanes gather their base numerics from
        the bundle."""
        src, dep = _dep()
        variants = None
        if served == "registry":
            reg = Registry()
            reg.register("a", dep)
            reg.register("b", _dep(seed=1)[1])
            dep, variants = reg, ["a", "b"] * 3
        frozen = StreamEngine(dep, capacity=4).serve(src, 6, seed=0,
                                                     variants=variants)
        eng = StreamEngine(dep, capacity=4,
                           adapt=AdaptConfig(lr_w=0.0, lr_theta=0.0))
        adapted = eng.serve(src, 6, seed=0, variants=variants)
        _assert_bitexact(frozen, adapted)
        ast = jax.device_get(eng.state["adapt"])
        np.testing.assert_array_equal(np.asarray(ast["dw"]), 0.0)
        np.testing.assert_array_equal(np.asarray(ast["dtheta"]), 0.0)

    def test_unlabeled_streams_bit_identical_to_frozen(self):
        """No labels -> no update is ever applied, whatever the lr."""
        src, dep = _dep()
        frozen = StreamEngine(dep, capacity=4).serve(
            _MaskLabels(src, labeled=()), 4, seed=0)
        eng = StreamEngine(dep, capacity=4,
                           adapt=AdaptConfig(lr_w=0.5, lr_theta=1e-3))
        adapted = eng.serve(_MaskLabels(src, labeled=()), 4, seed=0)
        _assert_bitexact(frozen, adapted)
        ast = jax.device_get(eng.state["adapt"])
        assert int(np.asarray(ast["n_updates"]).sum()) == 0
        np.testing.assert_array_equal(np.asarray(ast["dw"]), 0.0)

    def test_artifact_reports_adaptation_off(self):
        src, dep = _dep()
        art = StreamEngine(dep, capacity=2).serve(src, 2,
                                                  seed=0).to_artifact()
        ad = art["adaptation"]
        assert ad["enabled"] is False
        assert ad["lanes"] == [] and ad["n_updates"] == 0


# ---------------------------------------------------------------------------
# lane isolation property
# ---------------------------------------------------------------------------

class TestLaneIsolation:
    def test_updates_never_perturb_other_lanes(self):
        """4 streams on 4 lanes, labels only on streams 0 and 2: lanes
        1/3 keep exactly-zero deltas and their streams' logits stay
        bit-equal to the frozen serve, even though lanes 0/2 are
        learning next to them in the same batched fold/readout."""
        src, dep = _dep()
        frozen = StreamEngine(dep, capacity=4).serve(
            _MaskLabels(src, labeled=(0, 2)), 4, seed=0)
        eng = StreamEngine(dep, capacity=4,
                           adapt=AdaptConfig(rule="surrogate", lr_w=0.5))
        rep = eng.serve(_MaskLabels(src, labeled=(0, 2)), 4, seed=0)

        ast = jax.device_get(eng.state["adapt"])
        n_upd = np.asarray(ast["n_updates"])
        dw = np.asarray(ast["dw"])
        assert n_upd[0] > 0 and n_upd[2] > 0
        assert np.linalg.norm(dw[0]) > 0 and np.linalg.norm(dw[2]) > 0
        # unpaced admission is sid-order, so stream i rode lane i
        for lane in (1, 3):
            assert n_upd[lane] == 0
            np.testing.assert_array_equal(dw[lane], 0.0)

        by_id = {r.stream_id: r for r in rep.results}
        for r in frozen.results:
            if r.stream_id in (1, 3):
                np.testing.assert_array_equal(
                    np.asarray(by_id[r.stream_id].logits),
                    np.asarray(r.logits))

    def test_reward_rule_also_isolated(self):
        src, dep = _dep()
        eng = StreamEngine(dep, capacity=4,
                           adapt=AdaptConfig(rule="reward", lr_w=0.1))
        eng.serve(_MaskLabels(src, labeled=(1,)), 4, seed=0)
        ast = jax.device_get(eng.state["adapt"])
        n_upd = np.asarray(ast["n_updates"])
        assert n_upd[1] > 0
        for lane in (0, 2, 3):
            assert n_upd[lane] == 0
            np.testing.assert_array_equal(np.asarray(ast["dw"])[lane], 0.0)


# ---------------------------------------------------------------------------
# delta checkpoint round trip + negative paths
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def adapted_engine():
    """One adapting serve whose lane 0 actually learned something."""
    src, dep = _dep()
    eng = StreamEngine(dep, capacity=2,
                       adapt=AdaptConfig(rule="surrogate", lr_w=0.5))
    eng.serve(src, 4, seed=0)
    return src, dep, eng


class TestDeltaRoundTrip:
    def test_harvest_save_load_apply_reserve(self, adapted_engine,
                                             tmp_path):
        """The full loop: the re-registered deployment's effective
        weights are EXACTLY what the adapted lane served —
        quantize(w_q_base + dw) at theta_base + dtheta — and it serves
        as a registry entry beside its base."""
        src, dep, eng = adapted_engine
        h = eng.harvest(0)
        assert h["n_updates"] > 0 and np.linalg.norm(h["dw"]) > 0
        deploy_mod.save_adapt_delta(tmp_path, h["base"], dw=h["dw"],
                                    dtheta=h["dtheta"], lane=h["lane"],
                                    n_updates=h["n_updates"],
                                    meta={"why": "test"})
        delta = deploy_mod.load_adapt_delta(tmp_path, h["base"])
        assert delta["n_updates"] == h["n_updates"]
        assert delta["meta"] == {"why": "test"}
        np.testing.assert_array_equal(delta["dw"], h["dw"])

        adapted = deploy_mod.apply_adapt_delta(h["base"], delta)
        w_base = p2m_layer.effective_weights(h["base"].params["p2m"],
                                             h["base"].model_cfg.p2m)
        w_served = p2m_layer.effective_weights(
            {**h["base"].params["p2m"],
             "w": np.asarray(w_base) + h["dw"]}, h["base"].model_cfg.p2m)
        w_adapted = p2m_layer.effective_weights(adapted.params["p2m"],
                                                adapted.model_cfg.p2m)
        np.testing.assert_array_equal(np.asarray(w_adapted),
                                      np.asarray(w_served))
        assert adapted.coeffs.v_threshold == pytest.approx(
            float(h["base"].coeffs.v_threshold) + h["dtheta"])
        assert adapted.record["adapted"]["n_updates"] == h["n_updates"]

        # same compat key -> registers and serves beside its base
        assert compat_key(adapted) == compat_key(dep)
        reg = Registry()
        reg.register("base", dep)
        entry = reg.register("base+adapt", adapted)
        rep = StreamEngine(reg, capacity=2,
                           default_entry="base+adapt").serve(src, 2, seed=1)
        assert len(rep.results) == 2
        assert all(r.entry == "base+adapt" and r.entry_uid == entry.uid
                   for r in rep.results)

    def test_zero_delta_is_identity(self, tmp_path):
        _, dep = _dep()
        w = p2m_layer.effective_weights(dep.params["p2m"],
                                        dep.model_cfg.p2m)
        deploy_mod.save_adapt_delta(tmp_path, dep,
                                    dw=np.zeros_like(np.asarray(w)),
                                    dtheta=0.0)
        same = deploy_mod.apply_adapt_delta(
            dep, deploy_mod.load_adapt_delta(tmp_path, dep))
        np.testing.assert_array_equal(
            np.asarray(p2m_layer.effective_weights(same.params["p2m"],
                                                   same.model_cfg.p2m)),
            np.asarray(w))
        assert same.coeffs.v_threshold == dep.coeffs.v_threshold

    def test_wrong_base_digest_rejected(self, tmp_path):
        _, dep = _dep(seed=0)
        _, other = _dep(seed=1)
        w = p2m_layer.effective_weights(dep.params["p2m"],
                                        dep.model_cfg.p2m)
        deploy_mod.save_adapt_delta(tmp_path, dep,
                                    dw=np.zeros_like(np.asarray(w)),
                                    dtheta=0.0)
        with pytest.raises(ValueError, match="digests to"):
            deploy_mod.load_adapt_delta(tmp_path, other)

    def test_stale_base_uid_rejected(self, tmp_path):
        _, dep = _dep()
        w = p2m_layer.effective_weights(dep.params["p2m"],
                                        dep.model_cfg.p2m)
        deploy_mod.save_adapt_delta(tmp_path, dep,
                                    dw=np.zeros_like(np.asarray(w)),
                                    dtheta=0.0, base_uid=3)
        assert deploy_mod.load_adapt_delta(
            tmp_path, dep, expect_uid=3)["base_uid"] == 3
        with pytest.raises(ValueError, match="hot-swapped"):
            deploy_mod.load_adapt_delta(tmp_path, dep, expect_uid=7)

    def test_tampered_stamp_rejected(self, tmp_path):
        _, dep = _dep()
        w = p2m_layer.effective_weights(dep.params["p2m"],
                                        dep.model_cfg.p2m)
        ckpt = deploy_mod.save_adapt_delta(
            tmp_path, dep, dw=np.zeros_like(np.asarray(w)), dtheta=0.0)
        index = ckpt / "index.json"
        good = json.loads(index.read_text())

        bad = json.loads(json.dumps(good))
        del bad["extra"]["base"]["digest"]
        index.write_text(json.dumps(bad))
        with pytest.raises(ValueError, match="corrupt"):
            deploy_mod.load_adapt_delta(tmp_path, dep)

        bad = json.loads(json.dumps(good))
        bad["extra"]["delta_schema"] = "p2m-deploy/v1"
        index.write_text(json.dumps(bad))
        with pytest.raises(ValueError, match="not an adaptation delta"):
            deploy_mod.load_adapt_delta(tmp_path, dep)

    def test_not_a_delta_checkpoint_rejected(self, tmp_path):
        """A plain training checkpoint (no delta stamp) is refused."""
        from repro.checkpoint import store
        _, dep = _dep()
        store.save_checkpoint(tmp_path, 0, {"dw": np.zeros(3)},
                              {"schema": "something-else"})
        with pytest.raises(ValueError, match="not an adaptation delta"):
            deploy_mod.load_adapt_delta(tmp_path, dep)

    def test_dw_shape_mismatch_rejected(self, tmp_path):
        _, dep = _dep()
        with pytest.raises(ValueError, match="shape"):
            deploy_mod.save_adapt_delta(tmp_path, dep,
                                        dw=np.zeros((2, 2)), dtheta=0.0)


class TestHarvestValidation:
    def test_frozen_engine_has_nothing_to_harvest(self):
        src, dep = _dep()
        eng = StreamEngine(dep, capacity=2)
        eng.serve(src, 2, seed=0)
        with pytest.raises(ValueError, match="without adapt"):
            eng.harvest(0)

    def test_never_served_lane_rejected(self, adapted_engine):
        _, _, eng = adapted_engine
        with pytest.raises(ValueError, match="out of range"):
            eng.harvest(99)
        fresh = StreamEngine(_dep()[1], capacity=2, adapt=AdaptConfig())
        with pytest.raises(ValueError, match="never served"):
            fresh.harvest(0)


# ---------------------------------------------------------------------------
# the v5 stats gate on a LIVE adapting artifact
# ---------------------------------------------------------------------------

class TestLiveArtifactGate:
    def test_adapting_artifact_passes_v5_gate(self, adapted_engine,
                                              tmp_path):
        src, _, eng = adapted_engine
        art = eng.serve(src, 2, seed=3).to_artifact()
        assert art["schema"] == "p2m-stream-serving/v5"
        ad = art["adaptation"]
        assert ad["enabled"] and ad["rule"] == "surrogate"
        assert ad["n_updates"] == sum(r["n_updates"] for r in ad["lanes"])
        path = tmp_path / "serving_stats.json"
        path.write_text(json.dumps(art))
        proc = subprocess.run(
            [sys.executable, str(REPO / "tools" / "check_stream_stats.py"),
             str(path), "--streams", "2"],
            capture_output=True, text=True)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "adapting (surrogate)" in proc.stdout


# ---------------------------------------------------------------------------
# lane-mesh composition (forced 8 host devices, like test_stream_shard)
# ---------------------------------------------------------------------------

_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    from repro.core.codesign import P2MModelConfig
    from repro.core.leakage import CircuitConfig, LeakageConfig
    from repro.core.p2m_layer import P2MConfig
    from repro.core.snn import SpikingCNNConfig
    from repro.data import sources
    from repro.stream import deploy as deploy_mod
    from repro.stream.adapt import AdaptConfig
    from repro.stream.engine import StreamEngine
    from repro.stream.shard import make_lane_executor

    assert jax.device_count() == 8, jax.device_count()
    hw = 16
    src = sources.resolve_dataset("synthetic-gesture", hw=hw,
                                  duration_ms=400.0)
    model = P2MModelConfig(
        p2m=P2MConfig(out_channels=8, n_sub=2, t_intg_ms=100.0,
                      leak=LeakageConfig(circuit=CircuitConfig.NULLIFIED)),
        backbone=SpikingCNNConfig(channels=(8, 16), input_hw=(hw, hw),
                                  fc_hidden=32, n_classes=src.n_classes,
                                  first_layer_external=True),
        coarse_window_ms=200.0)
    dep = deploy_mod.fresh_deployment(model, seed=0)

    def assert_same(a_rep, b_rep, tag):
        key = lambda r: r.stream_id
        for a, b in zip(sorted(a_rep.results, key=key),
                        sorted(b_rep.results, key=key)):
            assert a.prediction == b.prediction, (tag, a.stream_id)
            np.testing.assert_array_equal(np.asarray(a.logits),
                                          np.asarray(b.logits))
        assert a_rep.total_layer1_spikes == b_rep.total_layer1_spikes, tag
        print(tag, "bitexact")

    # 1) adaptation-off (zero-lr) on the mesh == frozen single-device
    frozen = StreamEngine(dep, capacity=4).serve(src, 6, seed=0)
    off = AdaptConfig(lr_w=0.0, lr_theta=0.0)
    for dev in (2, 4):
        eng = StreamEngine(dep, capacity=4, adapt=off,
                           executor=make_lane_executor(dev))
        assert_same(frozen, eng.serve(src, 6, seed=0), f"off_d{dev}")
        ast = jax.device_get(eng.state["adapt"])
        assert float(np.abs(np.asarray(ast["dw"])).max()) == 0.0

    # 2) LEARNING on the mesh == learning on one device: per-lane
    # updates have no cross-lane reduction, so shard_map over lanes
    # must not change a bit of the logits OR the learned deltas
    cfg = AdaptConfig(rule="surrogate", lr_w=0.5)
    e1 = StreamEngine(dep, capacity=4, adapt=cfg)
    r1 = e1.serve(src, 6, seed=0)
    e2 = StreamEngine(dep, capacity=4, adapt=cfg,
                      executor=make_lane_executor(2))
    r2 = e2.serve(src, 6, seed=0)
    assert_same(r1, r2, "learn_d2")
    a1 = jax.device_get(e1.state["adapt"])
    a2 = jax.device_get(e2.state["adapt"])
    assert int(np.asarray(a1["n_updates"]).sum()) > 0
    np.testing.assert_array_equal(np.asarray(a1["n_updates"]),
                                  np.asarray(a2["n_updates"]))
    np.testing.assert_array_equal(np.asarray(a1["dw"]),
                                  np.asarray(a2["dw"]))
    np.testing.assert_array_equal(np.asarray(a1["dtheta"]),
                                  np.asarray(a2["dtheta"]))
    h1, h2 = e1.harvest(0), e2.harvest(0)
    np.testing.assert_array_equal(h1["dw"], h2["dw"])
    assert h1["n_updates"] == h2["n_updates"]
    print("MESH_ADAPT_PASS")
""")


@pytest.mark.slow
def test_adaptation_composes_with_lane_mesh():
    """Forced 8-host-device subprocess: adaptation-off serves on 2/4
    device meshes are bit-identical to the frozen single-device serve,
    and a LEARNING serve produces bit-identical logits and deltas on
    the mesh vs one device."""
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "MESH_ADAPT_PASS" in proc.stdout
