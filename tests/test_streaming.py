"""Online streaming inference subsystem tests (repro.stream).

The load-bearing guarantee: replaying a fixture recording through the
online leak-aware accumulator and reading out at every T_INTG boundary
matches the offline path — ``data.binning.bin_chunks`` frames through
the offline batched forward (``repro.stream.deploy.offline_forward``) —
within tight tolerance, across ≥2 T_INTG values, ≥2 circuit variants,
and BOTH phase-2 protocols' deployed checkpoints."""
from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core.leakage import CircuitConfig  # noqa: E402
from repro.data import fixtures, sources  # noqa: E402
from repro.data.binning import bin_chunks, slot_us_for  # noqa: E402
from repro.data.formats import concat_chunks  # noqa: E402
from repro.stream import deploy as deploy_mod  # noqa: E402
from repro.stream.engine import STATS_SCHEMA, StreamEngine  # noqa: E402

HW = 16


@pytest.fixture(scope="module")
def fixture_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("dvs128-stream")
    fixtures.make_dvs128_fixture(root, n_recordings=1,
                                 trials_per_recording=4)
    return root


@pytest.fixture(scope="module")
def file_source(fixture_root):
    return sources.resolve_dataset("dvs128", hw=HW,
                                   data_root=str(fixture_root), split="all")


@pytest.fixture(scope="module")
def trained(fixture_root, tmp_path_factory):
    """One tiny sweep over 2 circuits × 2 T_INTG with keep_params, both
    protocols — the deployment menu every parity case slices from."""
    out = tmp_path_factory.mktemp("deploy")
    return deploy_mod.train_and_deploy(
        out, dataset="dvs128", data_root=str(fixture_root), hw=HW,
        protocols=("frozen", "unfrozen"), smoke=True,
        t_intg_grid_ms=(100.0, 1000.0),
        circuits=(CircuitConfig.BASIC, CircuitConfig.NULLIFIED),
        log=lambda *_: None)


def _offline_frames(source, index: int, t_intg_ms: float, n_sub: int
                    ) -> np.ndarray:
    """The OFFLINE binning of one recording: [n_slots, n_sub, H, W, 2]."""
    n_slots = source.n_slots(t_intg_ms)
    slot_us = slot_us_for(t_intg_ms, n_sub)
    frames = bin_chunks([source.sample_events(index)],
                        n_total=n_slots * n_sub, slot_us=slot_us,
                        sensor_hw=source.sensor_hw, out_hw=(HW, HW))
    return frames.reshape(n_slots, n_sub, HW, HW, 2)


class _PinnedSource:
    """Source wrapper replaying a FIXED sample sequence (round-robin) —
    so the parity tests know exactly which recording each serving lane
    streamed."""

    def __init__(self, src, indices):
        self._src = src
        self._indices = list(indices)
        self._i = 0
        for attr in ("name", "height", "width", "n_classes", "duration_ms",
                     "sensor_hw"):
            setattr(self, attr, getattr(src, attr))

    def n_slots(self, t_intg_ms):
        return self._src.n_slots(t_intg_ms)

    def iter_event_chunks(self, key, *, chunk_us, slot_us=None):
        idx = self._indices[self._i % len(self._indices)]
        self._i += 1
        return self._src.iter_event_chunks(key, chunk_us=chunk_us,
                                           slot_us=slot_us, index=idx)


# ---------------------------------------------------------------------------
# replay layer
# ---------------------------------------------------------------------------

class TestReplay:
    def test_file_replay_rebins_to_offline_frames(self, file_source):
        """Chunk-by-chunk re-binning of the replayed stream reproduces
        the offline binner's frames exactly (same slot grid, same
        sensor→model downscale)."""
        t_intg, n_sub = 100.0, 2
        slot_us = slot_us_for(t_intg, n_sub)
        chunk_us = slot_us  # one chunk per fine sub-slot
        label, chunks = file_source.iter_event_chunks(
            jax.random.PRNGKey(0), chunk_us=chunk_us, index=1)
        assert label == file_source.samples[1].label
        offline = _offline_frames(file_source, 1, t_intg, n_sub)
        n_total = offline.shape[0] * n_sub
        got = []
        for i, c in enumerate(chunks):
            got.append(bin_chunks([c], n_total=1, slot_us=slot_us,
                                  sensor_hw=file_source.sensor_hw,
                                  out_hw=(HW, HW), t0_us=i * chunk_us)[0])
        assert len(got) == n_total          # empty chunks yielded too
        np.testing.assert_array_equal(
            np.stack(got).reshape(offline.shape), offline)

    def test_file_replay_conserves_events(self, file_source):
        ev = file_source.sample_events(0)
        _, chunks = file_source.iter_event_chunks(
            jax.random.PRNGKey(0), chunk_us=50_000, index=0)
        replayed = concat_chunks(chunks)
        dur_us = int(file_source.duration_ms * 1000)
        in_window = int((ev.t < dur_us).sum())
        assert len(replayed) == in_window > 0
        assert (np.diff(replayed.t) >= 0).all()    # time-ordered replay

    def test_synthetic_replay_chunks(self):
        src = sources.resolve_dataset("synthetic-gesture", hw=HW)
        label, chunks = src.iter_event_chunks(
            jax.random.PRNGKey(3), chunk_us=100_000, slot_us=50_000)
        chunks = list(chunks)
        assert 0 <= label < src.n_classes
        assert len(chunks) == 20            # 2000 ms / 100 ms
        total = sum(len(c) for c in chunks)
        assert total > 0
        for i, c in enumerate(chunks):      # timestamps inside the chunk
            if len(c):
                assert c.t.min() >= i * 100_000
                assert c.t.max() < (i + 1) * 100_000

    def test_bad_chunk_width_raises(self, file_source):
        with pytest.raises(ValueError, match="does not divide"):
            file_source.iter_event_chunks(jax.random.PRNGKey(0),
                                          chunk_us=300_000)


# ---------------------------------------------------------------------------
# backbone streaming step parity (snn)
# ---------------------------------------------------------------------------

def test_backbone_stream_step_matches_batched():
    from repro.core import snn

    cfg = snn.SpikingCNNConfig(channels=(8, 16, 16, 16), input_hw=(HW, HW),
                               fc_hidden=32, n_classes=5,
                               first_layer_external=True)
    key = jax.random.PRNGKey(0)
    params, state = snn.spiking_cnn_init(key, cfg)
    B, T = 2, 6
    x = jax.random.poisson(jax.random.PRNGKey(1),
                           1.0, (B, T, HW // 2, HW // 2, 8)).astype(
                               jnp.float32)
    logits_ref, _, _ = snn.spiking_cnn_apply(params, state, x, cfg,
                                             train=False)
    mem = snn.spiking_cnn_stream_init(cfg, B)
    acc = jnp.zeros((B, cfg.n_classes))
    for t in range(T):
        lt, mem = snn.spiking_cnn_stream_step(params, state, mem,
                                              x[:, t], cfg)
        acc = acc + lt
    np.testing.assert_allclose(np.asarray(acc / T), np.asarray(logits_ref),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# streaming vs offline parity — the acceptance bar
# ---------------------------------------------------------------------------

class TestStreamingOfflineParity:
    def _parity_case(self, trained, file_source, tmp_path, protocol,
                     record, capacity=2, use_kernel=False):
        result = trained["results"][protocol]
        ckpt = tmp_path / f"ckpt_{protocol}_{record['label']}_" \
                          f"{record['t_intg_ms']:g}"
        deploy_mod.deploy_from_sweep(result, _model_of(trained), record,
                                     ckpt)
        dep = deploy_mod.load_deployment(ckpt)
        n_sub = dep.model_cfg.p2m.n_sub
        indices = [0, 1, 2]
        frames = np.stack([_offline_frames(file_source, i,
                                           record["t_intg_ms"], n_sub)
                           for i in indices])
        off = deploy_mod.offline_forward(dep, jnp.asarray(frames))
        off_logits = np.asarray(off["logits"])

        engine = StreamEngine(dep, capacity=capacity,
                              use_kernel=use_kernel)
        report = engine.serve(_PinnedSource(file_source, indices),
                              len(indices), seed=0)
        assert len(report.results) == len(indices)
        by_id = {r.stream_id: r for r in report.results}
        for k, idx in enumerate(indices):
            r = by_id[k]
            assert r.label == file_source.samples[idx].label
            np.testing.assert_allclose(
                np.asarray(r.logits), off_logits[k], rtol=1e-5, atol=1e-5,
                err_msg=f"{protocol} {record['label']} "
                        f"T={record['t_intg_ms']} stream {k}")
            assert r.prediction == int(np.argmax(off_logits[k]))
            assert r.n_readouts == file_source.n_slots(record["t_intg_ms"])

    @pytest.mark.parametrize("protocol", ["frozen", "unfrozen"])
    def test_parity_all_cells(self, trained, file_source, tmp_path,
                              protocol):
        """Every (circuit, T_INTG) record of the trained grid — 2
        circuits × 2 T_INTG — serves online with logits matching the
        offline batched forward."""
        records = trained["results"][protocol].records
        assert len(records) == 4
        assert {r["circuit"] for r in records} == {"a", "c"}
        assert {r["t_intg_ms"] for r in records} == {100.0, 1000.0}
        for record in records:
            self._parity_case(trained, file_source, tmp_path, protocol,
                              record)

    def test_parity_use_kernel_all_cells(self, trained, file_source,
                                         tmp_path):
        """The fused stream_fold kernel path (use_kernel=True) holds the
        SAME offline-parity contract across the full 2 circuits ×
        2 T_INTG grid — the kernel folds the scan's deposits to a few
        ulp, so the telescoping to the offline curve-fit forward
        survives."""
        records = trained["results"]["frozen"].records
        for record in records:
            self._parity_case(trained, file_source, tmp_path / "kern",
                              "frozen", record, use_kernel=True)

    def test_parity_capacity_one_recycles(self, trained, file_source,
                                          tmp_path):
        """Sequential lane reuse (capacity 1 < streams) must not leak
        state across streams: parity still holds for every stream."""
        record = trained["results"]["frozen"].records[0]
        self._parity_case(trained, file_source, tmp_path / "c1", "frozen",
                          record, capacity=1)

    def test_spike_level_parity(self, trained, file_source, tmp_path):
        """Window-by-window layer-1 spike maps from the online readout
        equal the offline forward's bit-for-bit (one cell, driven through
        the low-level fold/readout steps)."""
        record = deploy_mod.select_record(
            trained["results"]["frozen"].records, t_intg_ms=100.0,
            label="c@m=0.06")
        ckpt = tmp_path / "spike_ckpt"
        deploy_mod.deploy_from_sweep(trained["results"]["frozen"],
                                     _model_of(trained), record, ckpt)
        dep = deploy_mod.load_deployment(ckpt)
        n_sub = dep.model_cfg.p2m.n_sub
        frames = _offline_frames(file_source, 0, 100.0, n_sub)
        off = deploy_mod.offline_forward(dep, jnp.asarray(frames[None]))
        off_spikes = np.asarray(off["spikes"][0])

        engine = StreamEngine(dep, capacity=2)
        fns = engine.fns
        state = fns.init_state()
        active = jnp.asarray([True, False])
        group = dep.model_cfg.coarsen_group()
        n_slots = frames.shape[0]
        on_spikes = []
        for t in range(n_slots):
            for c in range(engine.chunks_per_window):
                fr = np.zeros((2, engine.chunk_slots, HW, HW, 2),
                              np.float32)
                lo = c * engine.chunk_slots
                fr[0] = frames[t, lo:lo + engine.chunk_slots]
                state = fns.fold(state, jnp.asarray(fr), active)
            cm = jnp.asarray([(t + 1) % group == 0, False])
            state, out = fns.readout(state, active, cm)
            on_spikes.append(np.asarray(out["spikes"][0]))
        np.testing.assert_array_equal(np.stack(on_spikes), off_spikes)


def _model_of(trained) -> object:
    """The base model config the sweep trained (rebuild from any
    checkpoint's embedded config — cell fields are re-pinned by
    deploy_from_sweep)."""
    dep = deploy_mod.load_deployment(
        next(iter(trained["checkpoints"].values())))
    return dep.model_cfg


# ---------------------------------------------------------------------------
# deployment handshake
# ---------------------------------------------------------------------------

class TestDeployment:
    def test_checkpoint_roundtrip(self, trained):
        for proto, ckpt in trained["checkpoints"].items():
            dep = deploy_mod.load_deployment(ckpt, trained["artifact"])
            assert dep.protocol == proto
            assert dep.record == trained["records"][proto]
            v = dep.record["variant"]
            leak = dep.model_cfg.p2m.leak
            assert leak.circuit.value == v["circuit"]
            assert leak.v_threshold == v["v_threshold"]
            assert dep.model_cfg.p2m.t_intg_ms == dep.record["t_intg_ms"]

    def test_artifact_cross_check_rejects_foreign_record(self, trained,
                                                         tmp_path):
        import json
        art = json.loads(trained["artifact"].read_text())
        for r in art["records"]:
            r["t_intg_ms"] = 7.0          # no record matches anymore
        bad = tmp_path / "foreign.json"
        bad.write_text(json.dumps(art))
        ckpt = next(iter(trained["checkpoints"].values()))
        with pytest.raises(ValueError, match="different runs"):
            deploy_mod.load_deployment(ckpt, bad)

    def test_select_record_filters_and_ranks(self, trained):
        recs = trained["results"]["frozen"].records
        best = deploy_mod.select_record(recs)
        assert best["accuracy"] == max(r["accuracy"] for r in recs)
        only_t = deploy_mod.select_record(recs, t_intg_ms=1000.0)
        assert only_t["t_intg_ms"] == 1000.0
        with pytest.raises(ValueError, match="no sweep record"):
            deploy_mod.select_record(recs, t_intg_ms=123.0)

    def test_non_deploy_checkpoint_rejected(self, tmp_path):
        from repro.checkpoint import store
        store.save_checkpoint(tmp_path, 0, {"w": np.zeros(3)}, {})
        with pytest.raises(ValueError, match="not a streaming deployment"):
            deploy_mod.load_deployment(tmp_path)

    def test_select_record_deterministic_tie_break(self):
        """Equal-accuracy records must pick the SAME winner regardless of
        list order and of the variant-dict key order — registry compat
        keys (and the served weights) must be reproducible across
        processes with different dict orderings."""
        import random
        a = {"label": "c@m=0.06", "protocol": "frozen", "t_intg_ms": 100.0,
             "n_sub": 2, "accuracy": 0.5,
             "variant": {"circuit": "c", "mismatch": 0.06}}
        b = {"label": "a", "protocol": "frozen", "t_intg_ms": 100.0,
             "n_sub": 2, "accuracy": 0.5, "variant": {"circuit": "a"}}
        c = {"label": "a", "protocol": "unfrozen", "t_intg_ms": 100.0,
             "n_sub": 2, "accuracy": 0.5, "variant": {"circuit": "a"}}
        # same content, reversed variant-dict insertion order
        a2 = dict(a, variant={"mismatch": 0.06, "circuit": "c"})
        pools = [[a, b, c], [c, b, a], [b, a2, c], [c, a2, b]]
        winners = [deploy_mod.select_record(p) for p in pools]
        assert all(w["label"] == winners[0]["label"]
                   and w["protocol"] == winners[0]["protocol"]
                   for w in winners)
        # label then protocol break the tie: "a"/frozen sorts first
        assert winners[0]["label"] == "a"
        assert winners[0]["protocol"] == "frozen"
        # accuracy still dominates any tie-break field
        best = dict(b, accuracy=0.9)
        assert deploy_mod.select_record([a, best, c]) is best
        # untrained records (accuracy=None) sort without crashing
        untrained = [dict(a, accuracy=None), dict(b, accuracy=None)]
        random.Random(0).shuffle(untrained)
        assert deploy_mod.select_record(untrained)["label"] == "a"

    def _tamper(self, ckpt, mutate):
        """Rewrite the checkpoint's extras via ``mutate(extra) -> extra``
        (the on-disk corruption load_deployment must refuse)."""
        import json as json_mod
        from pathlib import Path
        (step_dir,) = [p for p in Path(ckpt).iterdir()
                       if p.name.startswith("step_") and p.is_dir()]
        idx = json_mod.loads((step_dir / "index.json").read_text())
        idx["extra"] = mutate(idx["extra"])
        (step_dir / "index.json").write_text(json_mod.dumps(idx))

    @pytest.fixture()
    def ckpt_copy(self, trained, tmp_path):
        import shutil
        src = trained["checkpoints"]["frozen"]
        dst = tmp_path / "ckpt_tampered"
        shutil.copytree(src, dst)
        return dst

    def test_load_rejects_missing_extras(self, ckpt_copy):
        self._tamper(ckpt_copy,
                     lambda e: {k: v for k, v in e.items() if k != "record"})
        with pytest.raises(ValueError, match="corrupt"):
            deploy_mod.load_deployment(ckpt_copy)

    def test_load_rejects_record_config_mismatch(self, ckpt_copy):
        def mutate(e):
            e["record"] = dict(e["record"], t_intg_ms=7.0)
            return e
        self._tamper(ckpt_copy, mutate)
        with pytest.raises(ValueError, match="mismatch"):
            deploy_mod.load_deployment(ckpt_copy)

    def test_load_rejects_variant_circuit_mismatch(self, ckpt_copy):
        def mutate(e):
            v = dict(e["record"]["variant"])
            v["circuit"] = "b" if v.get("circuit") != "b" else "a"
            e["record"] = dict(e["record"], variant=v)
            return e
        self._tamper(ckpt_copy, mutate)
        with pytest.raises(ValueError, match="wrong leak numerics"):
            deploy_mod.load_deployment(ckpt_copy)

    def test_load_rejects_malformed_model_config(self, ckpt_copy):
        def mutate(e):
            e["model_config"] = {"p2m": {"nonsense": True}}
            return e
        self._tamper(ckpt_copy, mutate)
        with pytest.raises(ValueError, match="malformed"):
            deploy_mod.load_deployment(ckpt_copy)

    def test_registry_meta_roundtrips(self, trained):
        """train_and_deploy stamps dataset/sensor_hw registry metadata
        into the checkpoint and load_deployment restores it."""
        for ckpt in trained["checkpoints"].values():
            dep = deploy_mod.load_deployment(ckpt)
            assert dep.meta["dataset"] == "dvs128"
            assert tuple(dep.meta["sensor_hw"]) == (128, 128)


# ---------------------------------------------------------------------------
# engine lifecycle + serving-stats artifact
# ---------------------------------------------------------------------------

class TestEngineLifecycle:
    def test_more_streams_than_lanes(self):
        src = sources.resolve_dataset("synthetic-gesture", hw=HW)
        dep = _fresh_dep(src)
        engine = StreamEngine(dep, capacity=2)
        report = engine.serve(src, 5, seed=0)
        assert len(report.results) == 5 > engine.capacity
        n_windows = src.n_slots(dep.t_intg_ms)
        assert all(r.n_readouts == n_windows for r in report.results)
        assert all(r.n_coarse_frames ==
                   n_windows // dep.model_cfg.coarsen_group()
                   for r in report.results)
        # continuous batching: later streams admitted at later windows
        assert max(r.admitted_window for r in report.results) > 0
        assert report.total_readouts == 5 * n_windows
        # per-stream layer-1 spike counts partition the fleet total
        assert sum(r.n_layer1_spikes for r in report.results) == \
            report.total_layer1_spikes > 0

    def test_stats_artifact_schema(self):
        src = sources.resolve_dataset("synthetic-gesture", hw=HW)
        dep = _fresh_dep(src)
        report = StreamEngine(dep, capacity=2).serve(src, 2, seed=1)
        art = report.to_artifact()
        assert art["schema"] == STATS_SCHEMA
        for key in ("deployed", "n_streams", "capacity", "accuracy",
                    "streams", "latency_ms", "throughput"):
            assert key in art
        assert {"readout_p50", "readout_p99", "readout_mean", "fold_p50",
                "fold_p99"} <= set(art["latency_ms"])
        assert {"wall_s", "events_per_s", "events_per_s_per_device",
                "readouts_per_s", "streams_per_s"} <= set(art["throughput"])
        # unsharded serve still carries the v3 sharding block (1 device)
        assert art["sharding"] == {"devices": 1, "bin_workers": 1,
                                   "padded_capacity": 2,
                                   "lanes_per_shard": 2,
                                   "per_shard_admitted": [2]}
        for s in art["streams"]:
            assert {"stream_id", "label", "prediction", "n_events",
                    "n_readouts", "logits", "entry", "entry_uid"} <= set(s)
            assert s["entry"] == "default"   # single-deployment engine
        # v4: single-deployment serving still emits the registry block —
        # one synthetic "default" entry whose ledger covers the fleet
        assert art["admission"]["n_rejected"] == 0
        reg = art["registry"]
        assert reg["max_entries"] == 1 and reg["compat"]
        (row,) = reg["entries"]
        assert row["name"] == "default"
        assert row["n_admitted"] == row["n_finished"] == 2
        assert art["throughput"]["events_per_s"] > 0

    def test_resolution_mismatch_rejected(self):
        src16 = sources.resolve_dataset("synthetic-gesture", hw=HW)
        src20 = sources.resolve_dataset("synthetic-gesture", hw=20)
        dep = _fresh_dep(src16)
        with pytest.raises(ValueError, match="resolution"):
            StreamEngine(dep, capacity=1).serve(src20, 1)

    def test_coarse_group_mismatch_rejected(self):
        """A stream too short for the deployed coarse window (its window
        count not a multiple of the coarsen group) must be rejected, not
        served to a vacuous all-zero prediction."""
        src = sources.resolve_dataset("synthetic-gesture", hw=HW,
                                      duration_ms=600.0)
        dep = _fresh_dep(src)   # T_INTG=200 ms, coarse 1000 ms → group 5
        with pytest.raises(ValueError, match="coarse group"):
            StreamEngine(dep, capacity=1).serve(src, 1)

    def test_bad_chunks_per_window_rejected(self):
        src = sources.resolve_dataset("synthetic-gesture", hw=HW)
        with pytest.raises(ValueError, match="divide"):
            StreamEngine(_fresh_dep(src), capacity=1, chunks_per_window=3)

    def test_strided_p2m_deployment_serves(self):
        """The charge accumulator must live at the conv OUTPUT resolution
        — a stride-2 in-pixel layer (with the matching backbone
        first_stride) serves without shape errors."""
        from repro.core.codesign import P2MModelConfig
        from repro.core.leakage import LeakageConfig
        from repro.core.p2m_layer import P2MConfig
        from repro.core.snn import SpikingCNNConfig

        src = sources.resolve_dataset("synthetic-gesture", hw=HW)
        model = P2MModelConfig(
            p2m=P2MConfig(out_channels=8, n_sub=2, t_intg_ms=200.0,
                          stride=2,
                          leak=LeakageConfig(
                              circuit=CircuitConfig.NULLIFIED)),
            backbone=SpikingCNNConfig(channels=(8, 16), input_hw=(HW, HW),
                                      fc_hidden=32, n_classes=src.n_classes,
                                      first_stride=2,
                                      first_layer_external=True),
            coarse_window_ms=1000.0)
        dep = deploy_mod.fresh_deployment(model, seed=0)
        report = StreamEngine(dep, capacity=2).serve(src, 2, seed=0)
        assert len(report.results) == 2
        assert all(r.n_coarse_frames == 2 for r in report.results)


def _fresh_dep(src):
    from repro.core.codesign import P2MModelConfig
    from repro.core.leakage import LeakageConfig
    from repro.core.p2m_layer import P2MConfig
    from repro.core.snn import SpikingCNNConfig

    model = P2MModelConfig(
        p2m=P2MConfig(out_channels=8, n_sub=2, t_intg_ms=200.0,
                      leak=LeakageConfig(circuit=CircuitConfig.NULLIFIED)),
        backbone=SpikingCNNConfig(channels=(8, 16, 16, 16),
                                  input_hw=(HW, HW), fc_hidden=64,
                                  n_classes=src.n_classes,
                                  first_layer_external=True),
        coarse_window_ms=1000.0)
    return deploy_mod.fresh_deployment(model, seed=0)


def _fast_dep(src, t_intg_ms=100.0, coarse_ms=200.0, *, n_sub=2,
              circuit=CircuitConfig.NULLIFIED, seed=0):
    """Small deployment with a short T_INTG so paced runs finish fast."""
    from repro.core.codesign import P2MModelConfig
    from repro.core.leakage import LeakageConfig
    from repro.core.p2m_layer import P2MConfig
    from repro.core.snn import SpikingCNNConfig

    model = P2MModelConfig(
        p2m=P2MConfig(out_channels=8, n_sub=n_sub, t_intg_ms=t_intg_ms,
                      leak=LeakageConfig(circuit=circuit)),
        backbone=SpikingCNNConfig(channels=(8, 16), input_hw=(HW, HW),
                                  fc_hidden=32, n_classes=src.n_classes,
                                  first_layer_external=True),
        coarse_window_ms=coarse_ms)
    return deploy_mod.fresh_deployment(model, seed=seed)


# ---------------------------------------------------------------------------
# admission control, pacing, and the v4 stats contract
# ---------------------------------------------------------------------------

def _check_stream_stats():
    import sys
    from pathlib import Path
    tools = Path(__file__).resolve().parents[1] / "tools"
    if str(tools) not in sys.path:
        sys.path.insert(0, str(tools))
    import check_stream_stats
    return check_stream_stats


class _CountingSource:
    """Source wrapper tracking how many replay iterators are OPEN
    (returned by iter_event_chunks and not yet fully consumed) — the
    lazy-admission regression: eager opening would put every offered
    stream live at once."""

    def __init__(self, src):
        self._src = src
        self.n_opened = 0
        self._live = 0
        self.max_live = 0
        for attr in ("name", "height", "width", "n_classes", "duration_ms",
                     "sensor_hw"):
            setattr(self, attr, getattr(src, attr))

    def n_slots(self, t_intg_ms):
        return self._src.n_slots(t_intg_ms)

    def iter_event_chunks(self, key, *, chunk_us, slot_us=None):
        label, chunks = self._src.iter_event_chunks(
            key, chunk_us=chunk_us, slot_us=slot_us)
        n_chunks = int(round(self.duration_ms * 1000 / chunk_us))
        self.n_opened += 1
        self._live += 1
        self.max_live = max(self.max_live, self._live)

        def tracked():
            for i, c in enumerate(chunks):
                if i + 1 == n_chunks:
                    self._live -= 1
                yield c

        return label, tracked()


class TestAdmissionControl:
    def test_lazy_admission_bounds_open_streams(self):
        """Streams are opened at ADMISSION, not offer: with 6 streams on
        2 lanes, at most 2 replay iterators are ever live (the eager bug
        opened all 6 up front)."""
        src = _CountingSource(sources.resolve_dataset(
            "synthetic-gesture", hw=HW, duration_ms=400.0))
        dep = _fast_dep(src, t_intg_ms=100.0, coarse_ms=200.0)
        engine = StreamEngine(dep, capacity=2)
        report = engine.serve(src, 6, seed=0)
        assert len(report.results) == 6
        assert src.n_opened == 6
        assert src.max_live <= engine.capacity
        assert report.max_open_streams <= engine.capacity

    def test_shed_and_deferred_accounting(self):
        """A bounded pending queue sheds offered load beyond
        capacity + max_pending and defers the rest; the artifact ledger
        balances (offered = admitted + shed, admitted all served)."""
        src = sources.resolve_dataset("synthetic-gesture", hw=HW,
                                      duration_ms=400.0)
        dep = _fast_dep(src, t_intg_ms=100.0, coarse_ms=200.0)
        engine = StreamEngine(dep, capacity=1)
        report = engine.serve(src, 5, seed=0, max_pending=1)
        # window 0: 1 admitted + 1 pending, the other 3 offers shed
        assert report.n_offered == 5
        assert report.n_admitted == 2
        assert report.n_shed == 3
        assert report.n_deferred == 1
        assert len(report.results) == 2
        art = report.to_artifact()
        assert _check_stream_stats().check(art, 2) == []
        deferred = [s for s in art["streams"]
                    if s["admitted_window"] > s["offered_window"]]
        assert len(deferred) == 1

    def test_offered_rate_staggers_offers(self):
        """offered_rate trickles offers on the replay clock: at 1 stream
        per T_INTG window, stream i is offered at window i; with an
        unbounded pending queue every late offer waits instead of being
        shed."""
        src = sources.resolve_dataset("synthetic-gesture", hw=HW,
                                      duration_ms=400.0)
        dep = _fast_dep(src, t_intg_ms=100.0, coarse_ms=200.0)
        rate = 10.0  # streams/s → exactly 1 offer per 100 ms window
        engine = StreamEngine(dep, capacity=2)
        report = engine.serve(src, 4, seed=0, offered_rate=rate)
        by_id = sorted(report.results, key=lambda r: r.stream_id)
        assert [r.offered_window for r in by_id] == [0, 1, 2, 3]
        assert all(r.admitted_window >= r.offered_window for r in by_id)
        assert report.n_shed == 0 and len(report.results) == 4

    def test_bad_admission_args_rejected(self):
        src = sources.resolve_dataset("synthetic-gesture", hw=HW,
                                      duration_ms=400.0)
        dep = _fast_dep(src)
        engine = StreamEngine(dep, capacity=1)
        with pytest.raises(ValueError, match="offered_rate"):
            engine.serve(src, 1, offered_rate=0.0)
        with pytest.raises(ValueError, match="max_pending"):
            engine.serve(src, 1, max_pending=-1)


class TestPacedServing:
    def test_paced_predictions_bit_exact_vs_unpaced(self):
        """Pacing only inserts sleeps: a paced serve of the same seed
        produces bit-identical logits, predictions, event counts, and
        admission/finish windows as the unpaced replay."""
        src = sources.resolve_dataset("synthetic-gesture", hw=HW,
                                      duration_ms=400.0)
        dep = _fast_dep(src, t_intg_ms=100.0, coarse_ms=200.0)
        r_fast = StreamEngine(dep, capacity=2).serve(src, 3, seed=0)
        r_paced = StreamEngine(dep, capacity=2).serve(src, 3, seed=0,
                                                      paced=True)
        key = lambda r: r.stream_id  # noqa: E731
        for a, b in zip(sorted(r_fast.results, key=key),
                        sorted(r_paced.results, key=key)):
            assert a.label == b.label
            assert a.prediction == b.prediction
            assert a.n_events == b.n_events
            assert a.admitted_window == b.admitted_window
            assert a.finished_window == b.finished_window
            np.testing.assert_array_equal(np.asarray(a.logits),
                                          np.asarray(b.logits))
        # unpaced runs carry no deadlines; paced ones one per readout
        assert not r_fast.miss_margin_ms
        assert len(r_paced.miss_margin_ms) == r_paced.total_readouts
        # paced replay holds each window to the wall clock: 3 streams of
        # 4 windows on 2 lanes run windows 0..7, and window 7 cannot
        # start before t_start + 7·t_intg = 0.7 s
        assert r_paced.wall_s >= 7 * 0.1

    def test_paced_artifact_v4_schema_and_zero_misses_unloaded(self):
        """The paced stats artifact passes the v4 schema gate, and an
        UNLOADED run (2 lanes, 200 ms windows, trivial compute) misses no
        deadline."""
        css = _check_stream_stats()
        src = sources.resolve_dataset("synthetic-gesture", hw=HW,
                                      duration_ms=400.0)
        dep = _fast_dep(src, t_intg_ms=200.0, coarse_ms=400.0)
        engine = StreamEngine(dep, capacity=2)
        # warm the per-stream event-generation jit outside the paced run
        engine.serve(src, 2, seed=0)
        report = engine.serve(src, 2, seed=0, paced=True)
        art = report.to_artifact()
        assert art["schema"] == STATS_SCHEMA == "p2m-stream-serving/v5"
        assert css.check(art, 2, paced=True, max_miss_rate=0.0) == []
        ddl = art["deadlines"]
        assert ddl["n_misses"] == 0 and ddl["miss_rate"] == 0.0
        assert ddl["n_deadlines"] == report.total_readouts > 0
        assert ddl["margin_ms"]["max"] <= 0.0
        assert sum(ddl["histogram"]["counts"]) == ddl["n_deadlines"]
        assert all(s["n_misses"] == 0 for s in art["streams"])
        assert all(s["miss_margin_max_ms"] <= 0.0 for s in art["streams"])

    def test_unpaced_artifact_passes_v4_schema(self):
        css = _check_stream_stats()
        src = sources.resolve_dataset("synthetic-gesture", hw=HW)
        dep = _fresh_dep(src)
        report = StreamEngine(dep, capacity=2).serve(src, 2, seed=1)
        art = report.to_artifact()
        assert css.check(art, 2) == []
        assert art["paced"] is False
        assert art["deadlines"]["n_deadlines"] == 0
        # paced gate must reject an unpaced artifact
        assert css.check(art, 2, paced=True) != []

    def test_prefetch_off_matches_prefetch_on(self):
        """The async host-binning worker is a pure pipeline change: the
        inline (prefetch=False) fold produces identical results."""
        src = sources.resolve_dataset("synthetic-gesture", hw=HW,
                                      duration_ms=400.0)
        dep = _fast_dep(src, t_intg_ms=100.0, coarse_ms=200.0)
        r_on = StreamEngine(dep, capacity=2).serve(src, 3, seed=0)
        r_off = StreamEngine(dep, capacity=2,
                             prefetch=False).serve(src, 3, seed=0)
        key = lambda r: r.stream_id  # noqa: E731
        for a, b in zip(sorted(r_on.results, key=key),
                        sorted(r_off.results, key=key)):
            assert a.prediction == b.prediction
            np.testing.assert_array_equal(np.asarray(a.logits),
                                          np.asarray(b.logits))


# ---------------------------------------------------------------------------
# multi-worker host binning pool: determinism + lifecycle
# ---------------------------------------------------------------------------

def _assert_reports_identical(ref, got):
    """Bit-for-bit serving parity: per-stream outcomes and the fleet
    ledger (the binning-pool / sharding determinism contract)."""
    key = lambda r: r.stream_id  # noqa: E731
    assert len(ref.results) == len(got.results)
    for a, b in zip(sorted(ref.results, key=key),
                    sorted(got.results, key=key)):
        assert a.label == b.label
        assert a.prediction == b.prediction
        assert a.n_events == b.n_events
        assert a.n_readouts == b.n_readouts
        assert a.offered_window == b.offered_window
        assert a.admitted_window == b.admitted_window
        assert a.finished_window == b.finished_window
        np.testing.assert_array_equal(np.asarray(a.logits),
                                      np.asarray(b.logits))
    for k in ("n_offered", "n_admitted", "n_shed", "n_deferred",
              "total_events", "total_readouts", "total_layer1_spikes"):
        assert getattr(ref, k) == getattr(got, k), k


class TestBinningPool:
    @pytest.mark.parametrize("paced", [False, True])
    def test_multi_worker_binning_bit_identical(self, paced):
        """2- and 4-worker binning pools produce bit-identical frames →
        predictions, logits, and admission ledger vs the single-worker
        pipeline AND vs the inline prefetch=False oracle, paced and
        unpaced."""
        src = sources.resolve_dataset("synthetic-gesture", hw=HW,
                                      duration_ms=400.0)
        dep = _fast_dep(src, t_intg_ms=100.0, coarse_ms=200.0)
        base = StreamEngine(dep, capacity=4).serve(src, 6, seed=0,
                                                   paced=paced)
        oracle = StreamEngine(dep, capacity=4, prefetch=False).serve(
            src, 6, seed=0, paced=paced)
        _assert_reports_identical(base, oracle)
        for workers in (2, 4):
            engine = StreamEngine(dep, capacity=4, bin_workers=workers)
            assert engine.bin_workers == workers
            got = engine.serve(src, 6, seed=0, paced=paced)
            _assert_reports_identical(base, got)
            _assert_reports_identical(oracle, got)
            assert got.to_artifact()["sharding"]["bin_workers"] == workers

    def test_worker_partition_is_contiguous_and_total(self):
        """Every lane is owned by exactly one worker, ownership is
        contiguous (a lane slice per worker), and all workers get lanes
        when capacity >= workers — the single-owner rule that keeps
        per-lane chunk order deterministic."""
        src = sources.resolve_dataset("synthetic-gesture", hw=HW,
                                      duration_ms=400.0)
        dep = _fast_dep(src, t_intg_ms=100.0, coarse_ms=200.0)
        engine = StreamEngine(dep, capacity=4, bin_workers=3)
        owners = [engine._worker_of(i) for i in range(4)]
        assert owners == sorted(owners)          # contiguous slices
        assert set(owners) == {0, 1, 2}          # no idle worker
        with_cap1 = StreamEngine(dep, capacity=1, bin_workers=4)
        assert with_cap1._worker_of(0) == 0

    def test_worker_threads_join_on_serve_exception(self):
        """A readout failure mid-serve must drain-and-join every bin
        worker on the way out (try/finally): no daemon thread may leak
        holding an open stream iterator."""
        import dataclasses
        import threading

        src = sources.resolve_dataset("synthetic-gesture", hw=HW,
                                      duration_ms=400.0)
        dep = _fast_dep(src, t_intg_ms=100.0, coarse_ms=200.0)
        engine = StreamEngine(dep, capacity=2, bin_workers=2)
        real_readout = engine.fns.readout
        calls = {"n": 0}

        def boom(state, active, coarse_mask):
            calls["n"] += 1
            if calls["n"] >= 2:   # let the warmup call through
                raise RuntimeError("injected readout failure")
            return real_readout(state, active, coarse_mask)

        engine.fns = dataclasses.replace(engine.fns, readout=boom)
        with pytest.raises(RuntimeError, match="injected readout"):
            engine.serve(src, 4, seed=0)
        leaked = [t for t in threading.enumerate()
                  if t.name.startswith("stream-bin-worker")]
        assert leaked == []

    def test_bad_bin_workers_rejected(self):
        src = sources.resolve_dataset("synthetic-gesture", hw=HW,
                                      duration_ms=400.0)
        with pytest.raises(ValueError, match="bin_workers"):
            StreamEngine(_fast_dep(src), capacity=2, bin_workers=0)


# ---------------------------------------------------------------------------
# one host-to-device copy and one fold per T_INTG window
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def window_src():
    return sources.resolve_dataset("synthetic-gesture", hw=HW,
                                   duration_ms=400.0)


@pytest.fixture(scope="module")
def window_deps(window_src):
    """Two compat-equal deployments of 4 sub-slots a window, so a window
    can arrive in 1, 2 or 4 replay chunks."""
    return (_fast_dep(window_src, n_sub=4),
            _fast_dep(window_src, n_sub=4, circuit=CircuitConfig.BASIC,
                      seed=1))


def _window_engine(deps, mode: str, chunks_per_window: int):
    """A 2-lane engine in ``mode`` and the ``variants`` its serve takes."""
    from repro.stream.adapt import AdaptConfig
    from repro.stream.registry import Registry

    kw = {"capacity": 2, "chunks_per_window": chunks_per_window}
    if mode == "registry":
        reg = Registry()
        reg.register("a", deps[0])
        reg.register("b", deps[1])
        return StreamEngine(reg, **kw), ["a", "b", "a"]
    if mode == "adapt":
        return StreamEngine(deps[0], adapt=AdaptConfig(), **kw), None
    return StreamEngine(deps[0], prefetch=mode != "inline", **kw), None


def _wrap_fold(engine, wrap) -> None:
    """Replace the engine's jitted fold by ``wrap(fold)``; every mode's
    fold is ``fold(state, frames, active, *extra)``."""
    import dataclasses

    engine.fns = dataclasses.replace(engine.fns,
                                     fold=wrap(engine.fns.fold))


def _per_chunk(fold, chunk_slots: int):
    """The oracle: the same jitted fold driven chunk by chunk over the
    window batch's sub-slot ranges, one call per replay chunk."""
    def fold_chunks(state, frames, *rest):
        for lo in range(0, frames.shape[1], chunk_slots):
            state = fold(state, frames[:, lo:lo + chunk_slots], *rest)
        return state
    return fold_chunks


@pytest.mark.parametrize("chunks_per_window", [1, 2, 4])
@pytest.mark.parametrize("mode", ["plain", "registry", "adapt", "inline"])
def test_one_fold_per_window_matches_per_chunk_folds(
        window_src, window_deps, mode, chunks_per_window):
    """``serve`` folds each T_INTG window in ONE call on the whole
    [capacity, n_sub, H, W, 2] batch, whatever the replay granularity;
    its answers are those of folding the same frames one replay chunk at
    a time: predictions, spike counts and readout counts exactly, logits
    to a few ulp. 3 streams on 2 lanes, so a lane turns over."""
    engine, variants = _window_engine(window_deps, mode, chunks_per_window)
    seen = []

    def counted(fold):
        def call(state, frames, *rest):
            seen.append(np.array(frames))
            return fold(state, frames, *rest)
        return call

    _wrap_fold(engine, counted)
    got = engine.serve(window_src, 3, seed=0, variants=variants)

    oracle, _ = _window_engine(window_deps, mode, chunks_per_window)
    _wrap_fold(oracle, lambda fold: _per_chunk(fold, oracle.chunk_slots))
    ref = oracle.serve(window_src, 3, seed=0, variants=variants)

    windows = len(got.readout_s)
    assert windows == max(r.finished_window for r in got.results) > 0
    # the warm-up's call, then one per window, each on all n_sub slots
    assert [f.shape[1] for f in seen] == [engine.n_sub] * (1 + windows)
    assert len(got.fold_s) == windows * chunks_per_window
    # streams 0 and 1 hold lanes 0 and 1 from the first window: each
    # window's batch holds their next n_sub slots as the offline binner
    # bins the whole stream
    key0 = jax.random.PRNGKey(0)
    n_windows = window_src.n_slots(engine.dep.t_intg_ms)
    for lane in (0, 1):
        _, chunks = window_src.iter_event_chunks(
            jax.random.fold_in(key0, lane), chunk_us=engine.chunk_us,
            slot_us=engine.slot_us)
        offline = bin_chunks([concat_chunks(chunks)],
                             n_total=n_windows * engine.n_sub,
                             slot_us=engine.slot_us,
                             sensor_hw=window_src.sensor_hw,
                             out_hw=(HW, HW))
        assert offline.any()
        np.testing.assert_array_equal(
            np.stack([f[lane] for f in seen[1:1 + n_windows]]),
            offline.reshape(n_windows, engine.n_sub, HW, HW, 2))
    assert len(got.results) == len(ref.results) == 3
    key = lambda r: r.stream_id  # noqa: E731
    for a, b in zip(sorted(ref.results, key=key),
                    sorted(got.results, key=key)):
        assert (a.stream_id, a.prediction, a.n_readouts, a.n_events,
                a.n_layer1_spikes) == (b.stream_id, b.prediction,
                                       b.n_readouts, b.n_events,
                                       b.n_layer1_spikes)
        np.testing.assert_array_max_ulp(
            np.asarray(a.logits, np.float32),
            np.asarray(b.logits, np.float32), maxulp=4)
    for k in ("total_events", "total_readouts", "total_layer1_spikes"):
        assert getattr(ref, k) == getattr(got, k), k
    if mode == "adapt":
        assert got.adaptation["n_updates"] == ref.adaptation["n_updates"] > 0


@pytest.mark.parametrize("mode", ["plain", "registry"])
def test_second_serve_matches_a_fresh_engine(window_src, window_deps, mode):
    """The engine's device lane state stays resident across ``serve``
    calls and admission resets each lane: a second serve on one frozen
    engine, over the lane state the first left, gives a fresh engine's
    predictions, spike counts and logits, bit for bit."""
    engine, variants = _window_engine(window_deps, mode, 1)
    engine.serve(window_src, 3, seed=1, variants=variants)
    got = engine.serve(window_src, 3, seed=0, variants=variants)
    fresh, _ = _window_engine(window_deps, mode, 1)
    ref = fresh.serve(window_src, 3, seed=0, variants=variants)
    _assert_reports_identical(ref, got)
    key = lambda r: r.stream_id  # noqa: E731
    for a, b in zip(sorted(ref.results, key=key),
                    sorted(got.results, key=key)):
        assert a.n_layer1_spikes == b.n_layer1_spikes, a.stream_id


# ---------------------------------------------------------------------------
# CLI end-to-end (CI also drives this directly as the streaming smoke step)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_stream_cli_smoke(tmp_path):
    """`launch/stream.py --smoke` end-to-end: fixture generation → tiny
    train+deploy → serve → serving-stats artifact with the v1 schema."""
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path

    src_dir = Path(__file__).resolve().parents[1] / "src"
    env = dict(os.environ, PYTHONPATH=str(src_dir), JAX_PLATFORMS="cpu")
    out = tmp_path / "stream"
    cmd = [sys.executable, "-m", "repro.launch.stream", "--smoke",
           "--streams", "4", "--capacity", "2", "--out", str(out)]
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr
    art = json.loads((out / "stream_serving_dvs128.json").read_text())
    assert art["schema"] == STATS_SCHEMA
    assert art["n_streams"] == 4
    assert len(art["streams"]) == 4
    assert art["deployed"]["protocol"] == "frozen"
    assert (out / "deploy" / "ckpt_frozen").is_dir()


# ---------------------------------------------------------------------------
# keep_params seam (core/sweep.py)
# ---------------------------------------------------------------------------

def test_run_grid_keep_params_shapes(trained):
    for proto, result in trained["results"].items():
        assert set(result.final_params) == {(100.0, 2), (1000.0, 2)}
        G = len(result.labels)
        for cell in result.final_params.values():
            bb_leaf = jax.tree.leaves(cell["backbone"])[0]
            assert bb_leaf.shape[0] == G       # unpadded variant axis
            p2m_w = cell["p2m"]["w"]
            if proto == "unfrozen":
                assert p2m_w.shape[0] == G     # per-variant layer 1
            else:
                assert p2m_w.ndim == 4         # shared layer 1
