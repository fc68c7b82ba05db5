"""Mesh-sharded lane-parallel serving (repro.stream.shard +
repro.serve.slots.ShardedSlots): sharded-vs-single-device serving parity
(via a subprocess with 8 forced host devices, like test_sweep_shard.py)
plus in-process unit coverage of the lane executor and the per-shard slot
bookkeeping.

The parity bar: no lane's numerics read a neighbour's (no cross-lane
reduction), so every prediction, admission ledger entry and layer-1 spike
count is EXACTLY equal to the unsharded serve, for any device count,
padded or not, paced or unpaced, prefetching or inline. Logits agree to
``MESH_ATOL``: the per-device batch is smaller than the whole lane axis,
and the compiler may pick another reduction order for a backbone matmul
at that shape (on the CPU, one lane per device moves a logit by one ulp).
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core.sweep_exec import MeshExecutor  # noqa: E402
from repro.serve.slots import ShardedSlots  # noqa: E402
from repro.stream.shard import (LANE_AXIS, LaneExecutor,  # noqa: E402
                                make_lane_executor)

REPO = Path(__file__).resolve().parents[1]
# sharded vs one-device logits: a few ulp of |logit| <~ 1
MESH_ATOL = 1e-6


class TestLaneExecutor:
    def test_default_is_single_device(self):
        ex = make_lane_executor(None)
        assert ex.devices == 1 and not ex.is_sharded
        assert ex.axis == LANE_AXIS

    def test_is_a_mesh_executor(self):
        """One executor family: the lane executor reuses the sweep
        engine's mesh/padding/spec machinery wholesale."""
        assert issubclass(LaneExecutor, MeshExecutor)
        assert LaneExecutor(devices=1).padded_size(3) == 3
        assert LaneExecutor(devices=4).padded_size(3) == 4
        assert LaneExecutor(devices=4).padded_size(8) == 8

    def test_validates_devices_eagerly(self):
        """A bad --devices must fail at construction, before any stream
        is opened."""
        if jax.device_count() >= 4:
            assert make_lane_executor(4).devices == 4
        else:
            with pytest.raises(ValueError, match="force_host_platform"):
                make_lane_executor(4)

    def test_rejects_nonpositive(self):
        with pytest.raises(ValueError):
            LaneExecutor(devices=0)

    def test_single_device_shard_is_identity(self):
        ex = LaneExecutor(devices=1)
        fn = lambda x: x + 1  # noqa: E731
        assert ex.shard(fn, in_specs=(None,), out_specs=None) is fn


class TestShardedSlots:
    def test_degenerates_to_slot_manager(self):
        s = ShardedSlots(4)
        assert (s.devices, s.padded_capacity, s.lanes_per_shard) == (1, 4, 4)
        assert s.admit("a") == 0 and s.admit("b") == 1
        assert s.active_mask() == [True, True, False, False]
        assert s.release(0) == "a"
        assert s.admit("c") == 0          # lowest free lane again

    def test_admission_order_matches_single_manager(self):
        """Shard-major scan → lowest free GLOBAL lane: placement is
        identical to a devices=1 SlotManager, which is what makes sharded
        serving replay-identical."""
        s = ShardedSlots(4, devices=2)
        assert [s.admit(i) for i in "abcd"] == [0, 1, 2, 3]
        assert s.admit("e") is None       # full
        s.release(1)
        s.release(2)
        assert s.admit("e") == 1          # lowest freed, shard 0
        assert s.admit("f") == 2          # then shard 1

    def test_padding_lanes_never_admitted(self):
        s = ShardedSlots(3, devices=2)    # pads 3 -> 4
        assert s.padded_capacity == 4 and s.lanes_per_shard == 2
        assert [s.admit(i) for i in "abc"] == [0, 1, 2]
        assert s.admit("d") is None       # lane 3 is padding
        assert s.active_mask() == [True, True, True, False]
        with pytest.raises(ValueError, match="padding"):
            s.release(3)

    def test_pure_padding_shard(self):
        s = ShardedSlots(2, devices=4)    # shards 2,3 hold no real lane
        assert s.padded_capacity == 4 and s.lanes_per_shard == 1
        assert [s.admit(i) for i in "ab"] == [0, 1]
        assert s.admit("c") is None
        assert s.per_shard_occupied() == [1, 1, 0, 0]
        with pytest.raises(ValueError, match="padding"):
            s.release(2)

    def test_shard_of_and_occupied_order(self):
        s = ShardedSlots(6, devices=3)
        assert [s.shard_of(i) for i in range(6)] == [0, 0, 1, 1, 2, 2]
        for item in "abcdef":
            s.admit(item)
        s.release(1)
        assert [lane for lane, _ in s.occupied()] == [0, 2, 3, 4, 5]
        assert s.n_occupied == 5 and s.n_free == 1
        with pytest.raises(ValueError, match="outside"):
            s.shard_of(6)

    def test_counters_and_flags(self):
        s = ShardedSlots(2, devices=2)
        assert s.is_empty() and not s.is_full()
        s.admit("a")
        s.admit("b")
        assert s.is_full() and not s.is_empty()
        assert s.capacity == 2

    def test_rejects_bad_args(self):
        with pytest.raises(ValueError, match="capacity"):
            ShardedSlots(0)
        with pytest.raises(ValueError, match="devices"):
            ShardedSlots(2, devices=0)


def _tiny_serve(devices, capacity=4, n_streams=6, paced=False,
                prefetch=True, bin_workers=None):
    from repro.core.codesign import P2MModelConfig
    from repro.core.leakage import CircuitConfig, LeakageConfig
    from repro.core.p2m_layer import P2MConfig
    from repro.core.snn import SpikingCNNConfig
    from repro.data import sources
    from repro.stream import deploy as deploy_mod
    from repro.stream.engine import StreamEngine

    hw = 16
    src = sources.resolve_dataset("synthetic-gesture", hw=hw,
                                  duration_ms=400.0)
    model = P2MModelConfig(
        p2m=P2MConfig(out_channels=8, n_sub=2, t_intg_ms=100.0,
                      leak=LeakageConfig(circuit=CircuitConfig.BASIC)),
        backbone=SpikingCNNConfig(channels=(8, 16), input_hw=(hw, hw),
                                  fc_hidden=32, n_classes=src.n_classes,
                                  first_layer_external=True),
        coarse_window_ms=200.0)
    dep = deploy_mod.fresh_deployment(model, seed=0)
    engine = StreamEngine(dep, capacity=capacity, prefetch=prefetch,
                          executor=make_lane_executor(devices),
                          bin_workers=bin_workers)
    return engine.serve(src, n_streams, seed=0, paced=paced)


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs >1 device (run under XLA_FLAGS="
                           "--xla_force_host_platform_device_count=8)")
class TestShardedServing:
    """In-process sharded serving — active under the CI multi-device
    step; the full padded/paced/prefetch matrix lives in the subprocess
    test below."""

    def test_sharded_serving_bit_identical(self):
        n_dev = min(2, jax.device_count())
        base = _tiny_serve(devices=None)
        got = _tiny_serve(devices=n_dev)
        key = lambda r: r.stream_id  # noqa: E731
        for a, b in zip(sorted(base.results, key=key),
                        sorted(got.results, key=key)):
            assert a.prediction == b.prediction
            assert a.n_events == b.n_events
            assert a.admitted_window == b.admitted_window
            np.testing.assert_allclose(np.asarray(a.logits),
                                       np.asarray(b.logits), rtol=0,
                                       atol=MESH_ATOL)
        assert got.total_layer1_spikes == base.total_layer1_spikes
        art = got.to_artifact()
        assert art["sharding"]["devices"] == n_dev
        assert sum(art["sharding"]["per_shard_admitted"]) == got.n_admitted
        assert art["throughput"]["events_per_s_per_device"] * n_dev == \
            pytest.approx(art["throughput"]["events_per_s"])


_PARITY_SCRIPT = textwrap.dedent("""
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
    from repro.stream.engine import StreamEngine
    from repro.stream.shard import make_lane_executor

    assert jax.device_count() == 8, jax.device_count()
    hw = 16
    src = sources.resolve_dataset("synthetic-gesture", hw=hw,
                                  duration_ms=400.0)
    model = P2MModelConfig(
        p2m=P2MConfig(out_channels=8, n_sub=2, t_intg_ms=100.0,
                      leak=LeakageConfig(circuit=CircuitConfig.BASIC)),
        backbone=SpikingCNNConfig(channels=(8, 16), input_hw=(hw, hw),
                                  fc_hidden=32, n_classes=src.n_classes,
                                  first_layer_external=True),
        coarse_window_ms=200.0)
    dep = deploy_mod.fresh_deployment(model, seed=0)

    def serve(capacity, devices):
        eng = StreamEngine(dep, capacity=capacity,
                           executor=make_lane_executor(devices))
        return eng.serve(src, 6, seed=0)

    def assert_same(a_rep, b_rep, tag):
        key = lambda r: r.stream_id
        assert len(a_rep.results) == len(b_rep.results), tag
        for a, b in zip(sorted(a_rep.results, key=key),
                        sorted(b_rep.results, key=key)):
            assert a.label == b.label, tag
            assert a.prediction == b.prediction, (tag, a.stream_id)
            assert a.n_events == b.n_events, tag
            assert a.n_readouts == b.n_readouts, tag
            assert a.offered_window == b.offered_window, tag
            assert a.admitted_window == b.admitted_window, tag
            assert a.finished_window == b.finished_window, tag
            np.testing.assert_allclose(np.asarray(a.logits),
                                       np.asarray(b.logits), rtol=0,
                                       atol={atol!r}, err_msg=tag)
        for k in ("n_offered", "n_admitted", "n_shed", "n_deferred",
                  "total_events", "total_readouts", "total_layer1_spikes"):
            assert getattr(a_rep, k) == getattr(b_rep, k), (tag, k)
        print(tag, "parity")

    # capacity 4: divisible (2, 4) and padded (8 -> padded_capacity 8
    # with 4 padding lanes); capacity 3 over 2 devices pads 3 -> 4
    base4 = serve(4, None)
    for dev in (2, 4, 8):
        assert_same(base4, serve(4, dev), f"c4_d{dev}")
    assert_same(serve(3, None), serve(3, 2), "c3_d2_padded")

    # paced, prefetch off, and multi-worker binning on the sharded path
    eng_w = StreamEngine(dep, capacity=4, executor=make_lane_executor(2))
    eng_w.serve(src, 4, seed=0)                       # warm the jits
    base_paced = StreamEngine(dep, capacity=4).serve(src, 6, seed=0,
                                                     paced=True)
    assert_same(base_paced, eng_w.serve(src, 6, seed=0, paced=True),
                "c4_d2_paced")
    assert_same(base4, StreamEngine(
        dep, capacity=4, executor=make_lane_executor(2),
        prefetch=False).serve(src, 6, seed=0), "c4_d2_noprefetch")
    assert_same(base4, StreamEngine(
        dep, capacity=4, executor=make_lane_executor(2),
        bin_workers=4).serve(src, 6, seed=0), "c4_d2_w4")
    art = eng_w.serve(src, 6, seed=0).to_artifact()
    assert art["sharding"] == {"devices": 2, "bin_workers": 2,
                               "padded_capacity": 4, "lanes_per_shard": 2,
                               "per_shard_admitted": [4, 2]}
    print("PARITY_PASS")
""")


@pytest.mark.slow
def test_sharded_serving_matches_single_device():
    """Forced 8-host-device run: devices in {2, 4, 8} plus a
    non-divisible capacity (3 lanes over 2 devices), paced, inline
    (prefetch=False), and multi-worker binning — every prediction, ledger
    counter, and spike count exactly equal to the unsharded serve, logits
    within ``MESH_ATOL``."""
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    env.pop("XLA_FLAGS", None)   # the script must own the device count
    script = _PARITY_SCRIPT.replace("{atol!r}", repr(MESH_ATOL))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "PARITY_PASS" in proc.stdout
