"""The trace reduction on a small trace recorded on a TPU v5e (four lanes
of nmnist34.saturated, a few windows), and its interval arithmetic."""
from __future__ import annotations

import pytest

from perfbench_cells import ROOT

from bench import catalog, trace

DATA = ROOT / "tests" / "bench" / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    return trace.load(DATA)


def test_union_and_gaps():
    spans = [("a", 0, 4), ("b", 2, 6), ("c", 8, 9), ("d", 12, 20)]
    busy = trace.union(spans, 1, 15)
    assert busy == [(1, 6), (8, 9), (12, 15)]
    assert trace.gaps(busy, 0, 16) == [(0, 1), (6, 8), (9, 12), (15, 16)]


def test_names():
    assert trace.op_name("%fusion.3 = f32[4]{0} fusion(f32[4]{0} %p)") \
        == "fusion.3"
    assert trace.base_name("jit_fold_body(1278257366)") == "jit_fold_body"
    assert trace.base_name("copy-done.12") == "copy-done"


def test_recorded_trace_has_a_window_a_device_and_the_programs(recorded):
    assert recorded.window is not None
    assert [d.name for d in recorded.devices] == ["/device:TPU:0"]
    names = {trace.base_name(n) for n, _, _ in recorded.devices[0].modules}
    assert {"jit_fold_body", "jit_readout_body"} <= names


def test_busy_idle_and_program_time(recorded):
    s = trace.summarize(recorded, n_devices=1)
    assert 0 < s["busy_s"] < s["window_s"]
    lo, hi = recorded.window
    dev = recorded.devices[0]
    fold = trace.program_seconds(dev, ("fold_body", "stream_fold"), lo, hi)
    readout = trace.program_seconds(dev, ("readout_body",), lo, hi)
    assert fold > 0 and readout > 0
    # programs are busy time, and no program runs outside the ops' union
    assert fold + readout <= s["busy_s"] * 1.05 + 1e-4
    bd = s["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10
    assert all(v > 0 for _, v in bd["device_ops"] + bd["idle_gaps"])
    assert bd["idle_gaps"] == sorted(bd["idle_gaps"], key=lambda g: -g[1])


def test_no_window_or_too_few_devices_reads_nothing(recorded):
    assert trace.summarize(recorded, n_devices=4) is None
    empty = trace.Trace(devices=recorded.devices, host=[], window=None)
    assert trace.summarize(empty, n_devices=1) is None


def test_metric_readers_on_the_recorded_trace(recorded):
    s = trace.summarize(recorded, n_devices=1)
    ctx = {"trace": s, "trace_data": recorded, "chips": 1,
           "traced_windows": 3, "lane_windows": 12,
           "model": catalog.config("p2m_nmnist34")["model"],
           "peak": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
           "fold_s": [0.001, 0.003], "readout_s": []}
    got = {m: catalog.metric(m).reduce(ctx) for m in (
        "device_idle_share", "fold_device_ms", "fold_roofline",
        "readout_device_ms", "mfu", "host_feed_ms", "window_sync_ms")}
    assert 0 < got["device_idle_share"] < 100
    assert 0 < got["fold_roofline"] < 100 and 0 < got["mfu"] < 100
    assert got["fold_device_ms"] > 0 and got["readout_device_ms"] > 0
    assert got["host_feed_ms"] == pytest.approx(2.0)
    assert got["window_sync_ms"] is None
