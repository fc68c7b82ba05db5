"""Spikformer's work count against a hand count at the published widths,
and its two readers on traces that hold nothing for them."""
from __future__ import annotations

import pytest

import perfbench_cells  # noqa: F401  (puts the repository on the path)

from bench import catalog, trace, work, work_spikformer

MODEL = catalog.config("p2m_spikformer_gesture128")["model"]
MS = 1_000_000  # ns


def test_published_counts():
    sps = (64 * 64 * 64 * (9 * 32) * 2 + 32 * 32 * 128 * (9 * 64) * 2
           + 16 * 16 * 256 * (9 * 128) * 2)
    assert sps == 3 * 150_994_944
    rpe = 8 * 8 * 256 * (9 * 256) * 2
    assert rpe == 75_497_472
    block = (3 * 64 * 256 * 256 * 2          # Q, K, V
             + 16 * 2 * 64 * 16 * 16 * 2     # Kᵀ·V and Q·(Kᵀ·V), 16 heads
             + 64 * 256 * 256 * 2            # output projection
             + 2 * 64 * 256 * 1024 * 2)      # MLP
    assert block == 101_711_872
    head = 256 * 11 * 2
    assert work_spikformer.backbone_flops_per_step(MODEL) \
        == sps + rpe + 2 * block + head == 731_911_680
    # SPS stage 0 in the pixel: 128·128·32 sites, 3·3·2 taps
    assert work.layer1_flops_per_subslot(MODEL) == 18_874_368


def test_membranes_and_least_time():
    assert work_spikformer.membrane_floats(MODEL) == 475_136 + 327_680
    assert work_spikformer.backbone_min_bytes_per_step(MODEL) \
        == 2 * 802_816 * 4
    assert work_spikformer.steps_per_lane_window(MODEL) == pytest.approx(0.1)
    assert work_spikformer.model_flops_per_lane_window(MODEL) \
        == pytest.approx(4 * 18_874_368 + 731_911_680 / 10)
    least, bound = work_spikformer.backbone_least_seconds(
        MODEL, 1000, work.peak("TPU v5 lite"))
    assert bound == "bytes"
    assert least == pytest.approx(1000 * 2 * 802_816 * 4 / 819e9)


def _ctx(tr):
    summary = None if tr is None else {"busy_s": 0.0, "window_s": 0.1,
                                       "breakdown": {}}
    return {"trace_data": tr, "trace": summary, "chips": 1,
            "traced_windows": 4, "lane_windows": 32, "model": MODEL,
            "peak": work.peak("TPU v5 lite")}


@pytest.mark.parametrize("metric", ["backbone_roofline", "spikformer_mfu"])
def test_readers_read_nothing_without_a_trace(metric):
    assert catalog.metric(metric).reduce(_ctx(None)) is None


def test_backbone_roofline_needs_readout_programs():
    reader = catalog.metric("backbone_roofline")
    fold_only = trace.Trace(
        devices=[trace.Device("/device:TPU:0",
                              modules=[("jit_fold_body(3)", 0, 2 * MS)])],
        host=[], window=(0, 100 * MS))
    assert reader.reduce(_ctx(fold_only)) is None
    fold_only.window = None
    assert reader.reduce(_ctx(fold_only)) is None
    # 4 ms of readout programs for 32 lane-windows: 3.2 backbone steps
    with_readout = trace.Trace(
        devices=[trace.Device("/device:TPU:0",
                              modules=[("jit_readout_body(7)", 0, 4 * MS)])],
        host=[], window=(0, 100 * MS))
    least = 3.2 * 2 * 802_816 * 4 / 819e9
    assert reader.reduce(_ctx(with_readout)) == pytest.approx(
        100 * least / 4e-3)


def test_readers_read_nothing_for_the_cnn():
    ctx = _ctx(trace.Trace(
        devices=[trace.Device("/device:TPU:0",
                              modules=[("jit_readout_body(7)", 0, 4 * MS)])],
        host=[], window=(0, 100 * MS)))
    ctx["model"] = catalog.config("p2m_gesture128")["model"]
    for metric in ("backbone_roofline", "spikformer_mfu"):
        assert catalog.metric(metric).reduce(ctx) is None


def test_spikformer_mfu_counts_layer1_and_the_required_steps():
    ctx = _ctx(trace.Trace(devices=[], host=[], window=(0, 100 * MS)))
    flops = 32 * (4 * 18_874_368 + 731_911_680 / 10)
    assert catalog.metric("spikformer_mfu").reduce(ctx) == pytest.approx(
        100 * flops / (0.1 * 197e12))
