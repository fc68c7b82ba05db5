"""Work counts against hand counts for both configurations."""
from __future__ import annotations

import pytest

import perfbench_cells  # noqa: F401  (puts the repository on the path)

from bench import catalog, work

GESTURE = catalog.config("p2m_gesture128")["model"]
NMNIST = catalog.config("p2m_nmnist34")["model"]


def test_gesture_counts():
    # 128·128·16 sites, 3·3·2 taps, multiply and add
    assert work.layer1_flops_per_subslot(GESTURE) == 9_437_184
    assert work.layer1_flops_per_window(GESTURE) == 4 * 9_437_184
    conv1 = 64 * 64 * 32 * (9 * 16) * 2
    conv2 = 32 * 32 * 64 * (9 * 32) * 2
    conv3 = 16 * 16 * 64 * (9 * 64) * 2
    fc = 8 * 8 * 64 * 512 * 2 + 512 * 11 * 2
    assert work.backbone_flops_per_coarse(GESTURE) == conv1 + conv2 + conv3 + fc
    assert work.backbone_flops_per_coarse(GESTURE) == 98_577_408
    assert work.model_flops_per_lane_window(GESTURE) == pytest.approx(
        4 * 9_437_184 + 98_577_408 / 100)
    assert work.fold_min_bytes_per_lane_window(GESTURE) == 2 * 2 ** 20


def test_nmnist_counts():
    assert work.layer1_flops_per_subslot(NMNIST) == 34 * 34 * 16 * 18 * 2
    conv1 = 17 * 17 * 32 * (9 * 16) * 2
    conv2 = 8 * 8 * 64 * (9 * 32) * 2
    conv3 = 4 * 4 * 64 * (9 * 64) * 2
    fc = 2 * 2 * 64 * 512 * 2 + 512 * 10 * 2
    assert work.backbone_flops_per_coarse(NMNIST) == conv1 + conv2 + conv3 + fc
    assert work.backbone_flops_per_coarse(NMNIST) == 6_474_752
    assert work.model_flops_per_lane_window(NMNIST) == pytest.approx(
        4 * 665_856 + 6_474_752 / 30)
    assert work.fold_min_bytes_per_lane_window(NMNIST) == 147_968


@pytest.mark.parametrize("model", [GESTURE, NMNIST], ids=["gesture", "nmnist"])
def test_fold_roofline_is_bound_by_the_charge_bytes(model):
    peak = work.peak("TPU v5 lite")
    least, bound = work.fold_least_seconds(model, 1000, peak)
    assert bound == "bytes"
    assert least == pytest.approx(
        1000 * work.fold_min_bytes_per_lane_window(model) / 819e9)
