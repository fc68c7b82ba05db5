"""Work the model requires, counted from a configuration's shapes.

Never read from the program: a change that does less or more work than
the model needs does not move these numbers.

* Layer 1 (the in-pixel conv), per lane and sub-slot:
  ``H'·W'·F·(k·k·C_in)·2`` FLOPs at the conv output ``H' × W'``.
* Backbone, counted once per coarse window per lane, which is what the
  model requires: each conv block ``h·w·C_out·(k·k·C_in)·2`` plus both
  FC layers. Stepping it more often is recomputation and does not count.
* The least layer-1 bytes per window and lane: the float32 charge state
  read once and written once, ``2·H'·W'·F·4``. Any fold that keeps the
  charge in device memory between windows moves at least this much.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def _geometry(model: dict) -> dict:
    p2m, bb = model["p2m"], model["backbone"]
    h, w = bb["input_hw"]
    s = p2m["stride"]
    return {"h": h // s, "w": w // s, "f": p2m["out_channels"],
            "k": p2m["kernel_size"], "c_in": p2m["in_channels"],
            "n_sub": p2m["n_sub"],
            "group": round(model["coarse_window_ms"] / p2m["t_intg_ms"])}


def layer1_flops_per_subslot(model: dict) -> float:
    g = _geometry(model)
    return float(g["h"] * g["w"] * g["f"] * g["k"] * g["k"] * g["c_in"] * 2)


def layer1_flops_per_window(model: dict) -> float:
    return layer1_flops_per_subslot(model) * _geometry(model)["n_sub"]


def backbone_flops_per_coarse(model: dict) -> float:
    bb = model["backbone"]
    g = _geometry(model)
    kb = bb["kernel_size"]
    h, w, c_in = g["h"] // 2, g["w"] // 2, bb["channels"][0]
    flops = 0
    for c_out in bb["channels"][1:]:
        flops += h * w * c_out * kb * kb * c_in * 2
        h, w, c_in = h // 2, w // 2, c_out
    flat = h * w * c_in
    flops += flat * bb["fc_hidden"] * 2 + bb["fc_hidden"] * bb["n_classes"] * 2
    return float(flops)


def model_flops_per_lane_window(model: dict) -> float:
    """Layer 1 per window plus the backbone's share of a coarse window."""
    return (layer1_flops_per_window(model)
            + backbone_flops_per_coarse(model) / _geometry(model)["group"])


def fold_min_bytes_per_lane_window(model: dict) -> float:
    g = _geometry(model)
    return float(2 * g["h"] * g["w"] * g["f"] * 4)


def fold_least_seconds(model: dict, lane_windows: float, peak: dict
                       ) -> tuple[float, str]:
    """Least device time for ``lane_windows`` windows of layer-1 fold on
    one chip of ``peak``, and which bound sets it."""
    t_bytes = (fold_min_bytes_per_lane_window(model) * lane_windows
               / peak["hbm_bytes_per_s"])
    t_flops = layer1_flops_per_window(model) * lane_windows / peak["bf16_flops"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")


def peak(kind: str) -> dict:
    """The published peaks of one chip, by JAX's ``device_kind``. A kind
    the table does not hold is an error, never a default."""
    kinds = json.loads(PEAKS.read_text())["kinds"]
    if kind not in kinds:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS.name} "
                       f"(have {sorted(kinds)})")
    return kinds[kind]
