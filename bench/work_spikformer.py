"""Work Spikformer-2-256 behind the P²M layer requires, counted from the
configuration's shapes (``bench/configs/p2m_spikformer_gesture128.json``).

Never read from the program: a change that does less or more work than
the model needs does not move these numbers.

* Layer 1 (SPS stage 0, in the pixel), per lane and sub-slot: as
  ``bench/work.py`` counts it, ``H·W·(D/8)·(3·3·2)·2`` FLOPs.
* Backbone, counted once per coarse window per lane, which is what the
  model requires (stepping it more often is recomputation and does not
  count), multiply and add each one FLOP:

  - SPS stages 1–3, each ``h·w·C_out·(3·3·C_in)·2`` at its input grid
    ``h × w`` (the sensor halved, then halved after each stage);
  - the relative-position conv, ``t·t·D·(3·3·D)·2`` at the token grid;
  - per encoder block, at ``N`` tokens: Q, K, V ``3·N·D·D·2``; per head
    ``Kᵀ·V`` and ``Q·(Kᵀ·V)``, ``2·N·d·d·2`` with ``d = D / heads``;
    the output projection ``N·D·D·2``; the MLP ``2·N·D·(r·D)·2``;
  - the head, ``D·n_classes·2``.

  Batch norm, LIF, pooling, residual sums and the token mean are
  elementwise and not counted.
* The least backbone bytes per step and lane: every LIF membrane, float32,
  read once and written once. Weights, read once per call whatever the
  lane count, are not counted.
"""
from __future__ import annotations

from bench import work

KERNEL = 3


def _shapes(model: dict) -> dict:
    p2m, bb = model["p2m"], model["backbone"]
    h, w = (n // p2m["stride"] // 2 for n in bb["input_hw"])
    d = bb["embed_dim"]
    stages, membranes = [], []
    c_in = d // 8
    for c_out in (d // 4, d // 2, d):
        stages.append((h, w, c_in, c_out))
        membranes.append(h * w * c_out)
        h, w, c_in = (h + 1) // 2, (w + 1) // 2, c_out
    n = h * w
    hid = bb["mlp_ratio"] * d
    membranes.append(n * d)                                    # RPE
    membranes += [(6 * n * d + n * hid)] * bb["depth"]         # per block
    return {"stages": stages, "tokens": n, "d": d, "hid": hid,
            "membranes": sum(membranes)}


def backbone_flops_per_step(model: dict) -> float:
    bb = model["backbone"]
    s = _shapes(model)
    k2 = KERNEL * KERNEL
    flops = sum(h * w * co * k2 * ci * 2 for h, w, ci, co in s["stages"])
    n, d, hid = s["tokens"], s["d"], s["hid"]
    flops += n * d * k2 * d * 2                                # RPE
    hd = d // bb["heads"]
    block = (3 * n * d * d * 2 + bb["heads"] * 2 * n * hd * hd * 2
             + n * d * d * 2 + 2 * n * d * hid * 2)
    flops += bb["depth"] * block + d * bb["n_classes"] * 2
    return float(flops)


def membrane_floats(model: dict) -> int:
    return _shapes(model)["membranes"]


def backbone_min_bytes_per_step(model: dict) -> float:
    return float(2 * membrane_floats(model) * 4)


def steps_per_lane_window(model: dict) -> float:
    """Backbone steps the model requires per lane and T_INTG window."""
    return model["p2m"]["t_intg_ms"] / model["coarse_window_ms"]


def model_flops_per_lane_window(model: dict) -> float:
    """Layer 1 every window plus the backbone's share of a coarse window."""
    return (work.layer1_flops_per_window(model)
            + backbone_flops_per_step(model) * steps_per_lane_window(model))


def backbone_least_seconds(model: dict, steps: float, peak: dict
                           ) -> tuple[float, str]:
    """Least device time for ``steps`` lane-steps of the backbone on one
    chip of ``peak``, and which bound sets it."""
    t_bytes = backbone_min_bytes_per_step(model) * steps \
        / peak["hbm_bytes_per_s"]
    t_flops = backbone_flops_per_step(model) * steps / peak["bf16_flops"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
