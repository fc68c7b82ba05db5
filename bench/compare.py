"""The comparison that decides ``correct``.

Every served stream is held to the plain reference run over the very
events it was fed (its pool entry). Three numbers are compared, each
against the limit its cell states (``bench/workloads/<cell>.json``):

``l1_flip_ppm``
    Layer-1 comparator decisions that differ, per million reference
    spikes. Per stream, the pooled layer-1 spike count may differ from
    the reference's by as many pooled outputs as the reference saw
    within :data:`NEAR_V` of the threshold (there float32 rounding of the
    charge decides the comparator either way); what lies beyond is
    counted: ``Σ_streams max(0, |served − reference| − near) / Σ
    reference · 1e6``. Covers the fold (leak ODE and conv deposit) and
    the comparator.
``logit_err``
    The mean over streams of each stream's relative logit error, ``max_c
    |served − reference| / max_c |reference|``. Covers the backbone step
    and the rate-decoded readout. A mean, not the widest stream: at the
    default matmul precision one LIF decision of the backbone that lies
    at its threshold can flip with the batch shape the program compiles,
    and moves one stream's logits by about 0.06 (§6 of PERF.md).
``pred_gap``
    How far the served prediction's reference logit lies below the
    reference's best, as a share of the stream's reference logit range
    (0 where the prediction is the reference's).

Besides, a run is incorrect where a stream was served other events than
its pool entry holds, read out a wrong number of windows, or returned a
non-finite logit, where nothing was compared, or where some layer of the
reference never fired (the comparison would then cover nothing there).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

NUMBERS = ("l1_flip_ppm", "logit_err", "pred_gap")
# a comparator decision within this many volts of the threshold may go
# either way under float32 rounding of the charge (its rounding is under
# 1e-8 V at the paper's swings); it is a thousandth of the smallest
# charge step a weight can deposit (dv_unit / 8 = 1.25 mV)
NEAR_V = 1e-7


def reference_forward(ref_mod, cfg: dict, dtype=jnp.float32):
    """The configuration's plain reference at its stated precision
    (``dtype=bfloat16`` gives the control)."""
    return ref_mod.make_forward(cfg["model"], bn_eps=cfg["bn_eps"],
                                near_v=NEAR_V,
                                precision=cfg["matmul_precision"],
                                dtype=dtype)


def numbers(served_spikes, served_logits, served_pred, pool_idx,
            ref: dict) -> dict:
    """The compared numbers for ``N`` served answers against ``P``
    reference answers (``pool_idx`` maps each served answer to its
    reference), and the widest stream's logit error beside them."""
    pool_idx = np.asarray(pool_idx, np.int64)
    rs = np.asarray(ref["layer1_spikes"], np.float64)[pool_idx]
    near = np.asarray(ref["layer1_near"], np.float64)[pool_idx]
    rl = np.asarray(ref["logits"], np.float64)[pool_idx]
    sl = np.asarray(served_logits, np.float64)
    diff = np.abs(np.asarray(served_spikes, np.float64) - rs)
    flips = np.maximum(diff - near, 0.0).sum()
    scale = np.maximum(np.abs(rl).max(axis=1), 1e-12)
    best = rl.max(axis=1)
    span = np.maximum(best - rl.min(axis=1), 1e-12)
    chosen = rl[np.arange(len(rl)), np.asarray(served_pred, np.int64)]
    rel = np.abs(sl - rl).max(axis=1) / scale
    return {"l1_flip_ppm": float(flips / max(rs.sum(), 1e-12) * 1e6),
            "logit_err": float(rel.mean()),
            "pred_gap": float(((best - chosen) / span).max()),
            "logit_err_widest": float(rel.max())}


def judge(results, pool, ref: dict, limits: dict, *,
          n_windows: int) -> dict:
    """Hold the served ``results`` (the engine's per-stream outcomes,
    labelled with their pool index) to the reference answers ``ref``
    (:func:`reference_forward` over the pool). Returns the numbers, the
    faults found and ``correct``."""
    faults = []
    if not results:
        faults.append("no stream was served")
        return {"numbers": {}, "faults": faults, "correct": False,
                "n_compared": 0}
    idx = np.asarray([r.label for r in results], np.int64)
    events = np.asarray([r.n_events for r in results], np.int64)
    wrong = np.flatnonzero(events != pool.n_events[idx])
    if wrong.size:
        r = results[int(wrong[0])]
        faults.append(f"{wrong.size} stream(s) served other events than "
                      f"their pool entry: stream {r.stream_id} counted "
                      f"{r.n_events}, pool entry {r.label} holds "
                      f"{int(pool.n_events[r.label])}")
    short = [r for r in results if r.n_readouts != n_windows]
    if short:
        faults.append(f"{len(short)} stream(s) read out other than "
                      f"{n_windows} windows (stream {short[0].stream_id}: "
                      f"{short[0].n_readouts})")
    logits = np.asarray([r.logits for r in results], np.float64)
    if not np.isfinite(logits).all():
        faults.append("non-finite served logits")
    fired = np.concatenate([
        np.asarray(ref["layer1_spikes"])[idx, None],
        np.asarray(ref["backbone_spikes"])[idx]], axis=1).sum(axis=0)
    if (fired <= 0).any():
        faults.append(f"reference layers {np.flatnonzero(fired <= 0).tolist()}"
                      f" (0 = layer 1) never fired: the comparison covers "
                      f"no decision there")
    nums = numbers([r.n_layer1_spikes for r in results], logits,
                   [r.prediction for r in results], idx, ref)
    for k in NUMBERS:
        if not nums[k] <= limits[k]:
            faults.append(f"{k} {nums[k]:.6g} exceeds its limit "
                          f"{limits[k]:.6g}")
    return {"numbers": nums, "faults": faults, "correct": not faults,
            "n_compared": len(results)}
