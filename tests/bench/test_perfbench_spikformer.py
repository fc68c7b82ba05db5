"""The ``gesture128.spikformer`` cell through ``bench/run.py`` on the CPU,
cut to a test size: a sound run compares correct, the bfloat16 control
and a broken timed path do not."""
from __future__ import annotations

import copy
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench_cells import ROOT  # noqa: F401  (puts the repository on the path)

from bench import catalog, compare, run

CELL = "gesture128.spikformer"


def small_spikformer_cell(*, hw: int = 64, embed_dim: int = 32,
                          heads: int = 4, capacity: int = 2,
                          duration_ms: float = 200.0, pool: int = 4) -> dict:
    """``gesture128.spikformer`` with its configuration cut to a CPU size:
    ``hw``×``hw`` sensor, ``embed_dim`` wide (``(hw/16)²`` tokens), short
    streams. The limits, the harness and the mix are the cell's own."""
    cell = catalog.cell(CELL)
    cfg = copy.deepcopy(cell["config"])
    m = cfg["model"]
    m["backbone"].update(embed_dim=embed_dim, heads=heads,
                         input_hw=[hw, hw])
    m["p2m"]["out_channels"] = embed_dim // 8
    cfg["stream"].update(height=hw, width=hw, duration_ms=duration_ms)
    cell["config"] = cfg
    cell["params"] = dict(cell["params"], capacity=capacity)
    cell["traffic"] = dict(cell["traffic"], pool=pool)
    return cell


def test_the_cell_keeps_its_published_widths():
    model = catalog.cell(CELL)["config"]["model"]
    assert catalog.cell(CELL)["config"]["reduced"] == []
    assert model["backbone"]["embed_dim"] == 256
    assert model["p2m"]["out_channels"] == 256 // 8
    assert catalog.cell(CELL)["params"]["capacity"] == 8


def test_a_sound_run_compares_correct():
    out = run.run_cell(small_spikformer_cell(), seed=2 ** 31 + 5,
                       seconds=1.0, trace=False, devices=jax.devices(),
                       log=lambda *_: None)
    assert out["correct"], out["faults"]
    assert out["attempted"] > 0
    assert {"setup_s", "events_per_s"} <= set(out["metrics"])
    # float32 on both sides on the CPU: the layer-1 charge to a few ulp
    # and no backbone decision moved
    assert out["checks"]["l1_flip_ppm"]["value"] == 0.0
    assert out["checks"]["pred_gap"]["value"] == 0.0


def test_a_fold_that_leaves_the_state_unchanged_compares_incorrect():
    def fault(engine):
        engine.fns = replace(engine.fns,
                             fold=lambda state, frames, active: state)

    out = run.run_cell(small_spikformer_cell(), seed=13, seconds=0.5,
                       trace=False, devices=jax.devices(), engine_hook=fault,
                       log=lambda *_: None)
    assert not out["correct"]
    assert out["faults"]


def test_the_bfloat16_control_fails_the_cells_limits():
    cell = small_spikformer_cell()
    cfg = cell["config"]
    ref_mod, weights, bn_state, pool = run.make_inputs(cfg, cell["traffic"], 5)
    got = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        fwd = compare.reference_forward(ref_mod, cfg, dtype)
        got[dtype] = jax.device_get(fwd(weights, bn_state, pool.counts))
    ref, ctl = got[jnp.float32], got[jnp.bfloat16]
    assert ref["layer1_spikes"].sum() > 0
    assert (ref["backbone_spikes"].sum(axis=0) > 0).all()
    nums = compare.numbers(ctl["layer1_spikes"], ctl["logits"],
                           np.argmax(ctl["logits"], axis=1),
                           np.arange(pool.size), ref)
    limits = cell["params"]["limits"]
    assert any(nums[k] > limits[k] for k in compare.NUMBERS), nums


@pytest.mark.parametrize("metric", ["backbone_roofline", "spikformer_mfu"])
def test_the_new_readers_list_the_new_cell_only(metric):
    bench = catalog.benchmark()
    entry = next(m for m in bench["per_layer"] if m["name"] == metric)
    assert entry["workloads"] == [CELL]
    assert entry["source"] == "device_trace"
    assert entry["moves"] == "events_per_s"
    assert metric in {m["name"] for m in catalog.cell(CELL)["per_layer"]}
