"""The comparison that decides ``correct`` catches what it must: the
bfloat16 control, and the timed path broken underneath a run (a fold
that leaves the state unchanged, half the lanes left out, an answer
altered where the readout produces it). The four-chip exchange fault
has no cell that could show it yet."""
from __future__ import annotations

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench_cells import tiny_cell

from bench import compare, run


def _state_unchanged(engine):
    engine.fns = replace(engine.fns, fold=lambda state, frames, active: state)


def _half_the_lanes(engine):
    fold = engine.fns.fold
    half = jnp.arange(engine.padded_capacity) < engine.padded_capacity // 2

    def broken(state, frames, active):
        return fold(state, frames, active & half)
    engine.fns = replace(engine.fns, fold=broken)


def _answer_altered(engine):
    readout = engine.fns.readout

    def broken(state, active, coarse_mask):
        state, out = readout(state, active, coarse_mask)
        bump = jnp.zeros_like(state["logits"]).at[:, 0].add(
            coarse_mask.astype(jnp.float32))
        return {**state, "logits": state["logits"] + bump}, out
    engine.fns = replace(engine.fns, readout=broken)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_the_lanes,
                                   _answer_altered],
                         ids=["state_unchanged", "half_the_lanes",
                              "answer_altered"])
def test_a_broken_timed_path_compares_incorrect(fault):
    cell = tiny_cell("gesture128.saturated")
    out = run.run_cell(cell, seed=11, seconds=0.5, trace=False,
                       devices=jax.devices(), engine_hook=fault,
                       log=lambda *_: None)
    assert not out["correct"]
    assert out["faults"]


@pytest.mark.parametrize("name", ["gesture128.saturated", "nmnist34.saturated"])
def test_the_bfloat16_control_fails_the_cells_limits(name):
    cell = tiny_cell(name)
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
