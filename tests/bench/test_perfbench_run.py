"""bench/run.py end to end on the CPU at a test size: it refuses to
measure without a TPU or without the program, and a sound run of each
cell's path compares correct."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from perfbench_cells import ROOT, tiny_cell

from bench import run


def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


ARGS = ["--workload", "gesture128.saturated", "--seed", "3",
        "--seconds", "1", "--trace", "0"]


def test_exits_nonzero_without_a_tpu():
    out = _run(ARGS, ROOT)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert not out.stdout.strip()


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(ARGS, tmp_path)
    assert out.returncode != 0
    assert not out.stdout.strip()


@pytest.mark.parametrize("mix", ["saturated", "paced"])
def test_a_sound_run_compares_correct(mix):
    cell = tiny_cell("gesture128.saturated", mix=mix)
    out = run.run_cell(cell, seed=2 ** 31 + 3, seconds=1.0, trace=False,
                       devices=jax.devices(), log=lambda *_: None)
    assert out["correct"], out["faults"]
    assert out["attempted"] > 0
    assert "setup_s" in out["metrics"]
    assert set(out["checks"]) == {"l1_flip_ppm", "logit_err", "pred_gap"}
    assert out["checks"]["l1_flip_ppm"]["value"] == 0.0
