"""Helpers shared by the benchmark's tests: the repository root on the
import path, and cells cut to a size the CPU test suite can run."""
from __future__ import annotations

import copy
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def tiny_cell(name: str, *, hw: int = 16, capacity: int = 4,
              duration_ms: float = 200.0, pool: int = 4,
              mix: str | None = None) -> dict:
    """A cell of ``BENCHMARK.json`` with its configuration cut to a CPU
    size: ``hw``×``hw`` sensor, narrow backbone, 100 ms coarse window.
    The limits and the harness are the cell's own; the traffic too,
    unless ``mix`` names another one."""
    from bench import catalog

    cell = catalog.cell(name)
    if mix is not None:
        cell["traffic"] = catalog.traffic(mix)
    cfg = copy.deepcopy(cell["config"])
    m = cfg["model"]
    m["backbone"].update(input_hw=[hw, hw], channels=[4, 8, 8, 8],
                         fc_hidden=32)
    m["p2m"]["out_channels"] = 4
    m["coarse_window_ms"] = 100.0
    cfg["stream"].update(height=hw, width=hw, duration_ms=duration_ms)
    cell["config"] = cfg
    cell["params"] = dict(cell["params"], capacity=capacity)
    cell["traffic"] = dict(cell["traffic"], pool=pool)
    return cell
