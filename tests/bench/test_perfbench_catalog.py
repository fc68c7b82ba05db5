"""The benchmark finds every part by name, and BENCHMARK.json keeps to
the form every later check reads."""
from __future__ import annotations

import json
import re

import pytest

from perfbench_cells import ROOT

from bench import catalog, compare, work

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
READERS = sorted(p.name[:-3] for p in (ROOT / "bench" / "metrics").glob("*.py"))
WORKLOADS = sorted(p.name[:-5] for p in (ROOT / "bench" / "workloads").glob("*.json"))


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_is_found_by_name(name):
    cell = catalog.cell(name, BENCH)
    assert cell["config"]["name"] == cell["entry"]["config"]
    assert cell["params"]["capacity"] >= 1
    assert set(cell["params"]["limits"]) == set(compare.NUMBERS)
    assert cell["traffic"]["arrivals"] in ("upfront", "even")
    ref = catalog.reference(cell["config"]["reference"])
    assert callable(ref.make_init) and callable(ref.make_forward)
    names = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert cell["per_layer"], f"{name} reports no per-layer metric"


@pytest.mark.parametrize("name", READERS)
def test_every_metric_reader_loads(name):
    assert callable(catalog.metric(name).reduce)


def test_every_declared_metric_has_a_reader():
    assert {m["name"] for m in BENCH["per_layer"]} <= set(READERS)


@pytest.mark.parametrize("name", WORKLOADS)
def test_every_workload_file_states_capacity_and_limits(name):
    params = catalog._json(ROOT / "bench" / "workloads" / f"{name}.json")
    assert params["capacity"] >= 1
    assert set(params["limits"]) == set(compare.NUMBERS)


def test_names_units_and_bounds_keep_the_form():
    assert BENCH["command"] == ["python3", "bench/run.py"]
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir()
    for c in BENCH["configs"]:
        assert NAME.match(c["name"])
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
        assert 1 <= len(c["source"]) <= 200 and len(c["why"]) <= 200
        assert catalog.config(c["name"])["source"] == c["source"]
        assert c["reduced"] == catalog.config(c["name"])["reduced"]
    seen = set()
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["name"] not in seen
        seen.add(m["name"])
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200


def test_peaks_carry_a_source_and_refuse_unknown_kinds():
    peaks = json.loads(work.PEAKS.read_text())
    assert "Google Cloud" in peaks["source"]
    assert work.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        work.peak("TPU v9 imaginary")


def test_configs_state_source_reduced_assumed_and_deployment():
    for c in BENCH["configs"]:
        cfg = catalog.config(c["name"])
        for k in ("source", "reduced", "assumed", "deployment", "precision",
                  "stream", "model"):
            assert k in cfg, (c["name"], k)
