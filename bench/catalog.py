"""Find the benchmark's parts by name.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric sits in a file of its own, named after it:

    BENCHMARK.json                     cells, metrics, configurations
    bench/configs/<config>.json        sizes, source, reduced, assumed
    bench/references/<reference>.py    plain reference a config names
    bench/traffic/<mix>.json           parameters of one traffic mix
    bench/workloads/<cell>.json        capacity, pool, limits of one cell
    bench/metrics/<metric>.py          one ``reduce(ctx)`` per metric

A new configuration, mix, cell or metric is new files plus entries in
``BENCHMARK.json``; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file {path} does not exist")
    return json.loads(path.read_text())


def _module(path: Path) -> ModuleType:
    """Import a file by path (metric names may hold dots)."""
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file {path} does not exist")
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark(root: Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json")


def config(name: str) -> dict:
    cfg = _json(BENCH / "configs" / f"{name}.json")
    if cfg.get("name") != name:
        raise ValueError(f"bench/configs/{name}.json names itself "
                         f"{cfg.get('name')!r}")
    return cfg


def reference(name: str) -> ModuleType:
    return _module(BENCH / "references" / f"{name}.py")


def traffic(name: str) -> dict:
    return _json(BENCH / "traffic" / f"{name}.json")


def metric(name: str) -> ModuleType:
    mod = _module(BENCH / "metrics" / f"{name}.py")
    if not callable(getattr(mod, "reduce", None)):
        raise ValueError(f"bench/metrics/{name}.py has no reduce(ctx)")
    return mod


def cell(name: str, bench: dict | None = None) -> dict:
    """One cell, whole: its ``BENCHMARK.json`` entry, its run parameters,
    its configuration and mix, and the metrics it reports (end-to-end
    with ``--trace 0``, per-layer with ``--trace 1``)."""
    bench = benchmark() if bench is None else bench
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(entries)})")
    entry = entries[name]

    def reports(m: dict) -> bool:
        return "workloads" not in m or name in m["workloads"]

    e2e = [m for m in bench["end_to_end"] if reports(m)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if reports(m) and m["moves"] in e2e_names]
    return {"name": name, "entry": entry,
            "params": _json(BENCH / "workloads" / f"{name}.json"),
            "config": config(entry["config"]),
            "traffic": traffic(entry["traffic"]),
            "end_to_end": e2e, "per_layer": layer}
