"""The readers of the serving loop's spans (``bench/spans.py`` and the
``program_span`` metrics that use it) on a hand-built trace with known
intervals, on the recorded TPU trace (which holds no program span), and
in the cells that list them."""
from __future__ import annotations

import json

import pytest

from perfbench_cells import ROOT

from bench import catalog, spans, trace

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
SPAN_OF = {"bin_ms": "p2m.bin", "bin_wait_ms": "p2m.bin_wait",
           "assemble_ms": "p2m.assemble", "h2d_ms": "p2m.h2d",
           "fold_dispatch_ms": "p2m.fold",
           "readout_dispatch_ms": "p2m.readout",
           "readout_wait_ms": "p2m.sync", "admit_ms": "p2m.admit",
           "finalise_ms": "p2m.finalise"}
READERS = sorted(SPAN_OF) + ["idle_untraced_share"]
MS = 1_000_000  # ns


def _ctx(tr, chips=1):
    return {"trace_data": tr, "trace": None, "chips": chips,
            "traced_windows": 1, "lane_windows": 1}


def _known(name):
    """A 100 ms bracket holding two ``name`` spans of 2 and 4 ms, one of
    8 ms that starts before it and one of 16 ms that starts at its end;
    neither of the last two counts."""
    host = [(name, 10 * MS, 12 * MS), (name, 50 * MS, 54 * MS),
            (name, -4 * MS, 4 * MS), (name, 100 * MS, 116 * MS),
            ("PjitFunction(fold_body)", 20 * MS, 30 * MS)]
    return trace.Trace(devices=[trace.Device("/device:TPU:0")], host=host,
                       window=(0, 100 * MS))


@pytest.mark.parametrize("metric", sorted(SPAN_OF))
def test_span_reader_is_the_mean_inside_the_bracket(metric):
    tr = _known(SPAN_OF[metric])
    assert catalog.metric(metric).reduce(_ctx(tr)) == pytest.approx(3.0)
    other = _known("p2m.elsewhere")
    assert catalog.metric(metric).reduce(_ctx(other)) is None


def test_span_ms_without_a_trace_or_a_bracket():
    assert spans.span_ms(_ctx(None), "p2m.h2d") is None
    tr = _known("p2m.h2d")
    tr.window = None
    assert spans.span_ms(_ctx(tr), "p2m.h2d") is None


def test_overlap_of_interval_lists():
    assert spans.overlap([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert spans.overlap([(0, 10)], [(10, 20)]) == 0
    assert spans.overlap([], [(0, 1)]) == 0


def test_idle_untraced_share_over_two_chips():
    # chip 0 idle 40-100 ms, chip 1 idle 70-100 ms; the serving thread
    # is in a span 40-80 and 90-100 ms; a worker's p2m.bin (80-90) and
    # JAX's own events name nothing
    devs = [trace.Device("/device:TPU:0", ops=[("fusion", 0, 40 * MS)]),
            trace.Device("/device:TPU:1", ops=[("fusion", 0, 30 * MS),
                                               ("copy", 25 * MS, 70 * MS)])]
    host = [("p2m.h2d", 40 * MS, 60 * MS), ("p2m.fold", 55 * MS, 80 * MS),
            ("p2m.bin", 80 * MS, 90 * MS),
            ("PjitFunction(fold_body)", 80 * MS, 90 * MS),
            ("p2m.sync", 90 * MS, 120 * MS)]
    tr = trace.Trace(devices=devs, host=host, window=(0, 100 * MS))
    got = catalog.metric("idle_untraced_share").reduce(_ctx(tr, chips=2))
    # chip 0: 10 of 60 ms untraced; chip 1: 10 of 30 ms
    assert got == pytest.approx(100 * (10 / 60 + 10 / 30) / 2)
    one = catalog.metric("idle_untraced_share").reduce(_ctx(tr, chips=1))
    assert one == pytest.approx(100 * 10 / 60)
    assert catalog.metric("idle_untraced_share").reduce(
        _ctx(tr, chips=4)) is None
    tr.host = [h for h in host if not h[0].startswith("p2m.")]
    assert catalog.metric("idle_untraced_share").reduce(
        _ctx(tr, chips=2)) is None


@pytest.mark.parametrize("metric", READERS)
def test_readers_read_nothing_from_a_program_without_spans(metric):
    recorded = trace.load(ROOT / "tests" / "bench" / "data"
                          / "small.xplane.pb")
    assert not any(n.startswith("p2m.") for n, _, _ in recorded.host)
    assert catalog.metric(metric).reduce(_ctx(recorded)) is None
    assert catalog.metric(metric).reduce(_ctx(None)) is None


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_cells_list_the_span_metrics_their_entries_name(name):
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    got = {m["name"] for m in catalog.cell(name, BENCH)["per_layer"]}
    for metric in READERS:
        assert entries[metric]["source"] == "program_span"
        assert entries[metric]["moves"] == "events_per_s"
        assert (metric in got) == (name in entries[metric]["workloads"])
    per_stream = {"admit_ms", "finalise_ms"}
    want = set(READERS) - (per_stream if name.startswith("gesture") else set())
    assert want <= got
