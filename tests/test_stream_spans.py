"""The serving loop's profiler spans (``p2m.*``, repro.stream.engine):
each step of ``StreamEngine.serve`` lands in a profiler trace under its
name, once per unit of its work, tagged with the ids of what caused it;
the engine's own ``fold_s``/``readout_s`` timers keep their shape, and
tracing changes no answer."""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core.codesign import P2MModelConfig  # noqa: E402
from repro.core.leakage import CircuitConfig, LeakageConfig  # noqa: E402
from repro.core.p2m_layer import P2MConfig  # noqa: E402
from repro.core.snn import SpikingCNNConfig  # noqa: E402
from repro.data import sources  # noqa: E402
from repro.stream import deploy as deploy_mod  # noqa: E402
from repro.stream.engine import StreamEngine  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace as trace_mod  # noqa: E402

HW = 16
SPANS = ("p2m.schedule", "p2m.admit", "p2m.pace", "p2m.bin", "p2m.bin_wait",
         "p2m.h2d", "p2m.fold", "p2m.readout", "p2m.sync", "p2m.finalise")


@pytest.fixture(scope="module")
def src():
    return sources.resolve_dataset("synthetic-gesture", hw=HW,
                                   duration_ms=400.0)


@pytest.fixture(scope="module")
def dep(src):
    """The small paced-test deployment: T_INTG 100 ms in 2 sub-slots, a
    200 ms coarse window, so a 400 ms stream is 4 windows of 2 chunks."""
    model = P2MModelConfig(
        p2m=P2MConfig(out_channels=8, n_sub=2, t_intg_ms=100.0,
                      leak=LeakageConfig(circuit=CircuitConfig.NULLIFIED)),
        backbone=SpikingCNNConfig(channels=(8, 16), input_hw=(HW, HW),
                                  fc_hidden=32, n_classes=src.n_classes,
                                  first_layer_external=True),
        coarse_window_ms=200.0)
    return deploy_mod.fresh_deployment(model, seed=0)


def _served(dep, src, tmp, *, n_streams, prefetch=True, paced=False,
            traced=True):
    """Serve on 2 lanes; with ``traced``, under the profiler inside a
    ``bench.window`` bracket. Returns the report, the engine, the
    trace as the benchmark reads it and every host ``p2m.*`` event with
    its stats."""
    engine = StreamEngine(dep, capacity=2, prefetch=prefetch)
    if not traced:
        return engine.serve(src, n_streams, seed=0, paced=paced), engine, \
            None, None
    with jax.profiler.trace(str(tmp)):
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW_EVENT):
            report = engine.serve(src, n_streams, seed=0, paced=paced)
    path = trace_mod.find(tmp)
    events = []
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            events += [(e.name, dict(e.stats)) for line in plane.lines
                       for e in line.events if e.name.startswith("p2m.")]
    return report, engine, trace_mod.load(path), events


@pytest.fixture(scope="module")
def prefetched(dep, src, tmp_path_factory):
    """5 streams on 2 lanes: lanes turn over, so streams are admitted
    and finalised inside the run."""
    return _served(dep, src, tmp_path_factory.mktemp("spans_prefetch"),
                   n_streams=5)


@pytest.fixture(scope="module")
def inline_paced(dep, src, tmp_path_factory):
    """Inline binning on the serving thread, held to the wall clock."""
    return _served(dep, src, tmp_path_factory.mktemp("spans_inline"),
                   n_streams=3, prefetch=False, paced=True)


def _count(tr, name):
    return sum(n == name for n, _, _ in tr.host)


def _stats(events, name):
    return [s for n, s in events if n == name]


@pytest.mark.parametrize("run", ["prefetched", "inline_paced"])
def test_every_span_counts_its_work(run, request):
    report, engine, tr, events = request.getfixturevalue(run)
    windows = max(r.finished_window for r in report.results)
    chunks = windows * engine.chunks_per_window
    want = {"p2m.schedule": windows, "p2m.readout": windows,
            "p2m.sync": windows, "p2m.bin_wait": windows,
            "p2m.h2d": windows, "p2m.fold": windows,
            "p2m.admit": report.n_admitted,
            "p2m.finalise": len(report.results),
            "p2m.bin": windows * engine.bin_workers,
            "p2m.pace": windows if report.paced else 0}
    assert {k: _count(tr, k) for k in SPANS} == want
    assert all(want[k] > 0 for k in SPANS if k != "p2m.pace")
    # the engine's outer timers keep one entry per chunk / per window
    assert len(report.fold_s) == chunks
    assert len(report.readout_s) == windows
    # one copy and one fold per window, each carrying all of its chunks
    for name in ("p2m.h2d", "p2m.fold"):
        stats = _stats(events, name)
        assert sum(s["chunks"] for s in stats) == chunks
        assert {(s["chunks"], s["slots"]) for s in stats} \
            == {(engine.chunks_per_window, engine.n_sub)}
    # the counts sit on the spans where the work happens: one bin job
    # per worker a window, over every occupied lane of it
    bins = _stats(events, "p2m.bin")
    assert sum(s["events"] for s in bins) == report.total_events
    assert sum(s["lanes"] for s in bins) == report.total_readouts
    assert all(0 < s["cells"] <= s["events"] for s in bins if s["lanes"])
    # a job clears what its worker wrote into the same batch two
    # windows earlier; the first use of each batch finds nothing
    cells = {s["window"]: s["cells"] for s in bins}
    assert [s["cleared"] for s in sorted(bins, key=lambda s: s["window"])] \
        == [cells.get(s["window"] - 2, 0)
            for s in sorted(bins, key=lambda s: s["window"])]
    sched = _stats(events, "p2m.schedule")
    assert sum(s["n_admitted"] for s in sched) == report.n_admitted
    assert sum(s["n_shed"] for s in sched) == report.n_shed
    assert sorted(s["window"] for s in _stats(events, "p2m.sync")) \
        == list(range(windows))


def test_every_span_name_appears_in_a_paced_inline_run(inline_paced):
    _, _, tr, _ = inline_paced
    assert {n for n, _, _ in tr.host if n.startswith("p2m.")} == set(SPANS)


def test_admit_nests_in_schedule(prefetched):
    _, _, tr, _ = prefetched
    sched = [(s, e) for n, s, e in tr.host if n == "p2m.schedule"]
    admits = [(s, e) for n, s, e in tr.host if n == "p2m.admit"]
    assert admits
    for s, e in admits:
        assert any(ss <= s and e <= se for ss, se in sched)


def test_h2d_bytes_are_the_dense_frames(prefetched):
    _, engine, _, events = prefetched
    nbytes = (engine.padded_capacity * engine.n_sub * HW * HW * 2
              * np.dtype(np.float32).itemsize)
    h2d = _stats(events, "p2m.h2d")
    assert h2d and all(s["bytes"] == nbytes for s in h2d)


def test_spans_carry_the_ids_of_what_caused_them(prefetched):
    report, _, _, events = prefetched
    admitted = {(s["stream"], s["window"], s["lane"])
                for s in _stats(events, "p2m.admit")}
    assert {(r.stream_id, r.admitted_window) for r in report.results} \
        == {(sid, w) for sid, w, _ in admitted}
    finished = {(s["stream"], s["window"])
                for s in _stats(events, "p2m.finalise")}
    assert finished == {(r.stream_id, r.finished_window - 1)
                        for r in report.results}
    windows = max(r.finished_window for r in report.results)
    assert sorted((s["window"], s["worker"])
                  for s in _stats(events, "p2m.bin")) \
        == [(w, 0) for w in range(windows)]


def test_tracing_changes_no_answer(prefetched, dep, src):
    traced, *_ = prefetched
    plain, *_ = _served(dep, src, None, n_streams=5, traced=False)
    assert [(r.stream_id, r.prediction, r.logits) for r in traced.results] \
        == [(r.stream_id, r.prediction, r.logits) for r in plain.results]


def test_readout_counts_the_backbone_steps_the_model_needed(prefetched):
    report, engine, _, events = prefetched
    readouts = _stats(events, "p2m.readout")
    assert {s["backbone"] for s in readouts} == {"cnn"}
    # lanes at a coarse boundary, against the capacity the program steps
    assert all(0 <= s["coarse_lanes"] <= engine.padded_capacity
               for s in readouts)
    assert sum(s["coarse_lanes"] for s in readouts) \
        == sum(r.n_coarse_frames for r in report.results)
