"""The serving engine's window binner (``StreamEngine._bin_window``):
each bin worker bins its lanes' replay chunks of a window in one pass,
straight into one of two reused window batches. The batch the fold is
handed must be, bit for bit, the offline binner's (``bin_chunks``) block
of each lane-chunk at its lane row and sub-slot range, with every other
row zero — through lane turnover, for any worker count, prefetching or
inline."""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core.codesign import P2MModelConfig  # noqa: E402
from repro.core.leakage import CircuitConfig, LeakageConfig  # noqa: E402
from repro.core.p2m_layer import P2MConfig  # noqa: E402
from repro.core.snn import SpikingCNNConfig  # noqa: E402
from repro.data.binning import bin_chunks, event_cells  # noqa: E402
from repro.data.formats import EventChunk  # noqa: E402
from repro.data.sources import EventSource  # noqa: E402
from repro.stream import deploy as deploy_mod  # noqa: E402
from repro.stream.engine import StreamEngine  # noqa: E402

SENSOR = 128
T_INTG_MS = 100.0
N_SUB = 4
CHUNKS_PER_WINDOW = 2
WINDOWS = 3          # per stream; 4 streams on 3 lanes make 6 windows
CAPACITY = 3
N_STREAMS = 4


def _key_id(key) -> tuple[int, ...]:
    return tuple(np.asarray(jax.random.key_data(key)).ravel().tolist())


class _ScriptedSource(EventSource):
    """Random events on a 128×128 sensor, ``WINDOWS`` T_INTG windows a
    stream. Each chunk spills 5 ms past both ends of its own time range,
    so the binner must drop events before its first slot and past its
    last; every third chunk of odd streams is empty, and stream 2 sends
    nothing in its second window."""

    def __init__(self, out_hw: int):
        self.name = "scripted"
        self.height = self.width = out_hw
        self.n_classes = 2
        self.duration_ms = WINDOWS * T_INTG_MS
        self.sensor_hw = (SENSOR, SENSOR)
        # serve(seed=0) opens stream ``sid`` with fold_in(PRNGKey(0), sid)
        self._sid = {_key_id(jax.random.fold_in(jax.random.PRNGKey(0), s)): s
                     for s in range(N_STREAMS)}

    def iter_event_chunks(self, key, *, chunk_us, slot_us=None):
        sid = self._sid[_key_id(key)]
        rng = np.random.default_rng(sid)
        n_chunks = round(self.duration_ms * 1000 / chunk_us)
        per_window = n_chunks // WINDOWS
        chunks = []
        for i in range(n_chunks):
            empty = ((sid % 2 and i % 3 == 2)
                     or (sid % 5 == 2 and i // per_window == 1))
            n = 0 if empty else int(rng.integers(50, 400))
            lo, hi = i * chunk_us - 5000, (i + 1) * chunk_us + 5000
            chunks.append(EventChunk(
                t=np.sort(rng.integers(lo, hi, n)).astype(np.int64),
                x=rng.integers(0, SENSOR, n).astype(np.int32),
                y=rng.integers(0, SENSOR, n).astype(np.int32),
                p=rng.integers(0, 2, n).astype(np.int8)))
        return sid % self.n_classes, iter(chunks)


def _engine(out_hw, bin_workers, prefetch):
    """A 3-lane engine whose device steps are stubs: the fold records a
    copy of the window batch it is handed, and nothing compiles."""
    model = P2MModelConfig(
        p2m=P2MConfig(out_channels=4, n_sub=N_SUB, t_intg_ms=T_INTG_MS,
                      leak=LeakageConfig(circuit=CircuitConfig.NULLIFIED)),
        backbone=SpikingCNNConfig(channels=(4, 8), input_hw=(out_hw, out_hw),
                                  fc_hidden=16, n_classes=2,
                                  first_layer_external=True),
        coarse_window_ms=T_INTG_MS)
    engine = StreamEngine(deploy_mod.fresh_deployment(model, seed=0),
                          capacity=CAPACITY,
                          chunks_per_window=CHUNKS_PER_WINDOW,
                          bin_workers=bin_workers, prefetch=prefetch)
    seen = []

    def fold(state, frames, active):
        seen.append(np.array(frames))
        return state

    engine.fns = dataclasses.replace(
        engine.fns, fold=fold,
        readout=lambda state, active, coarse: (
            state, {"n_spikes": np.zeros(CAPACITY, np.float32)}),
        reset_lane=lambda state, lane: state)
    return engine, seen


def _reference(engine, src, placements, n_windows):
    """Each window's batch as the offline binner makes it: every
    lane-chunk through ``bin_chunks`` at its lane row and sub-slot range,
    unoccupied rows zero."""
    h, w = engine.fns.in_hw
    want = np.zeros((n_windows, CAPACITY, N_SUB, h, w, 2), np.float32)
    events = {}
    key = jax.random.PRNGKey(0)
    for sid, (lane, first) in placements.items():
        _, chunks = src.iter_event_chunks(jax.random.fold_in(key, sid),
                                          chunk_us=engine.chunk_us)
        chunks = list(chunks)
        events[sid] = sum(len(c) for c in chunks)
        for i, chunk in enumerate(chunks):
            window = first + i // CHUNKS_PER_WINDOW
            lo = (i % CHUNKS_PER_WINDOW) * engine.chunk_slots
            want[window, lane, lo:lo + engine.chunk_slots] = bin_chunks(
                [chunk], n_total=engine.chunk_slots, slot_us=engine.slot_us,
                sensor_hw=src.sensor_hw, out_hw=(h, w),
                t0_us=i * engine.chunk_us)
    return want, events


@pytest.mark.parametrize("bin_workers,prefetch",
                         [(1, True), (2, True), (4, True),
                          (1, False), (2, False), (4, False)])
@pytest.mark.parametrize("out_hw", [128, 64])
def test_window_batch_is_the_offline_binners(out_hw, bin_workers, prefetch):
    """Streams 0-2 hold lanes 0-2 for windows 0-2; at window 3 stream 3
    is admitted again on lane 0 and lanes 1-2 are freed, so windows 3-5
    reuse both batches with two rows that must read zero."""
    src = _ScriptedSource(out_hw)
    engine, seen = _engine(out_hw, bin_workers, prefetch)
    report = engine.serve(src, N_STREAMS, seed=0)
    placements = {0: (0, 0), 1: (1, 0), 2: (2, 0), 3: (0, WINDOWS)}
    assert {r.stream_id: r.admitted_window for r in report.results} \
        == {sid: first for sid, (_, first) in placements.items()}
    n_windows = 2 * WINDOWS
    want, events = _reference(engine, src, placements, n_windows)
    # the warm-up's batch, then one per window
    assert len(seen) == 1 + n_windows
    assert not seen[0].any()
    got = np.stack(seen[1:])
    # the spills and empty chunks leave real work: events dropped at both
    # ends of a chunk, cells holding more than one event, rows that were
    # written and must read zero again
    assert want.max() > 1 and not want[WINDOWS:, 1:].any()
    assert want[:WINDOWS, 1:].any()
    np.testing.assert_array_equal(got, want)
    # every event taken counts, dropped ones too
    assert {r.stream_id: r.n_events for r in report.results} == events
    assert report.total_events == sum(events.values())
    assert sum(want.sum(axis=(1, 2, 3, 4, 5))) < report.total_events
    assert len(report.fold_s) == n_windows * CHUNKS_PER_WINDOW


@pytest.mark.parametrize("sensor,out,t_stop_us", [(128, 128, None),
                                                  (128, 64, None),
                                                  (34, 34, 7000)])
def test_bin_chunks_counts_each_event_by_the_rule(sensor, out, t_stop_us):
    """``bin_chunks`` (and so ``event_cells``, which it and the window
    binner share) against one event at a time: drop it before ``t0`` or
    past the last slot or at ``t_stop``, downscale, ON to channel 0."""
    rng = np.random.default_rng(sensor + out)
    n, slot_us, n_slots, t0 = 2000, 2500, 4, 1000
    t = rng.integers(t0 - 3000, t0 + n_slots * slot_us + 3000,
                     n).astype(np.int64)
    x = rng.integers(0, sensor, n).astype(np.int32)
    y = rng.integers(0, sensor, n).astype(np.int32)
    p = rng.integers(0, 2, n).astype(np.int8)
    want = np.zeros((n_slots, out, out, 2), np.float32)
    for ti, xi, yi, pi in zip(t, x, y, p):
        slot = (int(ti) - t0) // slot_us
        if 0 <= slot < n_slots and (t_stop_us is None or ti < t_stop_us):
            want[slot, int(yi) * out // sensor, int(xi) * out // sensor,
                 1 - int(pi)] += 1
    assert 0 < want.sum() < n
    chunks = [EventChunk(t[i:i + 500], x[i:i + 500], y[i:i + 500],
                         p[i:i + 500]) for i in range(0, n, 500)]
    got = bin_chunks(chunks, n_total=n_slots, slot_us=slot_us,
                     sensor_hw=(sensor, sensor), out_hw=(out, out),
                     t0_us=t0, t_stop_us=t_stop_us)
    np.testing.assert_array_equal(got, want)
    ok, cells = event_cells(t, x, y, p, t0_us=t0, n_slots=n_slots,
                            slot_us=slot_us, sensor_hw=(sensor, sensor),
                            out_hw=(out, out), t_stop_us=t_stop_us)
    assert ok.sum() == len(cells) == want.sum()
