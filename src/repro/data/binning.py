"""Streaming slot-binning: raw (t, x, y, p) event records → the engine's
per-slot event-frame format.

The sweep engine consumes ``[B, n_slots, n_sub, H, W, 2]`` float32 count
frames (ON/OFF on the last axis) at an arbitrary integration time T_INTG.
:func:`bin_chunks` folds a chunked event stream (repro.data.formats) into
a single recording's ``[n_total, H, W, 2]`` fine-slot histogram — one
``np.add.at`` scatter per chunk, never materializing the full event list
— with integer spatial downscaling from the sensor resolution to the
model resolution. :func:`event_cells` is the one event → cell rule it
shares with the serving engine's window binner (repro.stream.engine).
Cache layout and keying live in repro.data.cache.
"""
from __future__ import annotations

from typing import Iterable

import numpy as np

from repro.data.formats import EventChunk


def slot_us_for(t_intg_ms: float, n_sub: int) -> int:
    """Fine-slot width in µs for an integration time split into ``n_sub``
    sub-slots. Must be integral µs so file timestamps bin exactly."""
    us = t_intg_ms * 1000.0 / n_sub
    if abs(us - round(us)) > 1e-6 or round(us) <= 0:
        raise ValueError(
            f"t_intg_ms={t_intg_ms} / n_sub={n_sub} is not a whole number "
            f"of microseconds — file-backed binning needs integral slots")
    return int(round(us))


def event_cells(t: np.ndarray, x: np.ndarray, y: np.ndarray, p: np.ndarray,
                *, t0_us, n_slots: int, slot_us: int,
                sensor_hw: tuple[int, int], out_hw: tuple[int, int],
                t_stop_us: int | None = None
                ) -> tuple[np.ndarray, np.ndarray]:
    """Where each event lands in an ``[n_slots, H, W, 2]`` count block:
    the binner's one event → cell rule, shared by :func:`bin_chunks` and
    the serving engine's window binner. Returns the keep mask over the
    events and the flat cell index of each kept one.

    ``t0_us`` is the block's first slot start, a scalar or one per
    event. Events before it, past the last slot, or at/after
    ``t_stop_us`` are dropped; coordinates are downscaled ``sensor →
    out`` by integer scaling (x * W_out // W_sensor) and dropped off the
    grid; polarity p=1 (ON) goes to channel 0, p=0 (OFF) to channel 1.
    """
    sh, sw = sensor_hw
    oh, ow = out_hw
    slot = (t - t0_us) // slot_us
    yy = (y.astype(np.int64) * oh) // sh
    xx = (x.astype(np.int64) * ow) // sw
    ok = ((slot >= 0) & (slot < n_slots) & (yy >= 0) & (yy < oh)
          & (xx >= 0) & (xx < ow))
    if t_stop_us is not None:
        ok &= t < t_stop_us
    cells = ((slot * oh + yy) * ow + xx) * 2 + (1 - p.astype(np.int64))
    return ok, cells[ok]


def bin_chunks(chunks: Iterable[EventChunk], *, n_total: int, slot_us: int,
               sensor_hw: tuple[int, int], out_hw: tuple[int, int],
               t0_us: int = 0, t_stop_us: int | None = None) -> np.ndarray:
    """Accumulate an event-chunk stream into ``[n_total, H, W, 2]``
    float32 counts (channel 0 = ON, channel 1 = OFF, matching the
    synthetic generator). Events before ``t0_us``, past the last slot, or
    at/after ``t_stop_us`` (a labeled window's end — events beyond it
    belong to the NEXT sample, not this one) are dropped; coordinates are
    downscaled ``sensor → out`` by integer scaling (x * W_out // W_sensor)
    (:func:`event_cells`).
    """
    oh, ow = out_hw
    frames = np.zeros((n_total, oh, ow, 2), dtype=np.float32)
    flat = frames.reshape(-1)
    for c in chunks:
        if not len(c):
            continue
        _, cells = event_cells(c.t, c.x, c.y, c.p, t0_us=t0_us,
                               n_slots=n_total, slot_us=slot_us,
                               sensor_hw=sensor_hw, out_hw=out_hw,
                               t_stop_us=t_stop_us)
        np.add.at(flat, cells, 1.0)
    return frames


def frames_to_events(frames: np.ndarray, slot_us: int, *,
                     rng: np.random.Generator | None = None) -> EventChunk:
    """Expand a ``[n_total, H, W, 2]`` count histogram into discrete
    (t, x, y, p) records — the inverse direction of :func:`bin_chunks`,
    used by the fixture writers (repro.data.fixtures) to synthesize
    AEDAT / ``.bin`` files from the analytic generator's frames.

    Each count of ``c`` at (slot, y, x, pol) becomes ``c`` events with
    timestamps spread inside the slot (evenly, or uniformly when ``rng``
    is given), so re-binning at the same slot width recovers ``frames``
    exactly.
    """
    n_total = frames.shape[0]
    counts = np.rint(np.asarray(frames)).astype(np.int64)
    slot, y, x, pol = np.nonzero(counts)
    reps = counts[slot, y, x, pol]
    slot = np.repeat(slot, reps)
    y = np.repeat(y, reps)
    x = np.repeat(x, reps)
    pol = np.repeat(pol, reps)
    n = len(slot)
    if rng is None:
        # even spread: the k-th duplicate of a (slot, y, x, pol) cell with
        # count c offsets by k * slot_us // c — deterministic, and
        # re-binning at slot_us recovers the histogram exactly
        rank = np.zeros(n, dtype=np.int64)
        if n:
            # np.repeat keeps cell order, so within-cell rank is the
            # position minus the cell's start offset in the flat stream
            starts = np.repeat(np.cumsum(reps) - reps, reps)
            rank = np.arange(n) - starts
        cell_count = np.repeat(reps, reps)
        off = np.minimum(rank * slot_us // np.maximum(cell_count, 1),
                         slot_us - 1)
    else:
        off = rng.integers(0, slot_us, size=n)
    t = slot * slot_us + off
    order = np.argsort(t, kind="stable")
    return EventChunk(t=t[order].astype(np.int64),
                      x=x[order].astype(np.int32),
                      y=y[order].astype(np.int32),
                      p=(1 - pol[order]).astype(np.int8))
