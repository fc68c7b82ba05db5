"""The serving loop's own spans in a profiler trace.

``StreamEngine.serve`` opens one ``jax.profiler.TraceAnnotation`` named
``p2m.<step>`` around each step of its loop (``src/repro/stream/
engine.py``). They land on the trace's host plane, on the device planes'
clock, so they split the host's share of the traced bracket and name
the device's idle gaps. A program that opens none leaves every reader of
this module with nothing to read: it returns ``None``, not 0.
"""
from __future__ import annotations

from bench import trace

# spans of the serving thread; ``p2m.bin`` runs on the binning workers
SERVING = ("p2m.schedule", "p2m.admit", "p2m.pace", "p2m.bin_wait",
           "p2m.assemble", "p2m.h2d", "p2m.fold", "p2m.readout", "p2m.sync",
           "p2m.finalise")


def span_ms(ctx, name: str) -> float | None:
    """Mean duration, in ms, of the host events named ``name`` that start
    inside the traced bracket of ``ctx["trace_data"]``; ``None`` without
    a trace or without such an event."""
    t = ctx["trace_data"]
    if t is None or t.window is None:
        return None
    lo, hi = t.window
    ds = [e - s for n, s, e in t.host if n == name and lo <= s < hi]
    return 1e-6 * sum(ds) / len(ds) if ds else None


def overlap(a, b) -> float:
    """Length of the intersection of two sorted lists of disjoint
    ``(start, end)`` intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total
