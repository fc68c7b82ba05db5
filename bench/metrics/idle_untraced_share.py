"""Serving loop, untraced: the share, in %, of the device's idle time in
the traced bracket during which the serving thread is in none of its
``p2m.*`` spans (``bench/spans.py`` ``SERVING``), averaged over the
cell's chips. Idle time is the bracket less the union of each chip's
operation intervals. Lower is better: what the spans leave unnamed.

Read inside the traced bracket, where the profiler slows the host by
about a quarter. ``None`` where the program opens no serving span.
Moves ``events_per_s``.
"""
from bench import spans, trace


def reduce(ctx):
    t = ctx["trace_data"]
    if t is None or t.window is None or len(t.devices) < ctx["chips"]:
        return None
    lo, hi = t.window
    serving = trace.union([h for h in t.host if h[0] in spans.SERVING],
                          lo, hi)
    if not serving:
        return None
    shares = []
    for dev in t.devices[:ctx["chips"]]:
        idle = trace.gaps(trace.union(dev.ops, lo, hi), lo, hi)
        total = sum(e - s for s, e in idle)
        if total > 0:
            shares.append(1.0 - spans.overlap(idle, serving) / total)
    return 100.0 * sum(shares) / len(shares) if shares else None
