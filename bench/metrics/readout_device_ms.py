"""Layer-1 readout and backbone step: device time of the readout
programs per traced window, in ms, averaged over the cell's chips. The
programs are found by name: the engine's jitted ``readout_body`` (drift,
transfer curve, comparator, pool, backbone step). Moves
``events_per_s``.
"""
from bench import trace

PATTERNS = (r"readout_body",)


def reduce(ctx):
    t = ctx["trace_data"]
    if t is None or not ctx["traced_windows"]:
        return None
    lo, hi = t.window
    devs = t.devices[:ctx["chips"]]
    sec = sum(trace.program_seconds(d, PATTERNS, lo, hi) for d in devs)
    if sec <= 0:
        return None
    return 1e3 * sec / len(devs) / ctx["traced_windows"]
