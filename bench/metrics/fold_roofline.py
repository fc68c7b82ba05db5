"""Layer-1 fold's share of its roofline, in %.

The least time the chip could take for the traced window's occupied
lane-windows of fold (``bench/work.py``: the larger of the float32
charge state read and written once over HBM bandwidth, and the layer-1
conv FLOPs over bf16 peak; the bytes bound binds at the paper's shapes)
over the fold programs' device time in the trace. Moves
``events_per_s``.
"""
from bench import trace, work

PATTERNS = (r"fold_body", r"stream_fold")


def reduce(ctx):
    t = ctx["trace_data"]
    if t is None or not ctx["lane_windows"]:
        return None
    lo, hi = t.window
    sec = sum(trace.program_seconds(d, PATTERNS, lo, hi)
              for d in t.devices[:ctx["chips"]])
    if sec <= 0:
        return None
    least, _ = work.fold_least_seconds(ctx["model"], ctx["lane_windows"],
                                       ctx["peak"])
    return 100.0 * least / sec
