"""Spikformer backbone's share of its roofline, in %.

The least time the chip could take for the backbone work the model
requires in the traced window (``bench/work_spikformer.py``: one step per
coarse window per occupied lane, the larger of its FLOPs over bf16 peak
and its float32 membranes read and written once over HBM bandwidth; the
bytes bound binds at the published widths) over the device time of the
readout programs (``readout_body``: layer-1 readout and the backbone
step), which step the backbone on every lane every window. Moves
``events_per_s``.
"""
from bench import trace, work_spikformer

PATTERNS = (r"readout_body",)


def reduce(ctx):
    t, model = ctx["trace_data"], ctx["model"]
    if (t is None or t.window is None or ctx["peak"] is None
            or not ctx["lane_windows"]
            or model["backbone"].get("kind") != "spikformer"):
        return None
    lo, hi = t.window
    sec = sum(trace.program_seconds(d, PATTERNS, lo, hi)
              for d in t.devices[:ctx["chips"]])
    if sec <= 0:
        return None
    steps = ctx["lane_windows"] * work_spikformer.steps_per_lane_window(model)
    least, _ = work_spikformer.backbone_least_seconds(model, steps,
                                                      ctx["peak"])
    return 100.0 * least / sec
