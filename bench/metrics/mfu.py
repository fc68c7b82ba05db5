"""Whole step's share of the chip's peak, in %: the model FLOPs of the
traced window's occupied lane-windows (``bench/work.py``: the layer-1
conv every window, the backbone once per coarse window) over the traced
window's length times the chips times the bf16 peak. Moves
``events_per_s``.
"""
from bench import work


def reduce(ctx):
    s = ctx["trace"]
    if s is None or not ctx["lane_windows"] or s["window_s"] <= 0:
        return None
    flops = work.model_flops_per_lane_window(ctx["model"]) * ctx["lane_windows"]
    return 100.0 * flops / (s["window_s"] * ctx["chips"]
                            * ctx["peak"]["bf16_flops"])
