"""Whole step's share of the chip's peak for Spikformer behind the P²M
layer, in %: the model FLOPs of the traced window's occupied lane-windows
(``bench/work_spikformer.py``: layer 1 every window, the backbone once
per coarse window) over the traced window's length times the chips times
the bf16 peak. Moves ``events_per_s``.
"""
from bench import work_spikformer


def reduce(ctx):
    s, model = ctx["trace"], ctx["model"]
    if (s is None or not ctx["lane_windows"] or s["window_s"] <= 0
            or model["backbone"].get("kind") != "spikformer"):
        return None
    flops = (work_spikformer.model_flops_per_lane_window(model)
             * ctx["lane_windows"])
    return 100.0 * flops / (s["window_s"] * ctx["chips"]
                            * ctx["peak"]["bf16_flops"])
