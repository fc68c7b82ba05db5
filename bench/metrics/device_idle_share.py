"""Device idle share of the traced window, in %: ``1 - busy / window``,
busy being the union of operation intervals on each chip's device plane,
averaged over the cell's chips. Moves ``events_per_s``.
"""


def reduce(ctx):
    s = ctx["trace"]
    if s is None or s["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
