"""Readout sync in the real-time cell: the serving thread's wait per
window, in ms (the engine's ``readout_s`` span, which holds every fold
still queued on the device). Mean over the windows of the measured
``serve`` call outside the traced ones; moves
``readout_latency_p90_ms``.
"""


def reduce(ctx):
    xs = ctx["readout_s"]
    return 1e3 * sum(xs) / len(xs) if xs else None
