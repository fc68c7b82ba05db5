"""Host feed in the real-time cell: the serving thread's time per replay
chunk, in ms (the engine's ``fold_s`` span: binning wait, frame
assembly, host-to-device copy, fold dispatch). Mean over the chunks of
the measured ``serve`` call outside the traced windows; moves
``readout_latency_p90_ms``.
"""


def reduce(ctx):
    xs = ctx["fold_s"]
    return 1e3 * sum(xs) / len(xs) if xs else None
