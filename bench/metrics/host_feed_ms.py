"""Host feed: the serving thread's time per replay chunk, in ms.

The engine's own ``fold_s`` span: the wait for the binning workers, the
frame assembly, the host-to-device copy and the asynchronous fold
dispatch. A host-feed span, not a layer time. Mean over the chunks of
the measured ``serve`` call outside the traced windows (the profiler
slows those); moves ``events_per_s``.
"""


def reduce(ctx):
    xs = ctx["fold_s"]
    return 1e3 * sum(xs) / len(xs) if xs else None
