"""Readout sync: the serving thread's wait per window, in ms.

The engine's own ``readout_s`` span: from the readout dispatch to the
window's one device-to-host read, so it holds every fold still queued
on the device. A wait, not a layer time. Mean over the windows of the
measured ``serve`` call outside the traced ones (the profiler slows
those); moves ``events_per_s``.
"""


def reduce(ctx):
    xs = ctx["readout_s"]
    return 1e3 * sum(xs) / len(xs) if xs else None
