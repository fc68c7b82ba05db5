"""Host feed (H2D): ms per chunk to hand the dense frames to the device
(``jnp.asarray``: the transfer's enqueue; on a TPU the frames' host
relayout runs later, inside the fold's dispatch).

Mean duration of the serving loop's ``p2m.h2d`` spans (``bench/spans.py``)
that start inside the traced bracket. The profiler slows the host there
by about a quarter, so this splits the traced window among the loop's
steps; it does not restate the untraced ``host_feed_ms`` and
``window_sync_ms``, read outside the bracket. Moves ``events_per_s``.
"""
from bench import spans


def reduce(ctx):
    return spans.span_ms(ctx, "p2m.h2d")
