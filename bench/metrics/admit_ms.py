"""Admission: ms per admitted stream to open its replay iterator and
zero its lane (``open_stream``, ``reset_lane``).

Mean duration of the serving loop's ``p2m.admit`` spans (``bench/spans.py``)
that start inside the traced bracket. The profiler slows the host there
by about a quarter, so this splits the traced window among the loop's
steps; it does not restate the untraced ``host_feed_ms`` and
``window_sync_ms``, read outside the bracket. Moves ``events_per_s``.
"""
from bench import spans


def reduce(ctx):
    return spans.span_ms(ctx, "p2m.admit")
