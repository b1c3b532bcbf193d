"""Host-to-device copy of the layout per call: the program's
``graph.to_device`` span, which waits until the arrays have landed."""
from . import span_seconds


def read(ctx):
    s = span_seconds(ctx, "graph.to_device")
    return None if s is None else s / ctx.calls * 1e3
