"""Host side of each resident fixed point per call: the program's
``mis2.launch`` span, from the fixed point's start until its jitted
call returns (argument handling, dispatch, any compile)."""
from . import span_seconds


def read(ctx):
    s = span_seconds(ctx, "mis2.launch")
    return None if s is None else s / ctx.calls * 1e3
