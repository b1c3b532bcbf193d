"""Aggregation joins per call: the ``api.coarsen`` span less the fixed
points and the layout conversions inside it (Alg. 3's root joins,
phase-2 selection, phase-3 coupling rounds and the result pulls)."""
from . import FIXPOINT_SPANS, span_seconds


def read(ctx):
    total = span_seconds(ctx, "api.coarsen")
    if total is None:
        return None
    inner = sum(s or 0.0 for s in (span_seconds(ctx, n)
                                   for n in FIXPOINT_SPANS))
    inner += ctx.obs.total("graph.conversion_seconds")
    return (total - inner) / ctx.calls * 1e3
