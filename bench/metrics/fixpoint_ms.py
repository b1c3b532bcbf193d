"""Fixed point per call: the program's spans around its resident MIS-2
fixed points (``mis2.resident_fixed_point``,
``mis2.hybrid_fixed_point``), which block until the device is done."""
from . import FIXPOINT_SPANS, span_seconds


def read(ctx):
    found = [s for s in (span_seconds(ctx, n) for n in FIXPOINT_SPANS)
             if s is not None]
    return sum(found) / ctx.calls * 1e3 if found else None
