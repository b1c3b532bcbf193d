"""Alg. 3's label joins per call: the program's ``coarsen.root_join``,
``coarsen.phase2_join`` and ``coarsen.phase3_join`` spans, each ending
after its result has been pulled to the host."""
from . import span_seconds

JOINS = ("coarsen.root_join", "coarsen.phase2_join", "coarsen.phase3_join")


def read(ctx):
    found = [s for s in (span_seconds(ctx, n) for n in JOINS)
             if s is not None]
    return sum(found) / ctx.calls * 1e3 if found else None
