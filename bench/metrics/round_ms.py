"""Device time per MIS-2 round: the program's ``mis2.wait`` spans (the
wait for each resident fixed point's result) over the rounds they ran
(the ``mis2.rounds`` counter, every layout)."""
from . import span_seconds


def read(ctx):
    s = span_seconds(ctx, "mis2.wait")
    rounds = ctx.obs.total("mis2.rounds")
    return None if s is None or rounds <= 0 else s / rounds * 1e3
