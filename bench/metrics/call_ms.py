"""Wall time of the window over the facade calls it completed (closed
loop: every call returns host numpy answers, so it has synchronized)."""


def read(ctx):
    return ctx.window_s / ctx.calls * 1e3
