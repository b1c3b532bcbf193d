"""Process start to the start of the window: imports, JAX's start on the
device, the compile cache, the graph's generation and the warm-up
call."""


def read(ctx):
    return ctx.setup_s
