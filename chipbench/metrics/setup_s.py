"""Process start to the first request of the window: imports, engine,
traffic drawn, compile or compile-cache load, warm-up."""


def read(ctx):
    return ctx.setup_s
