"""Executor builds inside the window: the program's executor-cache misses
plus persistent-cache misses.  Warm-up should leave none."""


def read(ctx):
    return ctx.compiles
