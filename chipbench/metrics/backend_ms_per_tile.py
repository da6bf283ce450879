"""Host time inside each backend's ``run`` per tile: copy in, execute,
and the blocking copy out."""


def read(ctx):
    if not ctx.tiles:
        return None
    return ctx.backend_s / len(ctx.tiles) * 1e3
