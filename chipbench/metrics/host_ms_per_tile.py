"""Host time in the engine's entry points (feed / poll / drain) outside
the backend runs, per tile: batching, scheduling, scatter and decode."""


def read(ctx):
    if not ctx.tiles:
        return None
    return (ctx.engine_s - ctx.backend_s) / len(ctx.tiles) * 1e3
