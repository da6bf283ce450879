"""Share of the window's tile elements that are padding (columns to the
power-of-two width, rows to the tile height)."""


def read(ctx):
    total = sum(t.rows * t.n for t in ctx.tiles)
    if not total:
        return None
    real = sum(sum(t.lengths) for t in ctx.tiles)
    return 100.0 * (total - real) / total
