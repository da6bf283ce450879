"""Input elements of every request due in the window, per second from the
window's start to the last answer."""


def read(ctx):
    return ctx.elems_done / ctx.span_s if ctx.elems_done else None
