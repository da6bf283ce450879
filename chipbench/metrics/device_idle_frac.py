"""Share of the traced stretch in which no operation ran on the device
(mean over the chips)."""


def read(ctx):
    tr = ctx.trace
    if not tr or not tr["chips"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
