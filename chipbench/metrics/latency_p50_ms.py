"""Median latency, from each request's due time to its decoded answer in
the driver's hand, over every request due in the window (those answered
after it closes included)."""

import numpy as np


def read(ctx):
    if not ctx.latencies_s:
        return None
    return float(np.percentile(ctx.latencies_s, 50)) * 1e3
