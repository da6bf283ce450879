"""A kernel's share of its roofline over the traced stretch.

The least time the chips could take for the requests run while tracing —
their bytes (``opbytes.op_bytes`` of each request's own length, so no
padding counts) over the chips' HBM bandwidth; these ops do no arithmetic
worth a compute bound — divided by the device's busy time.  The executors carry no names a trace could tell apart, so the
share is read only where every traced tile went to the one backend; else
the reader returns nothing.
"""

from __future__ import annotations

from .opbytes import op_bytes

__all__ = ["roofline_share"]


def roofline_share(ctx, backend: str) -> float | None:
    tiles, tr = ctx.traced_tiles, ctx.trace
    if not tiles or not tr or not tr["busy_s"] or not ctx.peaks:
        return None
    if any(t.backend != backend for t in tiles):
        return None
    nbytes = sum(op_bytes(t.op, 1, n, t.k) for t in tiles for n in t.lengths)
    least_s = nbytes / (ctx.peaks["hbm_bytes_per_s"] * tr["chips"])
    return 100.0 * least_s / tr["busy_s"]
