"""The column-skipping Pallas kernel (``kernels/colskip``) on one chip: the bytes of its
tiles at HBM bandwidth over the device's busy time
(``chipbench/lib/roofline.py``)."""

from chipbench.lib.roofline import roofline_share


def read(ctx):
    return roofline_share(ctx, "colskip")
