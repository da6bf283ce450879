"""The peak table and the byte functions."""

import pytest

from chipbench.lib.opbytes import op_bytes
from chipbench.lib.peaks import PEAKS, UnknownDevice, peaks_for


def test_v5e_peaks_carry_their_source():
    p = peaks_for("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert "TPU v5e" in p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_unknown_device_is_an_error(kind):
    assert kind not in PEAKS
    with pytest.raises(UnknownDevice):
        peaks_for(kind)


def test_bytes_are_the_tile_read_and_the_answer_written():
    assert op_bytes("sort", 8, 1024) == 8 * 1024 * 4 * 2
    assert op_bytes("argsort", 8, 1024) == 8 * 1024 * 4 * 2
    assert op_bytes("topk", 8, 131072, 64) == 8 * 131072 * 4 + 8 * 64 * 8
    assert op_bytes("kmin", 2, 16, 3) == 2 * 16 * 4 + 2 * 3 * 8


@pytest.mark.parametrize("op,k", [("topk", None), ("kmin", 0),
                                  ("topk", 17), ("median", None)])
def test_bad_op_or_k_is_an_error(op, k):
    with pytest.raises(ValueError):
        op_bytes(op, 1, 16, k)


def test_roofline_counts_the_requests_not_the_padding():
    from types import SimpleNamespace

    from chipbench.lib.driver import TileRecord
    from chipbench.lib.roofline import roofline_share

    # two rows of 1000 in a tile padded to 8 x 1024
    tile = TileRecord("colskip", "sort", 8, 1024, None, (1000, 1000), True)
    ctx = SimpleNamespace(
        traced_tiles=[tile], peaks=peaks_for("TPU v5 lite"),
        trace={"busy_s": 1e-3, "chips": 1})
    want = 100.0 * 2 * op_bytes("sort", 1, 1000) / 819e9 / 1e-3
    assert roofline_share(ctx, "colskip") == pytest.approx(want)
    assert roofline_share(ctx, "radix_topk") is None
