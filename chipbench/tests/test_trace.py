"""The reduction from a trace to busy, idle and per-op seconds: on a
hand-made trace with known answers, and on a trace recorded on a TPU v5e
(a traced run of ``sorter1024.skewed``) in ``chipbench/data/``."""

import gzip
import json
import os

import pytest

from chipbench.lib.trace import reduce

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "data")


def test_hand_made_trace():
    trace = {
        "window": [0, 100],
        "devices": {
            "/device:TPU:0": [["%k.1 = u32[8] custom-call()", 10, 20],
                              ["%all-reduce.3 = s32[8] all-reduce()", 25, 10],
                              ["%k.1 = u32[8] custom-call()", 60, 10],
                              ["%late = u32[8] fusion()", 95, 50]],
            "/device:TPU:1": [["%k.1 = u32[8] custom-call()", 0, 100]],
        },
        "host": [["feed", 0, 50], ["backend.run:colskip", 5, 40],
                 ["poll", 55, 45]],
    }
    r = reduce(trace)
    assert r["chips"] == 2 and r["window_s"] == pytest.approx(100e-9)
    # chip 0: [10, 35) + [60, 70) + [95, 100) = 40 ns; chip 1: 100 ns
    assert r["busy_s"] == pytest.approx(70e-9)
    assert r["collective_s"] == pytest.approx(5e-9)
    assert r["op_s"]["%k.1"] == pytest.approx(65e-9)
    assert r["op_s"]["%late"] == pytest.approx(2.5e-9)
    # chip 0's idle: [0,10) backend.run, [35,60) feed then poll, [70,95)
    assert r["idle_by_host"] == pytest.approx(
        {"backend.run:colskip": 5e-9, "feed": 12.5e-9, "poll": 12.5e-9})


def test_recorded_chip_trace():
    with gzip.open(os.path.join(DATA, "trace-sorter1024.skewed.json.gz"),
                   "rt") as f:
        trace = json.load(f)
    r = reduce(trace)
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(1.947890869, abs=1e-9)
    assert r["busy_s"] == pytest.approx(1.283617863, abs=1e-9)
    idle = sum(r["idle_by_host"].values())
    assert idle + r["busy_s"] == pytest.approx(r["window_s"], abs=1e-9)
    top = max(r["op_s"], key=r["op_s"].get)
    assert top == "%sort_pallas.1"           # the colskip Pallas kernel
    assert r["op_s"][top] > 0.9 * r["busy_s"]
    assert r["collective_s"] == 0.0
    # on one chip the device waits inside the backend call (copies and
    # the blocking read-back), not in the engine's host code
    assert max(r["idle_by_host"], key=r["idle_by_host"].get) == \
        "backend.run:colskip"
    assert set(r["idle_by_host"]) <= {"feed", "poll", "drain", "wait",
                                      "generate", "none",
                                      "backend.run:colskip"}
