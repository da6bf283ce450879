"""The program's spans in a trace (``lib/spans.py``): self times, device
seconds per executor and idle time by the innermost span, on a hand-made
trace with the answers worked out by hand; and ``chipbench/spans.py``
rehearsed on the CPU at a tiny size."""

import importlib.util
import os

import pytest

from chipbench.lib import spans, trace
from chipbench.lib.spec import ROOT, Bench, load_bench


def _hand_made():
    """One tile on one chip, times in ns.  Host: the benchmark's ``feed``
    [0, 90) and ``backend.run:colskip`` [20, 60); the program's spans::

        feed     [1, 89)   bucket [2, 6)   schedule [8, 80)
        execute  [10, 70)  put [20, 28)  launch [28, 30)  wait [30, 52)
                           fetch [52, 58)
        scatter  [72, 78)

    and one ``feed`` before the window.  Device ops [0, 20), [30, 50) and
    [60, 70); modules ``jit_colskip`` [28, 52), ``jit_radix_topk``
    [58, 72) and one ``jit_colskip`` past the window's end."""
    p = "sortserve."
    thread = "/host:CPU#0"
    program = [[p + "feed", 1, 88], [p + "bucket", 2, 4],
               [p + "schedule", 8, 72], [p + "execute", 10, 60],
               [p + "execute.put", 20, 8], [p + "execute.launch", 28, 2],
               [p + "execute.wait", 30, 22], [p + "execute.fetch", 52, 6],
               [p + "scatter", 72, 6], [p + "feed", -10, 8]]
    return {
        "window": [0, 100],
        "devices": {"/device:TPU:0": [["%a = u32[8] fusion()", 0, 20],
                                      ["%k.1 = u32[8] custom-call()", 30, 20],
                                      ["%k.2 = u32[8] fusion()", 60, 10]]},
        "host": [["feed", 0, 90], ["backend.run:colskip", 20, 40]],
        "spans": [[n, s, d, thread] for n, s, d in program],
        "modules": {"/device:TPU:0": [["jit_colskip(7)", 28, 24],
                                      ["jit_radix_topk(9)", 58, 14],
                                      ["jit_colskip(7)", 100, 5]]},
    }


def test_hand_made_trace():
    r = spans.reduce(_hand_made())
    p = "sortserve."
    # self time = duration inside the window minus the nested spans'
    assert r["span_self_s"] == pytest.approx({
        p + "feed": 12e-9, p + "bucket": 4e-9, p + "schedule": 6e-9,
        p + "execute": 22e-9, p + "execute.put": 8e-9,
        p + "execute.launch": 2e-9, p + "execute.wait": 22e-9,
        p + "execute.fetch": 6e-9, p + "scatter": 6e-9})
    assert r["span_count"] == {k: 1 for k in r["span_self_s"]}
    assert r["executor_s"] == pytest.approx({"colskip": 24e-9,
                                             "radix_topk": 14e-9})
    assert r["executor_calls"] == {"colskip": 1, "radix_topk": 1}
    # idle [20, 30): put and the benchmark's backend.run start together,
    # the shorter is inner; [50, 60): fetch; [70, 100): the program's feed
    assert r["idle_by_span"] == pytest.approx({
        p + "execute.put": 10e-9, p + "execute.fetch": 10e-9,
        p + "feed": 30e-9})
    # what the benchmark's reduction files under backend.run, the
    # program's spans split; its own numbers stay as they were
    old = trace.reduce(_hand_made())
    assert old["idle_by_host"] == pytest.approx(
        {"backend.run:colskip": 20e-9, "feed": 30e-9})
    assert old["busy_s"] == pytest.approx(50e-9)


def test_a_trace_without_program_spans_gives_empty_tables():
    t = _hand_made()
    del t["spans"], t["modules"]
    r = spans.reduce(t)
    assert r["span_self_s"] == r["executor_s"] == {}
    assert r["idle_by_span"] == pytest.approx(
        {"backend.run:colskip": 20e-9, "feed": 30e-9})


def test_the_tool_splits_a_cell_on_the_cpu(tmp_path):
    import jax

    from chipbench.tests.test_cells import TINY

    path = os.path.join(ROOT, "chipbench", "spans.py")
    spec = importlib.util.spec_from_file_location("chipbench_spans", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    base = load_bench()
    bench = tool._AllMetrics(base.data, base.root)
    assert isinstance(bench, Bench)
    cell = "decode-topk.steady"
    out = tool.split(bench, cell, 2 ** 31 + 21, 1.5, jax.devices(),
                     keep=str(tmp_path), mix_overrides=TINY[cell])
    assert out["untraced"]["correct"] and out["traced"]["correct"]
    # host metrics read untraced too; the device's need a chip
    assert out["untraced"]["host_ms_per_tile.decode"] > 0
    assert "device_idle_frac.decode" not in out["untraced"]
    assert out["untraced"]["queue_wait_ms"] > 0
    split = out["spans"]
    assert split["tiles"] > 0
    assert {"sortserve." + s for s in (
        "feed", "bucket", "schedule", "execute", "execute.put",
        "execute.launch", "execute.wait", "execute.fetch",
        "scatter")} <= set(split["self_ms"])
    assert split["executor_ms"] == {}          # no device plane on the CPU
    assert os.listdir(tmp_path) == [f"{cell}-{2 ** 31 + 21}.xplane.pb"]
