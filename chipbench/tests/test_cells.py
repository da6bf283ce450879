"""Every cell rehearsed on the CPU at a tiny size: traffic, driver loop,
check and metric readers, as ``run.py`` would run them on the chip.

The program runs here with its CPU dispatch (the XLA reference in place of
the Pallas kernels).  Two drafts kept for later cells run too, added as
``BENCHMARK.json`` entries alone: the bank-mesh cell, in a child process
on four forced host devices, and the bursty decode mix.  Each fault the
cells can have, planted in the program under the harness, has to come out
as ``correct: false``, and so has the control.
"""

import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from chipbench.lib.cell import check, control_answer, run_cell
from chipbench.lib.spec import ROOT, load_bench

TINY = {
    "sorter1024.skewed": {"n": 64, "clients": 16, "prefill_requests": 256,
                          "warm_requests": 32},
    "sorter1024.uniform": {"n": 64, "clients": 16, "prefill_requests": 256,
                           "warm_requests": 32},
    "decode-topk.steady": {"mix": [{"op": "topk", "k": 5, "n": 1021,
                                    "data": "gauss", "sigma": 2.0,
                                    "pool_rows": 8}],
                           "steps_per_s": 20.0, "rows_min": 2,
                           "rows_max": 9},
    "sorter1024-mesh4.skewed": {"n": 64, "clients": 16,
                                "prefill_requests": 128, "warm_requests": 32},
}
ONE_CHIP = ["sorter1024.skewed", "sorter1024.uniform", "decode-topk.steady"]
BACKEND = {"sorter1024.skewed": "ColskipBackend",
           "decode-topk.steady": "RadixTopkBackend"}


def _run(cell, trace=False, seconds=1.5, records=None, seed=2 ** 31 + 9):
    import jax
    return run_cell(load_bench(), cell, seed, seconds, trace,
                    time.perf_counter(), devices=jax.devices(),
                    mix_overrides=TINY[cell], records_out=records,
                    log=lambda m: None)


def test_every_cell_has_a_rehearsal():
    cells = {w["name"] for w in load_bench().data["workloads"]}
    assert cells | {"sorter1024-mesh4.skewed"} == set(TINY)
    assert not cells & set(DRAFTS)


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_cell_runs_and_is_correct(cell):
    records = []
    r = _run(cell, records=records)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    # every request due in the window was waited for, drained ones too
    assert len(records) == r["attempted"]
    assert all(rec.t_done is not None for rec in records)
    assert list(r)[-1] == "checks"
    tail = ".decode" if cell.startswith("decode") else ""
    assert set(r["metrics"]) == {"elems_per_s", "latency_p50_ms" + tail,
                                 "latency_p95_ms" + tail, "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    # the control, the reference one precision down, must fail the check
    assert check(records, served=control_answer)["mismatched"] > 0


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_traced_cell_reports_per_layer_metrics(cell):
    r = _run(cell, trace=True)
    assert r["correct"]
    # the trace has no device plane on the CPU: device readers stay silent
    tail = ".decode" if cell.startswith("decode") else ""
    assert set(r["metrics"]) == {m + tail for m in (
        "pad_frac", "host_ms_per_tile", "backend_ms_per_tile",
        "compiles_in_window")}
    assert r["metrics"]["compiles_in_window" + tail]["value"] == 0
    assert "breakdown" in r and r["device"]["window_s"] > 0


def _plant(kind):
    """A fault in the answers a backend produces."""
    def fault(result, tile):
        vals = np.array(result.values)
        idx = None if result.indices is None else np.array(result.indices)
        out = vals.shape[1]
        half = tile.data.shape[0] // 2
        if kind == "answer_altered":
            vals[0, 0] ^= np.uint32(1)
        elif kind == "half_batch_left_out":
            vals[half:] = tile.data[half:, :out]
            if idx is not None:
                idx[half:] = np.arange(out)
        elif kind == "state_unchanged":
            vals = tile.data[:, :out].copy()
            if idx is not None:
                idx[:] = np.arange(out)
        result.values, result.indices = vals, idx
        return result
    return fault


@pytest.mark.parametrize("kind", ["answer_altered", "half_batch_left_out",
                                  "state_unchanged"])
@pytest.mark.parametrize("cell", sorted(BACKEND))
def test_fault_under_the_harness_fails_the_check(cell, kind, monkeypatch):
    from repro.sortserve import backends
    cls = getattr(backends, BACKEND[cell])
    orig, fault = cls.run, _plant(kind)
    monkeypatch.setattr(cls, "run",
                        lambda self, tile: fault(orig(self, tile), tile))
    r = _run(cell)
    assert r["correct"] is False
    assert r["checks"]["wrong_or_missing"]["value"] > 0


_MESH = textwrap.dedent("""
    import json, sys, time
    sys.path[:0] = [{root!r}, {src!r}]
    import jax
    if {cut}:
        import jax.numpy as jnp
        psum = jax.lax.psum

        def cut(x, axes):
            # the exchange between chips left out: only bank 0's bits
            # reach the others (the bank count, a Python int, still sums)
            if isinstance(x, int):
                return psum(x, axes)
            own = jax.lax.axis_index(axes) == 0
            return psum(jnp.where(own, x, jnp.zeros_like(x)), axes)
        jax.lax.psum = cut
    from chipbench.lib.cell import check, control_answer, run_cell
    from chipbench.lib.spec import load_bench
    bench = load_bench()
    # the mesh cell waits for a four-chip measurement: its entries here
    bench.data["configs"].append({{
        "name": "colskip-sorter-1024-mesh4",
        "file": "chipbench/configs/colskip-sorter-1024-mesh4.json"}})
    bench.data["workloads"].append({{
        "name": "sorter1024-mesh4.skewed",
        "config": "colskip-sorter-1024-mesh4", "traffic": "skewed",
        "chips": 4}})
    records = []
    r = run_cell(bench, "sorter1024-mesh4.skewed", 11, 2.0, False,
                 time.perf_counter(), devices=jax.devices(),
                 mix_overrides={tiny!r}, records_out=records,
                 log=lambda m: None)
    r["control"] = check(records, served=control_answer)
    print(json.dumps(r))
""")


@pytest.mark.parametrize("cut", [False, True])
def test_mesh_cell_on_four_devices(cut):
    code = _MESH.format(root=ROOT, src=os.path.join(ROOT, "src"), cut=cut,
                        tiny=TINY["sorter1024-mesh4.skewed"])
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["device"]["count"] == 4 and r["attempted"] > 0
    if cut:
        assert r["correct"] is False
    else:
        assert r["correct"] and r["control"]["mismatched"] > 0


# a draft: the bursty decode mix, here with make_workload's ops, dtypes and
# lengths in place of the vocabulary-wide rows
DRAFTS = {"decode-topk.bursty": ("decode-topk-dsv3", "decode-bursty")}
BURSTY = {"mix": [{"op": "sort", "data": "uniform"},
                  {"op": "argsort", "data": "uniform", "dtype": "int32"},
                  {"op": "topk", "data": "gauss", "sigma": 1e3, "k": [1, 8]},
                  {"op": "kmin", "data": "zipf", "s": 1.2, "domain": 100,
                   "k": 5, "share": 2}],
          "n": [16, 250], "block": 12, "rows_min": 1, "rows_max": 4,
          "steps_per_s": 40.0,
          "bursts": {"on_s": 0.25, "off_s": 0.5, "off_rate": 0.25}}


def test_a_draft_mix_runs_from_its_file_and_an_entry():
    import jax
    bench = load_bench()
    name, (config, traffic) = next(iter(DRAFTS.items()))
    bench.data["workloads"].append({"name": name, "config": config,
                                    "traffic": traffic, "chips": 1})
    records = []
    r = run_cell(bench, name, 2 ** 33 + 1, 1.5, False, time.perf_counter(),
                 devices=jax.devices(), mix_overrides=BURSTY,
                 records_out=records, log=lambda m: None)
    assert r["correct"] and r["attempted"] == len(records) > 0
    assert {rec.req.op for rec in records} == {"sort", "argsort", "topk",
                                              "kmin"}
    assert set(r["metrics"]) == {"elems_per_s", "setup_s"}
    assert check(records, served=control_answer)["mismatched"] > 0


def test_run_refuses_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "sorter1024.skewed", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300, cwd=ROOT)
    assert out.returncode == 2
    assert out.stdout == ""
    assert "no TPU" in out.stderr
