"""chip_smoke.py's phases at a tiny size on the CPU.

The script demands a TPU in ``main`` only; its phase functions take sizes
and engine configs, so the same checks run here with the Pallas kernels in
interpret mode.  The four-chip mesh phase runs in a child process with four
forced host devices.
"""

import importlib.util
import os
import subprocess
import sys
import textwrap

import pytest

from repro.sortserve import EngineConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phase_served_tiny(smoke):
    rep = smoke.phase_served(
        n_requests=24, min_len=16, max_len=256,
        config=EngineConfig(use_pallas=True, interpret=True,
                            sim_width_cap=64),
        expect_impl="interpret")
    assert rep["mismatches"] == 0 and rep["impl"]["interpret"] > 0


def test_phase_paper_tiny(smoke):
    rep = smoke.phase_paper(
        n=64, config=EngineConfig(use_pallas=True, interpret=True))
    assert set(rep["datasets"]) == {"clustered", "kruskal", "mapreduce",
                                    "normal", "uniform"}


def test_phase_topk_multibank_tiny(smoke):
    rep = smoke.phase_topk(rows=2, width=600, k=8, bank_width=256,
                           use_pallas=True, interpret=True)
    assert rep["shape"] == [2, 600]


def test_phase_fails_loudly(smoke):
    """A phase whose check fails raises; main turns that into exit 1."""
    with pytest.raises(smoke.SmokeFailure):
        smoke.phase_served(n_requests=8, min_len=16, max_len=64,
                           expect_impl="pallas")


def test_main_refuses_cpu(smoke, capsys):
    assert smoke.main([]) == 2
    out = capsys.readouterr()
    assert out.out == "" and "no TPU" in out.err


def test_phase_mesh_tiny_on_4_devices():
    code = textwrap.dedent(f"""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, {ROOT!r})
        import chip_smoke
        rep = chip_smoke.phase_mesh(n_requests=16, min_len=16, max_len=256,
                                    expect_impl="xla")
        assert set(rep) == {{"local", "mesh_1x4", "mesh_2x2"}}, rep
        assert rep["mesh_2x2"]["rounds"] > 0
        print("OK")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "OK" in out.stdout
