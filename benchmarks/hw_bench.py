"""Hardware-path tuning bench: fused collectives, persistent compile cache,
and a per-device-kind XLA flag sweep.

Three claims behind the multi-host hot path, each measured on a forced
4-device host-platform topology (``--xla_force_host_platform_device_count``
makes the 2x2 hosts x banks mesh testable on any CPU box):

  * **fused rounds** — the speculative-tree fusion batches ``fuse``
    consecutive bit planes' saw-a-1/saw-a-0 predicates into one manager
    ``psum`` round.  At N=1024 / w=32, fuse=2 must cut collective rounds
    >= 1.5x vs the one-psum-per-plane walk while values, order, CR, and
    cycle telemetry stay bit-identical (the rows carry a response digest
    compared across fuse values).
  * **persistent compile cache** — a cold process populates a jax
    persistent compilation-cache directory; a second, fresh process must
    start with zero XLA compiles (every AOT build served from disk:
    ``persistent_misses == 0`` with hits > 0).
  * **flag sweep** — the MaxText-style XLA flag block (SNIPPETS) adapted
    per device kind: each candidate set serves the same workload in a
    subprocess (flags only bind at backend init) and reports wall time
    plus the measured-vs-modeled cycle ratio through the engine's
    ``calibration.*`` table.  ``scripts/hw_tune.py`` turns the winning
    set into a ``--hw-profile`` file.

Every measurement runs in a subprocess: XLA flags and compile counters
are process-scoped, so a fresh interpreter per data point is the only way
to keep them honest.  Workers re-enter this module via
``--worker {fused,persist}`` and write one JSON document to ``--json-out``.

    XLA_FLAGS= PYTHONPATH=src python -m benchmarks.run --only hw --out BENCH_9.json
    PYTHONPATH=src python -m benchmarks.hw_bench --smoke
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import subprocess
import sys
import tempfile
import time

DEV_COUNT = 4
N, W = 1024, 32
FUSE_VALUES = (1, 2, 4)

# candidate flag sets per jax platform, adapted from the SNIPPETS.md
# MaxText block; every flag is validated against the local XLA build by the
# subprocess itself (an unknown flag fails that candidate, not the bench)
FLAG_SETS = {
    "cpu": [
        ("baseline", []),
        ("single_thread_eigen", ["--xla_cpu_multi_thread_eigen=false"]),
        ("fast_math", ["--xla_cpu_enable_fast_math=true"]),
        ("concurrency_sched",
         ["--xla_cpu_enable_concurrency_optimized_scheduler=true"]),
    ],
    "gpu": [
        ("baseline", []),
        ("latency_hiding",
         ["--xla_gpu_enable_latency_hiding_scheduler=true"]),
        ("pipelined_collectives",
         ["--xla_gpu_enable_pipelined_all_reduce=true",
          "--xla_gpu_enable_pipelined_all_gather=true",
          "--xla_gpu_enable_while_loop_double_buffering=true"]),
        ("combine_thresholds",
         ["--xla_gpu_all_reduce_combine_threshold_bytes=134217728",
          "--xla_gpu_all_gather_combine_threshold_bytes=1073741824"]),
    ],
    "tpu": [
        ("baseline", []),
    ],
}


# --------------------------------------------------------------- worker side

def _engine(fuse: int, compile_cache: str | None = None):
    from repro.sortserve import EngineConfig, SortServeEngine
    # tile_rows=1: one request per tile keeps arrivals dense relative to
    # the modeled service time, so the scheduler's double-buffer hook sees
    # queued successors to stage (prefetch_hits > 0 in the committed rows)
    return SortServeEngine(EngineConfig(
        backends=("colskip_mesh",), mesh=True, mesh_hosts=2, fuse=fuse,
        compile_cache=compile_cache, tile_rows=1, banks=DEV_COUNT,
        bank_width=N // DEV_COUNT, bank_rows=8, sim_width_cap=4096,
        cache_size=0))


def _workload(n_requests: int):
    import numpy as np
    from repro.sortserve import SortRequest
    rng = np.random.default_rng(7)
    return [SortRequest("sort",
                        rng.integers(0, 1 << W, N, dtype=np.uint64)
                        .astype(np.uint32))
            for _ in range(n_requests)]


def _digest(resps) -> str:
    h = hashlib.sha1()
    for r in resps:
        h.update(r.values.tobytes())
        h.update(r.indices.tobytes() if r.indices is not None else b"-")
        h.update(str((int(r.cycles), int(r.column_reads))).encode())
    return h.hexdigest()


def _worker_fused(fuse_values, n_requests: int) -> dict:
    """Per-fuse serve of the same workload: timings + telemetry + digest."""
    import jax
    out = {"platform": jax.default_backend(),
           "device_kind": jax.devices()[0].device_kind,
           "n_devices": jax.device_count(), "per_fuse": {}}
    for fuse in fuse_values:
        reqs = _workload(n_requests)
        _engine(fuse).submit(reqs)             # warm the AOT signatures
        eng = _engine(fuse)
        reqs = _workload(n_requests)
        t0 = time.perf_counter()
        resps = eng.submit(reqs)
        dt = time.perf_counter() - t0
        telem = eng.telemetry()
        out["per_fuse"][str(fuse)] = {
            "wall_s": dt,
            "tiles": telem["batcher"]["tiles"],
            "digest": _digest(resps),
            "cycles_exact": telem["cycles_exact"],
            "column_reads": telem["column_reads"],
            "collectives": telem["collectives"],
            "calibration": telem["calibration"],
            "priors": eng.policy.export_priors(),
            "calibration_rows": eng._calib.profile_rows(),
        }
    return out


def _worker_persist(cache_dir: str, n_requests: int) -> dict:
    """One engine lifetime against a persistent compilation cache."""
    reqs = _workload(n_requests)
    t0 = time.perf_counter()
    eng = _engine(fuse=2, compile_cache=cache_dir)
    eng.submit(reqs)
    dt = time.perf_counter() - t0
    ec = eng.telemetry()["executor_cache"]
    return {"wall_s": dt, "aot_builds": ec["misses"],
            "persistent_hits": ec["persistent_hits"],
            "persistent_misses": ec["persistent_misses"]}


# --------------------------------------------------------------- parent side

def _spawn(worker: str, *, extra_flags=(), cache_dir: str | None = None,
           fuse_values=FUSE_VALUES, n_requests: int = 12,
           timeout: int = 1200) -> dict:
    """Run one measurement in a fresh interpreter and return its JSON.

    The child's XLA_FLAGS are fully replaced (forced device count + the
    candidate set) so measurements are comparable no matter what the
    parent inherited.  The child measures that forced host topology, so
    it is pinned to the CPU: it never asks for a chip the parent (which
    has touched JAX) may hold."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = " ".join(
        [f"--xla_force_host_platform_device_count={DEV_COUNT}"]
        + list(extra_flags))
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    with tempfile.TemporaryDirectory() as td:
        out_path = os.path.join(td, "out.json")
        cmd = [sys.executable, "-m", "benchmarks.hw_bench",
               "--worker", worker, "--json-out", out_path,
               "--fuse-values", ",".join(map(str, fuse_values)),
               "--requests", str(n_requests)]
        if cache_dir:
            cmd += ["--cache-dir", cache_dir]
        proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                              timeout=timeout)
        if proc.returncode != 0:
            raise RuntimeError(
                f"hw_bench worker {worker} failed:\n{proc.stderr[-4000:]}")
        with open(out_path) as f:
            return json.load(f)


def sweep_flags(platform: str | None = None, n_requests: int = 8) -> dict:
    """Serve the fuse=2 workload under each candidate flag set.

    Returns ``{platform, device_kind, results: [{name, flags, us_per_tile,
    ratio, error?}], best, priors, calibration}`` — everything
    ``scripts/hw_tune.py`` needs to emit a ``--hw-profile`` file."""
    probe = _spawn("fused", fuse_values=(2,), n_requests=n_requests)
    platform = platform or probe["platform"]
    results, best = [], None
    for name, flags in FLAG_SETS.get(platform, FLAG_SETS["cpu"]):
        try:
            got = _spawn("fused", extra_flags=flags, fuse_values=(2,),
                         n_requests=n_requests)
        except RuntimeError as e:       # unknown flag on this XLA build
            results.append({"name": name, "flags": flags,
                            "error": str(e)[-300:]})
            continue
        pf = got["per_fuse"]["2"]
        ratios = [row["ratio"] for row in pf["calibration_rows"]
                  if row["ratio"] > 0]
        entry = {
            "name": name, "flags": flags,
            "us_per_tile": pf["wall_s"] / max(pf["tiles"], 1) * 1e6,
            "ratio": sum(ratios) / len(ratios) if ratios else 0.0,
            "priors": pf["priors"],
            "calibration": pf["calibration_rows"],
        }
        results.append(entry)
        if best is None or entry["us_per_tile"] < best["us_per_tile"]:
            best = entry
    return {"platform": platform, "device_kind": probe["device_kind"],
            "forced_device_count": DEV_COUNT, "results": results,
            "best": best}


def _fused_rows(report, fused: dict) -> bool:
    base = fused["per_fuse"]["1"]
    ok_all = True
    for fuse in sorted(fused["per_fuse"], key=int):
        pf = fused["per_fuse"][fuse]
        coll = pf["collectives"]
        parity = (pf["digest"] == base["digest"]
                  and pf["cycles_exact"] == base["cycles_exact"]
                  and pf["column_reads"] == base["column_reads"]
                  and coll["planes"] == base["collectives"]["planes"])
        cr = coll["round_cr"]
        verdict = ("PASS" if parity and (fuse == "1" or cr >= 1.5)
                   else "MISS")
        ok_all = ok_all and verdict == "PASS"
        report(f"hw/fused_rounds_f{fuse}",
               pf["wall_s"] / max(pf["tiles"], 1) * 1e6,
               f"rounds={coll['rounds']} planes={coll['planes']} "
               f"round_cr={cr:.2f} prefetch_hits={coll['prefetch_hits']} "
               f"parity={'exact' if parity else 'BROKEN'} {verdict}")
    return ok_all


def _persist_rows(report, cold: dict, warm: dict) -> bool:
    report("hw/persist_cold", cold["wall_s"] * 1e6,
           f"aot_builds={cold['aot_builds']} "
           f"persistent_misses={cold['persistent_misses']} "
           f"persistent_hits={cold['persistent_hits']}")
    # the gate is the compile-free warm start; wall speedup is reported
    # but not gated — serve time dominates the pair and is noisy
    ok = warm["persistent_misses"] == 0 and warm["persistent_hits"] > 0
    report("hw/persist_warm", warm["wall_s"] * 1e6,
           f"aot_builds={warm['aot_builds']} "
           f"persistent_misses={warm['persistent_misses']} "
           f"persistent_hits={warm['persistent_hits']} "
           f"speedup={cold['wall_s'] / max(warm['wall_s'], 1e-9):.2f}x "
           f"{'PASS' if ok else 'MISS'}")
    return ok


def run(report):
    """benchmarks.run entry: fused rows, persist pair, flag sweep."""
    fused = _spawn("fused", n_requests=12)
    _fused_rows(report, fused)

    with tempfile.TemporaryDirectory() as cache_dir:
        cold = _spawn("persist", cache_dir=cache_dir)
        warm = _spawn("persist", cache_dir=cache_dir)
    _persist_rows(report, cold, warm)

    swept = sweep_flags()
    for entry in swept["results"]:
        if "error" in entry:
            report(f"hw/flags_{entry['name']}", 0.0, "SKIP flag rejected")
            continue
        best = entry is swept["best"] or entry["name"] == \
            (swept["best"] or {}).get("name")
        report(f"hw/flags_{entry['name']}", entry["us_per_tile"],
               f"ratio={entry['ratio']:.1f} n_flags={len(entry['flags'])}"
               + (" best" if best else ""))


# ----------------------------------------------------------------- CLI entry

def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced run with hard asserts (CI hw-smoke step)")
    ap.add_argument("--worker", choices=("fused", "persist"), default="")
    ap.add_argument("--json-out", default="", dest="json_out")
    ap.add_argument("--cache-dir", default="", dest="cache_dir")
    ap.add_argument("--fuse-values", default="1,2,4", dest="fuse_values")
    ap.add_argument("--requests", type=int, default=12)
    args = ap.parse_args(argv)

    if args.worker:
        fuse_values = tuple(int(s) for s in args.fuse_values.split(","))
        if args.worker == "fused":
            doc = _worker_fused(fuse_values, args.requests)
        else:
            doc = _worker_persist(args.cache_dir, args.requests)
        with open(args.json_out or "/dev/stdout", "w") as f:
            json.dump(doc, f)
        return 0

    rows = []

    def report(name, us, derived):
        rows.append((name, us, derived))
        print(f"{name},{us:.1f},{derived}", flush=True)

    if args.smoke:
        fused = _spawn("fused", fuse_values=(1, 2), n_requests=6)
        assert _fused_rows(report, fused), "fused parity/round-CR failed"
        with tempfile.TemporaryDirectory() as cache_dir:
            cold = _spawn("persist", cache_dir=cache_dir, n_requests=6)
            warm = _spawn("persist", cache_dir=cache_dir, n_requests=6)
        assert _persist_rows(report, cold, warm), (
            f"warm start not compile-free: {warm}")
        print("HW SMOKE OK")
        return 0

    run(report)
    return 0


if __name__ == "__main__":
    sys.exit(main())
