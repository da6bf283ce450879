"""Smoke run of the sort service on a TPU: every phase bit-checked.

    python3 chip_smoke.py               # one chip: phases A, B, C
    python3 chip_smoke.py --four-chips  # four chips: the bank-mesh phase only

One process owns the chip and starts no other.  The script exits non-zero,
and prints no result, when JAX finds no TPU or when any check of any phase
fails.  Its last line is one JSON object naming the device; the lines
before it are smoke timings (compile, cache and wall seconds of this run),
not benchmark metrics.

  A. the mixed served stream: ``make_workload`` (sort / argsort / topk /
     kmin over uint32 / int32 / float32, lengths 64-4096, seed 0) through
     ``SortServeEngine`` with the default config.  Every response equals
     ``solve_numpy``; colskip tiles ran the compiled Pallas kernel;
     ``radix_topk`` and ``jaxsort`` served tiles; ``numpy`` served none.
  B. the paper cell: N=1024, w=32, k=2 state recording over the five
     datasets of ``core/datasets.py``, forced through the colskip backend.
     Values, order, column reads and cycles equal ``core/colskip.py``.
  C. vocabulary-width top-k: ``radix_topk`` on (32, 131072) float32 with
     k=64 (the multi-bank Pallas path) equals ``lax.top_k``.
  --four-chips: the phase A workload through the bank mesh, 1x4 and 2x2,
     each bit-identical to a one-device colskip run of the same requests.

The phases are plain functions; a CPU test calls them at a tiny size.
Only ``main`` demands the chip.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core.colskip import colskip_sort  # noqa: E402
from repro.core.datasets import DATASETS, make_dataset  # noqa: E402
from repro.kernels.radix_topk import radix_topk  # noqa: E402
from repro.launch.sortserve import (  # noqa: E402
    check_against_oracle,
    make_workload,
)
from repro.sortserve import EngineConfig, SortRequest, SortServeEngine  # noqa: E402
from repro.sortserve.backends import (  # noqa: E402
    EXECUTOR_CACHE,
    checkout_cache_dir,
)

MESH_BACKENDS = ("colskip_mesh", "radix_topk", "jaxsort", "numpy")


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _count_impls(engine: SortServeEngine) -> collections.Counter:
    """Count the ``impl`` each colskip tile reports, as the tiles run."""
    impls: collections.Counter = collections.Counter()
    for be in engine.backends:
        if be.name in ("colskip", "colskip_mesh"):
            def counted(tile, _run=be.run):
                result = _run(tile)
                impls[result.meta["impl"]] += 1
                return result
            be.run = counted
    return impls


def _serve(config: EngineConfig, reqs) -> tuple[list, dict, dict]:
    engine = SortServeEngine(config)
    impls = _count_impls(engine)
    t0 = time.perf_counter()
    resps = engine.submit(reqs)
    wall = time.perf_counter() - t0
    telem = engine.telemetry()
    bad = sum(not check_against_oracle(q, r) for q, r in zip(reqs, resps))
    tiles = {name: pb["tiles"] for name, pb in telem["per_backend"].items()}
    ec = telem["executor_cache"]
    report = {"requests": len(reqs), "mismatches": bad, "tiles": tiles,
              "impl": dict(impls), "wall_s": wall,
              "executor_hits": ec["hits"], "executor_misses": ec["misses"],
              "persistent_hits": ec["persistent_hits"],
              "persistent_misses": ec["persistent_misses"],
              "rounds": telem["collectives"]["rounds"]}
    return resps, telem, report


def phase_served(n_requests: int = 400, min_len: int = 64,
                 max_len: int = 4096, seed: int = 0,
                 config: EngineConfig | None = None,
                 expect_impl: str = "pallas") -> dict:
    """Phase A: the mixed stream, oracle-checked response by response."""
    reqs = make_workload(n_requests, min_len, max_len, seed)
    _, _, rep = _serve(config or EngineConfig(), reqs)
    _check(rep["mismatches"] == 0, f"{rep['mismatches']} oracle mismatches")
    _check(set(rep["impl"]) == {expect_impl},
           f"colskip tiles ran {rep['impl']}, expected {expect_impl}")
    for name in ("colskip", "radix_topk", "jaxsort"):
        _check(rep["tiles"].get(name, 0) > 0, f"{name} served no tile")
    _check(rep["tiles"].get("numpy", 0) == 0,
           f"numpy backend served {rep['tiles'].get('numpy')} tiles")
    return rep


def phase_paper(n: int = 1024, w: int = 32, state_k: int = 2,
                seed: int = 0, config: EngineConfig | None = None) -> dict:
    """Phase B: the §V paper cell through the colskip backend, against the
    numpy hardware model (values, order, CR and cycles)."""
    config = config or EngineConfig(w=w, state_k=state_k)
    names = sorted(DATASETS)
    data = [make_dataset(name, n, w, seed).astype(np.uint32)
            for name in names]
    # a sort response carries the values, an argsort response the order
    reqs = [SortRequest(op=op, payload=x, backend="colskip")
            for x in data for op in ("sort", "argsort")]
    resps, _, rep = _serve(config, reqs)
    _check(rep["mismatches"] == 0, f"{rep['mismatches']} oracle mismatches")
    crs = {}
    for name, x, srt, arg in zip(names, data, resps[::2], resps[1::2]):
        hw = colskip_sort(x.astype(np.uint64), w, state_k)
        _check(np.array_equal(srt.values, hw.values.astype(np.uint32)),
               f"{name}: values differ from the numpy model")
        _check(np.array_equal(arg.indices, hw.order),
               f"{name}: order differs from the numpy model")
        for resp in (srt, arg):
            _check(resp.backend == "colskip",
                   f"{name}: served by {resp.backend}")
            _check(resp.column_reads == hw.column_reads,
                   f"{name}: CR {resp.column_reads} != {hw.column_reads}")
            _check(resp.cycles == hw.cycles,
                   f"{name}: cycles {resp.cycles} != {hw.cycles}")
        crs[name] = {"column_reads": int(hw.column_reads),
                     "cycles": int(hw.cycles)}
    rep["datasets"] = crs
    return rep


def phase_topk(rows: int = 32, width: int = 131072, k: int = 64,
               seed: int = 0, **radix_kwargs) -> dict:
    """Phase C: vocabulary-width top-k (multi-bank radix) vs ``lax.top_k``."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (rows, width),
                          jnp.float32)
    t0 = time.perf_counter()
    vals, idxs = jax.block_until_ready(radix_topk(x, k, **radix_kwargs))
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(radix_topk(x, k, **radix_kwargs))
    warm = time.perf_counter() - t0
    ref_v, ref_i = jax.lax.top_k(x, k)
    _check(np.array_equal(np.asarray(vals), np.asarray(ref_v)),
           "top-k values differ from lax.top_k")
    _check(np.array_equal(np.asarray(idxs), np.asarray(ref_i)),
           "top-k indices differ from lax.top_k")
    return {"shape": [rows, width], "k": k, "first_call_s": first,
            "warm_call_s": warm}


def _same(a, b) -> bool:
    if a is None or b is None:
        return a is None and b is None
    return np.array_equal(a, b)


def phase_mesh(n_requests: int = 400, min_len: int = 64, max_len: int = 4096,
               seed: int = 0, hosts=(1, 2), expect_impl: str = "pallas",
               local: EngineConfig | None = None) -> dict:
    """Four chips: the phase A stream through the bank mesh (one run per
    ``hosts`` layout), bit-identical to a one-device colskip run.  Routing
    is static in every run, so each request goes the same way in all."""
    reqs = make_workload(n_requests, min_len, max_len, seed)
    local = local or EngineConfig(adaptive_policy=False)
    base, _, rep_local = _serve(local, reqs)
    _check(rep_local["mismatches"] == 0,
           f"one-device run: {rep_local['mismatches']} oracle mismatches")
    _check(set(rep_local["impl"]) == {expect_impl},
           f"one-device colskip ran {rep_local['impl']}")
    out = {"local": rep_local}
    for h in hosts:
        cfg = EngineConfig(mesh=True, mesh_hosts=h, banks=4,
                           backends=MESH_BACKENDS, adaptive_policy=False)
        resps, telem, rep = _serve(cfg, reqs)
        tag = f"mesh_{h}x{4 // h}"
        _check(rep["mismatches"] == 0, f"{tag}: {rep['mismatches']} "
               "oracle mismatches")
        _check(rep["tiles"].get("colskip_mesh", 0) > 0,
               f"{tag}: colskip_mesh served no tile")
        _check(rep["rounds"] > 0, f"{tag}: no collective rounds")
        for q, a, b in zip(reqs, base, resps):
            same = (_same(a.values, b.values) and _same(a.indices, b.indices)
                    and a.column_reads == b.column_reads
                    and a.cycles == b.cycles
                    and a.backend == b.backend.replace("colskip_mesh",
                                                       "colskip"))
            _check(same, f"{tag}: request {q.request_id} ({q.op}, n={q.n}) "
                   "differs from the one-device run")
        out[tag] = rep
    return out


def _device() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true", dest="four_chips",
                    help="run only the bank-mesh phase, on four chips")
    args = ap.parse_args(argv)

    dev = _device()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev['platform']!r})",
              file=sys.stderr)
        return 2
    if args.four_chips and dev["count"] != 4:
        print(f"chip_smoke: --four-chips needs 4 devices, have "
              f"{dev['count']}", file=sys.stderr)
        return 2
    print(f"device: {dev}")
    # JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_cache
    EXECUTOR_CACHE.enable_persistent(checkout_cache_dir())
    print(f"compile cache: {EXECUTOR_CACHE.persistent_dir}")

    phases = ([("mesh", phase_mesh)] if args.four_chips else
              [("A_served", phase_served), ("B_paper", phase_paper),
               ("C_topk", phase_topk)])
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            rep = fn()
        except SmokeFailure as e:
            print(f"phase {name} FAILED: {e}", file=sys.stderr)
            return 1
        print(f"phase {name} ok in {time.perf_counter() - t0:.2f} s "
              f"(smoke timing): {json.dumps(rep, default=str)}", flush=True)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
