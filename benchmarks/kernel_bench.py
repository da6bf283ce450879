"""Kernel microbenchmarks: CR-count telemetry + plane-skip fractions.

The paper's metric is column reads; on TPU the analogue is bit-planes
visited.  We report, per workload: planes visited / 32 (skip fraction from
the leading-uniform certification) and wall time of the kernel in the mode
the platform resolves (:mod:`repro.kernels.dispatch`: compiled on TPU,
interpreted elsewhere; a CPU wall time is not a speed).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import make_dataset
from repro.kernels.bitonic import bitonic_sort, n_passes
from repro.kernels.colskip import colskip_sort_batched
from repro.kernels.radix_topk.kernel import threshold_pallas
from repro.kernels.radix_topk.ref import threshold_ref


def _timed(fn, *a):
    out = fn(*a)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out = fn(*a)
    jax.block_until_ready(out)
    return out, (time.perf_counter() - t0) * 1e6


def run(report):
    rng = np.random.default_rng(0)

    # --- radix_topk: plane-skip telemetry on router-like inputs ----------
    # softmax probs share sign + high exponent bits -> leading planes are
    # uniform and the kernel's s_top certification skips them (the paper's
    # leading-zero-column skip); wide mixed-sign logits have no skip.
    cases = {
        "router_probs": np.asarray(
            jax.nn.softmax(jnp.asarray(rng.normal(size=(64, 128)).astype(np.float32)))),
        "logits_wide": (rng.normal(size=(64, 128)) * 10.0).astype(np.float32),
    }
    for name, arr in cases.items():
        x = jnp.asarray(arr)
        (t, visited), us = _timed(
            lambda v: threshold_pallas(v, 8), x)
        tr = threshold_ref(x, 8)
        ok = np.array_equal(np.asarray(t), np.asarray(tr))
        report(
            name=f"kernel/radix_topk/{name}",
            us_per_call=us,
            derived=(f"planes_visited={int(np.asarray(visited).max())}/32 "
                     f"skip={1 - np.asarray(visited).max() / 32:.2f} "
                     + ("PASS" if ok else "MISS")),
        )

    # --- bitonic network (the merge-sorter analogue): dense pass count ----
    # paper's merge sorter: 10 cyc/num; bitonic on TPU: log2N(log2N+1)/2
    # full-width passes, data-independent.  Column skipping wins when data
    # has structure; the network wins on adversarial/uniform data.
    x = np.stack([make_dataset("mapreduce", 1024, 32, seed=s).astype(np.uint32)
                  for s in (1, 2)])
    (srt,), us = _timed(lambda a: (bitonic_sort(a, use_pallas=True),),
                        jnp.asarray(x))
    ok = all(np.array_equal(np.asarray(srt[i]), np.sort(x[i])) for i in range(2))
    report(name="kernel/bitonic_sort/mapreduce_1024", us_per_call=us,
           derived=f"passes={n_passes(1024)} (vs colskip CR-model) "
                   + ("PASS" if ok else "MISS"))

    # --- colskip sort kernel: CR telemetry matches hardware model --------
    for ds in ["uniform", "mapreduce"]:
        v = np.stack([make_dataset(ds, 128, 32, seed=s).astype(np.uint32)
                      for s in (1, 2)])
        (vals, order, crs, cyc), us = _timed(
            lambda a: colskip_sort_batched(a, 32, 2, use_pallas=True),
            jnp.asarray(v))
        sorted_ok = all(np.array_equal(np.asarray(vals[i]), np.sort(v[i]))
                        for i in range(2))
        report(
            name=f"kernel/colskip_sort/{ds}",
            us_per_call=us,
            derived=(f"cyc/num={float(np.asarray(cyc).mean()) / 128:.2f} "
                     f"speedup={32 / (float(np.asarray(cyc).mean()) / 128):.2f}x "
                     + ("PASS" if sorted_ok else "MISS")),
        )

    # --- colskip kernel: lane-packed vs dense mask carriers --------------
    # packed vs dense §III machine, each where the platform runs it (the
    # dense carrier has no compiled kernel, so on TPU it takes the XLA
    # reference); telemetry must agree bit-exactly
    # (the headline 1024-wide numbers live in benchmarks/packed_bench.py)
    v = np.stack([make_dataset("mapreduce", 128, 32, seed=s).astype(np.uint32)
                  for s in (1, 2)])
    vj = jnp.asarray(v)
    (out_p), us_p = _timed(lambda a: colskip_sort_batched(
        a, 32, 2, packed=True), vj)
    (out_d), us_d = _timed(lambda a: colskip_sort_batched(
        a, 32, 2, packed=False), vj)
    same = all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(out_p, out_d))
    report(name="kernel/colskip_sort/packed_vs_dense", us_per_call=us_p,
           derived=(f"dense_us={us_d:.0f} speedup={us_d / max(us_p, 1e-9):.2f}x "
                    + ("PASS" if same else "MISS")))
