"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout, on a machine holding the TPU chips the
cell asks for; the process owns them and starts no other.  With no TPU, or
fewer chips than the cell asks for, it exits 2 and prints no result.

``--trace 0`` measures the cell's end-to-end metrics; ``--trace 1`` runs
the same window with the profiler on for a short stretch and reports the
per-layer metrics, the device's busy seconds and a breakdown.  Both check
every answer of the window against the plain reference.  The last lines
of standard error give each number compared beside its limit; the last
line of standard output is the result, one JSON object.

JAX's persistent compilation cache is ``<checkout>/.jax_cache``, a fixed
path inside the checkout, so only a cell's first run in a checkout
compiles and two checkouts share nothing.
"""

import time

T_PROC = time.perf_counter()        # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench.lib.spec import load_bench  # noqa: E402


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seconds <= 0:
        ap.error("--seconds must be positive")

    bench = load_bench(ROOT)
    cell = bench.cell(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        _log(f"run.py: no TPU (JAX platform {devices[0].platform!r})")
        return 2
    if len(devices) < int(cell["chips"]):
        _log(f"run.py: {args.workload} needs {cell['chips']} chips, "
             f"have {len(devices)}")
        return 2

    from chipbench.lib.cell import run_cell, use_checkout_cache
    use_checkout_cache(ROOT)
    result = run_cell(bench, args.workload, args.seed, args.seconds,
                      bool(args.trace), T_PROC, devices=devices, log=_log)
    for name, c in result["checks"].items():
        _log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
