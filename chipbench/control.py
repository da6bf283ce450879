"""The readings that set ``correct``'s limits: program and control.

    python3 chipbench/control.py --workload sorter1024.skewed \
        --seeds 11,12,13 --seconds 5

On the chip, in one process: for each seed, one run of the cell at its own
load (``run_cell``), checked twice over the same window of requests.
First the program's answers against the plain reference (the lower
reading: sound runs give 0), then the control's answers, the reference
one precision down put in the program's place (the upper reading).  Each
line printed is one seed's readings.  The benchmark's own runs never run
the control.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench.lib.spec import load_bench  # noqa: E402


def readings(bench, cell: str, seed: int, seconds: float, devices,
             **overrides) -> dict:
    """One seed's program and control readings of the numbers compared."""
    from chipbench.lib.cell import check, control_answer, run_cell

    records: list = []
    result = run_cell(bench, cell, seed, seconds, False, time.perf_counter(),
                      devices=devices, records_out=records,
                      log=lambda m: print(m, file=sys.stderr), **overrides)
    control = check(records, served=control_answer)
    return {"seed": seed, "requests": len(records),
            "program": {k: c["value"] for k, c in result["checks"].items()},
            "program_correct": result["correct"],
            "control": {"wrong_or_missing": control["mismatched"]
                        + control["unanswered"]}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("control.py: no TPU", file=sys.stderr)
        return 2
    from chipbench.lib.cell import use_checkout_cache
    use_checkout_cache(ROOT)
    bench = load_bench(ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(bench, args.workload, seed, args.seconds,
                                  devices)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
