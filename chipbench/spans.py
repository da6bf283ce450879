"""The program's spans on the chip: a cell's tile time split inside out.

    python3 chipbench/spans.py --workload sorter1024.skewed \
        --seeds 11,12,13 --seconds 10 [--keep profiles]

On the chip, in one process: for each seed, one untraced and one traced
run of the cell (``run_cell``), each reading the cell's per-layer metrics
besides its end-to-end ones (the device's readers find nothing untraced).
The traced run's profile is reduced by ``lib/spans.py`` as well as by the
benchmark's own reduction, and the window's wall-clock queue wait is read
from the program's counters.  Each line printed is one seed's JSON:
``untraced`` and ``traced`` metrics, ``queue_wait_ms`` (mean per request,
where the program counts it), and ``spans`` in milliseconds per traced
tile: each ``sortserve.*`` span's self time, each executor's device time,
and the device's idle time by the innermost host span.  ``--keep`` copies
each profile there.  The benchmark's own runs never run this.
"""

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench.lib.spec import Bench, load_bench  # noqa: E402


class _AllMetrics(Bench):
    """Every metric the cell lists, traced or not."""

    def metrics_for(self, cell: str, trace: bool) -> list[dict]:
        return (super().metrics_for(cell, False)
                + super().metrics_for(cell, True))


def _per_tile_ms(reduced: dict) -> dict:
    tiles = reduced["span_count"].get("sortserve.execute", 0)
    if not tiles:
        return {}

    def ms(table):
        return {k: v / tiles * 1e3 for k, v in sorted(table.items())}
    return {"tiles": tiles, "self_ms": ms(reduced["span_self_s"]),
            "executor_ms": ms(reduced["executor_s"]),
            "executor_calls": reduced["executor_calls"],
            "idle_ms": ms(reduced["idle_by_span"])}


def split(bench, cell: str, seed: int, seconds: float, devices,
          keep: str | None = None, **overrides) -> dict:
    """One seed's untraced and traced runs (see the module docstring);
    ``overrides`` go to ``run_cell``."""
    from chipbench.lib import cell as cell_lib
    from chipbench.lib import spans

    found: dict = {}
    reduce_trace, driver_cls = cell_lib._reduce_trace, cell_lib.Driver

    def reduce_with_spans(logdir, keep_trace):
        # run_cell deletes the profile after its reduction: read it first
        (path,) = [os.path.join(d, f) for d, _, fs in os.walk(logdir)
                   for f in fs if f.endswith(".xplane.pb")]
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(path, os.path.join(keep, f"{cell}-{seed}.xplane.pb"))
        found["spans"] = _per_tile_ms(
            spans.reduce(spans.from_xplane_with_spans(path)))
        return reduce_trace(logdir, keep_trace)

    class Probe(driver_cls):
        def run(self, traffic, **kw):
            if not self.counting:                 # warm-up
                return super().run(traffic, **kw)
            q0 = self.engine.telemetry().get("queue_wait_s")
            out = super().run(traffic, **kw)
            q1 = self.engine.telemetry().get("queue_wait_s")
            if q0 is not None and q1["count"] > q0["count"]:
                found["queue_wait_ms"] = ((q1["sum"] - q0["sum"]) * 1e3
                                          / (q1["count"] - q0["count"]))
            return out

    out = {"seed": seed}
    cell_lib._reduce_trace, cell_lib.Driver = reduce_with_spans, Probe
    try:
        for traced in (False, True):
            found.clear()
            r = cell_lib.run_cell(
                bench, cell, seed, seconds, traced, time.perf_counter(),
                devices=devices, log=lambda m: print(m, file=sys.stderr),
                **overrides)
            key = "traced" if traced else "untraced"
            out[key] = {k: m["value"] for k, m in r["metrics"].items()}
            out[key]["correct"] = r["correct"]
            out[key]["queue_wait_ms"] = found.get("queue_wait_ms")
            if traced:
                out["breakdown"] = r.get("breakdown")
                out["device"] = r["device"]
                out["spans"] = found.get("spans")
    finally:
        cell_lib._reduce_trace, cell_lib.Driver = reduce_trace, driver_cls
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--keep", default=None,
                    help="directory to copy each profile into")
    args = ap.parse_args(argv)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("spans.py: no TPU", file=sys.stderr)
        return 2
    from chipbench.lib.cell import use_checkout_cache
    use_checkout_cache(ROOT)
    base = load_bench(ROOT)
    bench = _AllMetrics(base.data, base.root)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(split(bench, args.workload, seed, args.seconds,
                               devices, args.keep)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
