"""Find an open-loop cell's capacity: one sweep of offered step rates.

    python3 chipbench/sweep.py --workload decode-topk.steady --seed 5 \
        --seconds 8 --rates 2,4,6,8,10

One process on the chip: the engine is built and warmed once, then each
rate runs for ``--seconds``.  For each rate it prints the rows offered and
answered per second, the latency quartiles, and how late the last step was
fed.  Below capacity the lateness stays near zero; above it the backlog,
and so the lateness, grows through the run.  The cell's rate is then fixed
in its traffic file at about four fifths of the highest rate sustained;
the benchmark's runs never search for one.
"""

import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench.lib.spec import load_bench  # noqa: E402


def sweep(bench, cell_name: str, seed: int, seconds: float, rates,
          mix_overrides=None, engine_overrides=None) -> list[dict]:
    from chipbench.lib.cell import warm_up
    from chipbench.lib.driver import Driver, build_engine
    from chipbench.lib.traffic import WARM_STREAM, load_mix, make_traffic

    cell = bench.cell(cell_name)
    config = bench.config(cell["config"])
    mix = {**load_mix(bench.mix_path(cell["traffic"])),
           **(mix_overrides or {})}
    if mix["loop"] != "open":
        raise ValueError(f"{cell_name} is not an open-loop cell")
    driver = Driver(build_engine({**config["engine"],
                                  **(engine_overrides or {})}))
    base = make_traffic(mix, seed)
    warm_up(driver, base.twin(WARM_STREAM), mix)
    out = []
    for stream, rate in enumerate(rates, start=2):
        traffic = make_traffic({**mix, "steps_per_s": rate}, seed, stream)
        records, t0 = driver.open_loop(traffic, seconds=seconds)
        lat = [r.t_done - r.due for r in records if r.t_done is not None]
        last = max(records, key=lambda r: r.due)
        q = statistics.quantiles(lat, n=4) if len(lat) > 1 else lat * 3
        out.append({
            "steps_per_s": rate,
            "offered_rows_per_s": len(records) / seconds,
            "answered_rows_per_s": len(lat) / (max(r.t_done for r in records
                                                   if r.t_done) - t0),
            "latency_ms_q1_q2_q3": [v * 1e3 for v in q],
            "last_step_late_ms": (last.t_fed - last.due) * 1e3,
        })
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated steps per second")
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep.py: no TPU", file=sys.stderr)
        return 2
    from chipbench.lib.cell import use_checkout_cache
    use_checkout_cache(ROOT)
    rates = [float(r) for r in args.rates.split(",")]
    for row in sweep(load_bench(ROOT), args.workload, args.seed,
                     args.seconds, rates):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
