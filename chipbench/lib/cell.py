"""One run of one cell: set up, warm up, measure, check, read the metrics.

The run does its work in this order, so that what is timed is only the
served path, and what is checked is everything that path answered:

1. build the engine from the configuration file and draw the traffic;
2. warm up: drive the cell's own traffic (its own stream, so no payload
   of the window repeats) until a round compiles nothing; ``setup_s`` ends
   here, at the first request of the window;
3. the window: ``seconds`` of traffic, the profiler on for a short steady
   stretch of it in a traced run;
4. read the device's peak memory, reduce the trace;
5. check every request of the window against the plain reference
   (``reference.py``): answers that are wrong or never came, held to their
   limit.

The window's requests are every request due in it.  Those still open when
it closes are drained and waited for: their latency counts the wait, and
the rates run to the last answer.
"""

from __future__ import annotations

import gc
import gzip
import json
import os
import shutil
import statistics
import tempfile
from dataclasses import dataclass

from . import reference, trace as trace_lib
from .driver import Driver, build_engine, clock
from .peaks import peaks_for
from .traffic import WARM_STREAM, load_mix, make_traffic

__all__ = ["LIMITS", "Context", "check", "control_answer", "run_cell",
           "use_checkout_cache", "warm_up"]

# each number compared, and its limit: an exact comparison allows nothing
LIMITS = {"wrong_or_missing": 0}
TRACE_LEAD_S = 1.0             # the traced stretch starts this far in ...
TRACE_S = 2.0                  # ... and lasts this long (or less)
WARM_ROUNDS = (2, 12)          # fewest and most warm-up rounds


@dataclass
class Context:
    """What a metric reader (``chipbench/metrics/<name>.py``) sees."""

    setup_s: float
    latencies_s: list                    # every request due in the window
    elems_done: int                      # their input elements
    span_s: float                        # window start to the last answer
    engine_s: float                      # host time in feed / poll / drain
    backend_s: float                     # host time in backend runs
    tiles: list                          # driver.TileRecord, window only
    compiles: int                        # program's compile counters, delta
    peaks: dict
    trace: dict | None = None            # trace.reduce() of the traced run

    @property
    def traced_tiles(self) -> list:
        return [t for t in self.tiles if t.traced]


def use_checkout_cache(root: str) -> str:
    """Point JAX's persistent compilation cache at ``<root>/.jax_cache``.

    The program keeps its cache wherever ``JAX_COMPILATION_CACHE_DIR``
    says, so the benchmark gives it this fixed path inside the checkout:
    only a cell's first run there compiles, and no other checkout reads or
    writes it."""
    from repro.sortserve.backends import EXECUTOR_CACHE

    path = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    EXECUTOR_CACHE.enable_persistent(path)
    return path


def _device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def warm_up(driver: Driver, warm, mix: dict) -> int:
    """Drive the warm-up stream in rounds until a round compiles nothing.
    Returns the rounds run."""
    kw = ({"requests": int(mix["warm_requests"])} if warm.loop == "closed"
          else {"steps": int(mix["warm_steps"]), "paced": False})
    lo, hi = WARM_ROUNDS
    for r in range(1, hi + 1):
        before = driver.exec_misses()
        driver.run(warm, **kw)
        if r >= lo and driver.exec_misses() == before:
            return r
    raise RuntimeError(f"still compiling after {hi} warm-up rounds")


def served_answer(rec):
    """What the program answered a request: ``(values, indices)``, or
    None when no answer came."""
    if rec.resp is None:
        return None
    return rec.resp.values, rec.resp.indices


def control_answer(rec):
    """What the control (the reference one precision down) would answer."""
    q = rec.req
    return reference.control(q.op, q.payload, q.k)


def check(records, served=served_answer) -> dict:
    """Count wrong and missing answers among ``records`` (driver.Record),
    judged against the plain reference.  ``served`` gives each request's
    answer: the program's, or the control's in its place."""
    mismatched = unanswered = 0
    for rec in records:
        got = served(rec)
        if got is None:
            unanswered += 1
            continue
        q = rec.req
        if not reference.response_matches(q.op, q.payload, q.k, *got):
            mismatched += 1
    return {"mismatched": mismatched, "unanswered": unanswered}


def _quartiles_ms(values: list) -> list:
    if len(values) < 2:
        return [v * 1e3 for v in values]
    return [q * 1e3 for q in statistics.quantiles(values, n=4)]


def run_cell(bench, cell_name: str, seed: int, seconds: float, trace: bool,
             t_proc: float, *, devices, mix_overrides: dict | None = None,
             engine_overrides: dict | None = None, keep_trace: str | None = None,
             records_out: list | None = None, log=print) -> dict:
    """One run; returns the result line as a dict (``checks`` last).
    ``records_out`` receives the window's requests and answers."""
    cell = bench.cell(cell_name)
    config = bench.config(cell["config"])
    mix = {**load_mix(bench.mix_path(cell["traffic"])),
           **(mix_overrides or {})}
    device = _device_info(devices)
    peaks = peaks_for(device["kind"]) if device["platform"] == "tpu" else {}

    t_start = clock()
    engine = build_engine({**config["engine"], **(engine_overrides or {})})
    driver = Driver(engine)
    t_engine = clock()
    traffic = make_traffic(mix, seed)
    traffic.prefill(int(mix.get("prefill_requests", 0)))
    t_traffic = clock()
    rounds = warm_up(driver, traffic.twin(WARM_STREAM), mix)
    t_warm = clock()
    setup_s = t_warm - t_proc
    log(f"set-up {setup_s:.4f} s: start {t_start - t_proc:.4f}, engine "
        f"{t_engine - t_start:.4f}, traffic {t_traffic - t_engine:.4f}, "
        f"warm-up {t_warm - t_traffic:.4f} ({rounds} rounds)")

    # ---------------------------------------------------------- the window
    misses0 = driver.exec_misses()
    logdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    trace_from = min(TRACE_LEAD_S, seconds / 4)
    trace_to = trace_from + min(TRACE_S, seconds / 2)
    began: list[float] = []            # the first tick, and whether traced

    def tick(now: float) -> None:
        if not began:
            began.append(now)
        t = now - began[0]
        if len(began) == 1 and t >= trace_from:
            driver.start_trace(logdir)
            began.append(True)
        elif driver.traced and t >= trace_to:
            driver.stop_trace()

    pauses = _GcPauses()
    driver.counting = True
    with pauses:
        records, t0 = driver.run(traffic, seconds=seconds,
                                 on_tick=tick if trace else None)
    driver.stop_trace()
    compiles = driver.exec_misses() - misses0
    device = _device_info(devices)

    reduced = None
    if trace:
        try:
            reduced = _reduce_trace(logdir, keep_trace)
        finally:
            shutil.rmtree(logdir, ignore_errors=True)

    # --------------------------------------------------------- the check
    t_check = clock()
    counts = check(records)
    t_check = clock() - t_check
    if records_out is not None:
        records_out.extend(records)

    done = [r for r in records if r.t_done is not None]
    ctx = Context(
        setup_s=setup_s,
        latencies_s=[r.t_done - r.due for r in done],
        elems_done=sum(r.req.n for r in done),
        span_s=max((r.t_done for r in done), default=t0) - t0,
        engine_s=driver.counters.engine_s,
        backend_s=driver.counters.backend_s,
        tiles=driver.counters.tiles, compiles=compiles,
        peaks=peaks, trace=reduced)

    waits = [r.t_fed - r.due for r in records if r.t_fed is not None]
    late = sum(r.t_done > t0 + seconds for r in done)
    log(f"window: {len(records)} requests due in {seconds} s, {len(done)} "
        f"answered, {late} of them after the close, last answer at "
        f"{ctx.span_s:.4f} s; {len(ctx.tiles)} tiles; "
        f"check {t_check:.3f} s: {counts['mismatched']} wrong, "
        f"{counts['unanswered']} unanswered")
    log("generator lateness (due -> fed), ms quartiles: "
        f"{[round(q, 4) for q in _quartiles_ms(waits)]}, max "
        f"{max(waits, default=0.0) * 1e3:.4f}")
    log("longest single call, ms: " + ", ".join(
        f"{k} {v * 1e3:.4f}" for k, v in driver.counters.longest.items())
        + f"; garbage collections {pauses.count}, longest "
        f"{pauses.longest * 1e3:.4f} ms, all {pauses.total * 1e3:.4f} ms")

    metrics = {}
    for m in bench.metrics_for(cell_name, trace):
        value = bench.reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    compared = {"wrong_or_missing": counts["mismatched"]
                + counts["unanswered"]}
    failed = compared["wrong_or_missing"]
    result = {
        "correct": bool(records) and all(compared[k] <= LIMITS[k]
                                         for k in LIMITS),
        "attempted": len(records),
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if reduced is not None:
        top = sorted(reduced["op_s"].items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(reduced["idle_by_host"].items(),
                      key=lambda kv: -kv[1])[:10]
        result["breakdown"] = {"device_ops": [list(kv) for kv in top],
                               "idle_gaps": [list(kv) for kv in gaps]}
    result["checks"] = {k: {"value": compared[k], "limit": LIMITS[k]}
                        for k in LIMITS}
    return result


class _GcPauses:
    """The interpreter's garbage collections inside a ``with`` block: how
    many, the longest and their sum, so a stall of the driver can be told
    from one of the program."""

    def __init__(self):
        self.count, self.longest, self.total = 0, 0.0, 0.0
        self._t = 0.0

    def _callback(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = clock()
        else:
            dt = clock() - self._t
            self.count += 1
            self.longest = max(self.longest, dt)
            self.total += dt

    def __enter__(self):
        gc.callbacks.append(self._callback)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._callback)


def _reduce_trace(logdir: str, keep: str | None) -> dict:
    paths = [os.path.join(d, f) for d, _, fs in os.walk(logdir)
             for f in fs if f.endswith(".xplane.pb")]
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file, found {len(paths)}")
    plain = trace_lib.from_xplane(paths[0])
    if keep:
        with gzip.open(keep, "wt") as f:
            json.dump(plain, f)
    return trace_lib.reduce(plain)
