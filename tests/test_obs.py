"""Flight-recorder observability: span chains, windowed metrics, calibration.

The acceptance surface:

  * **chain completeness** — every request fed into a traced engine yields
    exactly one finalized span chain (feed -> bucket -> admit -> execute ->
    scatter -> retire), well-nested in wall time and consistent in virtual
    time, across bursty / mixed-width / strict / non-strict / defer / shed
    traffic (hypothesis sweep);
  * **vt conservation** — the per-bank execute spans in the exported trace
    sum to exactly ``scheduler.banks[].busy_cycles`` for exact-cycle
    backends (the trace is the bank accounting, drawn);
  * **zero-overhead default** — tracing off is the default, emits zero
    spans, and a *traced* run of the golden workload reproduces the
    recorded golden telemetry byte-identically (observation does not
    perturb the observed);
  * **windowed metrics / calibration primitives** — sliding-window counts,
    exact recent quantiles, snapshot/restore (the engine rollback path),
    and the measured-vs-modeled ratio table;
  * **profiler spans** — under ``jax.profiler.trace`` every device tile
    carries ``sortserve.execute`` ⊃ ``execute.run`` ⊃ put / launch / wait /
    fetch under one tile id, beside feed / bucket / schedule / scatter; with no profiler
    the golden workload's answers and telemetry are unchanged and nothing
    is written; every executor is named in its HLO; the wall-clock queue
    wait (feed -> launch) is exact under a fake clock.
"""

import contextlib
import glob
import json
import os

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.obs import CalibrationTable, LogBucketHistogram, Tracer, \
    WindowedCounter
from repro.sortserve import EngineConfig, SortRequest, SortServeEngine, \
    WatermarkPolicy
from test_continuous import GOLDEN, FakeClock, golden_payload, make_engine

from repro.launch.sortserve import make_workload


def traced_engine(clock=None, **over):
    tracer = Tracer()
    return make_engine(clock, tracer=tracer, **over), tracer


def reqs_of(lengths, op="sort", seed=0):
    rng = np.random.default_rng(seed)
    return [SortRequest(op=op, payload=rng.integers(
                0, 1 << 16, size=n, dtype=np.int64).astype(np.uint32))
            for n in lengths]


def assert_served_chain(chain):
    """One complete feed->retire chain, well-nested in both domains."""
    assert chain["status"] == "served"
    rec = chain["tile"]
    assert rec is not None, "served chain lost its tile record"
    assert chain["t_feed"] <= chain["t_bucket"] <= rec["t_exec0"] \
        <= rec["t_exec1"] <= chain["t_done"]
    assert rec["status"] == "retired"
    assert rec["arrive_vt"] is not None
    assert rec["admit_vt"] >= rec["arrive_vt"]
    assert rec["retire_vt"] >= rec["admit_vt"]
    assert rec["bank_ids"], "admitted tile placed on no banks"


# ----------------------------------------------------------- span chains
def test_every_request_yields_exactly_one_complete_chain():
    clock = FakeClock()
    eng, tracer = traced_engine(clock)
    reqs = reqs_of([8, 30, 64, 100, 16, 8, 120, 33])
    got = eng.submit(reqs)
    assert len(got) == len(reqs)
    rids = [r.request_id for r in reqs]
    chains = [c for c in tracer.chains if c["rid"] in rids]
    assert sorted(c["rid"] for c in chains) == sorted(rids)
    for chain in chains:
        assert_served_chain(chain)


def test_chain_vt_matches_scheduler_events():
    eng, tracer = traced_engine(FakeClock())
    eng.submit(reqs_of([16] * 8))
    kinds = [e["kind"] for e in tracer.events]
    assert kinds.count("arrive") == kinds.count("admit") \
        == kinds.count("retire") == 2          # 8 reqs / 4 rows = 2 tiles
    for chain in tracer.chains:
        rec = chain["tile"]
        evs = {e["kind"]: e for e in tracer.events
               if e["seq"] == rec["seq"]}
        assert evs["arrive"]["vt"] == rec["arrive_vt"]
        assert evs["admit"]["vt"] == rec["admit_vt"]
        assert evs["retire"]["vt"] == rec["retire_vt"]


def test_cache_hit_yields_instant_chain():
    clock = FakeClock()
    eng, tracer = traced_engine(clock, cache_size=8)
    rng = np.random.default_rng(3)
    payload = rng.integers(0, 99, size=32).astype(np.uint32)
    eng.submit([SortRequest(op="sort", payload=payload)])
    clock.tick(1.0)
    req2 = SortRequest(op="sort", payload=payload)
    eng.submit([req2])
    chain = tracer.chain_for(req2.request_id)
    assert chain["status"] == "cache_hit"
    assert chain["t_feed"] == chain["t_done"] == 1.0
    assert chain["tile"] is None


def test_shed_requests_finalize_as_shed_chains():
    eng, tracer = traced_engine(
        FakeClock(),
        admission=WatermarkPolicy(high_watermark=1, shed=True))
    session = eng.begin(strict=False)
    reqs = reqs_of([16] * 40)
    session.feed(reqs, flush=True)
    session.drain()
    failures = session.take_failures()
    assert failures, "overloaded watermark shed nothing"
    statuses = {c["rid"]: c["status"] for c in tracer.chains}
    for req, exc, _ in failures:
        assert statuses[req.request_id] == "shed"
    shed_events = [e for e in tracer.events if e["kind"] == "shed"]
    assert len(shed_events) == eng.scheduler.stats.shed
    for c in tracer.chains:
        if c["status"] == "served":
            assert_served_chain(c)


def test_deferred_requests_still_complete_with_defer_events():
    eng, tracer = traced_engine(
        FakeClock(),
        admission=WatermarkPolicy(high_watermark=1, shed=False,
                                  retry_after_vt=16.0))
    reqs = reqs_of([16] * 40)
    got = eng.submit(reqs)
    assert len(got) == len(reqs)
    assert eng.scheduler.stats.deferred > 0
    assert any(e["kind"] == "defer" for e in tracer.events)
    for chain in tracer.chains:
        assert_served_chain(chain)
    deferred_tiles = [c["tile"] for c in tracer.chains
                      if c["tile"]["defers"] > 0]
    assert deferred_tiles, "defer events but no chain carries a defer count"


@settings(max_examples=10, deadline=None)
@given(st.lists(st.tuples(st.integers(1, 12),        # burst size
                          st.integers(8, 100),       # payload length
                          st.booleans()),            # tick between bursts
                min_size=1, max_size=6),
       st.booleans())                                # strict session
def test_chain_sweep_bursty_mixed_width(bursts, strict):
    clock = FakeClock()
    eng, tracer = traced_engine(clock, backends=("numpy",))
    session = eng.begin(strict=strict)
    fed = []
    seed = 0
    for size, length, tick in bursts:
        seed += 1
        batch = reqs_of([length + i for i in range(size)], seed=seed)
        fed += batch
        session.feed(batch)
        if tick:
            clock.tick(0.5)
            session.poll()
    session.feed([], flush=True)
    session.drain()
    chains = {c["rid"]: c for c in tracer.chains}
    assert sorted(chains) == sorted(r.request_id for r in fed)
    for chain in chains.values():
        assert_served_chain(chain)


# ------------------------------------------------------- vt conservation
def test_bank_span_vt_sums_to_busy_cycles():
    """The exported per-bank spans ARE the busy-cycle accounting: for
    exact-cycle backends, summing each bank track's span durations (mapped
    back to cycles) reproduces ``banks[].busy_cycles`` exactly."""
    eng, tracer = traced_engine(FakeClock(), backends=("colskip", "numpy"))
    eng.submit(make_workload(30, min_len=8, max_len=128, seed=7,
                             ops=("sort", "argsort")))
    doc = eng.dump_trace("/dev/null")
    us_per_cycle = 1e6 / tracer.clock_hz
    per_bank: dict[int, float] = {}
    for ev in doc["traceEvents"]:
        if ev.get("ph") == "X" and ev["pid"] == 2:
            per_bank[ev["tid"]] = per_bank.get(ev["tid"], 0.0) \
                + ev["dur"] / us_per_cycle
    for bank in eng.pool.banks:
        assert round(per_bank.get(bank.index, 0.0)) == bank.busy_cycles


# --------------------------------------------------- off-by-default golden
def test_tracing_off_is_default_and_spanless():
    eng = make_engine()
    assert eng._tracer is None
    assert eng.scheduler.on_event is None
    eng.submit(reqs_of([16] * 4))
    with pytest.raises(RuntimeError, match="no tracer"):
        eng.dump_trace("/dev/null")


def _golden_workload():
    return make_workload(40, min_len=8, max_len=128, seed=21)


def _golden_payload(eng, got) -> dict:
    """The recorded golden file's shape, rebuilt from a live run."""
    from test_continuous import _bank_totals, _digest
    telem = eng.telemetry()
    return {
        "responses": [
            {"backend": r.backend, "cycles": r.cycles,
             "column_reads": r.column_reads,
             "bucket_shape": list(r.bucket_shape),
             "values": _digest(r.values), "indices": _digest(r.indices)}
            for r in got],
        "aggregate": {
            "column_reads": telem["column_reads"],
            "cycles_exact": telem["cycles_exact"],
            "cycles_estimated": telem["cycles_estimated"],
            "tiles": telem["scheduler"]["tiles"],
            "bank_totals": list(_bank_totals(eng)),
        },
    }


def test_traced_golden_workload_is_byte_identical():
    """Observation must not perturb the observed: the golden workload run
    with the recorder ON reproduces the recorded telemetry byte-for-byte,
    and the untraced default is pinned separately by test_continuous."""
    reqs = _golden_workload()
    tracer = Tracer()
    eng = make_engine(tracer=tracer)
    got = eng.submit(reqs)
    assert _golden_payload(eng, got) == json.loads(GOLDEN.read_text())
    assert tracer.span_count() == len(reqs)


@pytest.mark.parametrize("profiler", [False, True])
def test_program_spans_leave_the_golden_workload_byte_identical(
        profiler, tmp_path, monkeypatch):
    """The profiler spans on the serving path change nothing they observe:
    with no profiler (the default) and under a live ``jax.profiler``
    capture alike, the golden workload answers as recorded and the whole
    telemetry equals a plain run's; with no profiler nothing is written."""
    import jax

    make_engine(clock=FakeClock()).submit(_golden_workload())   # warm up
    ref = make_engine(clock=FakeClock())
    ref.submit(_golden_workload())
    monkeypatch.chdir(tmp_path)
    eng = make_engine(clock=FakeClock())
    with (jax.profiler.trace(str(tmp_path / "profile")) if profiler
          else contextlib.nullcontext()):
        got = eng.submit(_golden_workload())
    assert _golden_payload(eng, got) == json.loads(GOLDEN.read_text())
    assert eng.telemetry() == ref.telemetry()
    assert sorted(os.listdir(tmp_path)) == (["profile"] if profiler else [])


# ------------------------------------------------------- profiler spans
DEVICE_BACKENDS = ("colskip", "radix_topk", "jaxsort")
ROUND_TRIP = tuple(f"sortserve.execute.{step}"
                   for step in ("put", "launch", "wait", "fetch"))


def _profiled_spans(tmp_path, run) -> list[tuple[str, int, int, dict]]:
    """``run()`` under a ``jax.profiler`` capture: every ``sortserve.*``
    host event as (name, start_ns, end_ns, stats)."""
    import jax
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=options):
        run()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                           dict(e.stats)) for e in line.events
                          if e.name.startswith("sortserve.")]
    return spans


def test_profiler_spans_cover_every_tile_of_the_serving_path(tmp_path):
    eng = make_engine(FakeClock())
    session = eng.begin()
    # colskip up to the 128-wide simulation cap, jaxsort past it, radix
    # selection for top-k
    reqs = (reqs_of([16, 30, 9, 64, 40]) + reqs_of([200, 256], seed=1)
            + [SortRequest(op="topk", payload=r.payload, k=4)
               for r in reqs_of([50, 100, 128], seed=2)])
    spans = _profiled_spans(tmp_path, lambda: (
        session.feed(reqs[:6]), session.feed(reqs[6:], flush=True),
        session.drain()))
    names = {name for name, *_ in spans}
    assert {"sortserve.feed", "sortserve.bucket", "sortserve.schedule",
            "sortserve.scatter"} <= names
    executes = [sp for sp in spans if sp[0] == "sortserve.execute"]
    assert {sp[3]["backend"] for sp in executes} == set(DEVICE_BACKENDS)
    assert len({sp[3]["tile"] for sp in executes}) == len(executes) \
        == eng.telemetry()["batcher"]["tiles"]
    scattered = {sp[3]["tile"] for sp in spans
                 if sp[0] == "sortserve.scatter"}
    for _, start, end, stats in executes:
        (run,) = [sp for sp in spans if sp[0] == "sortserve.execute.run"
                  and sp[3]["tile"] == stats["tile"]]
        assert start <= run[1] <= run[2] <= end
        steps = [sp for sp in spans if sp[0] in ROUND_TRIP
                 and sp[3]["tile"] == stats["tile"]]
        # one round trip per tile, in order, inside its backend call
        assert [sp[0] for sp in sorted(steps, key=lambda sp: sp[1])] \
            == list(ROUND_TRIP)
        assert all(run[1] <= s <= e <= run[2] for _, s, e, _ in steps)
        fetch = next(sp for sp in steps if sp[0].endswith("fetch"))
        assert fetch[3]["arrays"] == {"colskip": 4, "radix_topk": 3,
                                      "jaxsort": 1}[stats["backend"]]
        assert fetch[3]["copies"] == 1
        assert stats["rows"] == 4 and stats["tile"] in scattered


@pytest.mark.parametrize("backend", DEVICE_BACKENDS + ("colskip_mesh",))
def test_every_executor_carries_its_backend_name(backend):
    from repro.sortserve.backends import resolve_backends

    (be,) = resolve_backends([backend])
    if backend == "colskip_mesh":
        fn, _ = be._mesh_executor(4, 16, 16)
    elif backend == "colskip":
        from repro.sortserve.backends import _compiled_colskip
        fn, _ = _compiled_colskip(4, 16, be.w, be.state_k, None,
                                  be.use_pallas, be.interpret, be.packed)
    else:
        fn, _ = be._executor(4, 16, 3, False) if backend == "radix_topk" \
            else be._executor(4, 16)
    hlo = fn.as_text()
    assert hlo.startswith(f"HloModule jit_{backend},")
    assert f'op_name="jit({backend})/{backend}/' in hlo


# -------------------------------------------------- wall-clock queue wait
def _ticking_engine(clock):
    """One backend whose every run takes one second of the fake clock."""
    eng = make_engine(clock, backends=("numpy",))
    be = eng.policy.by_name["numpy"]
    run = be.run

    def slow(tile):
        clock.tick(1.0)
        return run(tile)
    be.run = slow
    return eng


def _flushed_tiles(eng, clock):
    # twelve requests at t=0, three 4-row tiles launched at t=0, 1, 2
    eng.begin().feed(reqs_of([16] * 12), flush=True)
    return 4 * (0.0 + 1.0 + 2.0), 12


def _aged_bucket(eng, clock):
    # a bucket of three closes on age at t=0.75: fed at 0, 0 and 0.25
    session = eng.begin(max_age_s=0.5)
    session.feed(reqs_of([16, 16]))
    clock.tick(0.25)
    session.feed(reqs_of([16], seed=1))
    clock.tick(0.5)
    session.poll()
    return 0.75 + 0.75 + 0.5, 3


@pytest.mark.parametrize("load", [_flushed_tiles, _aged_bucket])
def test_queue_wait_is_exact_wall_time_under_a_fake_clock(load):
    clock = FakeClock()
    eng = _ticking_engine(clock)
    want_sum, want_count = load(eng, clock)
    telem = eng.telemetry()
    assert telem["queue_wait_s"] == {"sum": want_sum, "count": want_count}
    assert telem["requests"] == want_count
    counters = eng.telemetry_snapshot().counters
    assert counters["sortserve_queue_wait_seconds_total"] == want_sum
    assert counters["sortserve_queue_waits_total"] == want_count


# ------------------------------------------ colskip plane-step counter
def test_colskip_plane_steps_are_counted_exported_and_fetched_in_four(
        tmp_path):
    """Tiles on the colskip kernel add the plane steps they walked and the
    fixed loop's slots to the engine's counter and its OpenMetrics export,
    and the count rides in an output the tile already fetched: four
    arrays, as before, read back in one device->host copy, which the
    engine's readback counter and its export count."""
    from repro.core.datasets import make_dataset
    eng = make_engine(FakeClock(), backends=("colskip",), use_pallas=True,
                      interpret=True)
    reqs = [SortRequest(op="sort", payload=make_dataset(
        "kruskal", 64, 32, seed=i).astype(np.uint32)) for i in range(8)]
    spans = _profiled_spans(tmp_path, lambda: eng.submit(reqs))
    telem = eng.telemetry()
    steps, tiles = telem["colskip_plane_steps"], telem["batcher"]["tiles"]
    # four-row tiles, one kernel program each, w = 32, a full sort of 64
    assert steps["slots"] == tiles * 32 * 64
    assert 0 < steps["run"] < 0.6 * steps["slots"]
    counters = eng.telemetry_snapshot().counters
    assert counters["sortserve_colskip_plane_steps_total"] == steps["run"]
    assert counters["sortserve_colskip_plane_slots_total"] == steps["slots"]
    fetches = [sp[3] for sp in spans if sp[0] == "sortserve.execute.fetch"]
    assert len(fetches) == tiles and all(f["arrays"] == 4 for f in fetches)
    assert all(f["copies"] == 1 for f in fetches)
    assert telem["readback"] == {"tiles": tiles, "copies": tiles}
    assert counters["sortserve_readback_tiles_total"] == tiles
    assert counters["sortserve_readback_copies_total"] == tiles


# ------------------------------------------------------- chrome trace JSON
def test_export_is_valid_chrome_trace():
    eng, tracer = traced_engine(FakeClock())
    eng.submit(reqs_of([8, 16, 40, 80, 128, 9]))
    doc = eng.dump_trace("/dev/null")
    assert set(doc) == {"traceEvents", "displayTimeUnit", "otherData"}
    by_rid: dict[int, dict] = {}
    for ev in doc["traceEvents"]:
        assert ev["ph"] in ("X", "i", "M")
        if ev["ph"] == "X":
            assert ev["ts"] >= 0 and ev["dur"] >= 0
            assert ev["pid"] in (1, 2)
            if ev["pid"] == 1:
                spans = by_rid.setdefault(ev["tid"], {})
                spans[ev["name"].split()[0]] = ev
    for rid, spans in by_rid.items():
        outer = spans["request"]
        for name in ("bucket", "admit", "execute", "scatter"):
            child = spans[name]
            assert outer["ts"] <= child["ts"]
            assert child["ts"] + child["dur"] <= \
                outer["ts"] + outer["dur"] + 1e-6, \
                f"{name} span of rid {rid} escapes its request span"
    # bank tracks are labelled from the pool
    names = [ev["args"]["name"] for ev in doc["traceEvents"]
             if ev["ph"] == "M" and ev["name"] == "thread_name"]
    assert "bank 0" in names and "scheduler events" in names


def test_tracer_ring_is_bounded():
    tracer = Tracer(capacity=8)
    eng = make_engine(FakeClock(), tracer=tracer, backends=("numpy",))
    eng.submit(reqs_of([16] * 24, seed=5))
    assert tracer.span_count() == 8            # ring keeps the newest only
    assert len(tracer.tiles) <= 8 and len(tracer.events) <= 8


# ------------------------------------------------------- metric primitives
def test_windowed_counter_slides_and_restores():
    c = WindowedCounter(window_s=10.0)
    c.add(0.0, 2)
    c.add(5.0, 3)
    assert c.total(5.0) == 5 and c.all_time == 5
    snap = c.snapshot()
    c.add(20.0, 7)
    assert c.total(20.0) == 7                  # first two slid out
    assert c.all_time == 12
    c.restore(snap)
    assert c.total(5.0) == 5 and c.all_time == 5
    assert c.rate(5.0) == pytest.approx(1.0)   # 5 events over 5s of stream


def test_log_histogram_quantiles_are_exact_in_window():
    h = LogBucketHistogram(window_s=100.0, lo=1e-3)
    for i, v in enumerate([0.1, 0.2, 0.3, 0.4, 1000.0]):
        h.observe(float(i), v)
    assert h.percentile(4.0, 50) == 0.3
    assert h.percentile(4.0, 99) == 1000.0
    assert h.mean(4.0) == pytest.approx(200.2)
    assert h.all_time_count == 5
    lo, hi = h.bucket_bounds(1)
    assert lo == 1e-3 and hi == 2e-3


def test_engine_window_section_uses_fake_clock():
    clock = FakeClock()
    eng = make_engine(clock, metrics_window_s=10.0)
    eng.submit(reqs_of([16] * 8))
    w = eng.telemetry()["window"]
    assert w["requests"] == 8 and w["tiles"] == 2
    assert w["shed"] == 0 and w["shed_rate"] == 0.0
    assert w["queue_depth"] == 0
    assert 0.0 < w["occupancy"] <= 1.0
    clock.tick(11.0)                           # everything slides out
    w = eng.telemetry()["window"]
    assert w["requests"] == 0 and w["tiles"] == 0
    assert w["window_s"] == 10.0


def test_failed_submit_rolls_back_window_and_calibration():
    clock = FakeClock()
    eng = make_engine(clock)
    eng.submit(reqs_of([16] * 4))
    before = eng.telemetry()

    def boom(tile):
        raise RuntimeError("injected execute failure")

    eng.policy.by_name["numpy"].run = boom
    bad = [SortRequest(op="sort", payload=r.payload, backend="numpy")
           for r in reqs_of([16] * 4, seed=9)]
    with pytest.raises(RuntimeError, match="injected"):
        eng.submit(bad)
    after = eng.telemetry()
    assert after["window"] == before["window"]
    assert after["calibration"] == before["calibration"]


# ------------------------------------------------------------- calibration
def test_calibration_table_ratio():
    t = CalibrationTable(clock_hz=1e6)          # 1 cycle == 1 us
    t.record("colskip", 64, wall_s=2.0, modeled_cycles=1e6)
    t.record("colskip", 64, wall_s=2.0, modeled_cycles=1e6)
    assert t.ratio("colskip", 64) == pytest.approx(2.0)
    table = t.table()
    cell = table["colskip"]["64"]
    assert cell["tiles"] == 2
    assert cell["modeled_s"] == pytest.approx(2.0)
    assert cell["ratio"] == pytest.approx(2.0)
    assert t.ratio("nosuch", 64) is None


def test_warm_executions_populate_engine_calibration():
    eng = make_engine()                         # real clock: wall_s > 0
    for i in range(2):                          # 2nd round runs warm
        eng.submit(reqs_of([32] * 4, seed=10 + i))
    calib = eng.telemetry()["calibration"]
    assert calib, "no warm execution produced a calibration row"
    for backend, widths in calib.items():
        for width, cell in widths.items():
            assert cell["tiles"] >= 1
            assert cell["modeled_s"] > 0
            assert cell["ratio"] == pytest.approx(
                cell["wall_s"] / cell["modeled_s"])
