"""Fault-tolerant serving: injection, quarantine, verified retry.

The ISSUE-8 acceptance surface:

  * **faults-off is free** — an engine with the fault layer constructed but
    disabled reproduces the recorded seed-21 golden telemetry bit-exactly,
    and its exported trace is byte-identical to a ``faults=None`` engine's;
  * **verified retry** — the result guard rejects corrupted tiles, faulted
    executions re-arrive on a bounded virtual-time backoff, sinks still
    fire exactly once, and repeated in-memory failures escalate to a
    software fallback backend;
  * **quarantine lifecycle** — error scoring -> quarantine (out of
    ``try_place`` eligibility) -> probation probes -> reinstatement, with
    doubled duration on a failed probe;
  * **end-to-end chaos** — a seeded plan with a dead bank, a stuck lane, a
    slow bank, and transient errors serves every request exactly once with
    oracle-correct values, and the recovery story lands in ``fault.*``
    telemetry and RETRY/QUARANTINE trace instants;
  * **state discipline** — submit rollback restores quarantine state,
    injector RNG position, and every fault counter (hypothesis sweep);
  * **front-door backoff** — shed requests resubmit on the deterministic
    capped-exponential :class:`BackoffPolicy` schedule.
"""

import json
from types import SimpleNamespace

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from test_continuous import FakeClock, GOLDEN, _digest, make_engine

from repro.launch.sortserve import check_against_oracle, make_workload
from repro.sortserve import (
    AsyncSortServe,
    BackoffPolicy,
    BankDeadError,
    BankHealth,
    BankPool,
    ContinuousScheduler,
    CorruptResultError,
    FaultError,
    FaultInjector,
    FaultPlan,
    RecoveryPolicy,
    SortRequest,
    TransientFaultError,
    WatermarkPolicy,
    verify_tile_result,
)
from repro.sortserve.batcher import Tile
from repro.sortserve.faults import (
    BANK_HEALTHY,
    BANK_PROBATION,
    BANK_QUARANTINED,
)

SEED21 = dict(n_requests=40, min_len=8, max_len=128, seed=21)

# a plan that *names* every fault type but is disabled: the layer must be
# constructed and still contribute exactly nothing
DISABLED_PLAN = FaultPlan(seed=5, transient_rate=0.5, dead_banks=(0,),
                          stuck_lanes=((1, 3, 1),), slow_banks=((2, 2.0),),
                          enabled=False)


def _tile(values, op="sort", k=None):
    data = np.asarray(values, np.uint32)
    return Tile(op=op, data=data, k=k, entries=[], pad_rows=data.shape[0])


def _raw_tile(width: int, rows: int = 4) -> Tile:
    return Tile(op="sort", data=np.zeros((rows, width), np.uint32), k=None,
                entries=[], pad_rows=rows)


def _payload(eng, reqs) -> dict:
    """The golden-comparison surface for an arbitrary engine (the same
    digest schema ``tests/golden/continuous_telemetry.json`` records)."""
    got = eng.submit(reqs)
    telem = eng.telemetry()
    banks = telem["scheduler"]["banks"]
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
            "bank_totals": [sum(b["tiles_served"] for b in banks),
                            sum(b["rows_served"] for b in banks),
                            sum(b["busy_cycles"] for b in banks)],
        },
    }


# ------------------------------------------------------ faults-off golden
def test_disabled_fault_layer_is_byte_identical_to_absent():
    """Satellite 1: a traced seed-21 run with the fault layer constructed
    but disabled matches the recorded golden file bit-exactly AND exports
    a trace byte-identical to a ``faults=None`` engine's — the fault layer
    is invisible until armed."""
    import itertools

    from repro.obs import Tracer
    from repro.sortserve import request as request_mod
    docs, payloads = [], []
    # throwaway warm-up run: executor warmth is partly process-global (jit
    # caches), so both compared runs must start equally warm
    make_engine(clock=FakeClock()).submit(make_workload(**SEED21))
    for faults in (None, DISABLED_PLAN):
        # identical request ids across the two runs (global counter): the
        # trace keys rows by rid, so byte-identity needs equal numbering
        request_mod._req_counter = itertools.count(10_000)
        eng = make_engine(clock=FakeClock(), tracer=Tracer(), faults=faults)
        payloads.append(_payload(eng, make_workload(**SEED21)))
        docs.append(eng.dump_trace("/dev/null"))
    a, b = (json.dumps(p, sort_keys=True) for p in payloads)
    assert a == b
    ta, tb = (json.dumps(d, sort_keys=True) for d in docs)
    assert ta == tb                      # trace byte-identity, events included
    live = json.loads(json.dumps(payloads[1]))
    recorded = json.loads(GOLDEN.read_text())
    assert live["aggregate"] == recorded["aggregate"]
    assert live["responses"] == recorded["responses"]
    # the fault telemetry section exists (fixed shape) but recorded nothing
    ft = eng.telemetry()["fault"]
    assert ft["enabled"] is False
    assert ft["failures"] == ft["retries"] == ft["quarantines"] == 0


# ---------------------------------------------------- verification guard
def test_guard_accepts_clean_and_rejects_corruption():
    tile = _tile([[3, 1, 2, 40], [7, 5, 6, 8]])
    order = np.argsort(tile.data, axis=1).astype(np.uint32)
    clean = np.take_along_axis(tile.data, order, axis=1)
    verify_tile_result(tile, SimpleNamespace(values=clean, indices=order))

    bad_order = clean.copy()
    bad_order[0, 0], bad_order[0, 1] = bad_order[0, 1], bad_order[0, 0]
    with pytest.raises(CorruptResultError, match="not ordered"):
        verify_tile_result(tile, SimpleNamespace(values=bad_order,
                                                 indices=None))

    bad_gather = clean.copy()           # ordered, wrong gather + multiset
    bad_gather[0, 1] = bad_gather[0, 2]
    with pytest.raises(CorruptResultError, match="disagree"):
        verify_tile_result(tile, SimpleNamespace(values=bad_gather,
                                                 indices=order))
    with pytest.raises(CorruptResultError, match="permutation"):
        verify_tile_result(tile, SimpleNamespace(values=bad_gather,
                                                 indices=None))

    bad_idx = order.copy()
    bad_idx[0, 0] = 9                   # out of [0, 4)
    with pytest.raises(CorruptResultError, match="indices outside"):
        verify_tile_result(tile, SimpleNamespace(values=clean,
                                                 indices=bad_idx))

    topk = _tile([[3, 1, 2, 40]], op="topk", k=2)
    verify_tile_result(topk, SimpleNamespace(
        values=np.array([[40, 3]], np.uint32), indices=None))
    with pytest.raises(CorruptResultError, match="not ordered"):
        verify_tile_result(topk, SimpleNamespace(
            values=np.array([[3, 40]], np.uint32), indices=None))


def test_stuck_lane_injection_is_caught_and_blamed():
    """A stuck-at-1 lane corrupts exactly the bank's shard columns and the
    guard rejects the result, blaming the corrupting bank."""
    plan = FaultPlan(stuck_lanes=((0, 0, 1),))       # bank 0, bit 0 stuck 1
    inj = FaultInjector(plan)
    tile = _tile([[0, 2, 4, 6], [10, 12, 14, 16]])
    clean = np.sort(tile.data, axis=1)
    result = SimpleNamespace(values=clean.copy(), indices=None, meta={})
    corrupted = inj.inject(tile, result, bank_ids=(0, 1), bank_width=2)
    assert corrupted == (0,)
    assert inj.injected["stuck"] == 1
    vals = np.asarray(result.values)
    assert np.all(vals[:, :2] & 1 == 1)              # shard 0 forced odd
    assert np.array_equal(vals[:, 2:], clean[:, 2:])  # shard 1 untouched
    with pytest.raises(CorruptResultError):
        verify_tile_result(tile, result)


def test_injector_dead_and_transient_and_slow():
    plan = FaultPlan(seed=3, transient_rate=1.0, dead_banks=(2,),
                     slow_banks=((1, 4.0),))
    inj = FaultInjector(plan)
    tile = _tile([[1, 2]])
    res = SimpleNamespace(values=np.array([[1, 2]], np.uint32),
                          indices=None, meta={})
    with pytest.raises(BankDeadError) as ei:         # dead beats transient
        inj.inject(tile, res, bank_ids=(2, 1), bank_width=2)
    assert ei.value.bank_ids == (2,)
    with pytest.raises(TransientFaultError) as ei:
        inj.inject(tile, res, bank_ids=(0, 1), bank_width=2)
    assert ei.value.bank_ids == (0, 1)
    # rate-0 plan on a slow bank: annotation only, no raise
    calm = FaultInjector(FaultPlan(slow_banks=((1, 4.0),)))
    calm.inject(tile, res, bank_ids=(0, 1), bank_width=2)
    assert res.meta["fault_slow_mult"] == 4.0


# ------------------------------------------------------- health lifecycle
def test_bank_health_quarantine_probation_lifecycle():
    h = BankHealth(2, error_threshold=2, quarantine_vt=100.0,
                   probation_tiles=2, active=True)
    assert h.record_error([0], vt=0.0) == []         # score 1 < 2
    assert h.record_error([0], vt=10.0) == [0]       # quarantined
    assert h.records[0].state == BANK_QUARANTINED
    assert h.ineligible(vt=50.0) == frozenset({0})
    assert h.next_release_vt() == 110.0
    assert h.ineligible(vt=110.0) == frozenset()     # lazy release
    assert h.records[0].state == BANK_PROBATION
    probing, reinstated = h.record_ok([0, 1], vt=120.0)
    assert probing == [0] and reinstated == []
    probing, reinstated = h.record_ok([0], vt=130.0)
    assert reinstated == [0]                         # 2 clean probes
    assert h.records[0].state == BANK_HEALTHY
    assert (h.quarantines, h.probations, h.reinstated) == (1, 1, 1)

    # re-quarantine after reinstatement starts from the base duration again
    h.record_error([0], 200.0), h.record_error([0], 200.0)
    assert h.records[0].release_vt == 300.0
    h.ineligible(400.0)                              # -> probation
    assert h.record_error([0], 410.0) == [0]         # failed probe
    assert h.records[0].duration_vt == 200.0         # doubled
    assert h.records[0].release_vt == 610.0

    snap = h.snapshot()
    h.record_error([1], 700.0), h.record_error([1], 700.0)
    h.restore(snap)
    assert h.records[1].state == BANK_HEALTHY and h.records[1].errors == 0
    assert h.ineligible(500.0) == frozenset({0})


def test_try_place_excludes_quarantined_banks():
    pool = BankPool(banks=2, bank_width=32, bank_rows=4)
    assert pool.try_place(_raw_tile(16), 0, exclude=frozenset({0, 1})) is None
    pl = pool.try_place(_raw_tile(16), 1, exclude=frozenset({0}))
    assert pl is not None and set(pl.bank_ids) == {1}
    pool.retire(pl, 0)
    # an oversized tile waves over the surviving banks only
    pl = pool.try_place(_raw_tile(128), 2, exclude=frozenset({0}))
    assert pl is not None and set(pl.bank_ids) == {1} and pl.waves == 4


# ------------------------------------------------- scheduler retry path
class FlakyExec:
    """Raises FaultError for the first ``failures`` calls, then serves."""

    def __init__(self, failures: int, exc_factory=None):
        self.failures = failures
        self.calls = 0
        self.exc_factory = exc_factory or (
            lambda: TransientFaultError("injected", bank_ids=(0,)))

    def __call__(self, tile):
        self.calls += 1
        if self.calls <= self.failures:
            raise self.exc_factory()
        return SimpleNamespace(cycles=np.full(tile.shape[0], 10), meta={})


def _sched(banks=2, **kw):
    pool = BankPool(banks=banks, bank_width=32, bank_rows=4)
    health = BankHealth(banks, active=True, **kw.pop("health_kw", {}))
    return ContinuousScheduler(pool, health=health, **kw), pool, health


def test_scheduler_retries_fault_then_sink_fires_exactly_once():
    sched, pool, _ = _sched(recovery=RecoveryPolicy(max_retries=3,
                                                    backoff_base_vt=16.0))
    ex, sunk = FlakyExec(2), []
    sched.feed([_raw_tile(16)], ex,
               sink=lambda t, r, e: sunk.append((r, e)), strict=False)
    sched.pump()
    assert ex.calls == 3                             # 2 faults + success
    assert len(sunk) == 1 and sunk[0][1] is None     # exactly once, served
    assert sched.stats.fault_failures == 2
    assert sched.stats.retries == 2
    assert sched.stats.fault_exhausted == 0
    assert sched.vt >= 16.0 + 32.0                   # backoff advanced time
    assert all(b.free_rows == b.bank_rows for b in pool.banks)


def test_scheduler_exhausts_retries_into_typed_exec_fail():
    sched, pool, health = _sched(recovery=RecoveryPolicy(max_retries=2))
    ex, sunk = FlakyExec(99), []
    sched.feed([_raw_tile(16)], ex,
               sink=lambda t, r, e: sunk.append(e), strict=False)
    sched.pump()
    assert ex.calls == 3                             # initial + 2 retries
    assert len(sunk) == 1 and isinstance(sunk[0], TransientFaultError)
    assert sched.stats.fault_exhausted == 1
    assert sched.stats.exec_failures == 1
    assert health.records[0].errors == 3             # every attempt charged
    assert all(b.free_rows == b.bank_rows for b in pool.banks)


def test_non_fault_exceptions_keep_exec_fail_semantics():
    """Only FaultError takes the retry path; a plain RuntimeError fails the
    tile immediately (the pre-existing poison contract)."""
    sched, _, _ = _sched()
    calls, sunk = [], []

    def boom(tile):
        calls.append(1)
        raise RuntimeError("not a fault")

    sched.feed([_raw_tile(16)], boom,
               sink=lambda t, r, e: sunk.append(e), strict=False)
    sched.pump()
    assert len(calls) == 1 and sched.stats.retries == 0
    assert isinstance(sunk[0], RuntimeError)


def test_quarantine_steers_placement_and_wakes_stalled_queue():
    """Errors quarantine bank 0; the next tiles place on bank 1 only.  With
    *every* bank quarantined the scheduler fast-forwards to the earliest
    release instead of deadlocking."""
    sched, pool, health = _sched(
        health_kw=dict(error_threshold=1, quarantine_vt=500.0))
    events = []
    sched.on_event = lambda kind, tile, vt, **a: events.append((kind, vt, a))
    ex, sunk = FlakyExec(1, lambda: TransientFaultError("x", bank_ids=(0,))), []
    sched.feed([_raw_tile(16)], ex,
               sink=lambda t, r, e: sunk.append(e), strict=False)
    sched.pump()
    assert sunk == [None]
    assert [k for k, _, _ in events].count("quarantine") == 1
    assert [k for k, _, _ in events].count("retry") == 1
    # bank 0 is out: new placements go to bank 1
    pl = pool.try_place(_raw_tile(16), 99,
                        exclude=health.ineligible(sched.vt))
    assert set(pl.bank_ids) == {1}
    pool.retire(pl, 0)
    # now quarantine bank 1 too and feed: the queue can only stall until
    # the earliest release, then serves on the probation bank
    health.record_error([1], sched.vt)
    assert health.ineligible(sched.vt) == frozenset({0, 1})
    ok = FlakyExec(0)
    sched.feed([_raw_tile(16)], ok,
               sink=lambda t, r, e: sunk.append(e), strict=False)
    sched.pump()
    assert sunk == [None, None]
    assert any(k == "probe" for k, _, _ in events)
    assert sched.vt >= min(r.release_vt for r in health.records)


def test_slow_bank_stretches_service_time_not_cycle_credit():
    """A slow-bank plan (no errors) leaves values and bank-cycle credit
    identical to a faults-off run; only virtual service time stretches."""
    slow = FaultPlan(slow_banks=tuple((b, 4.0) for b in range(4)))
    reqs = make_workload(8, min_len=8, max_len=64, seed=9)
    base = make_engine(clock=FakeClock())
    eng = make_engine(clock=FakeClock(), faults=slow)
    a = [r for r in base.submit(reqs)]
    b = [r for r in eng.submit(reqs)]
    for ra, rb in zip(a, b):
        assert np.array_equal(ra.values, rb.values)
        assert ra.cycles == rb.cycles
    tb, te = base.telemetry(), eng.telemetry()
    busy = lambda t: sum(x["busy_cycles"] for x in t["scheduler"]["banks"])
    assert busy(tb) == busy(te)                      # credit conserved
    assert te["scheduler"]["continuous"]["makespan_vt"] > \
        tb["scheduler"]["continuous"]["makespan_vt"]
    assert te["fault"]["injected"]["slow"] > 0


# -------------------------------------------------------- engine chaos e2e
def test_chaos_run_every_request_exactly_once_and_oracle_correct():
    """Acceptance: a seeded plan with a permanently dead bank, a stuck
    lane, a slow bank, and >=5% transient errors — every request resolves
    exactly once with oracle-correct values, and the recovery story lands
    in fault telemetry and RETRY/QUARANTINE trace instants."""
    from repro.obs import Tracer
    plan = FaultPlan(seed=7, transient_rate=0.1, dead_banks=(3,),
                     stuck_lanes=((0, 5, 1),), slow_banks=((1, 4.0),))
    eng = make_engine(clock=FakeClock(), tracer=Tracer(), faults=plan)
    reqs = make_workload(**SEED21)
    got = eng.submit(reqs)
    assert len(got) == len(reqs)
    ids = [r.request_id for r in got]
    assert sorted(ids) == sorted(q.request_id for q in reqs)  # exactly once
    by_id = {r.request_id: r for r in got}
    assert all(check_against_oracle(q, by_id[q.request_id]) for q in reqs)
    ft = eng.telemetry()["fault"]
    assert ft["enabled"] is True
    assert ft["failures"] > 0 and ft["retries"] > 0
    assert ft["quarantines"] > 0
    assert ft["guard_failures"] > 0                  # stuck lane was caught
    assert ft["injected"]["dead"] > 0 and ft["injected"]["slow"] > 0
    assert ft["exhausted"] == 0                      # nothing gave up
    assert ft["per_bank"]["3"]["quarantines"] > 0    # the dead bank left
    names = {e["name"] for e in eng.dump_trace("/dev/null")["traceEvents"]}
    assert {"RETRY", "QUARANTINE"} <= names


def test_escalation_serves_from_software_fallback():
    """One bank, permanently dead: after ``escalate_after`` failed attempts
    the tile is served by a non-target backend — correct values, fallback
    counted, nothing exhausted."""
    plan = FaultPlan(dead_banks=(0,),
                     recovery=RecoveryPolicy(max_retries=6, escalate_after=2,
                                             backoff_base_vt=8.0))
    eng = make_engine(banks=1, faults=plan)
    reqs = make_workload(6, min_len=8, max_len=64, seed=4)
    got = eng.submit(reqs)
    by_id = {r.request_id: r for r in got}
    assert all(check_against_oracle(q, by_id[q.request_id]) for q in reqs)
    ft = eng.telemetry()["fault"]
    assert ft["fallbacks"] > 0
    assert ft["exhausted"] == 0
    assert all(r.backend in ("jaxsort", "numpy") for r in got
               if r.backend is not None) or ft["failures"] > 0


def test_no_fallback_available_exhausts_into_typed_failure():
    """Every backend in the target set and every bank dead: retries exhaust
    and the request surfaces the typed FaultError via take_failures."""
    plan = FaultPlan(dead_banks=(0,),
                     targets=frozenset({"colskip", "radix_topk", "jaxsort",
                                        "numpy"}),
                     recovery=RecoveryPolicy(max_retries=2))
    eng = make_engine(banks=1, faults=plan)
    s = eng.begin(strict=False)
    got = s.feed(make_workload(3, min_len=8, max_len=32, seed=2), flush=True)
    got += s.drain()
    fails = s.take_failures()
    assert not got and len(fails) == 3
    assert all(isinstance(exc, BankDeadError) for _, exc, _ in fails)
    assert eng.telemetry()["fault"]["exhausted"] > 0


def test_strict_submit_fault_rolls_back_fault_state_and_frees_banks():
    """A strict submit that exhausts retries raises the typed fault after
    full rollback: fault telemetry (quarantines, RNG, counters) restored,
    banks free, pending backoff re-arrivals aborted."""
    plan = FaultPlan(seed=1, transient_rate=1.0, targets=frozenset({"numpy"}),
                     recovery=RecoveryPolicy(max_retries=1,
                                             backoff_base_vt=8.0))
    eng = make_engine(backends=("numpy",), faults=plan)
    before = json.dumps(eng.telemetry()["fault"], sort_keys=True)
    rng_before = json.dumps(eng._injector.snapshot()["rng"], default=str,
                            sort_keys=True)
    with pytest.raises(TransientFaultError):
        eng.submit(make_workload(4, min_len=8, max_len=32, seed=6))
    assert json.dumps(eng.telemetry()["fault"], sort_keys=True) == before
    assert json.dumps(eng._injector.snapshot()["rng"], default=str,
                      sort_keys=True) == rng_before
    assert all(b.free_rows == b.bank_rows for b in eng.pool.banks)
    assert not eng.scheduler._queue
    assert all(p.cancelled for _, _, k, p in eng.scheduler._heap if k == 0)


# --------------------------------------------------- front-door backoff
def test_backoff_policy_schedule_and_validation():
    pol = BackoffPolicy(base_s=0.01, factor=2.0, cap_s=0.05, max_attempts=6)
    assert [pol.delay_s(n) for n in range(1, 6)] == \
        [0.01, 0.02, 0.04, 0.05, 0.05]
    with pytest.raises(ValueError):
        BackoffPolicy(base_s=0.0)
    with pytest.raises(ValueError):
        BackoffPolicy(factor=0.5)
    with pytest.raises(ValueError):
        BackoffPolicy(max_attempts=0)


def test_async_backoff_resubmits_shed_requests_until_served():
    """Satellite 2: requests shed under overload are resubmitted by the
    front door on the BackoffPolicy schedule and eventually all serve —
    no caller-visible RetryAfter, no silent drops."""
    import time

    clock = FakeClock()
    eng = make_engine(clock=clock, backends=("numpy",), tile_rows=2,
                      banks=2, bank_rows=2,
                      admission=WatermarkPolicy(high_watermark=1, shed=True,
                                                retry_after_vt=50.0))
    server = AsyncSortServe(eng, max_batch=16, max_wait_ms=50.0, clock=clock,
                            retry_policy=BackoffPolicy(base_s=1e-3,
                                                       cap_s=0.01,
                                                       max_attempts=12))
    # six distinct widths: six open buckets, all stamped at t=0, that age
    # out together on one tick of the fake clock once the collector holds
    # them all, so it dispatches them as ONE six-tile feed — with 2 banks
    # and high_watermark=1 at least one tile is deterministically shed, and
    # the shed requests ride the backoff schedule back in alone
    reqs = [SortRequest("sort", np.arange(w, dtype=np.uint32))
            for w in (8, 16, 32, 64, 128, 8)]
    futures = [server.submit(q) for q in reqs]
    give_up = time.monotonic() + 120
    while server._q.qsize():            # the collector has taken all six
        assert time.monotonic() < give_up
        time.sleep(1e-3)
    clock.tick(0.2)                     # every bucket crosses max_wait
    while not all(f.done() for f in futures):
        assert time.monotonic() < give_up, "shed requests never served"
        time.sleep(2e-3)
        clock.tick(0.01)                # the backoff schedule's clock
    got = [f.result() for f in futures]
    server.close()
    assert all(check_against_oracle(q, r) for q, r in zip(reqs, got))
    # the engine really shed (so the backoff path ran), yet every caller
    # got a served response
    assert eng.telemetry()["scheduler"]["continuous"]["shed"] > 0


# --------------------------------------------------------- property sweep
@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**16),
       rate=st.floats(0.0, 0.25),
       dead=st.booleans(),
       stuck=st.booleans())
def test_random_fault_plans_exactly_once_and_rollback(seed, rate, dead,
                                                      stuck):
    """Hypothesis sweep over random fault plans (targets the numpy backend
    so every example is compile-free): every request resolves exactly once
    — an oracle-correct response or a typed failure, never both, never
    neither — banks end free, and fault state survives a snapshot/restore
    round trip."""
    plan = FaultPlan(
        seed=seed, transient_rate=rate,
        dead_banks=(3,) if dead else (),
        stuck_lanes=((0, 2, 1),) if stuck else (),
        targets=frozenset({"numpy"}),
        recovery=RecoveryPolicy(max_retries=6, backoff_base_vt=8.0))
    eng = make_engine(backends=("numpy",), faults=plan)
    reqs = make_workload(10, min_len=8, max_len=64, seed=seed + 1)
    s = eng.begin(strict=False)
    got = s.feed(reqs, flush=True) + s.drain()
    fails = s.take_failures()
    served = [r.request_id for r in got]
    failed = [q.request_id for q, _ in fails]
    assert sorted(served + failed) == sorted(q.request_id for q in reqs)
    by_id = {r.request_id: r for r in got}
    assert all(check_against_oracle(q, by_id[q.request_id])
               for q in reqs if q.request_id in by_id)
    assert all(isinstance(exc, FaultError) for _, exc in fails)
    assert all(b.free_rows == b.bank_rows for b in eng.pool.banks)
    # quarantine/probation state, injector RNG, and counters round-trip
    # through the submit-rollback snapshot
    state = eng._snapshot_state()
    fault_before = json.dumps(eng.telemetry()["fault"], sort_keys=True)
    s2 = eng.begin(strict=False)
    s2.feed(make_workload(4, min_len=8, max_len=32, seed=seed + 2),
            flush=True)
    s2.drain(), s2.take_failures()
    eng._restore_state(state)
    assert json.dumps(eng.telemetry()["fault"],
                      sort_keys=True) == fault_before
