"""sortserve subsystem: e2e oracle equality, telemetry exactness, scheduling."""

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import colskip_sort, make_dataset, multibank_colskip_sort
from repro.launch.sortserve import check_against_oracle, make_workload
from repro.sortserve import (
    AsyncSortServe,
    BankPool,
    Batcher,
    ContinuousScheduler,
    EngineConfig,
    SortRequest,
    SortServeEngine,
    encode_payload,
    pow2_bucket,
)
from repro.sortserve.batcher import PAD_ASC, PAD_DESC
from repro.sortserve.request import decode_values


def small_engine(**over):
    cfg = dict(backends=("colskip", "radix_topk", "jaxsort", "numpy"),
               tile_rows=4, min_bucket=8, banks=4, bank_width=64,
               bank_rows=4, sim_width_cap=128)
    cfg.update(over)
    return SortServeEngine(EngineConfig(**cfg))


# --------------------------------------------------------------- encoding
def test_encode_matches_to_sortable_uint_and_roundtrips():
    import jax.numpy as jnp

    from repro.core.topk import to_sortable_uint

    rng = np.random.default_rng(0)
    floats = (rng.normal(size=256) * 1e4).astype(np.float32)
    ints = rng.integers(-(1 << 31), 1 << 31, 256, dtype=np.int64).astype(np.int32)
    uints = rng.integers(0, 1 << 32, 256, dtype=np.uint64).astype(np.uint32)
    for x in (floats, ints, uints):
        ours = encode_payload(x)
        ref = np.asarray(to_sortable_uint(jnp.asarray(x)))
        assert np.array_equal(ours, ref)
        assert np.array_equal(decode_values(ours, x.dtype), x)
    halfs = rng.normal(size=64).astype(np.float16)
    assert np.array_equal(decode_values(encode_payload(halfs), np.float16), halfs)


def test_request_validation():
    with pytest.raises(ValueError):
        SortRequest("sort", np.zeros((2, 2), np.uint32))
    with pytest.raises(ValueError):
        SortRequest("topk", np.arange(4, dtype=np.uint32))          # no k
    with pytest.raises(ValueError):
        SortRequest("topk", np.arange(4, dtype=np.uint32), k=5)     # k > n
    with pytest.raises(ValueError):
        SortRequest("sort", np.arange(4, dtype=np.uint32), k=2)     # stray k
    with pytest.raises(TypeError):
        SortRequest("sort", np.arange(4, dtype=np.float64))


# ------------------------------------------------------------------ batcher
def test_batcher_pow2_buckets_fixed_tiles_and_sentinels():
    b = Batcher(tile_rows=4, min_bucket=8)
    reqs = [SortRequest("sort", np.arange(n, dtype=np.uint32))
            for n in (3, 9, 17, 17, 33)]
    reqs.append(SortRequest("topk", np.arange(20, dtype=np.uint32), k=3))
    for r in reqs:
        b.add(r)
    tiles = b.flush()
    assert b.pending() == 0
    for t in tiles:
        bb, n = t.shape
        assert bb == 4 and n == pow2_bucket(n)                  # fixed shape
        pad = PAD_DESC if t.op == "topk" else PAD_ASC
        for req, row in t.entries:
            assert np.array_equal(t.data[row, :req.n], encode_payload(req.payload))
            assert (t.data[row, req.n:] == pad).all()
        assert (t.data[len(t.entries):] == pad).all()           # pad rows
    widths = sorted(t.shape[1] for t in tiles if t.op == "sort")
    assert widths == [8, 16, 32, 64]      # 3->8; 9->16; 17,17->32; 33->64
    assert {t.k for t in tiles if t.op == "topk"} == {4}        # pow2(3)


def test_batcher_signature_hit_rate():
    b = Batcher(tile_rows=2)
    for _ in range(2):
        for i in range(4):
            b.add(SortRequest("sort", np.arange(10, dtype=np.uint32)))
        b.flush()
    # 4 tiles, all sharing one (op, B, N, k) signature -> 3 hits
    assert b.stats.tiles == 4
    assert b.stats.signature_hits == 3
    assert b.stats.hit_rate == 0.75


# ---------------------------------------------------------------- scheduler
class _CountingExec:
    def __init__(self):
        self.calls = []

    def __call__(self, tile):
        self.calls.append(tile.shape)
        return type("R", (), {"cycles": np.full(tile.shape[0], 10)})()


def test_scheduler_occupancy_drain_and_bank_telemetry():
    pool = BankPool(banks=2, bank_width=64, bank_rows=4)
    sched = ContinuousScheduler(pool)
    b = Batcher(tile_rows=4, min_bucket=8)
    for _ in range(8):                      # two (4, 128) tiles, 2 shards each
        b.add(SortRequest("sort", np.arange(100, dtype=np.uint32)))
    tiles = b.flush()
    assert [t.shape for t in tiles] == [(4, 128), (4, 128)]
    ex = _CountingExec()
    results = sched.run(tiles, ex)
    assert len(results) == 2
    # second tile could not coexist (both banks full) -> a forced drain
    assert sched.stats.drains >= 2
    telem = sched.telemetry()
    assert all(bk["tiles_served"] == 2 for bk in telem["banks"])
    assert all(bk["rows_served"] == 8 for bk in telem["banks"])
    # synchronized stepping: each shard bank charged the full tile cycles
    assert all(bk["busy_cycles"] == 2 * 4 * 10 for bk in telem["banks"])
    assert all(bk.free_rows == bk.bank_rows for bk in pool.banks)


def test_scheduler_capacity_misuse_raises_value_error():
    """Tiles taller than bank_rows get a clear error, not an assert/spin."""
    pool = BankPool(banks=2, bank_width=64, bank_rows=2)
    b = Batcher(tile_rows=4, min_bucket=8)
    b.add(SortRequest("sort", np.arange(16, dtype=np.uint32)))
    with pytest.raises(ValueError, match="bank_rows"):
        ContinuousScheduler(pool).run(b.flush(), _CountingExec())
    # same contract on the oversized (wave) path: width forces 8 shards > 2
    pool2 = BankPool(banks=2, bank_width=32, bank_rows=2)
    b2 = Batcher(tile_rows=4, min_bucket=8)
    b2.add(SortRequest("sort", np.arange(256, dtype=np.uint32)))
    with pytest.raises(ValueError, match="bank_rows"):
        ContinuousScheduler(pool2).run(b2.flush(), _CountingExec())


def test_scheduler_oversized_tile_runs_in_waves():
    pool = BankPool(banks=2, bank_width=32, bank_rows=4)
    sched = ContinuousScheduler(pool)
    b = Batcher(tile_rows=4, min_bucket=8)
    b.add(SortRequest("sort", np.arange(256, dtype=np.uint32)))  # 8 shards > 2
    tiles = b.flush()
    ex = _CountingExec()
    sched.run(tiles, ex)
    assert sched.stats.oversized_tiles == 1
    assert sched.stats.oversized_waves == 4                     # ceil(8/2)
    # 8 % 2 == 0: every wave is full, so nothing frees early
    assert sched.stats.mid_wave_admissions == 0
    assert len(ex.calls) == 1


def _raw_tile(n_cols: int, rows: int = 4):
    """Scheduler-level tile with no requests attached (padding-only)."""
    from repro.sortserve.batcher import Tile
    return Tile(op="sort", data=np.zeros((rows, n_cols), np.uint32), k=None,
                entries=[], pad_rows=rows)


def test_scheduler_mid_wave_admission_on_partial_final_wave():
    """A queued tile is admitted the moment the final partial wave frees
    banks, instead of waiting for the oversized tile to fully retire."""
    pool = BankPool(banks=3, bank_width=32, bank_rows=4)
    sched = ContinuousScheduler(pool)
    # 128 cols -> 4 shards over 3 banks -> 2 waves, final wave needs 1 bank:
    # banks 1 and 2 idle through the last wave and admit the queued tile
    big, small = _raw_tile(128), _raw_tile(32)
    results = sched.run([big, small], _CountingExec())
    assert [t.shape for t, _ in results] == [(4, 128), (4, 32)]
    assert sched.stats.oversized_waves == 2
    assert sched.stats.mid_wave_admissions == 1
    telem = sched.telemetry()
    # tail bank busy both waves (2 x 40); early-freed bank 1 took the small
    # tile during the final wave (40 + 40); bank 2 freed after one wave
    assert telem["banks"][0]["busy_cycles"] == 80
    assert telem["banks"][1]["busy_cycles"] == 80
    assert telem["banks"][2]["busy_cycles"] == 40
    assert all(bk.free_rows == bk.bank_rows for bk in pool.banks)


def test_scheduler_mid_wave_backfills_pending_queue():
    """Pending tiles (not just the held one) backfill early-freed banks."""
    pool = BankPool(banks=3, bank_width=32, bank_rows=4)
    sched = ContinuousScheduler(pool)
    tiles = [_raw_tile(128), _raw_tile(32), _raw_tile(32)]
    results = sched.run(tiles, _CountingExec())
    assert len(results) == 3
    assert sched.stats.mid_wave_admissions == 2   # both small tiles admitted
    assert all(bk.free_rows == bk.bank_rows for bk in pool.banks)


# ----------------------------------------------------------- end-to-end
def test_e2e_mixed_stream_matches_numpy_oracle():
    engine = small_engine()
    reqs = make_workload(60, min_len=8, max_len=128, seed=42)
    resps = engine.submit(reqs)
    assert len(resps) == 60
    for req, resp in zip(reqs, resps):
        assert check_against_oracle(req, resp), (req.op, req.n, resp.backend)
    telem = engine.telemetry()
    assert telem["requests"] == 60
    assert len(telem["per_backend"]) >= 2
    assert telem["column_reads"] > 0
    used_widths = {r.bucket_shape[1] for r in resps}
    assert all(w == pow2_bucket(w) for w in used_widths)


def test_colskip_backend_cycles_match_hardware_model():
    """Per-request telemetry == the numpy §III simulator, cycle-exact."""
    engine = small_engine(tile_rows=1, bank_rows=1)
    rng = np.random.default_rng(5)
    for n in (16, 64, 128):                # pow-2 lengths: no column padding
        v = make_dataset("mapreduce", n, 32, seed=3)
        payload = v.astype(np.uint32)
        req = SortRequest("sort", payload, backend="colskip")
        resp = engine.submit([req])[0]
        hw = colskip_sort(payload.astype(np.uint64), w=32, k=2)
        assert resp.backend == "colskip"
        assert resp.cycles == hw.cycles
        assert resp.column_reads == hw.column_reads
        assert np.array_equal(resp.values, hw.values.astype(np.uint32))
        # non-pow2 length: telemetry covers the padded row instead
        m = n - 3
        resp2 = engine.submit(
            [SortRequest("sort", payload[:m], backend="colskip")])[0]
        padded = np.full(n, 0xFFFFFFFF, np.uint64)
        padded[:m] = payload[:m]
        hw2 = colskip_sort(padded, w=32, k=2)
        assert resp2.cycles == hw2.cycles
        assert resp2.column_reads == hw2.column_reads
    del rng


@pytest.mark.parametrize("state_k,banks", [(1, 2), (2, 4), (3, 8), (2, 16)])
def test_multibank_vs_colskip_cycle_equality(state_k, banks):
    """§V.C regression: bank management never changes cycles or order."""
    for dataset in ("uniform", "mapreduce"):
        v = make_dataset(dataset, 128, 32, seed=13)
        mono = colskip_sort(v, 32, state_k)
        mb = multibank_colskip_sort(v, 32, state_k, banks=banks)
        assert mb.cycles == mono.cycles
        assert mb.column_reads == mono.column_reads
        assert np.array_equal(mb.order, mono.order)
        assert np.array_equal(mb.values, mono.values)


class _FakeClock:
    """Deterministic monotonically advancing clock for EMA tests."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def tick(self, dt: float) -> float:
        self.t += dt
        return self.t


def _sort_tile(n: int):
    b = Batcher(tile_rows=1, min_bucket=8)
    b.add(SortRequest("sort", np.arange(n, dtype=np.uint32)))
    return b.flush()[0]


def test_adaptive_policy_measured_ema_overrides_width_cap():
    """Measured wall-clock (fake clock) beats the static sim_width_cap: a
    width past the cap routes back to the simulator once both contenders
    are measured and the simulator is faster — and flips again when the
    measurements flip (ROADMAP adaptive cost policy)."""
    from repro.sortserve.backends import CostPolicy, resolve_backends
    clock = _FakeClock()
    policy = CostPolicy(resolve_backends(("colskip", "jaxsort")),
                        sim_width_cap=64)
    tile = _sort_tile(256)
    assert policy.choose(tile).name == "jaxsort"   # prior: beyond the cap
    for _ in range(3):                             # measured: colskip faster
        t0 = clock()
        policy.observe("jaxsort", "sort", 256, 1, clock.tick(1e-2) - t0)
        t0 = clock()
        policy.observe("colskip", "sort", 256, 1, clock.tick(1e-4) - t0)
    assert policy.choose(tile).name == "colskip"
    for _ in range(60):                            # EMA converges back
        t0 = clock()
        policy.observe("jaxsort", "sort", 256, 1, clock.tick(1e-6) - t0)
    assert policy.choose(tile).name == "jaxsort"


def test_adaptive_policy_bounded_exploration_and_static_mode():
    from repro.sortserve.backends import CostPolicy, resolve_backends
    clock = _FakeClock()
    policy = CostPolicy(resolve_backends(("colskip", "jaxsort")),
                        sim_width_cap=1024, explore_after=4)
    tile = _sort_tile(32)
    assert policy.choose(tile).name == "colskip"   # prior: under the cap
    for _ in range(4):                             # saturate the prior's pick
        t0 = clock()
        policy.observe("colskip", "sort", 32, 1, clock.tick(1e-3) - t0)
    # alternative never measured -> one exploration probe
    assert policy.choose(tile).name == "jaxsort"
    t0 = clock()
    policy.observe("jaxsort", "sort", 32, 1, clock.tick(1.0) - t0)  # slow
    assert policy.choose(tile).name == "colskip"   # measured race settled
    # adaptive off: the static prior rules no matter what was measured
    static = CostPolicy(resolve_backends(("colskip", "jaxsort")),
                        sim_width_cap=1024, adaptive=False, explore_after=1)
    for _ in range(8):
        static.observe("colskip", "sort", 32, 1, 1.0)
    assert static.choose(tile).name == "colskip"


def test_adaptive_policy_ema_keys_separate_k():
    """kmin EMAs are per-k: the simulator's cost scales with the drain
    count, so a fast k=1 measurement must not route a k=128 tile."""
    from repro.sortserve.backends import CostPolicy, resolve_backends
    policy = CostPolicy(resolve_backends(("colskip", "jaxsort")),
                        sim_width_cap=64)
    for _ in range(3):                       # k=1 race: colskip wins
        policy.observe("colskip", "kmin", 256, 1, 1e-5, k=1)
        policy.observe("jaxsort", "kmin", 256, 1, 1e-3, k=1)
    assert policy.measured_s_per_row("colskip", "kmin", 256, k=1) is not None
    assert policy.measured_s_per_row("colskip", "kmin", 256, k=128) is None
    b = Batcher(tile_rows=1, min_bucket=8)
    b.add(SortRequest("kmin", np.arange(256, dtype=np.uint32), k=128))
    big_k = b.flush()[0]
    assert big_k.k == 128
    # unmeasured k=128 signature keeps the prior (jaxsort past the cap)
    assert policy.choose(big_k).name == "jaxsort"


def test_cli_rejects_mesh_with_local_engine_flags():
    """--use_pallas/--interpret only reach the local colskip engine; with
    --mesh they would be silently dropped, so the CLI refuses."""
    from repro.launch.sortserve import main
    with pytest.raises(SystemExit):
        main(["--mesh", "--use_pallas", "on", "--requests", "1"])
    with pytest.raises(SystemExit):
        main(["--mesh", "--interpret", "on", "--requests", "1"])


def test_engine_config_rejects_mesh_with_local_engine_flags():
    """Same contract one layer down, for programmatic callers."""
    with pytest.raises(ValueError, match="mesh"):
        EngineConfig(backends=("colskip_mesh",), mesh=True, use_pallas=True)
    with pytest.raises(ValueError, match="mesh"):
        EngineConfig(backends=("colskip_mesh",), mesh=True, interpret=False)


def test_engine_feeds_policy_ema_with_injected_clock():
    """The engine measures tile executions on its (injectable) clock and
    feeds the routing EMA — but only warm ones: a cold run's wall is
    compile-dominated and would poison the comparison."""
    from repro.sortserve.backends import EXECUTOR_CACHE
    EXECUTOR_CACHE.clear()
    clock = _FakeClock()
    engine = SortServeEngine(EngineConfig(
        backends=("colskip",), tile_rows=4, min_bucket=8, banks=4,
        bank_width=64, bank_rows=4, sim_width_cap=128, cache_size=0),
        clock=clock)
    engine.submit([SortRequest("sort", np.arange(16, dtype=np.uint32))])
    assert engine.policy.measured_s_per_row("colskip", "sort", 16) is None
    engine.submit([SortRequest("sort", np.arange(16, dtype=np.uint32)[::-1]
                               .copy())])
    assert engine.policy.measured_s_per_row("colskip", "sort", 16) is not None


def test_adaptive_policy_never_probes_simulator_far_past_cap():
    """Exploration toward the O(N*w)-per-output simulator is width-bounded:
    beyond 2x the cap the probe would stall the engine for exactly the
    pathological case the cap exists to prevent."""
    from repro.sortserve.backends import CostPolicy, resolve_backends
    policy = CostPolicy(resolve_backends(("colskip", "jaxsort")),
                        sim_width_cap=64, explore_after=2)
    wide = _sort_tile(512)                         # 8x the cap
    for _ in range(8):
        policy.observe("jaxsort", "sort", 512, 1, 1e-3)
    assert policy.choose(wide).name == "jaxsort"   # no probe: too far past cap
    near = _sort_tile(128)                         # within 2x the cap
    for _ in range(8):
        policy.observe("jaxsort", "sort", 128, 1, 1e-3)
    assert policy.choose(near).name == "colskip"   # probe allowed


def test_executor_cache_warm_hit_on_repeated_signature():
    """A second tile with the same (op, B, N, k, flags) signature runs on
    the warm compiled executor — no new compile, a cache hit."""
    from repro.sortserve.backends import EXECUTOR_CACHE
    engine = small_engine(cache_size=0)
    engine.submit([SortRequest("sort", np.arange(32, dtype=np.uint32))])
    h1, m1, _ = EXECUTOR_CACHE.counters()
    engine.submit([SortRequest("sort",
                               np.arange(32, dtype=np.uint32)[::-1].copy())])
    h2, m2, _ = EXECUTOR_CACHE.counters()
    assert m2 == m1                     # same signature: nothing recompiled
    assert h2 == h1 + 1
    ec = engine.telemetry()["executor_cache"]
    assert ec["hits"] >= 1 and ec["hit_rate"] > 0


def test_cost_policy_routing():
    engine = small_engine(sim_width_cap=64)
    rng = np.random.default_rng(0)
    r_narrow = SortRequest("sort", rng.integers(0, 99, 32, np.int64).astype(np.uint32))
    r_wide = SortRequest("sort", rng.integers(0, 99, 128, np.int64).astype(np.uint32))
    r_topk = SortRequest("topk", rng.normal(size=64).astype(np.float32), k=4)
    narrow, wide, tk = engine.submit([r_narrow, r_wide, r_topk])
    assert narrow.backend == "colskip"        # within the simulation cap
    assert wide.backend == "jaxsort"          # beyond it
    assert tk.backend == "radix_topk"         # selection op


def test_hinted_requests_never_coalesce_with_unhinted():
    """A hint routes only its own request; co-submitted same-shape requests
    keep policy routing (hints are part of the bucket key)."""
    engine = small_engine(sim_width_cap=64)
    payload = np.arange(32, dtype=np.uint32)
    hinted = SortRequest("sort", payload, backend="numpy")
    plain = SortRequest("sort", payload.copy())
    r_hint, r_plain = engine.submit([hinted, plain])
    assert r_hint.backend == "numpy"
    assert r_plain.backend == "colskip"


def test_unservable_op_rejected_at_ingress():
    """A request no enabled backend can serve fails before any tile runs."""
    engine = small_engine(backends=("radix_topk",))
    good = SortRequest("topk", np.arange(16, dtype=np.uint32), k=2)
    bad = SortRequest("sort", np.arange(16, dtype=np.uint32))
    with pytest.raises(ValueError, match="no enabled backend"):
        engine.submit([good, bad])
    assert engine.telemetry()["requests"] == 0      # nothing half-executed


def test_failed_batch_rolls_back_all_telemetry():
    """A mid-batch failure leaves every telemetry section as it was.

    The compiled-executor cache is exempt: it is process-global warm-compile
    state (the AOT analogue of the jit cache), and an executable built for a
    tile that later failed stays warm for the retry by design."""
    engine = small_engine()
    engine.submit(make_workload(8, min_len=8, max_len=64, seed=11))
    before = engine.telemetry()
    bad = SortRequest("sort", np.arange(16, dtype=np.uint32), backend="numpy")
    # poison the policy so execution (not ingress) fails mid-batch
    engine.policy.by_name["numpy"].run = None
    with pytest.raises(TypeError):
        engine.submit([SortRequest("sort", np.arange(16, dtype=np.uint32)),
                       bad])
    after = engine.telemetry()
    before.pop("executor_cache"), after.pop("executor_cache")
    # sliding-window rates divide by the wall clock at read time, so the
    # two reads can't be compared whole — the windowed *counts* must roll
    # back exactly
    win_before, win_after = before.pop("window"), after.pop("window")
    for key in ("requests", "tiles", "shed", "failed"):
        assert win_after[key] == win_before[key]
    assert after == before


def test_backend_hint_and_unknown_backend():
    engine = small_engine(backends=("numpy",))
    req = SortRequest("sort", np.arange(8, dtype=np.uint32), backend="colskip")
    with pytest.raises(KeyError):
        engine.submit([req])
    resp = engine.submit([SortRequest("sort", np.arange(8, dtype=np.uint32),
                                      backend="numpy")])[0]
    assert resp.backend == "numpy"


def test_verify_mode_flags_no_failures_on_good_backends():
    engine = small_engine(verify=True)
    reqs = make_workload(24, min_len=8, max_len=64, seed=7)
    engine.submit(reqs)
    assert engine.telemetry()["verify_failures"] == 0


def test_async_wrapper_matches_sync():
    sync = small_engine()
    reqs = make_workload(12, min_len=8, max_len=64, seed=9)
    expected = {q.request_id: r for q, r in zip(reqs, sync.submit(reqs))}

    server = AsyncSortServe(small_engine(), max_batch=8, max_wait_ms=20.0)
    futures = [server.submit(q) for q in reqs]
    got = [f.result(timeout=120) for f in futures]
    server.close()
    for q, resp in zip(reqs, got):
        exp = expected[q.request_id]
        assert resp.backend == exp.backend
        if exp.values is not None:
            assert np.array_equal(resp.values, exp.values)
        if exp.indices is not None:
            assert np.array_equal(resp.indices, exp.indices)


def test_async_bad_request_does_not_fail_neighbours():
    """One invalid co-batched request fails alone; neighbours still serve."""
    server = AsyncSortServe(small_engine(backends=("numpy",)),
                            max_batch=4, max_wait_ms=50.0)
    good = SortRequest("sort", np.arange(16, dtype=np.uint32))
    bad = SortRequest("sort", np.arange(16, dtype=np.uint32), backend="colskip")
    f_good, f_bad = server.submit(good), server.submit(bad)
    server.close()
    assert check_against_oracle(good, f_good.result(timeout=60))
    with pytest.raises(KeyError):
        f_bad.result(timeout=60)


def test_async_cancelled_future_does_not_kill_collector():
    server = AsyncSortServe(small_engine(), max_batch=2, max_wait_ms=30.0)
    doomed = server.submit(SortRequest("sort", np.arange(8, dtype=np.uint32)))
    doomed.cancel()
    good = SortRequest("sort", np.arange(8, dtype=np.uint32))
    fut = server.submit(good)
    assert check_against_oracle(good, fut.result(timeout=60))
    server.close()                       # would hang if the collector died


def test_async_close_serves_already_queued_requests():
    """Every future accepted before close() is served, never left hanging."""
    server = AsyncSortServe(small_engine(), max_batch=4, max_wait_ms=1.0)
    reqs = make_workload(6, min_len=8, max_len=32, seed=3)
    futures = [server.submit(q) for q in reqs]
    server.close()
    for q, f in zip(reqs, futures):
        assert check_against_oracle(q, f.result(timeout=60))


def test_async_close_is_idempotent_and_rejects_late_submits():
    server = AsyncSortServe(small_engine(), max_batch=4, max_wait_ms=1.0)
    server.close()
    server.close()                                   # second close: no-op
    with pytest.raises(RuntimeError):
        server.submit(SortRequest("sort", np.arange(8, dtype=np.uint32)))


def test_cost_policy_over_cap_prefers_non_simulating_backend():
    """Width past sim_width_cap must not fall back onto the simulator when a
    cheap backend is enabled."""
    engine = small_engine(backends=("colskip", "numpy"), sim_width_cap=64)
    resp = engine.submit(
        [SortRequest("sort", np.arange(256, dtype=np.uint32))])[0]
    assert resp.backend == "numpy"
    # ...but the simulator still serves when it is the only option
    engine2 = small_engine(backends=("colskip",), sim_width_cap=64)
    resp2 = engine2.submit(
        [SortRequest("sort", np.arange(256, dtype=np.uint32))])[0]
    assert resp2.backend == "colskip"


def test_duplicate_request_ids_rejected_at_ingress():
    engine = small_engine()
    a = SortRequest("sort", np.arange(8, dtype=np.uint32), request_id=7)
    b = SortRequest("kmin", np.arange(8, dtype=np.uint32), k=2, request_id=7)
    with pytest.raises(ValueError, match="duplicate request_id"):
        engine.submit([a, b])
    # engine unharmed: a fresh well-formed batch still serves
    assert engine.submit([SortRequest("sort", np.arange(8, dtype=np.uint32))])


def test_backend_kwargs_cannot_shadow_engine_w_state_k():
    with pytest.raises(ValueError):
        small_engine(backend_kwargs={"colskip": {"w": 16}})
    # non-conflicting keys still pass through
    eng = small_engine(backend_kwargs={"colskip": {"use_pallas": None}})
    assert eng.policy.by_name["colskip"].w == 32


def test_telemetry_json_roundtrip(tmp_path):
    import json

    engine = small_engine()
    engine.submit(make_workload(10, min_len=8, max_len=32, seed=1))
    path = tmp_path / "telemetry.json"
    telem = engine.dump_telemetry(str(path))
    loaded = json.loads(path.read_text())
    assert loaded["requests"] == telem["requests"] == 10
    assert "bucket_hit_rate" in loaded["batcher"]
    assert len(loaded["scheduler"]["banks"]) == 4


# -------------------------------------------------------- kmin early exit
def test_kmin_early_exit_cycle_regression():
    """The colskip hardware model stops after k drains: kmin telemetry is
    cycle-exact against the numpy model run with stop_after, and strictly
    cheaper than the full sort for small k (ROADMAP follow-up)."""
    engine = small_engine(tile_rows=1, bank_rows=1, sim_width_cap=4096,
                          backends=("colskip",))
    for n in (32, 128):
        v = make_dataset("mapreduce", n, 32, seed=3)
        payload = v.astype(np.uint32)
        full = engine.submit([SortRequest("sort", payload.copy())])[0]
        for k in (1, 2, 8):
            resp = engine.submit([SortRequest("kmin", payload.copy(), k=k)])[0]
            k_pad = pow2_bucket(k, 1)          # the tile's static drain count
            hw = colskip_sort(v, w=32, k=2, stop_after=k_pad)
            assert resp.backend == "colskip"
            assert resp.cycles == hw.cycles
            assert resp.column_reads == hw.column_reads
            assert resp.cycles < full.cycles
            assert np.array_equal(resp.values,
                                  np.sort(payload, kind="stable")[:k])
    # duplicates: the partial final drain is billed one stall per extra row
    dup = np.zeros(16, np.uint64)
    r_full = colskip_sort(dup, w=32, k=2)
    r_two = colskip_sort(dup, w=32, k=2, stop_after=2)
    assert r_full.cycles - r_full.drains == r_two.cycles - r_two.drains
    assert r_two.drains == 1 and r_full.drains == 15


# ------------------------------------------------------------ result cache
def test_result_cache_hit_serves_identical_response():
    engine = small_engine()
    payload = np.arange(64, dtype=np.uint32)[::-1].copy()
    first = engine.submit([SortRequest("sort", payload.copy())])[0]
    again = engine.submit([SortRequest("sort", payload.copy())])[0]
    assert np.array_equal(first.values, again.values)
    assert again.backend == first.backend
    assert again.cycles == first.cycles          # telemetry rides along
    assert again.meta.get("cache_hit") is True
    telem = engine.telemetry()
    assert telem["cache"]["hits"] == 1
    assert telem["cache"]["misses"] == 1
    assert telem["batcher"]["cache_hit_rate"] == 0.5
    # a hit executes nothing: scheduler tile count unchanged by the re-ask
    assert telem["scheduler"]["tiles"] == 1


def test_result_cache_key_separates_op_k_and_hint():
    engine = small_engine()
    payload = np.arange(32, dtype=np.uint32)
    r_sort = engine.submit([SortRequest("sort", payload.copy())])[0]
    r_kmin = engine.submit([SortRequest("kmin", payload.copy(), k=4)])[0]
    r_hint = engine.submit([SortRequest("sort", payload.copy(),
                                        backend="numpy")])[0]
    assert engine.telemetry()["cache"]["hits"] == 0      # all distinct keys
    assert r_hint.backend == "numpy"
    assert r_sort.backend == "colskip"
    assert len(r_kmin.values) == 4


def test_result_cache_lru_eviction_and_disable():
    engine = small_engine(cache_size=2)
    reqs = [SortRequest("sort", np.full(8, i, np.uint32)) for i in range(4)]
    engine.submit(reqs)
    assert engine.telemetry()["cache"]["size"] == 2      # capacity bound
    off = small_engine(cache_size=0)
    payload = np.arange(16, dtype=np.uint32)
    off.submit([SortRequest("sort", payload.copy())])
    off.submit([SortRequest("sort", payload.copy())])
    t = off.telemetry()
    assert t["cache"] == {"hits": 0, "misses": 0, "hit_rate": 0.0,
                          "size": 0, "capacity": 0}


def test_result_cache_not_poisoned_by_caller_mutation():
    """Responses never alias cache entries: in-place edits stay private.

    (Uses the numpy backend — jax-backed backends already hand out read-only
    views, but oracle results are plain writable arrays.)"""
    engine = small_engine()
    payload = np.arange(32, dtype=np.uint32)[::-1].copy()
    req = lambda: SortRequest("sort", payload.copy(), backend="numpy")
    first = engine.submit([req()])[0]
    first.values[:] = 0                        # hostile caller
    second = engine.submit([req()])[0]
    assert second.meta.get("cache_hit") is True
    assert np.array_equal(second.values, np.sort(payload))
    second.values[:] = 7                       # hit responses are private too
    third = engine.submit([req()])[0]
    assert np.array_equal(third.values, np.sort(payload))


def test_result_cache_not_poisoned_by_failed_batch():
    engine = small_engine()
    payload = np.arange(16, dtype=np.uint32)
    engine.policy.by_name["numpy"].run = None            # poison execution
    with pytest.raises(TypeError):
        engine.submit([SortRequest("sort", payload.copy(), backend="numpy")])
    t = engine.telemetry()
    assert t["cache"]["hits"] == 0 and t["cache"]["misses"] == 0
    assert t["cache"]["size"] == 0


# ------------------------------------------------------------- properties
@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 999), n_req=st.integers(1, 12))
def test_property_served_stream_equals_oracle(seed, n_req):
    engine = small_engine(backends=("colskip", "radix_topk", "jaxsort"))
    reqs = make_workload(n_req, min_len=4, max_len=48, seed=seed)
    for req, resp in zip(reqs, engine.submit(reqs)):
        assert check_against_oracle(req, resp)


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_directory_follows_the_environment(tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR, when set, is the cache directory and the
    engine sets no other; without it, the configured directory is used."""
    import os
    import subprocess
    import sys
    import textwrap
    want = str(tmp_path / ("env" if env_dir else "cfg"))
    code = textwrap.dedent(f"""
        import jax
        from repro.sortserve import EngineConfig, SortServeEngine
        from repro.sortserve.backends import EXECUTOR_CACHE
        SortServeEngine(EngineConfig(compile_cache={str(tmp_path / "cfg")!r}))
        assert jax.config.jax_compilation_cache_dir == {want!r}, \\
            jax.config.jax_compilation_cache_dir
        assert EXECUTOR_CACHE.persistent_dir == {want!r}
        print("OK")
    """)
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")]
        + [p for p in [os.environ.get("PYTHONPATH")] if p])
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = want
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


# ------------------------------------------------ one-copy packed read-back
def _executor_case(case: str, b: int, n: int):
    """``(executor, body, packs)`` for one case: the serving executor as
    the backend builds it, the tile body it compiles, and whether its
    outputs should come back packed in one buffer."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.colskip import colskip_sort_batched
    from repro.sortserve import backends as bk

    if case.startswith("colskip-"):
        pallas, stop = "interpret" in case, (5 if "kmin" in case else None)
        fn, _ = bk._compiled_colskip(b, n, 32, 2, stop, pallas, pallas, True)
        return fn, lambda x: colskip_sort_batched(
            x, 32, 2, use_pallas=pallas, interpret=pallas, stop_after=stop,
            packed=True, plane_steps=pallas), True
    if case.startswith("radix_topk-"):
        kmin = case.endswith("kmin")
        fn, _ = bk.RadixTopkBackend._executor(b, n, 3, kmin)
        return fn, lambda x: bk._radix_select(x, 3, kmin), True
    if case == "colskip_mesh":
        from repro.dist.bankmesh import sharded_tile_fn
        be = bk.ShardedColskipBackend()
        assert be.n_devices == 4 and n % 4 == 0
        fn, _ = be._mesh_executor(b, n, n)
        return fn, sharded_tile_fn(be.mesh, be.axis_name, be.w, be.state_k,
                                   n, be.packed, be.fuse), True
    if case == "jaxsort":
        fn, _ = bk.JaxSortBackend._executor(b, n)
        return fn, lambda x: jnp.argsort(x, axis=-1, stable=True), False

    def mixed(x):                       # float32 bits, NaN payloads included
        return (x, (x >> 3).astype(jnp.int32),
                jax.lax.bitcast_convert_type(x, jnp.float32), x[:, 0])

    body = {"mixed-32bit": mixed,
            "uint8-output": lambda x: (x, x.astype(jnp.uint8))}[case]
    return bk._aot_compile(case, body, b, n), body, case == "mixed-32bit"


def check_packed_round_trip(case: str, b: int = 4, n: int = 16) -> None:
    """The round trip returns the tile body's own outputs bit for bit, with
    their shapes and dtypes, in one device->host copy when they pack."""
    import jax
    import jax.numpy as jnp

    from repro.sortserve.backends import _round_trip
    from repro.sortserve.batcher import Tile

    rng = np.random.default_rng(17)
    data = rng.integers(0, 2**32, (b, n), dtype=np.uint32)
    data[:, ::3] = data[:, :1]                       # ties
    data[0, :4] = [0, 2**32 - 1, 0x7FC00001, 0xFF800000]
    fn, body, packs = _executor_case(case, b, n)
    tile = Tile(op="sort", data=data, k=None, entries=[], pad_rows=0)
    got = _round_trip(fn, tile)
    want = [np.asarray(o) for o in
            jax.tree.leaves(jax.jit(body)(jnp.asarray(data)))]
    assert (fn.layout is not None) is packs
    assert tile.obs["readback_copies"] == (1 if packs else len(want))
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert (g.shape, g.dtype) == (w.shape, w.dtype)
        assert g.tobytes() == w.tobytes()


@pytest.mark.parametrize("case", [
    "colskip-xla", "colskip-xla-kmin", "colskip-interpret",
    "colskip-interpret-kmin", "radix_topk-topk", "radix_topk-kmin",
    "colskip_mesh", "jaxsort", "mixed-32bit", "uint8-output"])
def test_packed_round_trip_equals_unpacked_outputs(case):
    """Multi-output executors of 32-bit outputs read a tile back in one
    packed copy that splits into exactly the unpacked outputs; jaxsort's
    single output and a body with a non-32-bit output stay unpacked.
    The mesh executor runs on a forced four-device CPU mesh."""
    if case != "colskip_mesh":
        check_packed_round_trip(case)
        return
    import os
    import subprocess
    import sys
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(here), "src"), here]))
    code = ("from test_sortserve import check_packed_round_trip\n"
            "check_packed_round_trip('colskip_mesh')\nprint('OK')\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "OK" in out.stdout
