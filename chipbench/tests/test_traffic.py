"""The traffic generator: the seed orders and draws, the work stays."""

import collections
import glob
import os

import numpy as np
import pytest

from chipbench.lib.spec import ROOT
from chipbench.lib.traffic import Traffic, kinds, load_mix

CLOSED = {"loop": "closed", "clients": 8, "n": 256, "w": 32, "block": 16,
          "bucket_age_ms": 100,
          "mix": [{"data": "kruskal", "op": "sort"},
                  {"data": "kruskal", "op": "argsort"},
                  {"data": "mapreduce", "op": "sort"},
                  {"data": "mapreduce", "op": "argsort"}]}
OPEN = {"loop": "open", "flush": True,
        "mix": [{"op": "topk", "k": 5, "n": 1021, "data": "gauss",
                 "sigma": 2.0, "pool_rows": 8}],
        "rows_min": 16, "rows_max": 128, "steps_per_s": 3.0, "block": 32,
        "schedule_seed": 17}
# make_workload's stream: four ops, three dtypes, lengths 64..4096
MIXED = {"loop": "open", "flush": False, "bucket_age_ms": 5, "block": 36,
         "rows_min": 1, "rows_max": 1, "steps_per_s": 50.0,
         "schedule_seed": 3, "n": [64, 512, 4096],
         "mix": [{"op": "sort", "data": "uniform"},
                 {"op": "argsort", "data": "uniform", "dtype": "int32"},
                 {"op": "topk", "data": "gauss", "sigma": 1e3, "k": [1, 64]},
                 {"op": "kmin", "data": "zipf", "s": 1.2, "domain": 1000,
                  "k": 10, "share": 2}]}


def _requests(spec, seed, count=64, stream=0):
    t = Traffic(spec, seed, stream)
    return [t.request(i) for i in range(count)]


def test_closed_loop_same_work_other_order():
    a, b = _requests(CLOSED, 5), _requests(CLOSED, 2 ** 40 + 3)
    for blk in range(4):
        sl = slice(16 * blk, 16 * blk + 16)
        assert collections.Counter(op for op, _, _ in a[sl]) == \
            collections.Counter(op for op, _, _ in b[sl])
    assert [op for op, _, _ in a] != [op for op, _, _ in b]


def test_closed_loop_is_a_function_of_the_seed():
    a, b = _requests(CLOSED, -7), _requests(CLOSED, -7)
    assert all(np.array_equal(x[1], y[1]) for x, y in zip(a, b))
    t = Traffic(CLOSED, -7)
    t.prefill(48)
    assert np.array_equal(t.request(40)[1], a[40][1])


def test_no_payload_repeats_within_a_run_or_across_streams():
    reqs = _requests(CLOSED, 9, 128) + _requests(CLOSED, 9, 128, stream=1)
    assert len({p.tobytes() for _, p, _ in reqs}) == len(reqs)
    assert all(p.dtype == np.uint32 and p.size == 256 for _, p, _ in reqs)


def test_open_loop_schedule_is_the_files_and_blocks_are_alike():
    a, b = Traffic(OPEN, 1), Traffic(OPEN, 99)
    sched_a = [a.step(j) for j in range(64)]
    assert sched_a == [b.step(j) for j in range(64)]
    rows = [r for _, r in sched_a]
    assert sorted(rows[:32]) == sorted(rows[32:]) and rows[:32] != rows[32:]
    # the 32 steps of a block take 32 / rate seconds
    assert abs(a.step(32)[0] - 32 / 3.0) < 1e-9
    assert min(rows) >= 16 and max(rows) <= 128
    other = Traffic({**OPEN, "schedule_seed": 18}, 1)
    assert [other.step(j) for j in range(64)] != sched_a
    # the seed draws the logits
    assert not np.array_equal(a.request(0)[1], b.request(0)[1])


def test_open_loop_rows_are_distinct_views_of_the_pool():
    t = Traffic(OPEN, 4)
    warm = t.twin(1)
    rows = [t.request(i)[1] for i in range(200)]
    rows += [warm.request(i)[1] for i in range(200)]
    assert len({r.tobytes() for r in rows}) == len(rows)
    assert all(r.base is not None and r.size == 1021 for r in rows)


def test_bursts_are_on_and_off_at_the_mean_rate():
    spec = {**OPEN, "block": 64, "bursts": {"on_s": 2.0, "off_s": 6.0,
                                            "off_rate": 0.25}}
    t = Traffic(spec, 1)
    due = np.array([t.step(j)[0] for j in range(64 * 20)])
    assert np.all(np.diff(due) >= 0)
    phase = due % 8.0
    on = np.count_nonzero(phase < 2.0)
    # on 2 s at rate r, off 6 s at r/4: 4 arrivals on for every 3 off
    assert on / len(due) == pytest.approx(4 / 7, abs=0.02)
    assert len(due) / due[-1] == pytest.approx(3.0, rel=0.02)
    # the steady schedule's row counts, in the same order
    assert [t.step(j)[1] for j in range(64)] == \
        [Traffic({**spec, "bursts": None}, 1).step(j)[1] for j in range(64)]


def test_a_mix_of_ops_dtypes_and_lengths_is_data():
    ks = kinds(MIXED)
    assert len(ks) == 3 * (1 + 1 + 2 + 1)
    a, b = (_requests(MIXED, s, 72) for s in (1, 2))

    def shape(reqs):
        return collections.Counter((op, p.dtype.name, p.size, k)
                                   for op, p, k in reqs)
    # the same multiset of kinds in every block, whatever the seed
    assert shape(a[:36]) == shape(a[36:]) == shape(b[:36])
    got = shape(a[:36])
    assert got[("kmin", "uint32", 4096, 10)] == 4
    assert got[("argsort", "int32", 64, None)] == 2
    assert got[("topk", "float32", 512, 64)] == 2
    # the open loop's order of kinds is the file's; the seed draws the data
    assert [r[0] for r in a] == [r[0] for r in b]
    assert not any(np.array_equal(x[1], y[1]) for x, y in zip(a, b))
    kmin = [p for op, p, _ in a if op == "kmin"]
    assert all(p.max() < 1000 for p in kmin)


@pytest.mark.parametrize("bad", [
    {"loop": "both"},
    {"mix": [{"op": "median", "data": "uniform"}]},
    {"mix": [{"op": "topk", "data": "uniform", "k": 300}]},
    {"mix": [{"op": "sort", "data": "gauss", "dtype": "uint32"}]},
    {"mix": [{"op": "sort", "data": "uniform", "colour": 1}]},
    {"block": 15},
])
def test_a_bad_mix_is_refused(bad):
    with pytest.raises((ValueError, KeyError)):
        kinds({**CLOSED, **bad})


def test_a_closed_loop_needs_its_bucket_age():
    spec = dict(CLOSED)
    del spec["bucket_age_ms"]
    with pytest.raises(KeyError):
        Traffic(spec, 1)


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(ROOT, "chipbench", "traffic", "*.json"))))
def test_every_mix_file_loads(path):
    assert kinds(load_mix(path))
