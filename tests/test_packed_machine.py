"""Lane-packed §III machine: bit-equivalence with the dense carriers.

The packed substrate (:mod:`repro.core.bitmatrix`) and both packed machine
realizations (kernel + jaxsort) must be *bit-identical* to the dense
implementations — values, order, CR, and cycle telemetry — across dataset
shapes the hardware cares about: random, pre-sorted, reverse-sorted, and
duplicate-heavy data; widths that are not multiples of the 32-bit lane;
``stop_after`` in {1, 7, N}; and state-table depths k in {0, 1, 2, 4}.
"""

import numpy as np
import pytest

import jax.numpy as jnp
from _hypothesis_compat import given, settings, st

from repro.core import colskip_sort
from repro.core.bitmatrix import (
    any_lane,
    cumsum_bits,
    pack_rows,
    packed_words,
    popcount,
    tail_mask,
    unpack_rows,
)
from repro.core.jaxsort import colskip_sort_jax
from repro.kernels.colskip import colskip_sort_batched

DATASETS = ("random", "sorted", "reverse", "dupes")


def _rows(kind: str, b: int, n: int, w: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    x = rng.integers(0, 1 << w, size=(b, n), dtype=np.uint64)
    if kind == "sorted":
        x = np.sort(x, axis=-1)
    elif kind == "reverse":
        x = np.sort(x, axis=-1)[:, ::-1].copy()
    elif kind == "dupes":
        x = x % 5                       # duplicate-heavy: long drain stalls
    return x.astype(np.uint32)


# ----------------------------------------------------------- substrate units
@pytest.mark.parametrize("n", [1, 5, 31, 32, 33, 64, 100])
def test_pack_roundtrip_popcount_anylane_cumsum(n):
    rng = np.random.default_rng(n)
    bits = rng.random((3, n)) < 0.4
    for arr in (bits, jnp.asarray(bits)):
        p = pack_rows(arr)
        assert p.shape == (3, packed_words(n))
        assert np.array_equal(np.asarray(unpack_rows(p, n)), bits)
        assert np.array_equal(np.asarray(popcount(p)).sum(-1), bits.sum(-1))
        assert np.array_equal(np.asarray(any_lane(p)), bits.any(-1))
        assert np.array_equal(np.asarray(cumsum_bits(p, n)),
                              np.cumsum(bits, -1))
    # tail padding must be zero so bitwise ops stay exact set operations
    tm = np.asarray(tail_mask(n))
    assert int(np.asarray(popcount(tm)).sum()) == n
    assert not (np.asarray(pack_rows(bits)) & ~tm).any()


@settings(max_examples=30, deadline=None)
@given(n=st.integers(1, 300), b=st.integers(1, 4),
       density=st.sampled_from([0.0, 0.1, 0.5, 0.9, 1.0]),
       seed=st.integers(0, 999))
def test_property_inlane_rank_equals_dense_rank(n, b, density, seed):
    """The in-lane drain rank (word-prefix sum + in-word popcount) is
    bit-identical to the dense expansion it replaced, for numpy and jax
    carriers, across widths that straddle word boundaries and densities
    from empty to full masks (PR-4 packed-drain satellite)."""
    rng = np.random.default_rng(seed)
    bits = rng.random((b, n)) < density
    dense_rank = np.cumsum(bits.astype(np.int32), axis=-1)
    p = pack_rows(bits)
    got_np = cumsum_bits(p, n)
    got_jax = np.asarray(cumsum_bits(jnp.asarray(p), n))
    assert got_np.dtype == np.int32
    assert np.array_equal(got_np, dense_rank)
    assert np.array_equal(got_jax, dense_rank)


# ------------------------------------------------- machine bit-equivalence
@settings(max_examples=20, deadline=None)
@given(kind=st.sampled_from(DATASETS),
       n=st.sampled_from([17, 24, 33, 64]),      # includes non-multiple-of-32
       k=st.sampled_from([0, 1, 2, 4]),
       stop_mode=st.sampled_from(["1", "7", "N"]),
       seed=st.integers(0, 999))
def test_property_packed_equals_dense_jax_machine(kind, n, k, stop_mode, seed):
    x = _rows(kind, 1, n, 16, seed)[0]
    stop = {"1": 1, "7": min(7, n), "N": None}[stop_mode]
    got_p = colskip_sort_jax(jnp.asarray(x), 16, k, stop, True)
    got_d = colskip_sort_jax(jnp.asarray(x), 16, k, stop, False)
    for field, a, b in zip(("values", "order", "crs", "cycles"), got_p, got_d):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (field, kind)
    # both must equal the numpy hardware model, telemetry included
    hw = colskip_sort(x.astype(np.uint64), 16, k, stop_after=stop)
    assert np.array_equal(np.asarray(got_p[0]), hw.values.astype(np.uint32))
    assert np.array_equal(np.asarray(got_p[1]), hw.order)
    assert int(got_p[2]) == hw.column_reads
    assert int(got_p[3]) == hw.cycles


@settings(max_examples=10, deadline=None)
@given(kind=st.sampled_from(DATASETS),
       n=st.sampled_from([24, 40, 64]),
       k=st.sampled_from([0, 2, 4]),
       stop_mode=st.sampled_from(["1", "7", "N"]),
       seed=st.integers(0, 999))
def test_property_packed_equals_dense_pallas_kernel(kind, n, k, stop_mode, seed):
    x = _rows(kind, 3, n, 16, seed)
    stop = {"1": 1, "7": min(7, n), "N": None}[stop_mode]
    got_p = colskip_sort_batched(jnp.asarray(x), 16, k, use_pallas=True,
                                 interpret=True, stop_after=stop, packed=True)
    got_d = colskip_sort_batched(jnp.asarray(x), 16, k, use_pallas=True,
                                 interpret=True, stop_after=stop, packed=False)
    for field, a, b in zip(("values", "order", "crs", "cycles"), got_p, got_d):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (field, kind)


# ------------------------------------------ bounded loops: tiles that skip
def _paper_rows(kinds, n: int, seed: int) -> np.ndarray:
    from repro.core.datasets import make_dataset
    return np.stack([make_dataset(kind, n, 32, seed=seed + i)
                     for i, kind in enumerate(kinds)]).astype(np.uint32)


def bounded_loop_tile(case: str):
    """``(rows, w, k, stop)``: one tile whose rows finish at different
    iterations and resume at different planes, so the kernel's plane loop
    starts below the MSB and its iteration loop ends before ``stop``."""
    skew = ("kruskal", "mapreduce") * 4                  # low s_top
    if case == "kruskal-mapreduce":
        return _paper_rows(skew, 48, 0), 32, 2, None
    if case == "k0":
        return _paper_rows(skew, 40, 10), 32, 0, None
    if case == "mapreduce":          # every row drains duplicates: < n iterations
        return _paper_rows(("mapreduce",) * 8, 64, 60), 32, 2, None
    if case == "equal-beside-uniform":
        rows = _paper_rows(("uniform",) * 2, 64, 20)
        rows[0] = 123456789
        return rows, 32, 2, None
    if case == "all-ones":
        rows = _paper_rows(("kruskal", "uniform", "mapreduce"), 40, 30)
        rows[1] = 0xFFFFFFFF
        return rows, 32, 2, None
    if case in ("kmin-1", "kmin-7"):
        return _paper_rows(skew, 64, 40), 32, 2, int(case[5:])
    if case == "zero-padded":                # 5 rows: 3 zero rows of padding
        return _paper_rows(("kruskal", "normal", "mapreduce", "kruskal",
                            "uniform"), 33, 50), 32, 2, None
    raise ValueError(case)


BOUNDED_LOOP_CASES = ("kruskal-mapreduce", "k0", "mapreduce",
                      "equal-beside-uniform",
                      "all-ones", "kmin-1", "kmin-7", "zero-padded")


@pytest.mark.parametrize("packed", [True, False], ids=["packed", "dense"])
@pytest.mark.parametrize("case", BOUNDED_LOOP_CASES)
def test_bounded_loop_kernel_equals_numpy_machine(case, packed):
    """The kernel's state-bounded plane and iteration loops are exact:
    values, order, CRs and cycles equal the numpy machine's, row by row,
    on both carriers."""
    x, w, k, stop = bounded_loop_tile(case)
    vals, order, crs, cyc = colskip_sort_batched(
        jnp.asarray(x), w, k, use_pallas=True, interpret=True,
        stop_after=stop, packed=packed)
    for r, row in enumerate(x):
        hw = colskip_sort(row.astype(np.uint64), w, k, stop_after=stop)
        assert np.array_equal(np.asarray(vals[r]), hw.values.astype(np.uint32))
        assert np.array_equal(np.asarray(order[r]), hw.order), (case, r)
        assert int(crs[r]) == hw.column_reads, (case, r)
        assert int(cyc[r]) == hw.cycles, (case, r)


@pytest.mark.parametrize("kind", DATASETS)
def test_packed_mesh_matches_dense_local(kind):
    """§V.C invariance holds for the packed carrier on a (1+-device) mesh."""
    from repro.dist.bankmesh import colskip_sort_mesh, make_bank_mesh
    mesh = make_bank_mesh()
    x = _rows(kind, 2, 64, 32, seed=7)
    got_m = colskip_sort_mesh(x, mesh, w=32, k=2, packed=True)
    got_l = colskip_sort_batched(jnp.asarray(x), 32, 2, use_pallas=False,
                                 packed=False)
    for field, a, b in zip(("values", "order", "crs", "cycles"), got_m, got_l):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (field, kind)


def test_dense_flag_available_end_to_end():
    """The serving engine can still run the dense baseline (--dense path)."""
    from repro.sortserve import EngineConfig, SortRequest, SortServeEngine
    payload = _rows("dupes", 1, 48, 32, seed=3)[0]
    packed = SortServeEngine(EngineConfig(
        backends=("colskip",), tile_rows=1, bank_rows=1, sim_width_cap=4096,
        cache_size=0, packed=True))
    dense = SortServeEngine(EngineConfig(
        backends=("colskip",), tile_rows=1, bank_rows=1, sim_width_cap=4096,
        cache_size=0, packed=False))
    rp = packed.submit([SortRequest("sort", payload.copy())])[0]
    rd = dense.submit([SortRequest("sort", payload.copy())])[0]
    assert np.array_equal(rp.values, rd.values)
    assert rp.cycles == rd.cycles and rp.column_reads == rd.column_reads
    assert rp.meta.get("pad_cols") == rd.meta.get("pad_cols")
