"""Pallas kernels (interpret=True) vs pure-jnp oracles — shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st
from test_packed_machine import BOUNDED_LOOP_CASES, bounded_loop_tile

from repro.core import colskip_sort
from repro.kernels.colskip import colskip_sort_batched
from repro.kernels.colskip.kernel import TB
from repro.kernels.colskip.ref import sort_ref
from repro.kernels.radix_topk import radix_topk, radix_topk_threshold
from repro.kernels.radix_topk.ref import threshold_ref


@pytest.mark.parametrize("b,n,k", [(4, 128, 8), (7, 256, 1), (16, 1024, 32),
                                   (3, 640, 5), (1, 128, 128)])
def test_radix_topk_threshold_kernel_vs_ref(b, n, k):
    rng = np.random.default_rng(b * 1000 + n + k)
    x = jnp.asarray(rng.normal(size=(b, n)).astype(np.float32) * 10)
    t1 = radix_topk_threshold(x, k, use_pallas=True, interpret=True)
    t2 = threshold_ref(x, k)
    assert np.array_equal(np.asarray(t1), np.asarray(t2))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,n,k", [(4, 128, 8), (2, 512, 16)])
def test_radix_topk_dtypes(b, n, k, dtype):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(b, n))).astype(dtype)
    v1, i1 = radix_topk(x, k, use_pallas=True, interpret=True)
    v2, i2 = jax.lax.top_k(x.astype(jnp.float32), k)
    assert np.array_equal(np.asarray(v1.astype(jnp.float32)), np.asarray(v2))
    assert np.array_equal(np.asarray(i1), np.asarray(i2))


def test_radix_topk_wide_rows_multibank_path():
    """Vocab-scale rows exercise the two-level (bank + manager) reduction."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 50000)).astype(np.float32))
    v1, i1 = radix_topk(x, 17, use_pallas=False, bank_width=8192)
    v2, i2 = jax.lax.top_k(x, 17)
    assert np.array_equal(np.asarray(v1), np.asarray(v2))
    assert np.array_equal(np.asarray(i1), np.asarray(i2))


def test_radix_topk_constant_rows():
    x = jnp.full((3, 256), -2.5, jnp.float32)
    v1, i1 = radix_topk(x, 4, use_pallas=True, interpret=True)
    v2, i2 = jax.lax.top_k(x, 4)
    assert np.array_equal(np.asarray(v1), np.asarray(v2))
    assert np.array_equal(np.asarray(i1), np.asarray(i2))


def test_radix_topk_plane_skip_telemetry():
    """Small-dynamic-range inputs must visit far fewer than 32 planes."""
    from repro.kernels.radix_topk.kernel import threshold_pallas
    x = jnp.asarray(np.random.default_rng(0).uniform(1.0, 2.0, (8, 256)).astype(np.float32))
    _, visited = threshold_pallas(x, 8, interpret=True)
    assert (np.asarray(visited) < 32).all()
    assert (np.asarray(visited) >= 1).all()


@settings(max_examples=25, deadline=None)
@given(n=st.sampled_from([128, 256]), k=st.integers(1, 16), seed=st.integers(0, 999))
def test_property_radix_topk_equals_lax(n, k, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(2, n)).astype(np.float32))
    v1, i1 = radix_topk(x, k, use_pallas=True, interpret=True)
    v2, i2 = jax.lax.top_k(x, k)
    assert np.array_equal(np.asarray(v1), np.asarray(v2))
    assert np.array_equal(np.asarray(i1), np.asarray(i2))


@pytest.mark.parametrize("b,n,w,k", [(3, 64, 16, 2), (2, 128, 32, 1), (4, 32, 8, 3)])
def test_colskip_kernel_vs_ref_and_hardware(b, n, w, k):
    rng = np.random.default_rng(b + n + w + k)
    x = rng.integers(0, 1 << w, size=(b, n)).astype(np.uint32)
    xv = jnp.asarray(x)
    v1, o1, c1, y1 = colskip_sort_batched(xv, w, k, use_pallas=True, interpret=True)
    v2, o2, c2, y2 = sort_ref(xv, w, k)
    assert np.array_equal(np.asarray(v1), np.asarray(v2))
    assert np.array_equal(np.asarray(c1), np.asarray(c2))
    assert np.array_equal(np.asarray(y1), np.asarray(y2))
    for r in range(b):
        hw = colskip_sort(x[r].astype(np.uint64), w, k)
        assert np.array_equal(np.asarray(v1[r]), hw.values.astype(np.uint32))
        assert int(c1[r]) == hw.column_reads
        assert int(y1[r]) == hw.cycles


def _plane_step_recount(x, w, k, stop, tb=TB):
    """Per program: the number of iterations, and the sum over them of the
    highest start plane among the program's unfinished rows, plus one —
    from the numpy machine's own per-iteration starts, with the kernel's
    zero rows of padding."""
    bp = -(-len(x) // tb) * tb
    x = np.pad(x, ((0, bp - len(x)), (0, 0)))
    starts = [colskip_sort(r.astype(np.uint64), w, k, stop_after=stop)
              .meta["starts"] for r in x]
    out = []
    for p in range(0, bp, tb):
        prog = starts[p:p + tb]
        iters = max(map(len, prog))
        out.append((iters, sum(max(s[i] for s in prog if len(s) > i) + 1
                               for i in range(iters))))
    return out


@pytest.mark.parametrize("case", BOUNDED_LOOP_CASES)
def test_colskip_plane_steps_equal_numpy_recount(case):
    """The kernel's plane-step count is the walk the tile's state allows:
    each program's count equals the numpy recount, rides in its first row
    beside unchanged CRs, and the iteration loop ends when the slowest row
    has drained."""
    x, w, k, stop = bounded_loop_tile(case)
    _, _, tel, _ = colskip_sort_batched(
        jnp.asarray(x), w, k, use_pallas=True, interpret=True,
        stop_after=stop, plane_steps=True)
    tel = np.asarray(tel)
    assert tel.shape == (len(x), 2)
    _, _, crs, _ = sort_ref(jnp.asarray(x), w, k, stop_after=stop)
    assert np.array_equal(tel[:, 0], np.asarray(crs))
    recount = _plane_step_recount(x, w, k, stop)
    firsts = np.arange(len(x)) % TB == 0
    assert list(tel[firsts, 1]) == [steps for _, steps in recount]
    assert not tel[~firsts, 1].any()
    stop_eff = stop or x.shape[1]
    for iters, steps in recount:
        assert steps <= iters * w and iters <= stop_eff
    if case == "mapreduce":
        assert recount[0][0] < stop_eff       # duplicates end it early


@pytest.mark.parametrize("kinds,lo,hi", [
    (("uniform", "normal") * 4, 0.97, 1.0),   # the .uniform cell's rows
    (("kruskal",) * 8, 0.0, 0.6)], ids=["uniform-normal", "kruskal"])
def test_colskip_plane_steps_follow_the_data(kinds, lo, hi):
    """Uniform data leaves little to skip (run within 3% of the fixed
    loop's slots); Kruskal edge weights, with their low ``s_top``, skip
    over 40% of the plane steps."""
    from repro.core.datasets import make_dataset
    n = 128
    x = np.stack([make_dataset(kind, n, 32, seed=70 + i)
                  for i, kind in enumerate(kinds)]).astype(np.uint32)
    _, _, tel, _ = colskip_sort_batched(jnp.asarray(x), 32, 2,
                                        use_pallas=True, interpret=True,
                                        plane_steps=True)
    run, slots = int(np.asarray(tel)[:, 1].sum()), 32 * n
    assert lo * slots <= run <= hi * slots, run / slots


def test_colskip_plane_steps_only_on_the_kernel():
    with pytest.raises(ValueError, match="plane steps"):
        colskip_sort_batched(jnp.zeros((2, 8), jnp.uint32), 8, 2,
                             use_pallas=False, plane_steps=True)


def test_colskip_kernel_batch_padding():
    """B not a multiple of the tile: padded rows must not leak into outputs."""
    rng = np.random.default_rng(9)
    x = rng.integers(0, 1 << 16, size=(5, 64)).astype(np.uint32)
    v, o, c, y = colskip_sort_batched(jnp.asarray(x), 16, 2,
                                      use_pallas=True, interpret=True)
    assert v.shape == (5, 64)
    for r in range(5):
        assert np.array_equal(np.asarray(v[r]), np.sort(x[r]))


@pytest.mark.parametrize("b,n", [(3, 64), (5, 256), (2, 1024), (7, 128)])
def test_bitonic_kernel_vs_ref(b, n):
    from repro.kernels.bitonic import bitonic_sort
    rng = np.random.default_rng(b * n)
    x = rng.integers(0, 2**32, (b, n), dtype=np.uint64).astype(np.uint32)
    got = np.asarray(bitonic_sort(jnp.asarray(x), use_pallas=True,
                                  interpret=True))
    assert np.array_equal(got, np.sort(x, axis=-1))


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 999), logn=st.integers(3, 8))
def test_property_bitonic_sorts(seed, logn):
    from repro.kernels.bitonic import bitonic_sort
    n = 1 << logn
    rng = np.random.default_rng(seed)
    x = rng.integers(0, 2**16, (2, n), dtype=np.uint64).astype(np.uint32)
    got = np.asarray(bitonic_sort(jnp.asarray(x), use_pallas=True,
                                  interpret=True))
    assert np.array_equal(got, np.sort(x, axis=-1))


@pytest.mark.parametrize("backend,use_pallas,interpret,want", [
    ("tpu", None, None, (True, False)),     # compiled on the chip
    ("tpu", None, True, (True, True)),      # explicit interpret is honoured
    ("tpu", False, None, (False, False)),
    ("cpu", None, None, (False, True)),     # XLA reference off the chip
    ("cpu", True, None, (True, True)),      # Pallas off the chip interprets
    ("cpu", None, True, (True, True)),
])
def test_dispatch_resolves_from_platform(monkeypatch, backend, use_pallas,
                                         interpret, want):
    from repro.kernels import dispatch
    monkeypatch.setattr(dispatch.jax, "default_backend", lambda: backend)
    assert dispatch.resolve(use_pallas, interpret) == want
    if interpret is None:          # None never becomes interpret on a TPU
        assert dispatch.resolve_interpret(None) == (backend != "tpu")


def test_colskip_dense_carrier_has_no_compiled_kernel(monkeypatch):
    """On TPU the dense baseline runs on the XLA reference (impl "xla");
    the packed machine runs the compiled kernel (impl "pallas")."""
    from repro.kernels import dispatch
    from repro.kernels.colskip.ops import resolve_colskip
    monkeypatch.setattr(dispatch.jax, "default_backend", lambda: "tpu")
    assert resolve_colskip(packed=True) == (True, False, "pallas")
    assert resolve_colskip(packed=False) == (False, False, "xla")
    assert resolve_colskip(packed=False, interpret=True) == (
        True, True, "interpret")


@pytest.mark.parametrize("use_pallas,interpret,impl", [
    (None, None, "xla"), (True, True, "interpret")])
def test_colskip_tile_reports_impl(use_pallas, interpret, impl):
    from repro.sortserve import SortRequest
    from repro.sortserve.backends import ColskipBackend
    from repro.sortserve.batcher import Batcher
    b = Batcher(tile_rows=2, min_bucket=8)
    b.add(SortRequest("sort", np.arange(40, 0, -1, dtype=np.uint32)))
    tile = b.flush()[0]
    res = ColskipBackend(use_pallas=use_pallas, interpret=interpret).run(tile)
    assert res.meta["impl"] == impl
    assert np.array_equal(res.values[0, :40], np.arange(1, 41))
