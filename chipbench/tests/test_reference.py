"""The plain reference against a brute-force reading of the tie-break
contract, and the control against the reference."""

import numpy as np
import pytest

from chipbench.lib.reference import control, reference, response_matches


def _brute(op, x, k):
    """Sort (value, index) pairs with Python's own sort."""
    idx = list(range(len(x)))
    if op == "topk":
        idx.sort(key=lambda i: (-float(x[i]), i))
    else:
        idx.sort(key=lambda i: (float(x[i]), i))
    if op in ("topk", "kmin"):
        idx = idx[:k]
    idx = np.asarray(idx, np.int64)
    return (None if op == "argsort" else x[idx],
            None if op == "sort" else idx)


@pytest.mark.parametrize("dtype", [np.uint32, np.int32, np.float32])
@pytest.mark.parametrize("op", ["sort", "argsort", "topk", "kmin"])
def test_reference_is_the_tie_break_contract(dtype, op):
    rng = np.random.default_rng(7)
    for _ in range(60):
        n = int(rng.integers(1, 200))
        x = (rng.integers(-4, 5, n) if dtype != np.uint32
             else rng.integers(0, 6, n)).astype(dtype)
        if dtype == np.float32:
            x[x == 0] = 0.5
        k = int(rng.integers(1, n + 1)) if op in ("topk", "kmin") else None
        want_v, want_i = _brute(op, x, k)
        got_v, got_i = reference(op, x, k)
        if want_v is not None:
            assert got_v.dtype == x.dtype
            assert np.array_equal(got_v, want_v)
        if want_i is not None:
            assert np.array_equal(got_i, want_i)


def test_ties_go_to_the_lower_index():
    x = np.array([1, 3, 3, 2, 3], np.uint32)
    assert reference("topk", x, 2)[1].tolist() == [1, 2]
    assert reference("kmin", x, 3)[1].tolist() == [0, 3, 1]
    assert reference("argsort", x)[1].tolist() == [0, 3, 1, 2, 4]


def test_reference_refuses_nan():
    with pytest.raises(ValueError):
        reference("sort", np.array([1.0, np.nan], np.float32))


def test_minus_zero_orders_just_below_plus_zero():
    # a seeded decode pool held both zeros once; the service's order has
    # -0 < +0, so the reference must too
    x = np.array([0.0, -0.0, 1.0, -0.0, -1e-45], np.float32)
    assert reference("argsort", x)[1].tolist() == [4, 1, 3, 0, 2]
    assert reference("kmin", x, 3)[1].tolist() == [4, 1, 3]
    assert reference("topk", x, 3)[1].tolist() == [2, 0, 1]
    v = reference("sort", x)[0]
    assert np.signbit(v).tolist() == [True, True, True, False, False]


def test_response_match_is_bit_exact_in_the_request_dtype():
    x = np.array([2.0, -1.0, 3.0], np.float32)
    v, i = reference("topk", x, 2)
    assert response_matches("topk", x, 2, v, i)
    assert not response_matches("topk", x, 2, v.astype(np.float64), i)
    assert not response_matches("topk", x, 2, v, i[::-1])
    assert not response_matches("topk", x, 2, None, i)
    assert not response_matches("sort", x, None, np.sort(x)[::-1], None)


@pytest.mark.parametrize("dataset", ["uniform", "kruskal"])
def test_control_fails_at_the_sorter_size(dataset):
    from chipbench.lib.datasets import make_rows
    rows = make_rows(dataset, np.random.default_rng(3), 4, 1024)
    for op in ("sort", "argsort"):
        for x in rows:
            assert not response_matches(op, x, None, *control(op, x))
            assert response_matches(op, x, None, *reference(op, x))


def test_control_fails_at_the_vocabulary_size():
    x = np.random.default_rng(4).standard_normal(129280, np.float32) * 2
    assert not response_matches("topk", x, 50, *control("topk", x, 50))
