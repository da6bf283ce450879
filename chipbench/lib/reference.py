"""The plain reference that decides ``correct``, and its control.

Plain numpy over the request's own payload, in the request's dtype; it
shares no code with the program.  The semantics are the service's
tie-break contract:

* ``sort`` / ``argsort`` / ``kmin``: ascending, equal values by ascending
  index (a stable sort);
* ``topk``: descending, equal values by ascending index.

Floats order by value, with -0 just below +0 (IEEE 754's total order,
which is the service's); NaN has no place in it, so the reference refuses
it, and the traffic never makes one.

The control is the same reference computed one precision down, the step
that would tempt a later change: the 32-bit keys cut to their top 16 bits
(a w=16 sorter), and float32 keys rounded to bfloat16.  It orders by the
cut key and answers with the payload's own values, so it breaks only the
guarantee of exact order.
"""

from __future__ import annotations

import numpy as np

__all__ = ["control", "reference", "response_matches"]


def _plain(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x)
    if x.ndim != 1:
        raise ValueError(f"payload must be 1-D, got {x.shape}")
    if np.issubdtype(x.dtype, np.floating) and np.isnan(x).any():
        raise ValueError("reference undefined for NaN payloads")
    return x


def _order_key(x: np.ndarray) -> np.ndarray:
    """float64 keys in the service's order: exact for 32-bit integers and
    floats, and -0 just below +0 (closer to 0 than any float32)."""
    key = x.astype(np.float64)
    if np.issubdtype(x.dtype, np.floating):
        key[(x == 0) & np.signbit(x)] = \
            -np.finfo(np.float64).smallest_subnormal
    return key


def _select(key: np.ndarray, k: int, largest: bool) -> np.ndarray:
    """Indices of the k largest (or smallest) keys, ordered by key then by
    ascending index: partition to the k-th key, then sort the few."""
    n = key.size
    if not 1 <= k <= n:
        raise ValueError(f"k={k} outside 1..{n}")
    kth = np.partition(key, n - k if largest else k - 1)[
        n - k if largest else k - 1]
    strict = np.flatnonzero(key > kth if largest else key < kth)
    ties = np.flatnonzero(key == kth)[:k - strict.size]
    idx = np.concatenate([strict, ties])
    return idx[np.lexsort((idx, -key[idx] if largest else key[idx]))]


def reference(op: str, x: np.ndarray, k: int | None = None,
              key: np.ndarray | None = None):
    """``(values, indices)`` the service must answer for ``op`` over ``x``
    (``values`` is None for argsort, ``indices`` None for sort).  ``key``
    orders in place of ``x``; the values are always ``x``'s own."""
    x = _plain(x)
    key = _order_key(x if key is None else key)
    if op in ("sort", "argsort"):
        idx = np.argsort(key, kind="stable")
    elif op == "kmin":
        idx = _select(key, k, largest=False)
    elif op == "topk":
        idx = _select(key, k, largest=True)
    else:
        raise ValueError(f"unknown op {op!r}")
    return (None if op == "argsort" else x[idx],
            None if op == "sort" else idx)


def _cut_key(x: np.ndarray) -> np.ndarray:
    """The key one precision below the payload's: top 16 bits of a 32-bit
    integer, float32 rounded to bfloat16."""
    if x.dtype == np.uint32 or x.dtype == np.int32:
        return x >> 16
    if x.dtype == np.float32:
        import ml_dtypes
        return x.astype(ml_dtypes.bfloat16).astype(np.float32)
    raise TypeError(f"no control precision below {x.dtype}")


def control(op: str, x: np.ndarray, k: int | None = None):
    """The reference one precision down (see the module docstring)."""
    x = _plain(x)
    return reference(op, x, k, key=_cut_key(x))


def response_matches(op: str, x: np.ndarray, k: int | None, values,
                     indices, answer=None) -> bool:
    """True when a response's values (bit-exact, in the payload's dtype)
    and indices equal the reference's; ``answer`` is a precomputed
    reference answer."""
    want_v, want_i = answer if answer is not None else reference(op, x, k)
    if want_v is not None and not _same_values(values, want_v):
        return False
    if want_i is not None and not _same_indices(indices, want_i):
        return False
    return True


def _same_values(got, want: np.ndarray) -> bool:
    if got is None:
        return False
    got = np.asarray(got)
    return (got.dtype == want.dtype and got.shape == want.shape
            and np.array_equal(np.ascontiguousarray(got).view(np.uint8),
                               np.ascontiguousarray(want).view(np.uint8)))


def _same_indices(got, want: np.ndarray) -> bool:
    if got is None:
        return False
    got = np.asarray(got)
    return (np.issubdtype(got.dtype, np.integer) and got.shape == want.shape
            and np.array_equal(got.astype(np.int64), want.astype(np.int64)))
