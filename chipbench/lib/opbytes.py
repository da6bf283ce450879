"""Bytes each op must move, from the payloads' shape alone.

The least any implementation can do: read the ``(rows, n)`` payloads once
and write what the op answers.  ``sort`` writes the values, ``argsort``
the int32 indices, ``topk`` / ``kmin`` ``k`` values and ``k`` indices per
row.  Give it the requests' own lengths, not a padded tile's shape:
padding is one implementation's choice.
Counting the op's own answer, and not what one executor happens to
produce, keeps the number the same whatever implements it, so a roofline
share built on it cannot pass 100% unless the time leaves out work.
"""

from __future__ import annotations

__all__ = ["op_bytes"]

INDEX_BYTES = 4


def op_bytes(op: str, rows: int, n: int, k: int | None = None,
             itemsize: int = 4) -> int:
    read = rows * n * itemsize
    if op == "sort":
        write = rows * n * itemsize
    elif op == "argsort":
        write = rows * n * INDEX_BYTES
    elif op in ("topk", "kmin"):
        if k is None or not 1 <= k <= n:
            raise ValueError(f"{op} needs 1 <= k <= {n}, got {k}")
        write = rows * k * (itemsize + INDEX_BYTES)
    else:
        raise ValueError(f"unknown op {op!r}")
    return read + write
