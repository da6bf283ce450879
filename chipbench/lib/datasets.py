"""The distributions a traffic mix draws from, made in bulk: one row per
request.

The paper's §V sorting datasets (``uniform``, ``normal``, ``clustered``,
``kruskal``, ``mapreduce``) are a copy of the program's
``repro.core.datasets`` generators, kept here so that a later change to
the program cannot move the benchmark's traffic; their values are
``w``-bit unsigned, returned as uint32 (w <= 32).  Besides them: ``zipf``,
uint32 ranks below ``domain`` with Zipf(``s``) weights (a database
column's skewed keys), and ``gauss``, float32 normal values of standard
deviation ``sigma`` (a model's logits).  Each function draws ``rows``
independent datasets of ``n`` values at once; every row has the
distribution of one call of the original.
"""

from __future__ import annotations

import functools

import numpy as np

__all__ = ["DATASETS", "make_rows"]


def _clip(x: np.ndarray, w: int) -> np.ndarray:
    return np.clip(x, 0, (1 << w) - 1).astype(np.uint32)


def uniform(rng: np.random.Generator, rows: int, n: int, w: int) -> np.ndarray:
    return rng.integers(0, 1 << w, size=(rows, n), dtype=np.uint64).astype(
        np.uint32)


def normal(rng: np.random.Generator, rows: int, n: int, w: int) -> np.ndarray:
    mean = float(1 << (w - 1))
    return _clip(np.rint(rng.normal(mean, mean / 3.0, size=(rows, n))), w)


def clustered(rng: np.random.Generator, rows: int, n: int,
              w: int) -> np.ndarray:
    """Two clusters centred at 2^15 and 2^25, sigma 2^13."""
    pick = rng.integers(0, 2, size=(rows, n)).astype(bool)
    sd = float(1 << 13)
    vals = np.where(pick, rng.normal(float(1 << 15), sd, size=(rows, n)),
                    rng.normal(float(1 << 25), sd, size=(rows, n)))
    return _clip(np.rint(vals), w)


def kruskal(rng: np.random.Generator, rows: int, n: int,
            w: int) -> np.ndarray:
    """MST edge weights: integer-rounded exponential, many repeats."""
    return _clip(np.floor(rng.exponential(scale=5000.0, size=(rows, n))), w)


_ZIPF_BITS = 20


@functools.lru_cache(maxsize=None)
def _zipf_table(groups: int, s: float = 1.1) -> np.ndarray:
    """Group of each of 2^20 equal slices of [0, 1) under Zipf(s)
    weights: a draw costs one gather, and each group's probability is
    exact to 2^-20."""
    weights = 1.0 / np.arange(1, groups + 1) ** s
    cdf = np.cumsum(weights / weights.sum())
    mid = (np.arange(1 << _ZIPF_BITS) + 0.5) / (1 << _ZIPF_BITS)
    return np.minimum(np.searchsorted(cdf, mid, side="right"),
                      groups - 1).astype(np.int64)


def mapreduce(rng: np.random.Generator, rows: int, n: int, w: int,
              groups: int = 48, spread: float = 16.0) -> np.ndarray:
    """Map keys: per dataset, ``groups`` centres below 2^19 with Zipf(1.1)
    weights, each value a centre plus a small exponential jitter."""
    centers = rng.integers(0, 1 << 19, size=(rows, groups), dtype=np.int64)
    table = _zipf_table(groups)
    which = table[rng.integers(0, 1 << _ZIPF_BITS, size=(rows, n),
                               dtype=np.uint32)]
    jitter = np.rint(rng.exponential(scale=spread, size=(rows, n)))
    vals = np.take_along_axis(centers, which, axis=1) + jitter.astype(np.int64)
    return _clip(vals, w)


def zipf(rng: np.random.Generator, rows: int, n: int, w: int = 32,
         s: float = 1.1, domain: int = 1 << 20) -> np.ndarray:
    """Ranks ``0 .. domain-1``, rank ``r`` with weight ``1 / (r+1)^s``."""
    if not 1 <= domain <= 1 << w:
        raise ValueError(f"domain={domain} outside 1..2^{w}")
    table = _zipf_table(int(domain), float(s))
    return table[rng.integers(0, 1 << _ZIPF_BITS, size=(rows, n),
                              dtype=np.uint32)].astype(np.uint32)


def gauss(rng: np.random.Generator, rows: int, n: int,
          sigma: float = 1.0) -> np.ndarray:
    out = rng.standard_normal((rows, n), dtype=np.float32)
    out *= np.float32(sigma)
    return out


DATASETS = {
    "uniform": uniform,
    "normal": normal,
    "clustered": clustered,
    "kruskal": kruskal,
    "mapreduce": mapreduce,
    "zipf": zipf,
    "gauss": gauss,
}
FLOAT_DATASETS = ("gauss",)      # the others are unsigned integers
PARAMS = {"zipf": ("w", "s", "domain"), "gauss": ("sigma",)}


def params_of(name: str, kind: dict) -> dict:
    """The parameters of dataset ``name`` that ``kind`` gives."""
    return {p: kind[p] for p in PARAMS.get(name, ("w",)) if p in kind}


def make_rows(name: str, rng: np.random.Generator, rows: int, n: int,
              **params) -> np.ndarray:
    """``rows`` fresh datasets of dataset ``name``: a (rows, n) array,
    uint32 or (``gauss``) float32.  ``params`` are the dataset's own:
    ``w``; ``w``, ``s`` and ``domain``; ``sigma``."""
    if name not in DATASETS:
        raise ValueError(f"unknown dataset {name!r}; have {sorted(DATASETS)}")
    unknown = set(params) - set(PARAMS.get(name, ("w",)))
    if unknown:
        raise ValueError(f"{name} takes no {sorted(unknown)}")
    if not 1 <= params.get("w", 32) <= 32:
        raise ValueError(f"w={params['w']} outside 1..32")
    if name not in PARAMS:
        params = {"w": params.get("w", 32)}
    return DATASETS[name](rng, rows, n, **params)
