"""The one general traffic generator.  A mix is a data file of parameters
(``chipbench/traffic/<mix>.json``); nothing about a mix lives in code.

A mix says what is asked and when.

**What** — ``mix``, a list of request kinds.  A kind gives ``op`` (sort,
argsort, topk, kmin), ``n`` (payload length), ``k`` (topk / kmin),
``data`` (a distribution of ``datasets.py``, with its own parameters:
``w``; ``s`` and ``domain``; ``sigma``), ``dtype`` (uint32 or int32 for
integer data, float32 for ``gauss``) and ``share`` (default 1).  A key a
kind leaves out takes the mix's top-level value.  ``n`` and ``k`` may be
lists: the kind then stands for one kind per ``(n, k)`` pair, each with
its ``share``.  A kind with ``pool_rows`` takes its payloads as rotations
of a seeded pool of that many rows, not as fresh draws: a view, so a wide
row costs nothing to make.

**When** — ``loop``:

* ``closed``: ``clients`` callers, each sending its next request the
  instant its previous one is answered (shuffle workers waiting for their
  sorted partition).  Buckets close on size or after ``bucket_age_ms``.
* ``open``: steps due on a Poisson schedule at ``steps_per_s``, step ``j``
  carrying ``rows_min``..``rows_max`` requests fed together (``flush``:
  its buckets closed at once; else they close on size or after
  ``bucket_age_ms``).  ``bursts`` (``on_s``, ``off_s``, ``off_rate``)
  makes the arrivals on/off: the off periods run at ``off_rate`` times
  the on periods' rate, and the mean stays ``steps_per_s``.

Steadiness: every block of ``block`` requests holds the same multiset of
kinds, and (open) every block of ``block`` steps the same row counts and
gaps, so two seeds ask for the same work.  In a closed loop the seed
orders the kinds and draws the data.  In an open loop the order of
arrivals decides the queue, so the schedule and the order of kinds are
the file's own (``schedule_seed``), the same in every run, and the seed
draws the data.

Payloads never repeat within a run.  Fresh rows are new draws.  Pool rows
are windows ``ext[r, s:s+n]`` of the pool laid twice end to end: a
rotation of row ``r`` by ``s``, with ``(r, s)`` distinct for every
request of the kind below ``pool_rows * n``.
"""

from __future__ import annotations

import itertools
import json
import math

import numpy as np

from .datasets import FLOAT_DATASETS, make_rows, params_of

__all__ = ["Traffic", "load_mix", "make_traffic"]

_ROT_STRIDE = 7919          # a prime: rotations i*7919 mod n are distinct
WARM_STREAM = 1             # warm-up draws from its own stream
WINDOW_STREAM = 0
_STREAMS = 8                # streams sharing one pool's rotations
_POOL_WORD = 2 ** 20        # the pools' draws, apart from the blocks'
KIND_KEYS = ("op", "n", "k", "data", "dtype", "share", "pool_rows", "w",
             "s", "domain", "sigma")
OPS = ("sort", "argsort", "topk", "kmin")


def seed_key(seed: int) -> int:
    """Any whole number, as the non-negative entropy numpy wants."""
    return int(seed) % (1 << 64)


def _rng(seed: int, *words: int) -> np.random.Generator:
    return np.random.default_rng([seed_key(seed), *words])


def load_mix(path: str) -> dict:
    with open(path) as f:
        spec = json.load(f)
    kinds(spec)                         # refuse a bad mix before any run
    return spec


def _listed(v) -> list:
    return list(v) if isinstance(v, list) else [v]


def kinds(spec: dict) -> list[dict]:
    """The mix's concrete request kinds, each with every key set."""
    if spec.get("loop") not in ("closed", "open"):
        raise ValueError("loop must be 'closed' or 'open'")
    top = {k: spec[k] for k in KIND_KEYS if k in spec}
    out = []
    for entry in spec["mix"]:
        unknown = set(entry) - set(KIND_KEYS)
        if unknown:
            raise ValueError(f"unknown kind keys {sorted(unknown)}")
        e = {**top, **entry}
        for n, k in itertools.product(_listed(e["n"]), _listed(e.get("k"))):
            kind = {**e, "n": int(n), "share": int(e.get("share", 1)),
                    "k": int(k) if e["op"] in ("topk", "kmin") else None}
            if kind["op"] not in OPS:
                raise ValueError(f"unknown op {kind['op']!r}")
            if kind["k"] is not None and not 1 <= kind["k"] <= kind["n"]:
                raise ValueError(f"{kind['op']} needs 1 <= k <= n")
            floating = kind["data"] in FLOAT_DATASETS
            kind.setdefault("dtype", "float32" if floating else "uint32")
            if kind["dtype"] not in (("float32",) if floating
                                     else ("uint32", "int32")):
                raise ValueError(f"{kind['data']} cannot be {kind['dtype']}")
            if "pool_rows" in kind and math.gcd(_ROT_STRIDE, kind["n"]) != 1:
                raise ValueError(f"n={kind['n']} shares a factor with "
                                 f"{_ROT_STRIDE}")
            out.append(kind)
    if int(spec["block"]) % sum(k["share"] for k in out):
        raise ValueError("block must be a multiple of the kinds' shares")
    return out


def _draw(kind: dict, rng: np.random.Generator, rows: int) -> np.ndarray:
    x = make_rows(kind["data"], rng, rows, kind["n"],
                  **params_of(kind["data"], kind))
    if kind["dtype"] == "int32":        # shifted by 2^31: the order stays
        x = (x ^ np.uint32(1 << 31)).view(np.int32)
    return x


class Schedule:
    """An open loop's steps: ``step(j) -> (due offset s, row count)``."""

    def __init__(self, spec: dict, stream: int):
        self.seed = spec["schedule_seed"]
        self.stream = stream
        self.rate = float(spec["steps_per_s"])
        self.block = int(spec["block"])
        lo, hi = int(spec["rows_min"]), int(spec["rows_max"])
        q = (np.arange(self.block) + 0.5) / self.block
        # the same row counts and gaps in every block: a grid over
        # U{lo..hi} and over the exponential quantiles, mean 1/rate exactly
        self._rows = lo + np.floor(q * (hi - lo + 1)).astype(int)
        gaps = -np.log1p(-q)
        self._gaps = gaps / gaps.mean() / self.rate
        self._due: list[float] = [0.0]
        self._count: list[int] = []
        self.bursts = spec.get("bursts")
        if self.bursts is not None:
            on, off = float(self.bursts["on_s"]), float(self.bursts["off_s"])
            share = float(self.bursts["off_rate"])
            if not (on > 0 and off > 0 and 0 <= share < 1):
                raise ValueError("bursts need on_s, off_s > 0 and "
                                 "0 <= off_rate < 1")
            self._on_rate = self.rate * (on + off) / (on + share * off)

    def _extend(self, j: int) -> None:
        while len(self._count) <= j:
            b = len(self._count) // self.block
            perm = _rng(self.seed, self.stream, b)
            rows = perm.permutation(self._rows)
            gaps = perm.permutation(self._gaps)
            for r, g in zip(rows, gaps):
                self._count.append(int(r))
                self._due.append(self._due[-1] + float(g))

    def _warp(self, t: float) -> float:
        """Steady time (arrivals at the mean rate) to on/off time."""
        on, off = float(self.bursts["on_s"]), float(self.bursts["off_s"])
        r_on = self._on_rate
        r_off = r_on * float(self.bursts["off_rate"])
        period, rem = divmod(t * self.rate, self.rate * (on + off))
        if r_off == 0 or rem < r_on * on:
            at = min(rem / r_on, on)
        else:
            at = on + (rem - r_on * on) / r_off
        return period * (on + off) + at

    def step(self, j: int) -> tuple[float, int]:
        self._extend(j)
        due = self._due[j]
        return (due if self.bursts is None else self._warp(due),
                self._count[j])


class Traffic:
    """Request ``i`` of a mix on one stream (``request(i)``), and in an
    open loop its steps (``step(j)``)."""

    def __init__(self, spec: dict, seed: int, stream: int = WINDOW_STREAM,
                 pools: dict | None = None):
        if not 0 <= stream < _STREAMS:
            raise ValueError(f"stream {stream} outside 0..{_STREAMS - 1}")
        self.spec, self.seed, self.stream = spec, seed, stream
        self.loop = spec["loop"]
        self.kinds = kinds(spec)
        self.block = int(spec["block"])
        shares = np.array([k["share"] for k in self.kinds])
        self._quota = shares * (self.block // int(shares.sum()))
        fresh = sorted({self._group_key(k) for k in self.kinds
                        if "pool_rows" not in k})
        self._group = [fresh.index(self._group_key(k))
                       if "pool_rows" not in k else -1 for k in self.kinds]
        self._groups = len(fresh)
        self._pools = pools if pools is not None else {
            i: self._pool(i, k) for i, k in enumerate(self.kinds)
            if "pool_rows" in k}
        self._blocks: dict[int, tuple] = {}
        if self.loop == "closed":
            self.clients = int(spec["clients"])
            self.flush = False
            self._order_seed = seed
            self.cursor = 0             # the next request index to send
        else:
            self.flush = bool(spec["flush"])
            self._order_seed = spec["schedule_seed"]
            self.schedule = Schedule(spec, stream)
            self.cursor = (0, 0)        # the next step, the next request
        self.bucket_age_s = (None if self.flush
                             else float(spec["bucket_age_ms"]) / 1e3)

    @staticmethod
    def _group_key(kind: dict) -> tuple:
        """Kinds that differ only in op and k share their draws."""
        return (kind["data"], kind["n"], kind["dtype"],
                tuple(sorted(params_of(kind["data"], kind).items())))

    def _pool(self, i: int, kind: dict) -> np.ndarray:
        base = _draw(kind, _rng(self.seed, _POOL_WORD + i),
                     int(kind["pool_rows"]))
        return np.concatenate([base, base], axis=1)

    def twin(self, stream: int) -> "Traffic":
        """The same mix and seed on another stream (the warm-up's): its
        own order and draws, and its own rotations of the same pools."""
        return Traffic(self.spec, self.seed, stream, self._pools)

    def _make_block(self, b: int) -> tuple:
        order = np.repeat(np.arange(len(self.kinds)), self._quota)
        order = _rng(self._order_seed, self.stream, b, 0).permutation(order)
        # each request's rank among its kind's, counted from request 0
        rank = np.empty(self.block, np.int64)
        seen = self._quota * b
        for j, kind in enumerate(order):
            rank[j] = seen[kind]
            seen[kind] += 1
        rows: list = [None] * self.block
        for d in range(self._groups):
            where = [j for j, m in enumerate(order) if self._group[m] == d]
            if where:
                data = _draw(self.kinds[order[where[0]]],
                             _rng(self.seed, self.stream, b, 1 + d),
                             len(where))
                for r, j in enumerate(where):
                    rows[j] = data[r]
        return order, rank, rows

    def prefill(self, requests: int) -> None:
        """Draw the blocks of the first ``requests`` requests now."""
        for b in range(-(-requests // self.block)):
            if b not in self._blocks:
                self._blocks[b] = self._make_block(b)

    def drawn(self, i: int) -> bool:
        """True when request ``i``'s block is already drawn."""
        return i // self.block in self._blocks

    def request(self, i: int) -> tuple[str, np.ndarray, int | None]:
        b, j = divmod(i, self.block)
        blk = self._blocks.get(b)
        if blk is None:
            blk = self._blocks[b] = self._make_block(b)
        order, rank, rows = blk
        m = int(order[j])
        kind = self.kinds[m]
        payload = rows[j]
        if payload is None:
            payload = self._rotation(m, kind, int(rank[j]))
        return kind["op"], payload, kind["k"]

    def _rotation(self, m: int, kind: dict, c: int) -> np.ndarray:
        pool_rows, n = int(kind["pool_rows"]), kind["n"]
        c += self.stream * (pool_rows * n // _STREAMS)
        turn, r = divmod(c, pool_rows)
        if turn >= n:
            raise ValueError("request index beyond the distinct rotations")
        s = (turn * _ROT_STRIDE) % n
        return self._pools[m][r, s:s + n]

    def step(self, j: int) -> tuple[float, int]:
        return self.schedule.step(j)


def make_traffic(spec: dict, seed: int, stream: int = WINDOW_STREAM):
    return Traffic(spec, seed, stream)
