"""Pluggable execution backends + cost-model-driven selection policy.

A backend executes one :class:`~repro.sortserve.batcher.Tile` — a ``(B, N)``
uint32 array in the sortable domain — and returns values/indices plus
whatever hardware telemetry it can model:

  ============  ======================  ===================================
  backend       ops                     telemetry
  ============  ======================  ===================================
  ``colskip``   sort, argsort, kmin     exact per-row CRs + cycles from the
                                        §III state-recording hardware model
                                        (:func:`colskip_sort_batched`)
  ``radix_topk`` topk, kmin             per-row discriminating-plane reads —
                                        the SIMD dual of column skipping
                                        (:mod:`repro.kernels.radix_topk`;
                                        jnp engine off-TPU, same algorithm)
  ``jaxsort``   sort, argsort, kmin     none (XLA comparison sort; serves
                                        widths beyond the simulation cap)
  ``numpy``     all                     none (reference oracle)
  ============  ======================  ===================================

Selection is done by :class:`CostPolicy` using the §V cost model
(:mod:`repro.core.costmodel`): column-skipping needs roughly
``w / 4.08 ≈ 7.84`` CR cycles per number (the paper's k=2 anchor), while a
radix top-k descent reads at most ``w`` bit planes *total* per row plus one
compaction pass per selected element — so selection ops route to
``radix_topk`` whenever ``w + k < n * w / 4.08``, i.e. essentially always
for ``n > 8``.  For full sorts the hardware model always prefers colskip;
in software the cycle-exact simulator costs O(N·w) per *output* element, so
the policy starts from a ``sim_width_cap`` *prior*: rows wider than the cap
go to ``jaxsort`` (their hardware cycles are then *estimated* from the cost
model, not simulated).  The prior only rules until the policy has **measured
wall-clock** for both contenders on a tile signature — every execution feeds
a per-``(backend, op, width)`` EMA (:meth:`CostPolicy.observe`) and once
both sides of a decision are measured, the faster one wins regardless of the
cap (the ROADMAP's adaptive cost policy; the §V model keeps supplying
hardware-cycle telemetry either way).

Execution itself runs through a process-level :class:`ExecutorCache` of
AOT-compiled tile executors keyed by ``(backend, B, N, k, flags)`` — a
tile whose signature was seen before skips tracing/lowering entirely and
goes straight to the warm executable.  Each executor carries its
backend's name (HLO module ``jit_<name>``, ops under the ``<name>``
scope) and returns several 32-bit outputs packed in one buffer, and every
device backend runs a tile through one round trip, :func:`_round_trip` —
put, launch, wait, fetch — each step a profiler span
(:func:`repro.obs.tracer.span`).
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from repro.core import costmodel
from repro.core.costmodel import estimate_colskip_cycles
from repro.obs.tracer import span

from .batcher import Tile

__all__ = [
    "BACKENDS",
    "Backend",
    "CostPolicy",
    "EXECUTOR_CACHE",
    "ExecutorCache",
    "TileResult",
    "estimate_colskip_cycles",
    "register_backend",
    "resolve_backends",
    "solve_numpy",
]

class ExecutorCache:
    """Process-level cache of AOT-compiled tile executors.

    Keys are full tile signatures — ``(backend, B, N, k/stop, flags...)`` —
    and values are ``jax.jit(...).lower(...).compile()`` executables
    (:func:`_aot_compile`), so a warm hit pays neither tracing nor
    lowering nor dispatch-cache hashing.  The cache is process-global on
    purpose: engines come and go (benchmarks build them per pass) but
    compiled executables are reusable across all of them, exactly like the
    jit cache they wrap.  Hit/miss counters feed the serving telemetry.
    """

    def __init__(self):
        self._fns: dict = {}
        self._building: dict = {}         # key -> Event for in-flight builds
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        # the persistent layer underneath: an in-process miss that jax's
        # persistent compilation cache serves from disk is a deserialization,
        # not a compile — the split feeds executor_cache telemetry
        self.persistent_hits = 0
        self.persistent_misses = 0
        self.persistent_dir: str | None = None
        self._listener_installed = False

    def enable_persistent(self, cache_dir=None) -> bool:
        """Wire the JAX persistent compilation cache under this cache.

        Every AOT build (``jit().lower().compile()``) then writes its
        serialized executable to the cache directory; a fresh process
        pointed at the same directory deserializes instead of compiling,
        so warm starts survive restarts.  ``JAX_COMPILATION_CACHE_DIR``,
        when set, is that directory and no other is set here; otherwise
        ``cache_dir`` is.  Serving executors are small and fast to
        compile, so both entry thresholds drop to "cache everything", and
        a listener counts the ``cache_hits`` / ``cache_misses`` events.
        Returns False when there is no directory to use.  Idempotent —
        re-enabling only repoints the directory."""
        import jax
        from jax._src import monitoring

        cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or cache_dir
        if not cache_dir:
            return False
        if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
            jax.config.update("jax_compilation_cache_dir", str(cache_dir))
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        if not self._listener_installed:
            monitoring.register_event_listener(self._on_cache_event)
            self._listener_installed = True
        self.persistent_dir = str(cache_dir)
        return True

    def _on_cache_event(self, event, **kw):
        # jax monitoring stream: one event per compilation-cache lookup
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.persistent_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            with self._lock:
                self.persistent_misses += 1

    def persistent_counters(self) -> tuple[int, int]:
        with self._lock:
            return self.persistent_hits, self.persistent_misses

    def get(self, key, build):
        """Return ``(executor, warm)`` for ``key``, compiling on miss.

        ``warm`` is per-call truth (not a global-counter diff): False when
        this call compiled *or waited on* the build — either way its wall
        time is compile-dominated and must not feed the routing EMA.
        Concurrent misses on one key run a single build; the rest wait."""
        while True:
            with self._lock:
                fn = self._fns.get(key)
                if fn is not None:
                    self.hits += 1
                    return fn, True
                event = self._building.get(key)
                if event is None:
                    event = threading.Event()
                    self._building[key] = event
                    break                     # we build
            event.wait()                      # someone else is compiling
            with self._lock:
                fn = self._fns.get(key)
                if fn is not None:
                    return fn, False          # shared the compile's latency
            # builder failed: loop and take over the build
        fn = None
        try:
            with span("sortserve.compile", key=repr(key)):
                fn = build()                  # compile outside the lock
        finally:
            with self._lock:
                if fn is not None:
                    self._fns[key] = fn
                self.misses += 1
                self._building.pop(key, None)
                event.set()                   # waiters re-check (or rebuild)
        return fn, False

    def counters(self) -> tuple[int, int, int]:
        with self._lock:
            return self.hits, self.misses, len(self._fns)

    def clear(self) -> None:
        with self._lock:
            self._fns.clear()
            self.hits = self.misses = 0


EXECUTOR_CACHE = ExecutorCache()


def checkout_cache_dir() -> str:
    """The entry points' compile-cache directory when
    ``JAX_COMPILATION_CACHE_DIR`` is not set: ``<checkout>/.jax_cache``,
    a fixed path (the path is part of the cache key), git-ignored."""
    return str(Path(__file__).resolve().parents[3] / ".jax_cache")


class _Executor:
    """A compiled tile executor and where its outputs lie in the one
    buffer it returns.

    ``layout`` holds ``(shape, dtype, start, stop)`` for each output, as
    uint32 words of the packed buffer, or is None when the executor
    returns its outputs as they are.  Every other attribute is the
    compiled executable's (``as_text``, ``cost_analysis``, ...)."""

    __slots__ = ("compiled", "layout")

    def __init__(self, compiled, layout):
        self.compiled = compiled
        self.layout = layout

    def __call__(self, x):
        return self.compiled(x)

    def __getattr__(self, name):
        return getattr(self.compiled, name)


def _aot_compile(name: str, body, b: int, n: int) -> _Executor:
    """The executor ``name`` for a ``(b, n)`` uint32 tile:
    ``jax.jit(body).lower(...).compile()``.

    The jitted function is called ``name`` and its body runs under the
    ``name`` scope, so the HLO module is ``jit_<name>`` and a device trace
    can tell the executors apart.  A body with several outputs, all of
    32-bit dtypes, returns them packed: each bitcast to uint32, flattened
    and concatenated into one buffer, so that :func:`_round_trip` reads a
    tile back in one device->host copy, each copy having a fixed delay
    whatever its size.  The layout is recorded while the body is traced.
    No output aliases the tile (a packed buffer is 1-D, the one
    single-output body returns int32), so the tile is not donated."""
    import jax
    import jax.numpy as jnp

    layout = []

    def fn(x):
        with jax.named_scope(name):
            out = body(x)
            if not isinstance(out, tuple) or len(out) < 2 or any(
                    o.dtype.itemsize != 4 for o in out):
                return out
            start = 0
            for o in out:
                layout.append((o.shape, o.dtype, start, start + o.size))
                start += o.size
            return jnp.concatenate([
                jax.lax.bitcast_convert_type(o, jnp.uint32).ravel()
                for o in out])

    fn.__name__ = fn.__qualname__ = name
    compiled = jax.jit(fn).lower(
        jax.ShapeDtypeStruct((b, n), jnp.uint32)).compile()
    return _Executor(compiled, tuple(layout) or None)


def _round_trip(fn: _Executor, tile: Tile, staged=None) -> tuple:
    """Run one tile through its executor: the outputs as numpy arrays.

    Four steps, each a profiler span carrying the tile id: ``put`` copies
    the tile to the device (skipped when ``staged`` already holds it),
    ``launch`` enqueues the executor, ``wait`` blocks until the device is
    done, and ``fetch`` copies the outputs back to the host: one copy of a
    packed executor's buffer, split into views by its layout, or one copy
    per output.  The copies issued are left in
    ``tile.obs["readback_copies"]`` for the engine's counter."""
    import jax
    import jax.numpy as jnp

    seq = tile.obs.get("seq")
    with span("sortserve.execute.put", tile=seq):
        arr = staged if staged is not None else jnp.asarray(tile.data,
                                                            jnp.uint32)
    with span("sortserve.execute.launch", tile=seq):
        out = fn(arr)
    outs = out if isinstance(out, tuple) else (out,)
    with span("sortserve.execute.wait", tile=seq):
        jax.block_until_ready(outs)
    layout = fn.layout
    with span("sortserve.execute.fetch", tile=seq,
              arrays=len(outs) if layout is None else len(layout),
              copies=len(outs)):
        if layout is None:
            host = tuple(np.asarray(o) for o in outs)
        else:
            flat = np.asarray(out)
            host = tuple(flat[start:stop].view(dtype).reshape(shape)
                         for shape, dtype, start, stop in layout)
    tile.obs["readback_copies"] = len(outs)
    return host


def _compiled_colskip(b: int, n: int, w: int, state_k: int,
                      stop: int | None, use_pallas: bool,
                      interpret: bool, packed: bool):
    """Warm executor for one colskip tile signature.  The Pallas kernel's
    executor returns its CRs as (B, 2), the plane steps in column 1."""
    from repro.kernels.colskip import colskip_sort_batched

    key = ("colskip", b, n, w, state_k, stop, use_pallas, interpret, packed)
    return EXECUTOR_CACHE.get(key, lambda: _aot_compile(    # -> (fn, warm)
        "colskip", lambda x: colskip_sort_batched(
            x, w, state_k, use_pallas=use_pallas, interpret=interpret,
            stop_after=stop, packed=packed, plane_steps=use_pallas), b, n))


@dataclass
class TileResult:
    """Backend output for one tile (all arrays row-aligned with the tile)."""

    values: np.ndarray                  # (B, out) uint32, sortable domain
    indices: np.ndarray | None          # (B, out) int32 positions, or None
    column_reads: np.ndarray | None     # (B,) per-row CR/plane-read counts
    cycles: np.ndarray | None           # (B,) per-row HW cycles (exact only)
    backend: str
    estimated_cycles: float | None = None   # cost-model estimate when not exact
    meta: dict = field(default_factory=dict)

    def modeled_cycles(self) -> float | None:
        """The tile's total modeled-cycle count in the §V domain: the exact
        per-row cycle telemetry summed when the backend simulates it, the
        cost-model estimate otherwise, None when neither exists (numpy
        oracle, radix plane reads) — the denominator of the engine's
        measured-vs-modeled calibration ratio."""
        if self.cycles is not None:
            return float(int(self.cycles.sum()))
        if self.estimated_cycles is not None:
            return float(self.estimated_cycles)
        return None


def solve_numpy(op: str, u: np.ndarray, k: int | None) -> tuple[np.ndarray, np.ndarray]:
    """Reference answer for one encoded row: (values_u32, indices).

    Shared by the numpy backend, the engine's verify mode, and the CLI/test
    oracles, so "bit-identical to the numpy oracle" is a single definition.
    """
    u = np.asarray(u, dtype=np.uint32)
    if op in ("sort", "argsort"):
        idx = np.argsort(u, kind="stable").astype(np.int32)
        return u[idx], idx
    if op == "kmin":
        idx = np.argsort(u, kind="stable")[:k].astype(np.int32)
        return u[idx], idx
    if op == "topk":
        # descending value, ascending-index ties: stable sort on bitwise-not
        idx = np.argsort(~u, kind="stable")[:k].astype(np.int32)
        return u[idx], idx
    raise ValueError(f"unknown op {op!r}")


class Backend:
    """Base class: subclasses set ``name``/``ops`` and implement ``run``."""

    name: str = "?"
    ops: frozenset = frozenset()

    def run(self, tile: Tile) -> TileResult:  # pragma: no cover - interface
        raise NotImplementedError

    def warm(self, b: int, n: int, op: str, k: int | None) -> bool:
        """Pre-compile this backend's executor for a tile signature.

        Session prewarming (``SortServeEngine.begin(traffic_class=...)``)
        calls this for every signature in the class's recorded menu, so the
        first real tile of a new session lands on a warm executable.
        Returns True only when this call actually compiled (a cache miss) —
        an already-warm signature, or a backend with no AOT executor (the
        base class, the numpy oracle), returns False, so the engine's
        ``prewarmed`` counter measures real compiles."""
        return False

    def __repr__(self):
        return f"<{type(self).__name__} {self.name} ops={sorted(self.ops)}>"


BACKENDS: dict[str, type[Backend]] = {}


def register_backend(cls: type[Backend]) -> type[Backend]:
    BACKENDS[cls.name] = cls
    return cls


def resolve_backends(names, **kwargs) -> list[Backend]:
    """Instantiate backends by name; unknown names raise with the menu."""
    out = []
    for name in names:
        if name not in BACKENDS:
            raise KeyError(f"unknown backend {name!r}; have {sorted(BACKENDS)}")
        out.append(BACKENDS[name](**kwargs.get(name, {})))
    return out


@register_backend
class NumpyBackend(Backend):
    """Pure-numpy oracle; supports every op, models no hardware."""

    name = "numpy"
    ops = frozenset(("sort", "argsort", "topk", "kmin"))

    def run(self, tile: Tile) -> TileResult:
        b, _ = tile.data.shape
        out = tile.k if tile.op in ("topk", "kmin") else tile.data.shape[1]
        vals = np.empty((b, out), np.uint32)
        idxs = np.empty((b, out), np.int32)
        for r in range(b):
            vals[r], idxs[r] = solve_numpy(tile.op, tile.data[r], tile.k)
        return TileResult(vals, idxs, None, None, self.name)


@register_backend
class ColskipBackend(Backend):
    """Cycle-exact column-skipping sorter (§III hardware model, batched).

    ``kmin`` runs the k-early-exit drain: the hardware model stops after the
    tile's k minima have drained, so the simulated CR/cycle telemetry covers
    only the executed iterations instead of a complete sort.
    """

    name = "colskip"
    ops = frozenset(("sort", "argsort", "kmin"))

    def __init__(self, w: int = 32, state_k: int = 2,
                 use_pallas: bool | None = None,
                 interpret: bool | None = None, packed: bool = True):
        from repro.kernels.colskip.ops import resolve_colskip
        self.w = w
        self.state_k = state_k
        # resolved once from the platform: compiled Pallas on TPU
        self.use_pallas, self.interpret, self.impl = resolve_colskip(
            use_pallas, interpret, packed)
        self.packed = packed

    def run(self, tile: Tile) -> TileResult:
        stop = tile.k if tile.op == "kmin" else None
        b, n = tile.data.shape
        fn, warm = _compiled_colskip(b, n, self.w, self.state_k, stop,
                                     self.use_pallas, self.interpret,
                                     self.packed)
        vals, order, crs, cycles = _round_trip(fn, tile)
        meta = {"w": self.w, "state_k": self.state_k, "stop_after": stop,
                "packed": self.packed, "impl": self.impl, "exec_warm": warm}
        if self.use_pallas:
            # plane steps the kernel walked, against its fixed w x stop per
            # TB-row program
            from repro.kernels.colskip.kernel import TB
            crs, steps = crs[:, 0], crs[:, 1]
            meta["plane_steps"] = {
                "run": int(steps.sum()),
                "slots": -(-b // TB) * self.w * min(stop or n, n)}
        return TileResult(vals, np.asarray(order, np.int32),
                          np.asarray(crs, np.int64), np.asarray(cycles, np.int64),
                          self.name, meta=meta)

    def warm(self, b: int, n: int, op: str, k: int | None) -> bool:
        stop = k if op == "kmin" else None
        _, hit = _compiled_colskip(b, n, self.w, self.state_k, stop,
                                   self.use_pallas, self.interpret,
                                   self.packed)
        return not hit


@register_backend
class ShardedColskipBackend(Backend):
    """Column-skipping sorter over a jax device mesh (§IV on real devices).

    Executes each tile through :func:`repro.dist.bankmesh.colskip_sort_mesh`:
    columns sharded over the mesh's bank axis, mixed-column judgement as one
    ``psum`` per bit plane.  Values, order, and CR/cycle telemetry are
    bit-identical to :class:`ColskipBackend` — §V.C's invariance of column
    skipping under multi-bank management — so the cost policy treats both
    simulators interchangeably.  Tiles whose width does not divide over the
    mesh run on one bank (same telemetry, by the same invariance).
    """

    name = "colskip_mesh"
    ops = frozenset(("sort", "argsort", "kmin"))

    def __init__(self, w: int = 32, state_k: int = 2, mesh=None,
                 axis_name="banks", packed: bool = True, fuse: int = 1):
        from repro.dist.bankmesh import make_bank_mesh, topology_fingerprint
        self.w = w
        self.state_k = state_k
        self.axis_name = axis_name
        self.packed = packed
        self.fuse = fuse
        self.mesh = mesh if mesh is not None else make_bank_mesh(
            axis_name=axis_name)
        # executor keys carry the topology fingerprint, NOT the mesh object:
        # an equal mesh rebuilt after a restart must hit, not recompile
        self._fingerprint = topology_fingerprint(self.mesh)
        # double buffer: id(tile) -> (tile, device array) staged by
        # prefetch() while the previous tile traverses planes.  Two slots,
        # FIFO-evicted: admitting tile X stages its successor Y before X
        # executes, so X's own staged entry must survive one more staging
        self._staged: dict = {}

    def _axes(self) -> tuple:
        return (tuple(self.axis_name)
                if isinstance(self.axis_name, (tuple, list))
                else (self.axis_name,))

    @property
    def n_devices(self) -> int:
        n = 1
        for a in self._axes():
            n *= self.mesh.shape[a]
        return n

    def _mesh_key(self, b: int, n: int, stop_eff: int) -> tuple:
        return ("colskip_mesh", b, n, self.w, self.state_k, stop_eff,
                self.packed, self.fuse, self._axes(), self._fingerprint)

    def _mesh_executor(self, b: int, n: int, stop_eff: int):
        from repro.dist.bankmesh import sharded_tile_fn
        # AOT-compiled through the executor cache (like the local
        # backends), so a cold mesh tile is visible as a cache miss —
        # the engine's warm-only EMA gate depends on that
        return EXECUTOR_CACHE.get(self._mesh_key(b, n, stop_eff),
                                  lambda: _aot_compile(
            "colskip_mesh",
            sharded_tile_fn(self.mesh, self.axis_name, self.w,
                            self.state_k, stop_eff, self.packed, self.fuse),
            b, n))

    def prefetch(self, tile: Tile) -> bool:
        """Stage the next tile's device transfer (double buffering).

        Called by the scheduler right before the current tile executes:
        ``jnp.asarray`` dispatches the host->device copy asynchronously, so
        the next tile's column shard lands while the current tile traverses
        planes.  The staged array is exactly what :meth:`run` would build —
        the compiled call path is unchanged.  Two slots, oldest evicted;
        restaging a tile refreshes it.  Returns True when a transfer was
        staged."""
        import jax.numpy as jnp
        n = tile.data.shape[1]
        if n % self.n_devices != 0 or self.n_devices <= 1:
            return False                 # one-bank fallback: nothing to hide
        self._staged.pop(id(tile), None)
        self._staged[id(tile)] = (tile, jnp.asarray(tile.data, jnp.uint32))
        while len(self._staged) > 2:
            del self._staged[next(iter(self._staged))]
        return True

    def run(self, tile: Tile) -> TileResult:
        from repro.dist.bankmesh import collective_rounds
        b, n = tile.data.shape
        n_dev = self.n_devices
        stop = tile.k if tile.op == "kmin" else None
        staged = self._staged.pop(id(tile), None)
        # the identity re-check guards id() reuse after a staged tile died
        prefetch_hit = staged is not None and staged[0] is tile
        coll = {"coll_rounds": 0, "coll_planes": 0, "coll_unfused_rounds": 0}
        if n % n_dev == 0 and n_dev > 1:
            stop_eff = min(stop, n) if stop is not None else n
            fn, warm = self._mesh_executor(b, n, stop_eff)
            vals, order, crs, cycles = _round_trip(
                fn, tile, staged[1] if prefetch_hit else None)
            banks_used = n_dev
            rounds = collective_rounds(self.w, stop_eff, self.fuse)
            coll = {"coll_rounds": rounds["rounds"],
                    "coll_planes": rounds["planes"],
                    "coll_unfused_rounds": rounds["unfused_rounds"]}
        else:
            fn, warm = _compiled_colskip(b, n, self.w, self.state_k, stop,
                                         False, None, self.packed)
            vals, order, crs, cycles = _round_trip(fn, tile)
            banks_used = 1
        return TileResult(vals, np.asarray(order, np.int32),
                          np.asarray(crs, np.int64),
                          np.asarray(cycles, np.int64), self.name,
                          meta={"w": self.w, "state_k": self.state_k,
                                "stop_after": stop, "mesh_banks": banks_used,
                                "packed": self.packed, "exec_warm": warm,
                                "impl": "xla", "fuse": self.fuse,
                                "prefetch_hit": prefetch_hit, **coll})

    def warm(self, b: int, n: int, op: str, k: int | None) -> bool:
        stop = k if op == "kmin" else None
        if n % self.n_devices == 0 and self.n_devices > 1:
            stop_eff = min(stop, n) if stop is not None else n
            _, hit = self._mesh_executor(b, n, stop_eff)
        else:
            _, hit = _compiled_colskip(b, n, self.w, self.state_k, stop,
                                       False, None, self.packed)
        return not hit


@register_backend
class RadixTopkBackend(Backend):
    """Bit-plane radix selection in the sortable-uint32 domain.

    Off-TPU this uses the pure-jnp engine (:mod:`repro.core.topk`) that is
    also the Pallas kernel's oracle — identical algorithm, so the
    discriminating-plane telemetry (the SIMD analogue of the paper's
    skippable uniform columns) is representative either way.  ``kmin`` is
    served as top-k on the bitwise complement (order reversal in uint32),
    which preserves the ascending-index tie-break exactly.
    """

    name = "radix_topk"
    ops = frozenset(("topk", "kmin"))

    @staticmethod
    def _executor(b: int, n: int, k: int, kmin: bool):
        return EXECUTOR_CACHE.get(("radix_topk", b, n, k, kmin),
                                  lambda: _aot_compile(
            "radix_topk", lambda x: _radix_select(x, k, kmin), b, n))

    def run(self, tile: Tile) -> TileResult:
        b, n = tile.data.shape
        fn, warm = self._executor(b, n, tile.k, tile.op == "kmin")
        vals, idxs, reads = _round_trip(fn, tile)
        reads = np.asarray(reads, np.int64)
        return TileResult(vals, np.asarray(idxs, np.int32),
                          reads, None, self.name,
                          meta={"planes_max": int(reads.max(initial=0)),
                                "exec_warm": warm})

    def warm(self, b: int, n: int, op: str, k: int | None) -> bool:
        if k is None:
            return False                    # selection ops always carry k
        _, hit = self._executor(b, n, k, op == "kmin")
        return not hit


@register_backend
class JaxSortBackend(Backend):
    """XLA comparison sort — the wide-row fallback past the simulation cap."""

    name = "jaxsort"
    ops = frozenset(("sort", "argsort", "kmin"))

    @staticmethod
    def _executor(b: int, n: int):
        import jax.numpy as jnp

        return EXECUTOR_CACHE.get(("jaxsort", b, n), lambda: _aot_compile(
            "jaxsort", lambda x: jnp.argsort(x, axis=-1, stable=True), b, n))

    def run(self, tile: Tile) -> TileResult:
        b, n = tile.data.shape
        fn, warm = self._executor(b, n)
        (order,) = _round_trip(fn, tile)
        order = np.asarray(order, np.int32)
        vals = np.take_along_axis(tile.data, order, axis=-1)
        if tile.op == "kmin":
            vals, order = vals[:, :tile.k], order[:, :tile.k]
        est = estimate_colskip_cycles(n) * b
        return TileResult(vals, order, None, None, self.name,
                          estimated_cycles=est, meta={"exec_warm": warm})

    def warm(self, b: int, n: int, op: str, k: int | None) -> bool:
        self._executor(b, n)
        return True


def _radix_select(u, k: int, kmin: bool):
    """Jitted tile body: (B, N) sortable-uint -> (values, indices, plane reads).

    ``kmin`` selects the k smallest by descending on the bitwise complement
    (an order reversal in uint32), then complements the values back.
    """
    from repro.core.topk import (
        discriminating_planes,
        exact_k_mask,
        kth_largest_sortable,
    )
    from repro.kernels.radix_topk.ops import compact_topk

    d = ~u if kmin else u
    thresh = kth_largest_sortable(d, k)[..., None]
    mask = exact_k_mask(d, thresh, k)
    vals, idxs = compact_topk(d, d, mask, k)
    if kmin:
        vals = ~vals
    # one CR per discriminating plane per row; uniform planes are skipped
    reads = discriminating_planes(u).sum(axis=-1)
    return vals, idxs, reads


class CostPolicy:
    """Route each tile to the cheapest capable backend (see module docstring).

    Two-layer decision:

      1. **Measured** — every executed tile feeds a per-``(backend, op,
         width)`` wall-clock EMA via :meth:`observe`; when both contenders
         of a decision are measured, the lower EMA wins outright.
      2. **Prior** — with no (or one-sided) measurements the §V cost model
         anchors and the static ``sim_width_cap`` software guard decide,
         exactly as before.  Once the prior's pick has been measured
         ``explore_after`` times while the alternative never ran, the policy
         routes one tile to the alternative so the comparison becomes
         measured (bounded exploration; disable with ``adaptive=False``).

    Sessions opened with a **traffic class** keep private per-class EMA
    priors on top of the engine-global one (a class's widths/ops can race
    differently from the aggregate stream); the global prior is always fed
    too and serves as the fallback until the class has its own samples.
    """

    def __init__(self, backends, sim_width_cap: int = 2048, w: int = 32, *,
                 adaptive: bool = True, ema_alpha: float = 0.25,
                 explore_after: int = 16):
        self.backends = list(backends)
        self.by_name = {b.name: b for b in self.backends}
        self.sim_width_cap = sim_width_cap
        self.w = w
        self.adaptive = adaptive
        self.ema_alpha = float(ema_alpha)
        self.explore_after = int(explore_after)
        # (backend, op, N, k, traffic_class) -> s/row EMA / sample count;
        # traffic_class None is the engine-global prior every class falls
        # back to until its own stream has been measured
        self._ema: dict[tuple, float] = {}
        self._obs: dict[tuple, int] = {}

    # ------------------------------------------------------------ measured
    def observe(self, backend_name: str, op: str, n: int, rows: int,
                wall_s: float, k: int | None = None,
                traffic_class: str | None = None) -> None:
        """Feed one measured tile execution into the per-signature EMA.

        ``k`` is part of the signature: a kmin tile's simulator cost scales
        with its drain count, so different k must never share an EMA.
        ``traffic_class`` additionally updates that class's private prior
        (sessions opened with ``begin(traffic_class=...)``) — the global
        (class-None) EMA is always updated too, so unclassified traffic
        keeps learning from every execution."""
        per_row = wall_s / max(1, rows)
        for cls in ({None, traffic_class} if traffic_class is not None
                    else (None,)):
            key = (backend_name, op, int(n), k, cls)
            prev = self._ema.get(key)
            self._ema[key] = per_row if prev is None else (
                (1.0 - self.ema_alpha) * prev + self.ema_alpha * per_row)
            self._obs[key] = self._obs.get(key, 0) + 1

    def export_priors(self, include_classes: bool = False) -> list[dict]:
        """The measured EMAs as a portable profile (the ``priors`` block
        of an hw_tune profile).  By default class-private EMAs are
        excluded — they describe one session's traffic — matching the
        hw_tune contract.  ``include_classes=True`` keeps them (with a
        ``traffic_class`` field on every row) for warm-state artifacts
        (:mod:`repro.sortserve.fleet`), where per-class priors are exactly
        the point of persisting."""
        out = []
        for key in sorted(self._ema, key=repr):
            backend, op, n, k, cls = key
            if cls is not None and not include_classes:
                continue
            row = {"backend": backend, "op": op, "n": n, "k": k,
                   "s_per_row": self._ema[key],
                   "samples": self._obs.get(key, 0)}
            if include_classes:
                row["traffic_class"] = cls
            out.append(row)
        return out

    def load_priors(self, priors) -> int:
        """Seed EMAs from a measured profile (``scripts/hw_tune.py`` or a
        warm-state artifact).  Live measurements outrank the profile:
        a signature that already has samples is left alone, and every
        loaded prior keeps updating from real traffic through
        :meth:`observe`.  Rows without a ``traffic_class`` field seed the
        engine-global prior; rows with one seed that class's private EMA.
        Returns the number of signatures seeded."""
        count = 0
        for p in priors:
            cls = p.get("traffic_class")
            key = (p["backend"], p["op"], int(p["n"]),
                   None if p.get("k") is None else int(p["k"]),
                   None if cls is None else str(cls))
            if key in self._ema:
                continue
            self._ema[key] = float(p["s_per_row"])
            self._obs[key] = max(1, int(p.get("samples", 1)))
            count += 1
        return count

    def measured_s_per_row(self, backend_name: str, op: str, n: int,
                           k: int | None = None,
                           traffic_class: str | None = None) -> float | None:
        """Current EMA for a signature (class-specific first, then the
        global prior), or None if never executed."""
        if traffic_class is not None:
            v = self._ema.get((backend_name, op, int(n), k, traffic_class))
            if v is not None:
                return v
        return self._ema.get((backend_name, op, int(n), k, None))

    def _pick_measured(self, a: Backend, b: Backend, op: str, n: int,
                       k: int | None, allow_explore: bool = True,
                       traffic_class: str | None = None):
        """Measured EMA comparison / bounded exploration between a (the
        prior's pick) and b (the alternative); None -> keep the prior."""
        if not self.adaptive or b is None:
            return None
        ea = self.measured_s_per_row(a.name, op, n, k, traffic_class)
        eb = self.measured_s_per_row(b.name, op, n, k, traffic_class)
        if ea is not None and eb is not None:
            return a if ea <= eb else b
        if allow_explore and eb is None and \
                self._obs.get((a.name, op, int(n), k, None),
                              0) >= self.explore_after:
            return b                        # one probe makes it a measured race
        return None

    # --------------------------------------------------------------- prior
    def modeled_throughput(self, n: int, state_k: int = 2,
                           banks: int = 1) -> float:
        """Numbers/s the modeled hardware would sustain on this width."""
        cpn = estimate_colskip_cycles(n, self.w) / n
        return costmodel.colskip_cost(cpn, n=n, w=self.w, k=state_k,
                                      banks=banks).throughput_num_per_s

    def choose(self, tile: Tile,
               traffic_class: str | None = None) -> Backend:
        if tile.hint is not None:       # hints are uniform per tile (bucket key)
            if tile.hint not in self.by_name:
                raise KeyError(f"hinted backend {tile.hint!r} not enabled")
            be = self.by_name[tile.hint]
            if tile.op not in be.ops:
                raise ValueError(f"backend {tile.hint!r} cannot serve {tile.op!r}")
            return be
        cands = [b for b in self.backends if tile.op in b.ops]
        if not cands:
            raise ValueError(f"no enabled backend serves op {tile.op!r}")
        n = tile.data.shape[1]
        if tile.op in ("topk", "kmin"):
            # radix descent: <= w plane reads + k compaction passes per row,
            # vs colskip's ~ n*w/4.08 CR cycles for the full min-search sort.
            radix_cost = self.w + (tile.k or 0)
            if radix_cost < estimate_colskip_cycles(n, self.w):
                for b in cands:
                    if b.name == "radix_topk":
                        return b
        by_name = {b.name: b for b in cands}
        # both cycle-exact simulators (local and mesh-sharded) rank the same:
        # §V.C — bank management never changes the modeled latency
        sim = next((by_name[nm] for nm in ("colskip", "colskip_mesh")
                    if nm in by_name), None)
        fast = next((by_name[nm] for nm in ("jaxsort", "numpy")
                     if nm in by_name), None)
        if sim is not None and fast is not None:
            # prior: simulate up to the cap; measured EMAs override it.  An
            # exploration probe *toward the simulator* is only allowed within
            # 2x the cap — the sim is O(N*w) per output element, and a probe
            # at arbitrary width would stall the engine for exactly the
            # pathological case the cap exists to prevent.
            prior, alt = (sim, fast) if n <= self.sim_width_cap else (fast, sim)
            allow = alt is not sim or n <= 2 * self.sim_width_cap
            return self._pick_measured(prior, alt, tile.op, n, tile.k,
                                       allow, traffic_class) or prior
        if sim is not None and n <= self.sim_width_cap:
            return sim                    # cycle-exact simulation, affordable
        # past the cap: any non-simulating backend before the O(N*w)-per-
        # output simulator, which is only a last resort
        if fast is not None:
            return fast
        return sim if sim is not None else cands[0]
