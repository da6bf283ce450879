"""Mesh-sharded bank pool: §IV multi-bank management on real devices.

The sortserve :class:`~repro.sortserve.scheduler.BankPool` is a single-process
model of the paper's bank manager — shard groups, drain policy, wave
execution.  This module is the distributed realization: a tile's columns are
sharded over a mesh axis (each device is one bank of the shard group) and the
column-skipping sort runs with the manager's OR-gates as collectives:

  * the mixed-column judgement is **one ``psum`` per bit plane** — the two
    saw-a-1 / saw-a-0 predicate bits of every bank, stacked and reduced
    together (the ``en_sync`` broadcast of the manager circuit);
  * state-table liveness (SL) is a ``psum`` of per-entry local hit bits;
  * the duplicate drain is bank-major: one gather of per-bank survivor
    counts (a psum of one-hot rows) gives every bank the exclusive prefix it
    needs to place its rows.

Because §V.C's result — bank management never changes the cycle count — holds
for the collective realization too, :class:`MeshBankPool` telemetry is
**bit-identical** to the single-process pool (asserted in tests), and the
backend may freely fall back to one bank when a tile's width does not divide
the mesh.

The serving engine drives its pool through the event-driven
:class:`~repro.sortserve.scheduler.ContinuousScheduler` (the only scheduler
since PR 5); `MeshBankPool` inherits the whole placement/readiness/drain
surface from :class:`~repro.sortserve.scheduler.BankPool`, so mesh-backed
banks take part in continuous admission — and in PR 5's watermark
backpressure — unchanged: tiles are granted device shard groups the moment
earlier mesh tiles drain, with no engine-batch flush barrier between them,
and the admission policy sees the mesh pool's queue depth and occupancy
through the identical signals (exercised by the ``--mesh`` CLI smoke and
tests/test_continuous.py).

Event-model invariants this module must preserve (pinned by
tests/test_bankmesh.py and tests/test_continuous.py):

1. **Virtual-time units** — mesh tiles report the same §V modeled-cycle
   telemetry as the local kernel, so their event-clock service durations
   (and therefore every admission decision) are identical to a local pool.
2. **Bank-cycle conservation** — §V.C on the mesh: one tile charges its
   cycle count to every device bank of its shard group, never more or less,
   so pool-wide ``busy_cycles`` is independent of device placement.
3. **Owner-scoped abort** — `MeshBankPool` adds no placement state outside
   `LogicalBank`, so `ContinuousScheduler.abort` releases device shard
   groups exactly like local banks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sortserve.scheduler import BankPool

__all__ = ["MeshBankPool", "collective_rounds", "colskip_sort_mesh",
           "make_bank_mesh", "sharded_tile_fn", "topology_fingerprint"]


def make_bank_mesh(devices=None, axis_name: str = "banks", *,
                   hosts: int = 1, host_axis: str = "hosts"):
    """Bank mesh over the given (default: all) devices.

    ``hosts=1`` (the default) builds the classic one-axis ``banks`` mesh.
    ``hosts>1`` builds the hierarchical 2-axis topology — a DCN ``hosts``
    axis over ICI ``banks`` shard groups — used by the multi-host serving
    path; the §IV manager gates then reduce over *both* axes (jax accepts
    axis-name tuples), so a tile's columns shard over every device of the
    2-D mesh while the predicate/drain semantics stay identical.
    """
    devs = list(devices if devices is not None else jax.devices())
    if hosts <= 1:
        return jax.make_mesh((len(devs),), (axis_name,), devices=devs)
    if len(devs) % hosts:
        raise ValueError(f"{len(devs)} devices not divisible over "
                         f"{hosts} hosts")
    return jax.make_mesh((hosts, len(devs) // hosts),
                         (host_axis, axis_name), devices=devs)


def topology_fingerprint(mesh) -> tuple:
    """Hashable identity of a mesh's *topology* rather than its object.

    Two meshes built over the same devices in the same arrangement — e.g.
    rebuilt after a fleet restart, or constructed independently by backend
    and pool — fingerprint equal, so executor/jit caches keyed on the
    fingerprint never double-compile them.  Captures axis names and sizes,
    the device platform/kind, and the participating process count (the
    DCN-vs-ICI split); everything the lowered executable's collectives
    actually specialize on.
    """
    devs = list(mesh.devices.flat)
    d0 = devs[0]
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
            getattr(d0, "platform", "?"), getattr(d0, "device_kind", "?"),
            len({getattr(d, "process_index", 0) for d in devs}),
            tuple(getattr(d, "id", i) for i, d in enumerate(devs)))


def _axes_tuple(axis_name) -> tuple:
    return tuple(axis_name) if isinstance(axis_name, (tuple, list)) \
        else (axis_name,)


def collective_rounds(w: int, stop: int, fuse: int = 1) -> dict:
    """Static per-tile manager-round accounting for the mesh hot path.

    Per §IV iteration: one SL-gate round (load), ``ceil(w / fuse)``
    traverse rounds (each fused block is a single psum), and one drain
    gather; plus the 2 assembly psums per tile.  ``planes`` is the
    plane-traversal count the unfused path would pay one round each for —
    ``rounds / planes`` is the mesh-side CR analogue the ``collectives``
    telemetry family reports.
    """
    blocks = -(-w // fuse)
    return {
        "rounds": stop * (blocks + 2) + 2,
        "unfused_rounds": stop * (w + 2) + 2,
        "planes": stop * w,
    }


def _colskip_tile_local(u_local, *, w: int, k: int, stop: int, axis_name,
                        packed: bool = True, fuse: int = 1):
    """Per-bank body of the sharded sort (called inside ``shard_map``).

    ``u_local``: (TB, N_local) — this bank's column shard of the tile.  The
    §III state machine itself is the shared
    :func:`repro.kernels.colskip.kernel.colskip_machine`; this wrapper only
    supplies the manager's combine points as collectives and assembles the
    global output.  Returns replicated ``(values (TB, stop), order (TB,
    stop), crs (TB,), cycles (TB,))`` matching the monolithic kernel
    bit-for-bit.
    """
    from repro.kernels.colskip.kernel import colskip_machine

    u = u_local.astype(jnp.uint32)
    tb, n_loc = u.shape
    axes = _axes_tuple(axis_name)      # ("banks",) or ("hosts", "banks")
    nbanks = jax.lax.psum(1, axes)                 # concrete: total banks
    bank = jax.lax.axis_index(axes)                # flat row-major index
    stop = min(stop, n_loc * nbanks)

    def or_any(local_bits):
        """Manager OR-gate: psum of stacked predicate bits — one collective
        per fused plane block (every branch's saw-a-1/saw-a-0 bits ride the
        same psum), reduced over the whole hosts x banks topology."""
        return jax.lax.psum(local_bits.astype(jnp.int32), axes) > 0

    def drain_counts(m_local):
        """Bank-major drain: every bank learns all survivor counts via one
        gather and takes its exclusive prefix.  The gather is a psum of
        one-hot rows (indexed by the flat ``axis_index`` above), so the
        counts come back replicated and the per-row drain state stays
        replicated too."""
        banks = jnp.arange(nbanks).reshape((-1,) + (1,) * m_local.ndim)
        m_all = jax.lax.psum(jnp.where(banks == bank, m_local, 0),
                             axes)                             # (C, TB, 1)
        before = jnp.where(banks < bank, m_all, 0).sum(0)      # (TB, 1)
        return m_all.sum(0), before

    def vary(x):
        """The masks start replicated (zeros) and end varying per bank."""
        return jax.lax.pcast(x, axes, to="varying")

    # the machine's mask carriers may be lane-packed; the manager gates above
    # see only predicate stacks and survivor counts either way, so the psum
    # pattern (one collective per fused block) is representation-invariant
    sorted_mask, out_pos, crs, drains = colskip_machine(
        u, w, k, stop, or_any=or_any, drain_counts=drain_counts,
        packed=packed, fuse=fuse, vary=vary)

    # output select: each bank scatters its drained rows into the global
    # (TB, stop) result; a psum assembles + broadcasts it (zeros elsewhere)
    rows = jnp.broadcast_to(jnp.arange(tb)[:, None], (tb, n_loc))
    cols = bank * n_loc + jnp.arange(n_loc, dtype=jnp.int32)[None, :]
    cols = jnp.broadcast_to(cols, (tb, n_loc))
    pos = jnp.where(sorted_mask, out_pos, stop)      # undrained -> dropped
    order_l = jnp.zeros((tb, stop), jnp.int32).at[rows, pos].set(
        cols, mode="drop")
    vals_l = jnp.zeros((tb, stop), jnp.uint32).at[rows, pos].set(
        u, mode="drop")
    order = jax.lax.psum(order_l, axes)
    vals = jax.lax.psum(vals_l, axes)
    return vals, order, crs, crs + drains


# keyed on topology_fingerprint(mesh) — NOT the mesh object — so two equal
# meshes (e.g. rebuilt after a fleet restart, or built independently by the
# backend and the pool) share one traced/compiled function
_SHARDED_FNS: dict = {}
_COMPILED_FNS: dict = {}


def _fn_key(mesh, axis_name, w, k, stop, packed, fuse):
    return (topology_fingerprint(mesh), _axes_tuple(axis_name),
            w, k, stop, packed, fuse)


def sharded_tile_fn(mesh, axis_name, w: int, k: int, stop: int,
                    packed: bool, fuse: int = 1):
    """The un-jitted shard-mapped tile body — callers pick how to compile
    it (plain ``jax.jit`` here; the sortserve backend AOT-compiles it into
    its executor cache so cold mesh tiles are visible as cache misses).
    ``axis_name`` may be one axis or a tuple (the 2-axis hosts topology)."""
    key = _fn_key(mesh, axis_name, w, k, stop, packed, fuse)
    fn = _SHARDED_FNS.get(key)
    if fn is None:
        axes = _axes_tuple(axis_name)
        body = functools.partial(_colskip_tile_local, w=w, k=k, stop=stop,
                                 axis_name=axes, packed=packed, fuse=fuse)
        fn = jax.shard_map(body, mesh=mesh, in_specs=P(None, axes),
                           out_specs=(P(), P(), P(), P()))
        _SHARDED_FNS[key] = fn
    return fn


def _compiled_tile_fn(mesh, axis_name, w: int, k: int, stop: int,
                      packed: bool, fuse: int = 1):
    key = _fn_key(mesh, axis_name, w, k, stop, packed, fuse)
    fn = _COMPILED_FNS.get(key)
    if fn is None:
        fn = jax.jit(sharded_tile_fn(mesh, axis_name, w, k, stop, packed,
                                     fuse))
        _COMPILED_FNS[key] = fn
    return fn


def colskip_sort_mesh(x, mesh, *, w: int = 32, k: int = 2,
                      axis_name="banks",
                      stop_after: int | None = None,
                      packed: bool = True, fuse: int = 1):
    """Sort rows of ``x`` (B, N) uint32 over the mesh's ``axis_name`` banks.

    Bit-identical to :func:`repro.kernels.colskip.colskip_sort_batched`
    (values, order, and CR/cycle telemetry) — §V.C's invariance of column
    skipping under multi-bank management, realized with collectives.  N must
    divide evenly over the axis (the product of sizes when ``axis_name`` is
    the 2-axis hosts tuple); callers fall back to one bank otherwise.
    ``packed`` selects the lane-packed mask carrier inside each bank;
    ``fuse`` batches that many bit planes per manager round (results are
    fuse-invariant, only ``collectives.rounds`` changes).
    """
    b, n = x.shape
    nbanks = 1
    for a in _axes_tuple(axis_name):
        nbanks *= mesh.shape[a]
    if n % nbanks:
        raise ValueError(f"N={n} not divisible over {nbanks} mesh banks")
    stop = n if stop_after is None else min(int(stop_after), n)
    if stop < 1:
        raise ValueError(f"stop_after={stop_after} must be >= 1")
    fn = _compiled_tile_fn(mesh, axis_name, w, k, stop, packed, fuse)
    return fn(jnp.asarray(x, jnp.uint32))


class MeshBankPool(BankPool):
    """A :class:`BankPool` whose shard groups execute on a jax device mesh.

    Placement, readiness gating, the drain policy, and wave execution are
    inherited unchanged — telemetry parity with the single-process pool is
    structural.  What changes is *where* a shard group's mixed-column
    judgement runs: the pool carries a one-axis device mesh, and the
    ``colskip_mesh`` backend executes each tile through
    :func:`colskip_sort_mesh` on it.  Logical banks and devices are distinct
    resources: the pool may model more banks than there are devices (several
    logical banks per device) — the §IV manager does not care, because the
    cycle count is bank-count invariant.
    """

    def __init__(self, banks: int = 8, bank_width: int = 1024,
                 bank_rows: int = 8, devices=None, axis_name: str = "banks",
                 hosts: int = 1, host_axis: str = "hosts"):
        super().__init__(banks, bank_width, bank_rows)
        self.mesh = make_bank_mesh(devices, axis_name, hosts=hosts,
                                   host_axis=host_axis)
        # the axis spec backends shard over: one name, or the 2-axis tuple
        # when the pool spans a DCN hosts axis
        self.axis_name = (host_axis, axis_name) if hosts > 1 else axis_name

    @property
    def n_devices(self) -> int:
        n = 1
        for a in _axes_tuple(self.axis_name):
            n *= self.mesh.shape[a]
        return n

    def bank_labels(self) -> list[str]:
        """Trace-export track names carrying the device each logical bank
        maps onto (banks cycle over the mesh axis when the pool models more
        banks than there are devices)."""
        devs = list(self.mesh.devices.flat)
        return [f"bank {b.index} @ {devs[b.index % len(devs)]}"
                for b in self.banks]
