"""Pallas TPU kernel: batched column-skipping in-memory sort (paper §III).

A (TB, N) tile of w-bit unsigned values is sorted per row with the full
hardware algorithm — iterative min-search with a k-entry state controller,
leading-uniform-column certification (s_top) and duplicate drain — carried as
loop state, with every mask/table living in VMEM-resident temporaries:

    1T1R array            -> (w, TB, ceil(N/32)) packed bit planes in VMEM
    CR (column read)      -> one plane-word fetch per traversed bit
    RE (wordline masking) -> alive-mask word update
    k-entry state table   -> k carried (sig, mask) entries (the near-memory SRAM)
    multi-bank manager    -> grid programs = banks; this kernel is one bank

Per-row CR/cycle counts are returned as telemetry — on hardware they ARE the
latency; here they feed the cost model and benchmarks.  The TPU-efficient path
for selection workloads is the radix_topk kernel; this kernel exists to run
the paper's exact control structure at tile granularity (and is the unit the
multi-bank tests shard).

NOTE on SIMD adaptation: rows traverse data-dependently different column
ranges; the kernel vectorizes by predicating each row's activity, so CR
telemetry stays per-row exact while a tile walks, each iteration, the planes
of its slowest unfinished row: from the highest start plane among the rows
still draining down to plane 0, and only until every row has drained.  The
planes above that start are inactive for every row, so skipping them is
exact.  A tile's wall-clock thus follows its slowest row, not all w planes of
every one of ``stop`` iterations — an explicitly recorded deviation from the
per-array hardware latency.  The bounds hold where no cross-bank gate
exists; the mesh realization keeps the fixed ``stop`` x ceil(w/fuse) walk
(see :func:`_run_machine`).

The default hot path is **lane-packed** (``packed=True``): the alive mask,
the sorted mask, and the k-entry table masks are carried as
``(…, ceil(N/32)) uint32`` words (:mod:`repro.core.bitmatrix`), and the w
bit planes of the tile are pre-packed once (outside the kernel) so a column
read is a word fetch instead of a (TB, N) shift — the software analogue of
the 1T1R column read returning 32 cells per word.  Drain positions live in a
``(TB, 32, W)`` layout (bit ``b`` of word ``i`` is element ``32 i + b``), so
the kernel never reshapes across lanes; the jitted wrapper turns them into
the (values, order) outputs with one XLA scatter and gather.  The dense
boolean machine (``packed=False``) is retained as the equivalence baseline;
both produce bit-identical values, order, CR, and cycle telemetry
(property-tested).  Only the packed carrier compiles for the TPU (Mosaic);
the dense one runs interpreted or under XLA.

Per-row state is kept as ``(TB, 1)`` columns and the state table as a tuple
of k entries with ``sig = -1`` marking an empty slot, so every loop carry is
an int32/uint32 vector — the forms Mosaic lowers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bitmatrix import LANE, pack_planes, packed_words, unpack_rows

from ..dispatch import resolve_interpret

TB = 8                           # rows per program: one (8, 128) sublane tile


def colskip_machine(u, w: int, k: int, stop: int, *,
                    or_any=None, drain_counts=None, packed: bool = True,
                    fuse: int = 1, vary=None):
    """Batched §III state machine, parameterized over the bank gates.

    ``u`` is one bank's (TB, N_local) column shard (the whole tile when run
    monolithically).  The two multi-bank-manager combine points are
    injectable so the same body serves both the single-bank Pallas kernel
    and the mesh-sharded realization (:mod:`repro.dist.bankmesh`):

      * ``or_any(bits)``   — OR per-row predicate stacks across banks
        ((TB, P) bool -> (TB, P) bool); identity for one bank;
      * ``drain_counts(m_local) -> (m_total, before)`` — global survivor
        count plus this bank's exclusive bank-major prefix, per row
        ((TB, 1) int32); ``(m, 0)`` for one bank.

    The gates see only small predicate stacks and survivor counts, so the
    same collectives serve the packed and dense carriers unchanged.  Under
    ``shard_map``, ``vary`` marks the initial (all-zero) masks as varying
    over the bank axes, as the masks the loop body returns are; per-row
    state is computed from the gates' outputs and stays replicated.

    ``fuse`` batches up to that many consecutive bit planes' predicate
    pairs into a single ``or_any`` round (the speculative tree of
    :func:`_traverse_planes`); results are bit-identical for any fuse, only
    the number of manager rounds changes.

    Returns ``(sorted_mask (TB, N), out_pos (TB, N), crs (TB,), drains
    (TB,))`` — local masks/positions plus replicated telemetry; callers
    assemble values/order from them.
    """
    if not 1 <= fuse <= 8:
        raise ValueError(f"fuse={fuse} out of range [1, 8]")
    u = u.astype(jnp.uint32)
    tb, n = u.shape
    if packed:
        planes = pack_planes(u, w)                         # (w, TB, W)
        carrier = _packed_carrier(lambda s: planes[s], n, planes.shape[-1])
    else:
        carrier = _dense_carrier(u)
    sorted_m, pos, crs, drains, _ = _run_machine(
        carrier, tb, w, k, stop, _list_gate(or_any), drain_counts, fuse,
        vary)
    if packed:
        sorted_m, pos = unpack_rows(sorted_m, n), _positions_from_words(pos, n)
    return sorted_m, pos, crs[:, 0], drains[:, 0]


def _list_gate(or_any):
    """Adapt a stacked ``(TB, P)`` OR gate to the machine's list of
    ``(TB, 1)`` predicates; no gate (one bank) stays None."""
    if or_any is None:
        return None

    def gate(cols):
        out = or_any(jnp.concatenate(cols, axis=-1))
        return [out[:, j:j + 1] for j in range(len(cols))]
    return gate


def _any_row(m):
    """Per-row "saw a bit" predicate of a mask: (TB, X) -> (TB, 1) bool."""
    return jnp.any(m != 0, axis=-1, keepdims=True)


def _bit_iota():
    return jax.lax.broadcasted_iota(jnp.int32, (1, LANE, 1), 1)


def _unpack_words3(words):
    """``(TB, W) uint32 -> (TB, 32, W) int32`` 0/1, bit ``b`` at ``[:, b]``."""
    return (words[:, None, :] >> _bit_iota().astype(jnp.uint32)) & 1


def _positions_from_words(pos3, n: int):
    """``(…, 32, W)`` drain positions -> element order ``(…, n)`` (XLA side)."""
    lead = pos3.shape[:-2]
    flat = jnp.swapaxes(pos3, -1, -2)
    return flat.reshape(lead + (flat.shape[-2] * LANE,))[..., :n]


def _repeat(body, init):
    """Run ``body(state) -> (state, more)`` until ``more`` is false."""
    return jax.lax.while_loop(lambda c: c[1], lambda c: body(c[0]),
                              (init, jnp.bool_(True)))[0]


class _Carrier:
    """How one mask representation stores, reads and drains the masks."""

    def __init__(self, zeros, pos_zeros, col, unsorted, count, drain,
                 any_row=_any_row, loop=jax.lax.fori_loop, repeat=_repeat):
        self.zeros = zeros          # tb -> empty mask
        self.pos_zeros = pos_zeros  # tb -> zero drain positions
        self.col = col              # sig -> bit-sig column mask
        self.unsorted = unsorted    # sorted mask -> unsorted elements
        self.count = count          # mask -> (TB, 1) int32 set elements
        # (alive, before, m_eff, count, pos) -> (drained mask, pos)
        self.drain = drain
        self.any_row = any_row      # mask -> (TB, 1) "saw a bit"
        self.loop = loop            # fori_loop(lo, hi, body, state)
        self.repeat = repeat        # repeat(body, state): see _repeat


def _in_vmem(loop, init):
    """Run ``loop(get, put)`` with its state in VMEM scratch, not in loop
    carries; returns the final state.

    Mosaic gives a carry the layout of its initial value, and a constant's
    layout is replicated, which the body's results cannot be relaid into;
    state that goes through memory has no such constraint."""
    leaves, tree = jax.tree.flatten(init)

    def scoped(*refs):
        for r, v in zip(refs, leaves):
            r[...] = v
        get = lambda: tree.unflatten([r[...] for r in refs])

        def put(st):
            for r, v in zip(refs, jax.tree.leaves(st)):
                r[...] = v
            return 0
        loop(get, put)
        return get()

    return pl.run_scoped(scoped, *[pltpu.VMEM(v.shape, v.dtype)
                                   for v in leaves])


def _vmem_loop(lo, hi, body, init):
    """``fori_loop`` whose state lives in VMEM scratch."""
    return _in_vmem(lambda get, put: jax.lax.fori_loop(
        lo, hi, lambda i, _: put(body(i, get())), 0), init)


def _vmem_repeat(body, init):
    """:func:`_repeat` with the state in VMEM scratch and ``more`` in the
    carry (an int32 scalar): in interpret mode a condition that read the
    scratch never saw the body's writes, and the loop did not end."""
    def loop(get, put):
        def step(_):
            st, more = body(get())
            put(st)
            return more.astype(jnp.int32)
        jax.lax.while_loop(lambda more: more > 0, step, jnp.int32(1))
    return _in_vmem(loop, init)


def _packed_carrier(col, n: int, nw: int, vmem: bool = False):
    """Lane-packed masks: ``(TB, W)`` uint32 words, element ``j`` in bit
    ``j % 32`` of word ``j // 32``; positions in the ``(TB, 32, W)`` layout.
    ``vmem`` keeps the loops' state in VMEM scratch (the Pallas kernel)."""
    i = jax.lax.broadcasted_iota(jnp.int32, (1, nw), 1)
    cnt = jnp.clip(n - LANE * i, 0, LANE)
    valid_w = jnp.where(cnt >= LANE, jnp.uint32(0xFFFFFFFF),
                        (jnp.uint32(1) << jnp.minimum(cnt, LANE - 1)
                         .astype(jnp.uint32)) - 1)                  # (1, W)
    # exclusive word-prefix of popcounts as one exact bf16 matmul: every
    # per-word count is <= 32 and the triangle is 0/1, both exact in bf16
    r = jax.lax.broadcasted_iota(jnp.int32, (nw, nw), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (nw, nw), 1)
    tri = (r < c).astype(jnp.bfloat16)
    below = jnp.uint32(0xFFFFFFFF) >> (
        jnp.uint32(LANE - 1) - _bit_iota().astype(jnp.uint32))  # (1, 32, 1)

    def popc(words):
        return jax.lax.population_count(words).astype(jnp.int32)

    def drain(alive, before, m_eff, count, pos3):
        cnt_w = popc(alive)                                         # (TB, W)
        if nw == 1:
            prefix = jnp.zeros_like(cnt_w)
        else:
            prefix = jnp.dot(cnt_w.astype(jnp.bfloat16), tri,
                             preferred_element_type=jnp.float32
                             ).astype(jnp.int32)
        # per-row scalars enter the (TB, 32, W) layout as (TB, 1, W) rows
        row3 = lambda r: jnp.broadcast_to(r, cnt_w.shape)[:, None, :]
        # 0-based bank-major drain rank of every set bit, (TB, 32, W)
        rank = (row3(before - 1) + prefix[:, None, :]
                + popc(alive[:, None, :] & below))
        bits = _unpack_words3(alive).astype(jnp.int32)
        keep3 = (bits == 1) & (rank < row3(m_eff))
        pos3 = jnp.where(keep3, row3(count) + rank, pos3)
        keep = jnp.sum(keep3.astype(jnp.int32) << _bit_iota(), axis=1)
        return keep.astype(jnp.uint32), pos3

    return _Carrier(
        zeros=lambda tb: jnp.zeros((tb, nw), jnp.uint32),
        pos_zeros=lambda tb: jnp.zeros((tb, LANE, nw), jnp.int32),
        col=col,
        unsorted=lambda s: ~s & valid_w,
        count=lambda m: jnp.sum(popc(m), axis=-1, keepdims=True),
        drain=drain, **(dict(loop=_vmem_loop, repeat=_vmem_repeat)
                        if vmem else {}))


def _dense_carrier(u):
    """Dense boolean masks, ``(TB, N)`` — the packed path's baseline."""
    n = u.shape[-1]

    def drain(alive, before, m_eff, count, pos):
        rank = before + jnp.cumsum(alive, -1) - 1
        keep = alive & (rank < m_eff)
        return keep, jnp.where(keep, count + rank, pos)

    return _Carrier(
        zeros=lambda tb: jnp.zeros((tb, n), bool),
        pos_zeros=lambda tb: jnp.zeros((tb, n), jnp.int32),
        col=lambda s: ((u >> s.astype(jnp.uint32)) & 1).astype(bool),
        unsorted=lambda s: ~s,
        count=lambda m: jnp.sum(m, axis=-1, keepdims=True, dtype=jnp.int32),
        drain=drain,
        any_row=lambda m: jnp.any(m, axis=-1, keepdims=True))


def _traverse_planes(car, alive, start, fresh, sigs, masks, s_top, crs, *,
                     w, k, tb, fuse, gate, first=0):
    """§III plane traversal from plane ``start`` down (one CR per plane),
    over the plane blocks from ``first`` on.

    Planes are walked in blocks of ``fuse``.  Within a block, plane ``i``'s
    saw-a-1/saw-a-0 pair is precomputed under every combination of the
    block's earlier mixed-column verdicts — a speculative tree of
    ``2^fuse - 1`` predicate pairs — so the whole block consumes ONE
    manager OR round instead of ``fuse``.  Verdicts then resolve locally,
    plane by plane, each one selecting the branch its successors read their
    precomputed pair from.  The tree enumerates every reachable alive mask
    exactly, so results are bit-identical for any ``fuse`` (property-tested
    in tests/test_bankmesh.py); ``fuse=1`` degenerates to the classic
    one-round-per-plane walk with an identical collective payload.
    """
    nblocks = -(-w // fuse)

    def block(bi, carry):
        alive, sigs, masks, s_top, seen, crs = carry
        sig0 = jnp.int32(w - 1) - bi * fuse
        # ghost planes of a partial last block fetch plane 0 (clamped) and
        # are discarded by the sig >= 0 guard in the verdict below
        cols = [car.col(jnp.maximum(sig0 - i, 0)) for i in range(fuse)]
        # speculative tree: branch index b over planes < i, bit j of b set
        # when plane j's verdict is hypothesized mixed
        hyps = [alive]
        pairs = []
        for i in range(fuse):
            for h in hyps:
                # (~col's tail bits are 1 but alive's are always 0)
                pairs.append(car.any_row(cols[i] & h))
                pairs.append(car.any_row(~cols[i] & h))
            if i + 1 < fuse:
                hyps = hyps + [h & ~cols[i] for h in hyps]
        anyb = gate(pairs)                    # 2 * (2^fuse - 1) x (TB, 1)
        branch = jnp.zeros((tb, 1), jnp.int32)
        for i in range(fuse):
            sig = sig0 - i
            active = (sig >= 0) & (sig <= start)           # (TB, 1)
            base = 2 * ((1 << i) - 1)
            p1, p0 = anyb[base], anyb[base + 1]
            for b in range(1, 1 << i):
                on = branch == b
                p1 = jnp.where(on, anyb[base + 2 * b], p1)
                p0 = jnp.where(on, anyb[base + 2 * b + 1], p0)
            mixed = active & p1 & p0                       # (TB, 1)
            branch = branch | (mixed.astype(jnp.int32) << i)
            new_alive = jnp.where(mixed, alive & ~cols[i], alive)
            if k > 0:
                # push (sig, mask) entry: shift the table toward older slots
                rec = mixed & fresh
                sigs = tuple(jnp.where(rec, s, old) for s, old in
                             zip((jnp.full((tb, 1), sig),) + sigs[:-1], sigs))
                masks = tuple(jnp.where(rec, m, old) for m, old in
                              zip((new_alive,) + masks[:-1], masks))
            first = mixed & fresh & (seen == 0)
            s_top = jnp.where(first, sig, s_top)
            seen = seen | first.astype(jnp.int32)
            crs = crs + active.astype(jnp.int32)
            alive = new_alive
        return alive, sigs, masks, s_top, seen, crs

    init = (alive, sigs, masks, s_top, jnp.zeros((tb, 1), jnp.int32), crs)
    out = car.loop(first, nblocks, block, init)
    return out[0], out[1], out[2], out[3], out[5]


def _run_machine(car, tb: int, w: int, k: int, stop: int, gate=None,
                 drain_counts=None, fuse: int = 1, vary=None):
    """The §III machine over one mask carrier.

    With no cross-bank ``gate`` (one bank) the loops follow the tile's own
    state: each traversal starts at the plane block of the highest start
    plane among the rows still draining (every plane above it is inactive
    for each of them, and the drained rows' results are discarded, so the
    skipped blocks change nothing), and the machine stops once every row
    has drained.  One reduction per iteration bounds both loops.  With a
    gate every bank must join every collective round, so the loops keep
    their fixed ``stop`` x ``ceil(w / fuse)`` trip counts.

    Returns ``(sorted_mask, positions, crs (TB, 1), drains (TB, 1), steps
    (TB, 1))`` in the carrier's own layout, ``steps`` being the plane steps
    walked (the same in every row); a table slot with ``sig < 0`` is
    empty."""
    bounded = gate is None
    if bounded:
        gate = lambda cols: cols
    if drain_counts is None:
        drain_counts = lambda m: (m, jnp.zeros_like(m))
    if vary is None:
        vary = lambda x: x
    kk = max(1, k)
    nblocks = -(-w // fuse)

    def load(sorted_m, sigs, masks):
        unsorted = car.unsorted(sorted_m)
        hit = gate([car.any_row(m & unsorted) for m in masks])  # SL gate
        live = [(s >= 0) & h for s, h in zip(sigs, hit)]
        # resume from the newest live entry; older entries stay valid
        alive, start = unsorted, jnp.full((tb, 1), -2, jnp.int32)
        for s, m, lv in reversed(list(zip(sigs, masks, live))):
            alive = jnp.where(lv, m & unsorted, alive)
            start = jnp.where(lv, s - 1, start)                # -2 -> s_top
        seen = jnp.zeros((tb, 1), bool)
        kept = []
        for s, lv in zip(sigs, live):
            seen = seen | lv
            kept.append(jnp.where(seen, s, -1))
        return alive, start, ~seen, tuple(kept)

    def body(st):
        sorted_m, sigs, masks, s_top, pos, count, crs, drains, steps = st
        done = count >= stop                                   # (TB, 1)
        alive, start, fresh, sigs = load(sorted_m, sigs, masks)
        start = jnp.where(start == -2, s_top, start)          # fresh rows
        first, more = 0, None
        if bounded:
            # the highest start plane of a pending row, plus 2; 0 once every
            # row has drained, which ends the loop after this no-op pass
            key = jnp.max(jnp.where(done, 0, start + 2))
            more = key > 0
            top = jnp.int32(w + 1) - key          # (w - 1) - highest start
            first = jnp.where(more, top // fuse if fuse > 1 else top, nblocks)
        alive, sigs, masks, s_top, crs2 = _traverse_planes(
            car, alive, start, fresh, sigs, masks, s_top,
            jnp.zeros((tb, 1), jnp.int32), w=w, k=k, tb=tb, fuse=fuse,
            gate=gate, first=first)
        # rows already finished must not mutate state or counters
        alive = jnp.where(done, jnp.zeros_like(alive), alive)
        crs = crs + jnp.where(done, 0, crs2)
        m_tot, before = drain_counts(car.count(alive))
        # k-early-exit: drain only the still-needed duplicates (bank-major)
        m_eff = jnp.minimum(m_tot, stop - count)
        keep, pos = car.drain(alive, before, m_eff, count, pos)
        return (sorted_m | keep, sigs, masks, s_top, pos, count + m_eff, crs,
                drains + jnp.maximum(m_eff - 1, 0),
                steps + (nblocks - first) * fuse), more

    empty = vary(car.zeros(tb))
    row0 = jnp.zeros((tb, 1), jnp.int32)
    st0 = (empty,
           (jnp.full((tb, 1), -1, jnp.int32),) * kk,  # table sigs (-1 empty)
           (empty,) * kk,                             # table masks
           jnp.full((tb, 1), w - 1, jnp.int32),       # s_top
           vary(car.pos_zeros(tb)),                   # pos
           row0, row0, row0, row0)                    # count, crs, drains, steps
    if bounded:       # every iteration drains >= 1 element of a pending row
        st = car.repeat(body, st0)
    else:
        st = car.loop(0, stop, lambda i, st: body(st)[0], st0)
    sorted_m, _, _, _, pos, _, crs, drains, steps = st
    return sorted_m, pos, crs, drains, steps


def _sort_kernel(w: int, k: int, stop: int, carrier, drained,
                 in_ref, pos_ref, tel_ref, cyc_ref):
    tb = pos_ref.shape[0]
    sorted_m, pos, crs, drains, steps = _run_machine(
        carrier(in_ref), tb, w, k, stop)
    # undrained elements (early exit) get position `stop`: dropped later
    pos_ref[...] = jnp.where(drained(sorted_m), pos, stop)
    # telemetry: per-row CRs, and the program's plane steps in its first row
    first_row = jax.lax.broadcasted_iota(jnp.int32, (tb, 1), 0) == 0
    tel_ref[:, 0:1] = crs
    tel_ref[:, 1:2] = jnp.where(first_row, steps, 0)
    cyc_ref[...] = crs + drains


@functools.partial(jax.jit,
                   static_argnames=("w", "k", "tb", "interpret", "stop_after",
                                    "packed", "plane_steps"))
def sort_pallas(x: jax.Array, w: int = 32, k: int = 2, tb: int = TB,
                interpret: bool | None = None, stop_after: int | None = None,
                packed: bool = True, plane_steps: bool = False):
    """Sort rows of ``x`` (B, N) uint32 ascending; returns
    (values, order, column_reads, cycles) with per-row telemetry.
    ``stop_after`` is the per-row k-early-exit drain (outputs (B, stop));
    ``packed=False`` selects the dense-boolean equivalence baseline.
    ``plane_steps=True`` returns column_reads as (B, 2): column 0 the
    per-row CRs, column 1 the plane steps each ``tb``-row program walked,
    in the program's first row (0 in its others), against the fixed
    loop's ``w * stop`` — one device array, so reading it back costs no
    extra copy.
    ``interpret=None`` resolves from the platform (compiled on TPU)."""
    interpret = resolve_interpret(interpret)
    if not (packed or interpret):
        raise ValueError("the dense carrier has no compiled kernel; run it "
                         "interpreted or on the XLA reference")
    b, n = x.shape
    stop = n if stop_after is None else min(int(stop_after), n)
    if stop < 1:
        raise ValueError(f"stop_after={stop_after} must be >= 1")
    x = x.astype(jnp.uint32)
    bp = (b + tb - 1) // tb * tb
    if bp != b:
        x = jnp.pad(x, ((0, bp - b), (0, 0)))
    rows = lambda *blk: pl.BlockSpec(blk, lambda i: (i,) + (0,) * (
        len(blk) - 1))
    if packed:
        # the kernel reads pre-packed bit planes, (w, TB, W) per program
        nw = packed_words(n)
        arg, pos_blk = pack_planes(x, w), (tb, LANE, nw)
        in_spec = pl.BlockSpec((w, tb, nw), lambda i: (0, i, 0))
        carrier = lambda ref: _packed_carrier(lambda s: ref[s], n, nw,
                                              vmem=True)
        drained = lambda sorted_w: _unpack_words3(sorted_w) == 1
    else:
        arg, pos_blk, in_spec = x, (tb, n), rows(tb, n)
        carrier = lambda ref: _dense_carrier(ref[...])
        drained = lambda sorted_m: sorted_m
    pos, tel, cyc = pl.pallas_call(
        functools.partial(_sort_kernel, w, k, stop, carrier, drained),
        grid=(bp // tb,),
        in_specs=[in_spec],
        out_specs=[rows(*pos_blk), rows(tb, 2), rows(tb, 1)],
        out_shape=[jax.ShapeDtypeStruct((bp,) + pos_blk[1:], jnp.int32),
                   jax.ShapeDtypeStruct((bp, 2), jnp.int32),
                   jax.ShapeDtypeStruct((bp, 1), jnp.int32)],
        interpret=interpret,
    )(arg)
    if packed:
        pos = _positions_from_words(pos, n)
    # assemble outputs in XLA: element j drained at position pos[j]
    row_ix = jnp.broadcast_to(jnp.arange(bp)[:, None], (bp, n))
    cols = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None, :], (bp, n))
    order = jnp.zeros((bp, stop), jnp.int32).at[row_ix, pos].set(
        cols, mode="drop")
    vals = jnp.take_along_axis(x, order, axis=1)
    return (vals[:b], order[:b], tel[:b] if plane_steps else tel[:b, 0],
            cyc[:b, 0])
