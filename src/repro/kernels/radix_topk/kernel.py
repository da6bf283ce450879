"""Pallas TPU kernel: bit-plane radix top-k threshold descent with plane skip.

The paper's column-skipping min-search, re-tiled for the TPU memory
hierarchy:

  * the 1T1R bit-planar array becomes a ``(TB, N)`` tile of sortable-uint32
    values resident in VMEM;
  * a "column read" becomes one VPU pass over the tile (masked popcount of a
    bit plane);
  * the near-memory state controller becomes scalar loop state (prefix/need
    registers) carried through a ``fori_loop``;
  * **column skipping**: leading non-discriminating planes are certified by a
    one-pass per-row AND/OR reduction (the paper's all-0s/all-1s judgement,
    amortized over the whole tile) and the descent *starts below them* with
    the prefix pre-loaded from the AND register — the exact analogue of
    reloading a recorded RE state and resuming at column ``s-1``.

The kernel returns, per row, the sortable-uint32 value of the k-th largest
element (the selection threshold) plus the number of planes actually visited
(CR-count telemetry, reported by ``benchmarks/kernel_bench.py``).  Index
compaction happens outside (see ``ops.py``) — it is O(N) element ops and
bandwidth-bound either way.

Block shape guidance: ``(TB, N)`` must fit VMEM alongside ~4 (TB, N) u32
temporaries; with the default TB=8 a 16k-wide row tile costs ~2.5MB.  N must
be a multiple of 128 (lane width); TB a multiple of 8 (sublane) for packed
layouts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..dispatch import resolve_interpret

DEFAULT_TB = 8
MAX_N = 16384  # per-block trailing width; wider inputs are banked in ops.py


def _to_sortable(x):
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    mask = jnp.where(b >> 31 == 1, jnp.uint32(0xFFFFFFFF), jnp.uint32(0x80000000))
    return b ^ mask


def _or_and(u):
    """Per-row bitwise OR and AND of a (TB, N) uint32 tile, (TB, 1) each.

    Mosaic has no unsigned or bitwise reductions, so lane halves are
    folded with ``|`` / ``&`` while they stay 128-aligned, and each bit
    plane of what is left is one int32 max (OR) / min (AND)."""
    o = a = u
    while o.shape[1] % 256 == 0:
        h = o.shape[1] // 2
        o, a = o[:, :h] | o[:, h:], a[:, :h] & a[:, h:]
    v_or = v_and = jnp.uint32(0)
    for p in range(32):
        o_p = jnp.max(_plane(o, p), 1, keepdims=True).astype(jnp.uint32)
        a_p = jnp.min(_plane(a, p), 1, keepdims=True).astype(jnp.uint32)
        v_or, v_and = v_or | o_p << p, v_and | a_p << p
    return v_or, v_and


def _plane(u, p: int):
    """Bit ``p`` of every element, as int32 0/1."""
    return ((u >> p) & 1).astype(jnp.int32)


def _threshold_kernel(k: int, x_ref, thresh_ref, visited_ref):
    u = _to_sortable(x_ref[...])                       # (TB, N) uint32
    tb = u.shape[0]

    # --- certify leading uniform planes (the skippable columns) ----------
    u_or, u_and = _or_and(u)                           # (TB, 1) each
    mixed = u_or ^ u_and                               # per-row discriminating planes
    s_top = jnp.int32(-1)                              # -1 if constant
    for p in range(32):                                # highest mixed plane
        s_top = jnp.where(jnp.max(_plane(mixed, p)) > 0, p, s_top)

    # prefix pre-load: bits above s_top are uniform per row -> take from AND
    hi_of = lambda p: ~((jnp.uint32(1) << p.astype(jnp.uint32) << 1) - 1)
    hi0 = jnp.where(s_top >= 31, jnp.uint32(0),
                    jnp.where(s_top < 0, jnp.uint32(0xFFFFFFFF),
                              hi_of(jnp.maximum(s_top, 0))))
    prefix0 = u_and & hi0                              # (TB, 1)
    need0 = jnp.full((tb, 1), k, jnp.int32)

    def body(j, carry):
        prefix, need = carry
        plane = (s_top - j).astype(jnp.uint32)         # s_top, s_top-1, ..., 0
        bit = jnp.uint32(1) << plane
        hi_mask = ~((bit << jnp.uint32(1)) - jnp.uint32(1))
        cand = (u & hi_mask) == prefix
        c1 = jnp.sum((cand & ((u & bit) != 0)).astype(jnp.int32), axis=1,
                     keepdims=True)
        take_hi = c1 >= need
        prefix = jnp.where(take_hi, prefix | bit, prefix)
        need = jnp.where(take_hi, need, need - c1)
        return prefix, need

    n_planes = jnp.maximum(s_top + 1, 0)
    prefix, _ = jax.lax.fori_loop(0, n_planes, body, (prefix0, need0))
    thresh_ref[...] = prefix
    visited_ref[...] = jnp.full((tb, 1), n_planes, jnp.int32)


@functools.partial(jax.jit, static_argnames=("k", "tb", "interpret"))
def threshold_pallas(x: jax.Array, k: int, tb: int = DEFAULT_TB,
                     interpret: bool | None = None):
    """Per-row k-th-largest threshold (sortable-uint32) + planes-visited.

    ``x``: (B, N) float32, N <= MAX_N.  B is padded to a multiple of ``tb``.
    ``interpret=None`` resolves from the platform (compiled on TPU).
    """
    interpret = resolve_interpret(interpret)
    b, n = x.shape
    if n > MAX_N:
        raise ValueError(f"N={n} > MAX_N={MAX_N}; bank at the ops level")
    bp = (b + tb - 1) // tb * tb
    if bp != b:
        # pad rows with -inf so their thresholds are well-defined junk
        x = jnp.pad(x, ((0, bp - b), (0, 0)), constant_values=-jnp.inf)
    grid = (bp // tb,)
    thresh, visited = pl.pallas_call(
        functools.partial(_threshold_kernel, k),
        grid=grid,
        in_specs=[pl.BlockSpec((tb, n), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((tb, 1), lambda i: (i, 0)),
                   pl.BlockSpec((tb, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((bp, 1), jnp.uint32),
                   jax.ShapeDtypeStruct((bp, 1), jnp.int32)],
        interpret=interpret,
    )(x)
    return thresh[:b, 0], visited[:b, 0]
