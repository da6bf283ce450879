"""Pallas TPU kernel: batched bitonic sort network.

The paper's conventional-hardware baseline is a merge sorter (246.1 Kum^2,
10 cycles/number).  The TPU-native analogue of a hardware sorting network is
the bitonic network: log2(N)*(log2(N)+1)/2 compare-exchange passes, each a
full-width VPU pass over the (TB, N) tile in VMEM — fully SIMD, no
data-dependent control, the "dense" counterpart the column-skipping kernel
is compared against in benchmarks/kernel_bench.py.

Passes are unrolled at trace time (N static, power of two): stage k doubles
the sorted-run length, substage j exchanges lane i with lane i^j in the
direction given by bit k of i.  Lane i's partner i^j is fetched by two
lane rotations (``pltpu.roll`` by j and by N-j) and a select on bit j of an
iota — lane rotations are what the TPU's cross-lane unit does natively,
where a reshape to ``(TB, N/2j, 2, j)`` is refused by Mosaic, and a
``take_along_axis`` gather made XLA's CPU backend (used for interpret-mode
tests) compile the unrolled network pathologically slowly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..dispatch import resolve_interpret


def _bitonic_kernel(x_ref, out_ref):
    u = x_ref[...]                                # (TB, N) uint32
    tb, n = u.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (tb, n), 1)
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            lower = lane & j == 0                 # lane i pairs with i + j
            # one of the two rotations by j brings lane i^j to lane i;
            # rotating the lane iota too says which, whatever the direction
            fwd = pltpu.roll(lane, j, 1) == lane ^ j
            partner = jnp.where(fwd, pltpu.roll(u, j, 1),
                                pltpu.roll(u, n - j, 1))
            up = lane & k == 0                    # ascending region
            # keep the min where lower == up, else the max (Mosaic has no
            # unsigned min/max; an unsigned compare and a select do it)
            u = jnp.where((u < partner) == (lower == up), u, partner)
            j //= 2
        k *= 2
    out_ref[...] = u


@functools.partial(jax.jit, static_argnames=("tb", "interpret"))
def sort_pallas(x: jax.Array, tb: int = 8, interpret: bool | None = None):
    """Ascending sort of each row of ``x`` (B, N) uint32; N a power of two.
    ``interpret=None`` resolves from the platform (compiled on TPU)."""
    interpret = resolve_interpret(interpret)
    b, n = x.shape
    assert n & (n - 1) == 0, f"bitonic needs power-of-two N, got {n}"
    bp = (b + tb - 1) // tb * tb
    if bp != b:
        x = jnp.pad(x, ((0, bp - b), (0, 0)),
                    constant_values=jnp.uint32(0xFFFFFFFF))
    out = pl.pallas_call(
        _bitonic_kernel,
        grid=(bp // tb,),
        in_specs=[pl.BlockSpec((tb, n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tb, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, n), jnp.uint32),
        interpret=interpret,
    )(x.astype(jnp.uint32))
    return out[:b]


def n_passes(n: int) -> int:
    """Compare-exchange passes = log2(N)(log2(N)+1)/2 (the latency model)."""
    ln = n.bit_length() - 1
    return ln * (ln + 1) // 2
