"""JIT'd public ops over the radix_topk kernel.

``radix_topk`` is the framework's top-k engine (MoE routing, sampling,
gradient compression).  Dispatch policy:

  * On TPU the Pallas kernel computes thresholds (compiled, VMEM-tiled);
    everywhere else the pure-jnp oracle path is used — the algorithm is
    identical, so dry-run cost analysis remains representative
    (:mod:`repro.kernels.dispatch` resolves this once, from the platform).
  * Rows wider than ``kernel.MAX_N`` are split into *banks*; per-bank top-k
    candidates are concatenated and reduced by a second pass — exactly the
    paper's multi-bank management (sub-sorters + manager select), and exact
    because the global top-k is contained in the union of bank top-ks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.topk import (
    exact_k_mask,
    from_sortable_uint,
    kth_largest_sortable,
    to_sortable_uint,
)
from ..dispatch import resolve
from . import kernel as _k


def radix_topk_threshold(x: jax.Array, k: int, *, use_pallas: bool | None = None,
                         interpret: bool | None = None) -> jax.Array:
    """Sortable-uint32 threshold (k-th largest) per row of ``x`` (B, N)."""
    use_pallas, interpret = resolve(use_pallas, interpret)
    if use_pallas:
        t, _ = _k.threshold_pallas(x.astype(jnp.float32), k,
                                   interpret=interpret)
        return t
    return kth_largest_sortable(to_sortable_uint(x.astype(jnp.float32)), k)


def topk_mask_from_threshold(x: jax.Array, thresh: jax.Array, k: int) -> jax.Array:
    """Exact-k boolean mask from a per-row threshold; low-index tie-break."""
    u = to_sortable_uint(x.astype(jnp.float32))
    return exact_k_mask(u, thresh[..., None], k)


def compact_topk(x, u, mask, k):
    """Gather the k selected entries per row, ordered (value desc, index asc)."""
    b, n = u.shape
    slot = jnp.cumsum(mask, axis=-1) - 1                      # 0..k-1 per row
    rows = jnp.broadcast_to(jnp.arange(b)[:, None], (b, n))
    cols = jnp.broadcast_to(jnp.arange(n)[None, :], (b, n))
    slot = jnp.where(mask, slot, k)                           # k -> dropped
    vals_u = jnp.zeros((b, k + 1), jnp.uint32).at[rows, slot].set(
        jnp.broadcast_to(u, (b, n)), mode="drop")[:, :k]
    idxs = jnp.zeros((b, k + 1), jnp.int32).at[rows, slot].set(
        cols.astype(jnp.int32), mode="drop")[:, :k]
    # order by value desc, index asc: slots are already index-ascending, so a
    # stable sort on the inverted value alone preserves tie order (and stays
    # uint32 — no 64-bit keys, TPU-safe)
    order = jnp.argsort(~vals_u, axis=-1, stable=True)
    vals_u = jnp.take_along_axis(vals_u, order, axis=-1)
    idxs = jnp.take_along_axis(idxs, order, axis=-1)
    return from_sortable_uint(vals_u, x.dtype), idxs


@functools.partial(jax.jit, static_argnames=("k", "use_pallas", "interpret", "bank_width"))
def radix_topk(x: jax.Array, k: int, *, use_pallas: bool | None = None,
               interpret: bool | None = None, bank_width: int = _k.MAX_N):
    """Top-k (values, indices) over the trailing axis; lax.top_k semantics.

    Two-level multi-bank reduction for wide rows (vocab-scale sampling).
    """
    orig_shape = x.shape
    n = orig_shape[-1]
    xf = x.reshape((-1, n))
    b = xf.shape[0]

    if n <= bank_width:
        thresh = radix_topk_threshold(xf, k, use_pallas=use_pallas, interpret=interpret)
        mask = topk_mask_from_threshold(xf, thresh, k)
        vals, idxs = compact_topk(xf, to_sortable_uint(xf.astype(jnp.float32)), mask, k)
    else:
        # multi-bank: pad to C banks, per-bank top-k', manager-select pass
        c = -(-n // bank_width)
        npad = c * bank_width - n
        xp = jnp.pad(xf, ((0, 0), (0, npad)), constant_values=-jnp.inf)
        xb = xp.reshape(b * c, bank_width)
        kb = min(k, bank_width)
        tb_ = radix_topk_threshold(xb, kb, use_pallas=use_pallas, interpret=interpret)
        mb = topk_mask_from_threshold(xb, tb_, kb)
        vb, ib = compact_topk(xb, to_sortable_uint(xb.astype(jnp.float32)), mb, kb)
        # global index of each bank candidate
        bank_of = (jnp.arange(b * c, dtype=jnp.int32) % c)[:, None]
        gidx = ib + bank_of * bank_width
        cand_v = vb.reshape(b, c * kb)
        cand_i = gidx.reshape(b, c * kb)
        tg = radix_topk_threshold(cand_v, k, use_pallas=use_pallas, interpret=interpret)
        mg = topk_mask_from_threshold(cand_v, tg, k)
        # NOTE tie-break: bank candidates are (value desc, index asc) within
        # banks and banks are ordered, so low-global-index ties win, matching
        # lax.top_k.
        vals, slots = compact_topk(cand_v, to_sortable_uint(cand_v.astype(jnp.float32)), mg, k)
        idxs = jnp.take_along_axis(cand_i, slots, axis=-1)

    return (vals.reshape(orig_shape[:-1] + (k,)),
            idxs.reshape(orig_shape[:-1] + (k,)))
