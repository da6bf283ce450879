"""PartitionSpec rules for the model zoo (params, activations, caches).

Mesh vocabulary is fixed across the tree (see ``launch/mesh.py``):

  * ``"data"``  — batch / FSDP axis (weights are additionally sliced along it
    so no device ever holds a full copy of a large tensor);
  * ``"model"`` — tensor-parallel axis (vocab, FFN hidden, attention heads,
    MoE experts);
  * ``"pod"``   — optional pure data-replication axis across pods.

:func:`param_specs` is rule-based on the leaf's *path and shape*, not on a
per-arch table, so every config in ``repro.configs`` — dense, MoE, SSM,
hybrid, enc-dec, VLM — gets specs from the same small set of invariants:

  1. a dimension is only sharded when the axis size divides it exactly;
  2. matmul weights put ``"model"`` on their parallel dimension (out-features
     for up/gate/qkv projections, in-features for ``down``/``wo``, the expert
     axis for MoE banks, the vocab axis for embedding/head);
  3. any leaf big enough to matter (> 1 MiB) is additionally FSDP-sharded on
     ``"data"`` along its largest remaining divisible dimension, so no
     > 32 MiB leaf is ever fully replicated.

Passing ``axis_sizes`` with an impossible size (the ``serve_tp`` variant uses
``2**62``) disables an axis through rule 1 — that is how the dry-run turns
FSDP off for decode without a second rule set.
"""

from __future__ import annotations

import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["act_specs", "cache_spec", "dp_axes", "param_specs"]

DEFAULT_AXIS_SIZES = {"model": 16, "data": 16}
FSDP_MIN_BYTES = 1 << 20        # below this, replication is cheaper than comms

# projections whose parallel (model) dimension is the *input* features dim:
# they consume a model-sharded activation and produce the residual stream
_REDUCE_IN = {"down", "wo"}
# leaves that carry the vocabulary on some dimension
_VOCAB = {"embed", "head"}


def dp_axes(mesh) -> tuple:
    """Data-parallel axis names of a mesh, major-to-minor."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def _path_names(path) -> list:
    out = []
    for key in path:
        for attr in ("key", "name", "idx"):
            if hasattr(key, attr):
                out.append(str(getattr(key, attr)))
                break
    return out


def _leaf_spec(names: list, leaf, sizes: dict) -> P:
    shape = tuple(leaf.shape)
    nd = len(shape)
    name = names[-1] if names else ""
    assign: dict[int, str] = {}      # dim index -> axis name

    def fits(dim: int, axis: str) -> bool:
        n = sizes.get(axis, 0)
        return (0 <= dim < nd and dim not in assign
                and axis not in assign.values()
                and n > 1 and shape[dim] % n == 0)

    def take(dim: int, axis: str) -> bool:
        if fits(dim, axis):
            assign[dim] = axis
            return True
        return False

    # ---- rule 2: place the tensor-parallel axis -------------------------
    if nd >= 2:
        if "moe" in names and name in ("gate", "up", "down") and nd >= 3:
            take(nd - 3, "model")           # expert banks: shard the E axis
        elif name in _VOCAB:
            # vocab-parallel embedding / head: vocab is the larger dimension
            take(int(np.argmax(shape[-2:])) + nd - 2, "model")
        elif name in _REDUCE_IN:
            take(nd - 2, "model") or take(nd - 1, "model")
        else:
            take(nd - 1, "model") or take(nd - 2, "model")

    # ---- rule 3: FSDP on the largest remaining divisible dimension ------
    nbytes = int(np.prod(shape or (1,))) * jax.dtypes.canonicalize_dtype(
        leaf.dtype).itemsize
    if nbytes >= FSDP_MIN_BYTES:
        for dim in sorted(range(nd), key=lambda d: -shape[d]):
            if take(dim, "data"):
                break

    return P(*[assign.get(d) for d in range(nd)])


def param_specs(params, axis_sizes: dict | None = None):
    """Pytree of :class:`PartitionSpec`, congruent with ``params``.

    ``params`` may be real arrays or ``ShapeDtypeStruct``s (the dry-run path).
    ``axis_sizes`` maps axis name -> device count used for the divisibility
    rule; the default is the 16x16 production pod.
    """
    sizes = dict(DEFAULT_AXIS_SIZES if axis_sizes is None else axis_sizes)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = [_leaf_spec(_path_names(path), leaf, sizes) for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


def act_specs(mesh) -> dict:
    """Activation sharding constraints for the block boundaries.

    Keys are what ``models/*`` ask for via ``shard_act``: ``resid`` (B, S, d),
    ``tokens`` (T, d) flattened token streams, ``logits`` (B, S, V) with the
    padded vocab on ``model``.  ``mesh`` rides along so layers that need
    shard_map (the MoE expert-parallel path) can grab it.
    """
    dp = dp_axes(mesh) or None
    tp = "model" if "model" in mesh.axis_names else None

    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    return {
        "mesh": mesh,
        "resid": ns(dp, None, None),
        "tokens": ns(dp, None),
        "logits": ns(dp, None, tp),
    }


# decode-state leaf kinds (see models/api.cache_kinds) -> trailing dims after
# the leading (L, B) pair; the batch dim is the only one worth sharding for
# every family (head counts are often tiny and odd), so kinds only differ in
# rank here — kept as an explicit table so new cache layouts must opt in.
_CACHE_RANK = {
    "kv": 5,        # (L, B, T, KV, Dh)
    "kvscale": 4,   # (L, B, T, KV)
    "xkv": 5,       # (L, B, enc_ctx, KV, Dh)
    "wkv": 5,       # (L, B, H, Dh, Dh)
    "vec": 3,       # (L, B, d)
    "conv": 4,      # (L, B, d_conv-1, di)
    "ssm": 4,       # (L, B, di, state)
}


def cache_spec(mesh, batch: int, kind: str = "kv") -> P:
    """Spec for one decode-cache leaf: batch on the DP axes when divisible."""
    if kind not in _CACHE_RANK:
        raise KeyError(f"unknown cache kind {kind!r}; have {sorted(_CACHE_RANK)}")
    dp = dp_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    lead = dp if (dp and batch % dp_size == 0) else None
    rank = _CACHE_RANK[kind]
    return P(None, lead, *([None] * (rank - 2)))
