"""`repro.dist` — distributed execution subsystem.

The paper's §IV multi-bank manager combines per-bank bit-plane predicates
through OR-gates so C banks behave as one sorter; ``core/distsort.py``
realizes that circuit as ``psum``/``pmax`` collectives.  This package is the
layer that puts those collectives to work on an actual device mesh:

  * :mod:`repro.dist.sharding`  — PartitionSpec rules for the model zoo
    (params, activations, caches, batches);
  * :mod:`repro.dist.compress`  — error-feedback top-k gradient compression
    whose global threshold is the multi-bank OR-gate applied to training;
  * :mod:`repro.dist.pipeline`  — GPipe-style stage pipelining over a mesh
    axis (``ppermute`` ring);
  * :mod:`repro.dist.bankmesh`  — ``MeshBankPool``: the sortserve bank pool
    with shard groups mapped onto mesh devices, one ``psum`` per bit plane.
"""

from .compress import ef_topk_psum, ef_topk_psum_auto
from .sharding import act_specs, cache_spec, dp_axes, param_specs

__all__ = [
    "act_specs",
    "cache_spec",
    "dp_axes",
    "ef_topk_psum",
    "ef_topk_psum_auto",
    "param_specs",
]
