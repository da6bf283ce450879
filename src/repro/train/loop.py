"""Training step factory (pjit-ready, donated state, remat inside models).

Two step builders:

  * :func:`make_train_step` — the single-logical-replica step jit/pjit runs
    under GSPMD (the dry-run path); an optional ``grad_reduce`` hook lets a
    wrapper intercept gradients before the optimizer;
  * :func:`make_dp_train_step` — explicit ``shard_map`` data parallelism over
    a mesh axis, with optional error-feedback top-k gradient compression
    (:func:`repro.dist.compress.ef_topk_psum_tree`): the paper's multi-bank
    OR-gate picks one global sparsification threshold across ranks, selected
    entries ride a dense ``psum``, residuals stay local in the ``"ef"`` slot
    of the train state.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelCfg
from repro.models import api
from .optimizer import adamw_init, adamw_update, cosine_lr

TrainState = Dict[str, Any]   # {"params": pytree, "opt": {master,m,v,step}}


def init_state(cfg: ModelCfg, key) -> TrainState:
    params = api.init(cfg, key)
    return {"params": params, "opt": adamw_init(params)}


def make_train_step(cfg: ModelCfg, *, act_specs=None, peak_lr=3e-4,
                    warmup=100, total_steps=10_000, weight_decay=0.1,
                    clip=1.0, unroll=False, microbatches=1,
                    grad_reduce=None):
    """Returns train_step(state, batch) -> (state, metrics).

    ``microbatches`` > 1 enables gradient accumulation (a lax.scan over
    micro-slices of the global batch): the standard way to bound per-layer
    activation-checkpoint memory (L x B_mb x S x d) at large L.  Gradients
    accumulate in fp32.

    ``grad_reduce(grads, state) -> (grads, extra_state)`` runs between the
    backward pass and the optimizer; ``extra_state`` (a dict) is merged into
    the returned state.  This is the hook data-parallel wrappers use for
    all-reduce / compression.
    """

    def grads_of(params, batch):
        def loss_fn(p):
            return api.loss(cfg, p, batch, act_specs=act_specs, unroll=unroll)
        return jax.value_and_grad(loss_fn, has_aux=True)(params)

    def train_step(state: TrainState, batch):
        if microbatches == 1:
            (total, metrics), grads = grads_of(state["params"], batch)
        else:
            mb = jax.tree.map(
                lambda a: a.reshape(microbatches, a.shape[0] // microbatches,
                                    *a.shape[1:]), batch)
            g0 = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                              state["params"])

            def body(acc, one):
                g_acc, l_acc = acc
                (l, _), g = grads_of(state["params"], one)
                g_acc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                                     g_acc, g)
                return (g_acc, l_acc + l), None

            (g_sum, l_sum), _ = jax.lax.scan(body, (g0, jnp.float32(0)), mb)
            grads = jax.tree.map(lambda g: g / microbatches, g_sum)
            total = l_sum / microbatches
            metrics = {"ce": total}
        extra = {}
        if grad_reduce is not None:
            grads, extra = grad_reduce(grads, state)
        lr = cosine_lr(state["opt"]["step"] + 1, peak=peak_lr, warmup=warmup,
                       total=total_steps)
        params, opt, gnorm = adamw_update(
            grads, state["opt"], lr=lr, weight_decay=weight_decay, clip=clip)
        out_metrics = {"loss": total, "lr": lr, "grad_norm": gnorm, **metrics}
        return {"params": params, "opt": opt, **extra}, out_metrics

    return train_step


# ------------------------------------------------ explicit data parallelism

def init_dp_state(cfg: ModelCfg, key, mesh, *, axis_name: str = "data",
                  compress: bool = False) -> TrainState:
    """Train state for :func:`make_dp_train_step`.

    With ``compress=True`` the state carries an ``"ef"`` pytree of per-rank
    error-feedback residuals, stored with a leading device axis (sharded
    along ``axis_name``) since each rank's residual is private.
    """
    state = init_state(cfg, key)
    if compress:
        n_dev = mesh.shape[axis_name]
        state["ef"] = jax.tree.map(
            lambda p: jnp.zeros((n_dev,) + p.shape, jnp.float32),
            state["params"])
    return state


def make_dp_train_step(cfg: ModelCfg, mesh, *, axis_name: str = "data",
                       compress_ratio: float | None = None, **kw):
    """``shard_map`` data-parallel train step over ``mesh[axis_name]``.

    Params/optimizer are replicated; the batch is sharded on its leading
    dim.  Gradient reduction is either a plain ``pmean`` or — when
    ``compress_ratio`` is set — the error-feedback top-k compressed
    all-reduce from :mod:`repro.dist.compress` (``compress_ratio=1.0``
    degenerates to the exact ``pmean``, which tests assert).  Returns
    ``step(state, batch)`` ready to ``jax.jit``; build the matching state
    with :func:`init_dp_state`.
    """
    from jax import shard_map
    from repro.dist.compress import ef_topk_psum_tree

    n_dev = mesh.shape[axis_name]

    def grad_reduce(grads, state):
        if compress_ratio is None:
            return jax.tree.map(
                lambda g: jax.lax.pmean(g, axis_name), grads), {}
        red, err = ef_topk_psum_tree(grads, state["ef"],
                                     ratio=compress_ratio,
                                     axis_name=axis_name)
        return jax.tree.map(lambda r: r / n_dev, red), {"ef": err}

    inner = make_train_step(cfg, grad_reduce=grad_reduce, **kw)

    def local_step(state, batch):
        state = dict(state)         # never mutate the caller's pytree
        ef = state.pop("ef", None)
        if ef is not None:          # strip the leading (sharded) device axis
            state["ef"] = jax.tree.map(lambda a: a[0], ef)
        new_state, metrics = inner(state, batch)
        if "ef" in new_state:
            new_state["ef"] = jax.tree.map(lambda a: a[None],
                                           new_state["ef"])
        metrics = {k: jax.lax.pmean(v, axis_name) for k, v in metrics.items()}
        return new_state, metrics

    def state_specs(state):
        return {k: (P(axis_name) if k == "ef" else P()) for k in state}

    def step(state, batch):
        fn = shard_map(local_step, mesh=mesh,
                       in_specs=(state_specs(state), P(axis_name)),
                       out_specs=(state_specs(state), P()))
        return fn(state, batch)

    return step
