"""Server-side aggregation: FedAvg deltas + adaptive server optimizers.

The paper aggregates with YoGi (FedScale's default adaptive aggregator).
Aggregation treats the weighted-mean client delta as a pseudo-gradient for
the server optimizer (Reddi et al., Adaptive Federated Optimization).
"""
from __future__ import annotations

import functools
from typing import Any, List, Tuple

import jax
import jax.numpy as jnp

from repro.optim import SERVER_OPTIMIZERS, Optimizer, apply_updates

PyTree = Any


def weighted_sum(w: jnp.ndarray, d: jnp.ndarray) -> jnp.ndarray:
    """``sum_j w[j] * d[j]`` over the leading client axis, at full f32
    precision: the TPU's default dot would round both operands to
    bfloat16."""
    return jnp.tensordot(w.astype(d.dtype), d, axes=1,
                         precision=jax.lax.Precision.HIGHEST)


def weighted_delta(deltas: PyTree, weights: jnp.ndarray) -> PyTree:
    """deltas: pytree with leading client axis (C, ...); weights: (C,)."""
    w = weights / jnp.maximum(weights.sum(), 1e-9)
    return jax.tree.map(lambda d: weighted_sum(w, d), deltas)


# --------------------------------------------------- non-finite quarantine
# Server-side graceful degradation: a client that uploads a non-finite
# delta (injected corruption fault, or genuinely diverged local training)
# is quarantined — its weight is zeroed and its delta replaced by zeros so
# it cannot poison the weighted mean — and a last-resort gate on the
# aggregate keeps even a finite-per-client overflow out of the global
# params. Because `weighted_delta` normalizes by the surviving weight sum,
# dropping a client (or a whole lost shard's worth of clients)
# automatically renormalizes the aggregation over the survivors.

def finite_rows(deltas: PyTree) -> jnp.ndarray:
    """(C,) bool: True where every element of client j's delta is finite
    across all leaves of the stacked delta pytree (leaves (C, ...))."""
    masks = [jnp.all(jnp.isfinite(d.reshape(d.shape[0], -1)), axis=1)
             for d in jax.tree.leaves(deltas)]
    return functools.reduce(jnp.logical_and, masks)


def zero_nonfinite_rows(deltas: PyTree, finite: jnp.ndarray) -> PyTree:
    """Replace quarantined clients' delta rows with zeros. Required before
    aggregation even at weight 0: ``0 * nan`` is ``nan``, so a poisoned row
    would still contaminate the tensordot."""
    def clean(d):
        shape = (finite.shape[0],) + (1,) * (d.ndim - 1)
        return jnp.where(finite.reshape(shape), d, jnp.zeros((), d.dtype))
    return jax.tree.map(clean, deltas)


def tree_finite(tree: PyTree) -> jnp.ndarray:
    """Scalar bool: every element of every leaf is finite."""
    checks = [jnp.all(jnp.isfinite(l)) for l in jax.tree.leaves(tree)]
    return functools.reduce(jnp.logical_and, checks)


def make_server_optimizer(name: str, lr: float) -> Optimizer:
    if name not in SERVER_OPTIMIZERS:
        raise KeyError(f"unknown server optimizer {name!r}")
    return SERVER_OPTIMIZERS[name](lr)


def server_update(params: PyTree, agg_delta: PyTree, opt: Optimizer,
                  opt_state: PyTree) -> Tuple[PyTree, PyTree]:
    """Pseudo-gradient = -delta (so +delta is the descent direction)."""
    pseudo_grad = jax.tree.map(lambda d: -d, agg_delta)
    updates, opt_state = opt.update(pseudo_grad, opt_state, params)
    return apply_updates(params, updates), opt_state
