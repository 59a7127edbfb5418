"""Activation-sharding context for the model code.

The model layers are mesh-agnostic; the launcher declares which mesh axes
carry the batch ("data"/"pod") and the tensor-parallel dimension ("model"),
and the model inserts ``with_sharding_constraint`` on the residual stream so
GSPMD keeps activations batch-sharded instead of letting parameter shardings
propagate into them (measured: without this, the residual stream inherits
the embedding table's layout — full-batch-replicated f32 all-reduces per
layer; see EXPERIMENTS §Perf iteration 0).

Outside a launcher context (smoke tests, the FL sim on one device) every
constraint is a no-op.
"""
from __future__ import annotations

from contextlib import contextmanager
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

AxisName = Union[str, Tuple[str, ...], None]

_AXES = {"batch": None, "model": None, "gather_weights": False}


def _ambient_mesh():
    """The mesh whose axes bare-PartitionSpec constraints resolve against:
    the one the launcher entered with ``jax.set_mesh``, or None."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def set_axes(batch: AxisName = None, model: AxisName = None,
             gather_weights: bool = False) -> None:
    _AXES["batch"] = batch
    _AXES["model"] = model
    _AXES["gather_weights"] = gather_weights


@contextmanager
def activation_axes(batch: AxisName = None, model: AxisName = None,
                    gather_weights: bool = False):
    prev = dict(_AXES)
    set_axes(batch, model, gather_weights)
    try:
        yield
    finally:
        _AXES.update(prev)


@jax.custom_vjp
def _grad_shard_hint(w):
    return w


def _gsh_fwd(w):
    return w, (w.ndim, w.shape)


def _gsh_bwd(res, g):
    """Pin the weight cotangent SHARDED on dim0 so the partitioner lowers
    the 256-way gradient reduction as reduce-scatter (half an all-reduce's
    bytes) instead of all-reduce + local slice (§Perf iteration 3)."""
    ndim, shape = res
    mesh = _ambient_mesh()
    if mesh is None:
        return (g,)
    total = 1
    for s in mesh.shape.values():
        total *= s
    axes = tuple(mesh.shape.keys())
    if shape[0] % total == 0:
        spec = P(axes, *([None] * (ndim - 1)))
        g = jax.lax.with_sharding_constraint(g, spec)
    return (g,)


_grad_shard_hint.defvjp(_gsh_fwd, _gsh_bwd)


def weight_cast(w, dtype):
    """Cast a weight to the compute dtype at its use site. Under the FSDP
    strategy the tree was already pre-cast to bf16 while sharded (see
    ``precast_params``) so the cast is a no-op there; in-layer
    constraint/barrier tricks for bf16 *gathers* were tried and REFUTED —
    the CPU float-normalization pass rewrites bf16 collectives to f32, so
    dtype wins are estimated analytically (§Perf iteration 2 log). The
    gradient-reduce-scatter hint below IS an op-level change and measures."""
    w = w.astype(dtype)
    if _AXES.get("gather_weights") and w.ndim >= 2:
        w = _grad_shard_hint(w)
    return w


_PRECAST_EXCLUDE = ("router",)


def precast_params(params, dtype):
    """FSDP: convert every large float matrix to the compute dtype ONCE,
    while still sharded, before the layer scan. The per-layer all-gather
    inside the loop then necessarily moves bf16 (half the bytes), and the
    scan's transpose reduces bf16 cotangents. No-op unless the launcher set
    gather_weights."""
    if not _AXES.get("gather_weights"):
        return params

    def one(path, leaf):
        name = getattr(path[-1], "key", "")
        if (hasattr(leaf, "dtype") and leaf.dtype == jnp.float32
                and leaf.ndim >= 2 and min(leaf.shape) >= 32
                and name not in _PRECAST_EXCLUDE):
            return leaf.astype(dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(one, params)


def _axis_size(mesh_shape, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        n = 1
        for a in axis:
            n *= _axis_size(mesh_shape, a)
        return n
    return mesh_shape.get(axis, 1)


def constrain(x, *kinds: Optional[str]):
    """constrain(h, "batch", None, None) — kinds name logical roles."""
    if _AXES["batch"] is None and _AXES["model"] is None:
        return x
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    mesh_shape = dict(mesh.shape)
    dims = []
    for size, kind in zip(x.shape, kinds):
        if kind == "dpbatch":    # batch axes excluding the model axis
            b = _AXES.get("batch")
            if isinstance(b, tuple):
                ax = tuple(a for a in b if a != _AXES.get("model")) or None
            else:
                ax = None if b == _AXES.get("model") else b
        else:
            ax = _AXES.get(kind) if kind else None
        if ax is not None and size % _axis_size(mesh_shape, ax) == 0:
            # drop sub-axes that aren't in this mesh
            if isinstance(ax, tuple):
                ax = tuple(a for a in ax if a in mesh_shape) or None
            elif ax not in mesh_shape:
                ax = None
        else:
            ax = None
        dims.append(ax)
    # drop axes that would repeat across dims (e.g. batch=(data,model)
    # together with a `model`-sharded trailing dim)
    used = set()
    clean = []
    for ax in dims:
        names = ax if isinstance(ax, tuple) else ((ax,) if ax else ())
        if any(n in used for n in names):
            clean.append(None)
        else:
            used.update(names)
            clean.append(ax)
    dims = clean
    if all(d is None for d in dims):
        return x
    return jax.lax.with_sharding_constraint(x, P(*dims))
