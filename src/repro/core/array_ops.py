"""Array (vector/matrix/tensor) distributed operators — paper Table I.

These are the MPI-heritage collectives, re-hosted on JAX named-axis
collectives inside ``shard_map``.  Two API levels:

  * **global-view** functions (``allreduce``, ``allgather``, …): take a
    row-sharded global array + an ``HPTMTContext`` and wrap ``shard_map``
    themselves.  These are the paper's *eager array operators* — they work on
    any mesh (principle (c)) and degrade to local ops on a single device
    (principle (d)).
  * **in-spmd** functions (``spmd_*``): usable *inside* an existing
    ``shard_map`` region (model code, table kernels); thin shims over
    ``jax.lax`` so every layer of the stack speaks the same operator
    vocabulary.

Global-view calling conventions (each shard owns one leading-dim block):

  ===============  =======================  ==============================
  operator         input (global)           output (global)
  ===============  =======================  ==============================
  allreduce        (S, *rest) row-sharded   (*rest) replicated
  allgather        (N, *rest) row-sharded   (N, *rest) replicated
  alltoall         (N, *rest) row-sharded   (N, *rest) row-sharded
  reduce_scatter   (N, *rest) replicated    (N/S… row-sharded blocks)
  broadcast        (S, *rest) row-sharded   (*rest) replicated (root block)
  gather           (N, *rest) row-sharded   (S, N, *rest); zeros off-root
  scatter          (N, *rest) replicated    (N, *rest) row-sharded
  reduce           (S, *rest) row-sharded   (S, *rest); zeros off-root
  ===============  =======================  ==============================

TPU adaptation (DESIGN.md §2): XLA SPMD has no rooted collectives, so
Broadcast/Gather/Reduce/Scatter are expressed with masking + unrooted
collectives — which is how they lower on TPU interconnects anyway.
"""
from __future__ import annotations

from typing import Sequence, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .context import HPTMTContext
from .operator import Abstraction, operator

AxisName = Union[str, Sequence[str]]

_REDUCERS = {
    "sum": jax.lax.psum,
    "max": jax.lax.pmax,
    "min": jax.lax.pmin,
}


# ---------------------------------------------------------------------------
# in-SPMD collectives (usable inside shard_map)
# ---------------------------------------------------------------------------
def spmd_allreduce(x, axis: AxisName, op: str = "sum"):
    if op == "mean":
        size = jax.lax.psum(jnp.ones((), jnp.float32), axis)
        return jax.lax.psum(x, axis) / size.astype(x.dtype)
    if op == "prod":
        # no pprod primitive; all_gather + local prod (small payloads only).
        g = jax.lax.all_gather(x, axis)
        return jnp.prod(g, axis=0)
    return _REDUCERS[op](x, axis)


def spmd_allgather(x, axis: AxisName, *, tiled: bool = True, gather_axis: int = 0):
    return jax.lax.all_gather(x, axis, axis=gather_axis, tiled=tiled)


def spmd_alltoall(x, axis: AxisName, *, split_axis: int = 0, concat_axis: int = 0):
    return jax.lax.all_to_all(x, axis, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)


def spmd_reduce_scatter(x, axis: AxisName, *, scatter_axis: int = 0, op: str = "sum"):
    if op != "sum":
        raise NotImplementedError("reduce_scatter supports sum only")
    return jax.lax.psum_scatter(x, axis, scatter_dimension=scatter_axis, tiled=True)


def spmd_broadcast(x, axis: str, root: int = 0):
    """Rooted broadcast = mask + allreduce (TPU-idiomatic)."""
    idx = jax.lax.axis_index(axis)
    masked = jnp.where(idx == root, x, jnp.zeros_like(x))
    return jax.lax.psum(masked, axis)


def spmd_reduce(x, axis: str, root: int = 0, op: str = "sum"):
    """Rooted reduce: full value on ``root``, zeros elsewhere."""
    full = spmd_allreduce(x, axis, op=op)
    idx = jax.lax.axis_index(axis)
    return jnp.where(idx == root, full, jnp.zeros_like(full))


def spmd_gather(x, axis: str, root: int = 0):
    """Rooted gather: concatenated value on ``root``, zeros elsewhere."""
    g = jax.lax.all_gather(x, axis, axis=0, tiled=True)
    idx = jax.lax.axis_index(axis)
    return jnp.where(idx == root, g, jnp.zeros_like(g))


def spmd_scatter(x, axis: str, root: int = 0):
    """Rooted scatter: root's buffer split into blocks across the axis."""
    n = jax.lax.axis_size(axis)
    full = spmd_broadcast(x, axis, root=root)
    idx = jax.lax.axis_index(axis)
    piece = x.shape[0] // n
    return jax.lax.dynamic_slice_in_dim(full, idx * piece, piece, axis=0)


def spmd_ppermute(x, axis: str, perm):
    return jax.lax.ppermute(x, axis, perm=perm)


# ---------------------------------------------------------------------------
# global-view eager operators (paper Table I)
# ---------------------------------------------------------------------------
def _row_spec(ctx: HPTMTContext, ndim: int) -> P:
    return P(ctx.data_axis, *([None] * (ndim - 1)))


def _rep_spec(ndim: int) -> P:
    return P(*([None] * ndim))


@operator("array.allreduce", Abstraction.ARRAY)
def allreduce(x, *, ctx: HPTMTContext, op: str = "sum"):
    """AllReduce: combine one block per shard with SUM/MIN/MAX/MEAN/PROD."""
    if not ctx.is_distributed:
        red = {"sum": jnp.sum, "max": jnp.max, "min": jnp.min,
               "mean": jnp.mean, "prod": jnp.prod}[op]
        return red(x, axis=0)
    fn = ctx.shard_map(
        lambda v: spmd_allreduce(v[0], ctx.data_axis, op=op),
        in_specs=_row_spec(ctx, x.ndim), out_specs=_rep_spec(x.ndim - 1))
    return fn(x)


@operator("array.allgather", Abstraction.ARRAY)
def allgather(x, *, ctx: HPTMTContext):
    """AllGather: every shard receives the concatenation of all shards."""
    if not ctx.is_distributed:
        return x
    fn = ctx.shard_map(
        lambda v: spmd_allgather(v, ctx.data_axis),
        in_specs=_row_spec(ctx, x.ndim), out_specs=_rep_spec(x.ndim))
    return fn(x)


@operator("array.alltoall", Abstraction.ARRAY)
def alltoall(x, *, ctx: HPTMTContext):
    """AllToAll: transpose the (shard, block) layout of a row-sharded array."""
    if not ctx.is_distributed:
        return x
    fn = ctx.shard_map(
        lambda v: spmd_alltoall(v, ctx.data_axis),
        in_specs=_row_spec(ctx, x.ndim), out_specs=_row_spec(ctx, x.ndim))
    return fn(x)


@operator("array.reduce_scatter", Abstraction.ARRAY)
def reduce_scatter(x, *, ctx: HPTMTContext):
    """ReduceScatter: sum shard contributions, scatter result row-blocks."""
    if not ctx.is_distributed:
        return x
    fn = ctx.shard_map(
        lambda v: spmd_reduce_scatter(v, ctx.data_axis),
        in_specs=_rep_spec(x.ndim), out_specs=_row_spec(ctx, x.ndim))
    return fn(x)


@operator("array.broadcast", Abstraction.ARRAY)
def broadcast(x, *, ctx: HPTMTContext, root: int = 0):
    """Broadcast: shard ``root``'s block to every shard (replicated)."""
    if not ctx.is_distributed:
        return x[root]
    fn = ctx.shard_map(
        lambda v: spmd_broadcast(v[0], ctx.data_axis, root=root),
        in_specs=_row_spec(ctx, x.ndim), out_specs=_rep_spec(x.ndim - 1))
    return fn(x)


@operator("array.gather", Abstraction.ARRAY)
def gather(x, *, ctx: HPTMTContext, root: int = 0):
    """Gather: concatenation of all shards on ``root`` (zeros elsewhere)."""
    if not ctx.is_distributed:
        return x[None]
    fn = ctx.shard_map(
        lambda v: spmd_gather(v, ctx.data_axis, root=root)[None],
        in_specs=_row_spec(ctx, x.ndim), out_specs=_row_spec(ctx, x.ndim + 1))
    return fn(x)


@operator("array.scatter", Abstraction.ARRAY)
def scatter(x, *, ctx: HPTMTContext, root: int = 0):
    """Scatter: split ``root``'s (replicated) buffer into one block/shard."""
    if not ctx.is_distributed:
        return x
    fn = ctx.shard_map(
        lambda v: spmd_scatter(v, ctx.data_axis, root=root),
        in_specs=_rep_spec(x.ndim), out_specs=_row_spec(ctx, x.ndim))
    return fn(x)


@operator("array.reduce", Abstraction.ARRAY)
def reduce(x, *, ctx: HPTMTContext, root: int = 0, op: str = "sum"):
    """Reduce: combined value in ``root``'s block, zeros elsewhere."""
    if not ctx.is_distributed:
        red = {"sum": jnp.sum, "max": jnp.max, "min": jnp.min,
               "mean": jnp.mean}[op]
        return red(x, axis=0, keepdims=True)
    fn = ctx.shard_map(
        lambda v: spmd_reduce(v[0], ctx.data_axis, root=root, op=op)[None],
        in_specs=_row_spec(ctx, x.ndim), out_specs=_row_spec(ctx, x.ndim))
    return fn(x)
