"""Distributed table operators — paper Tables II/III and the shuffle (Fig 2).

Every distributed operator is one ``shard_map`` region: local columnar
kernels + the bucket-exchange **shuffle** primitive built on the array
AllToAll operator (paper: "Shuffle is similar to the array AllToAll
operation … what makes these two operations different are the data structure
[and] how we select which values are scattered" §IV-B-1).

Static-shape adaptation (DESIGN.md §2 item 1): shuffles move fixed-capacity
buckets; overflow (rows that exceed bucket or output capacity) is *counted
and returned* so the caller — per the paper's §VII-F prescription, the
workflow layer — can react (retry with a larger capacity), instead of
silently corrupting data.

The data movement itself lives in ``core/exchange.py`` (DESIGN.md §3): all
columns are bit-packed into one uint32 buffer so each shuffle issues exactly
ONE AllToAll (counts ride a fused metadata row), bucketing/compaction are
counting-sort scatters (zero ``argsort`` on the shuffle path), and the row
hashes computed for partitioning are carried through the exchange so join /
set-op kernels never rehash post-shuffle.

Operators implemented here (→ paper table):
  select, project                          — Table II (local)
  union, difference, cartesian             — Table II (distributed)
  intersect, join, orderby, aggregate,
  groupby(+aggregate)                      — Table III (distributed)
  shuffle                                  — Fig 2 primitive
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .array_ops import spmd_allgather, spmd_allreduce, spmd_ppermute
from .context import HPTMTContext
from .exchange import (check_no_reserved, compact_rows, exchange_rows,
                       hash_shuffle, key_compare_u32, lex_order,
                       order_lanes, range_shuffle, take_hashes)
from .operator import Abstraction, Style, operator
from .table import (DistTable, Table, _pad_axis0, partitioning_ascending,
                    partitioning_keys, partitioning_kind,
                    range_partitioning)

Cols = Dict[str, jnp.ndarray]


# ===========================================================================
# shard_map plumbing
# ===========================================================================
def _run_sharded(ctx: HPTMTContext, impl: Callable, args, out_specs):
    """Run ``impl(*local_args, axis=...)`` over the context's data axis.

    Single-device contexts run the same impl with ``axis=None`` (collectives
    become identities) — principle (d), same operator everywhere.
    """
    if not ctx.is_distributed:
        return impl(*args, axis=None)
    fn = ctx.shard_map(
        functools.partial(impl, axis=ctx.data_axis),
        in_specs=P(ctx.data_axis), out_specs=out_specs)
    return fn(*args)


def _local_parts(dt_cols: Cols, counts: jnp.ndarray) -> Tuple[Cols, jnp.ndarray]:
    """Inside shard_map: per-shard column blocks + scalar count."""
    return dt_cols, counts[0]


def _mask_for(count: jnp.ndarray, capacity: int) -> jnp.ndarray:
    return jnp.arange(capacity, dtype=jnp.int32) < count


def _compact_cols(cols: Cols, keep: jnp.ndarray,
                  out_capacity: int) -> Tuple[Cols, jnp.ndarray, jnp.ndarray]:
    """Move kept rows to the front; truncate to ``out_capacity``.

    Returns (columns, new_count, n_truncated).  Sort-free: delegates to the
    exchange engine's cumsum-scatter compaction (DESIGN.md §3).
    """
    return compact_rows(cols, keep, out_capacity)


def _sort_cols(cols: Cols, sort_keys: Sequence[jnp.ndarray],
               mask: jnp.ndarray) -> Tuple[Cols, jnp.ndarray]:
    """Sort valid rows by lexicographic keys; invalid rows go last."""
    order = jnp.lexsort(tuple(sort_keys[::-1]) + (~mask,))
    return {k: v[order] for k, v in cols.items()}, order


# ===========================================================================
# the shuffle primitive (Fig 2)
# ===========================================================================
def _bucket_capacity(capacity: int, n_shards: int, factor: float) -> int:
    if n_shards == 1:
        return capacity
    return max(1, min(capacity, math.ceil(capacity * factor / n_shards)))


def _partitioned_on(dt: DistTable, keys: Sequence[str],
                    ctx: HPTMTContext) -> bool:
    """True when ``dt``'s rows are already hash-co-located on ``keys``.

    Metadata is trusted only on an exact ``(ordered keys, n_shards)`` match —
    the murmur chain is order-sensitive, so ("a","b") and ("b","a") describe
    different layouts (DESIGN.md §4).
    """
    return (ctx.n_shards > 1
            and dt.partitioning == (tuple(keys), ctx.n_shards))


def _shuffle_impl(cols: Cols, counts: jnp.ndarray, *, key_names, n_shards,
                  bucket, out_capacity, axis, dest_fn=None):
    cols, count = _local_parts(cols, counts)
    if dest_fn is None:
        out, new_count, overflow = hash_shuffle(
            cols, count, key_names, n_shards, bucket, out_capacity, axis)
    else:
        capacity = next(iter(cols.values())).shape[0]
        mask = _mask_for(count, capacity)
        dest = jnp.where(mask, dest_fn(cols, mask), n_shards)
        bufs, valid, ov_send = exchange_rows(cols, dest, n_shards, bucket,
                                             axis)
        out, new_count, ov_recv = compact_rows(bufs, valid, out_capacity)
        overflow = ov_send + ov_recv
    if axis is not None:
        overflow = spmd_allreduce(overflow, axis)
    return out, new_count[None], overflow


@operator("table.shuffle", Abstraction.TABLE)
def shuffle(dt: DistTable, keys: Sequence[str], *, ctx: HPTMTContext,
            out_capacity: Optional[int] = None, bucket_factor: float = 2.0,
            ) -> Tuple[DistTable, jnp.ndarray]:
    """Re-distribute rows so equal keys land on the same shard (Fig 2).

    A no-op (elided at trace level, DESIGN.md §4) when ``dt.partitioning``
    already records a hash exchange on exactly these keys — unless the call
    also asks for a resize (``out_capacity`` differing from the input
    capacity), which must run regardless of layout so the output shape and
    overflow accounting never depend on input provenance.  The output
    carries ``(keys, n_shards)`` partitioning metadata so downstream
    join/groupby/set ops on the same keys skip their own shuffle.
    """
    n = ctx.n_shards
    if _partitioned_on(dt, keys, ctx) and (out_capacity is None
                                           or out_capacity == dt.capacity):
        return dt, jnp.zeros((), jnp.int32)
    bucket = _bucket_capacity(dt.capacity, n, bucket_factor)
    out_cap = out_capacity or dt.capacity
    impl = functools.partial(
        _shuffle_impl, key_names=tuple(keys), n_shards=n, bucket=bucket,
        out_capacity=out_cap, )
    cols, counts, overflow = _run_sharded(
        ctx, impl, (dt.columns, dt.counts),
        out_specs=(P(ctx.data_axis), P(ctx.data_axis), P()))
    return DistTable(cols, counts, (tuple(keys), n)), overflow


# ===========================================================================
# local operators (Table II: Select / Project)
# ===========================================================================
@operator("table.select", Abstraction.TABLE, distributed=False)
def select(dt: DistTable, predicate: Callable[[Cols], jnp.ndarray], *,
           ctx: HPTMTContext) -> DistTable:
    """Filter rows by a per-row predicate over the columns (Table II)."""

    def impl(cols, counts, *, axis):
        cols, count = _local_parts(cols, counts)
        cap = next(iter(cols.values())).shape[0]
        keep = predicate(cols) & _mask_for(count, cap)
        out, n, _ = _compact_cols(cols, keep, cap)
        return out, n[None]

    cols, counts = _run_sharded(
        ctx, impl, (dt.columns, dt.counts),
        out_specs=(P(ctx.data_axis), P(ctx.data_axis)))
    # rows never change shards: the partitioning layout survives filtering
    return DistTable(cols, counts, dt.partitioning)


@operator("table.project", Abstraction.TABLE, distributed=False)
def project(dt: DistTable, columns: Sequence[str], *,
            ctx: HPTMTContext) -> DistTable:
    """Keep only the named columns (Table II). Purely local.

    Partitioning metadata — hash AND range alike — survives only while
    every key column is still present (DESIGN.md §4/§9): a projection
    that drops a key loses the evidence of how rows were placed/ordered.
    """
    part = dt.partitioning
    if part is not None and not set(partitioning_keys(part)) <= set(columns):
        part = None
    return DistTable({k: dt.columns[k] for k in columns}, dt.counts, part)


# ===========================================================================
# OrderBy (Table III) — multi-key distributed sample sort (DESIGN.md §9)
# ===========================================================================
def _normalize_order(by, ascending, column_names, kwarg: str):
    """Validate sort keys/directions eagerly; returns ``(keys, ascending)``.

    ``by`` is a column name or a sequence of them; ``ascending`` a bool or
    a per-key sequence.  Errors name the offending kwarg and value before
    anything traces (the join-validation style).
    """
    keys = (by,) if isinstance(by, str) else tuple(by)
    if not keys:
        raise ValueError(f"{kwarg}= needs at least one key column")
    missing = [k for k in keys if k not in column_names]
    if missing:
        raise ValueError(f"{kwarg}= names unknown column(s) {missing}; "
                         f"table has {sorted(column_names)}")
    if isinstance(ascending, bool):
        asc = (ascending,) * len(keys)
    else:
        asc = tuple(bool(a) for a in ascending)
        if len(asc) != len(keys):
            raise ValueError(
                f"ascending= has {len(asc)} entries for {len(keys)} "
                f"{kwarg}= keys — provide one bool, or one per key")
    return keys, asc


def _orderby_impl(cols: Cols, counts: jnp.ndarray, *, keys, ascending,
                  n_shards, bucket, out_capacity, n_samples, axis):
    local_cols, count = _local_parts(cols, counts)
    out, new_count, overflow = range_shuffle(
        local_cols, count, keys, ascending, n_shards, bucket, out_capacity,
        axis, n_samples=n_samples)
    if axis is not None:
        overflow = spmd_allreduce(overflow, axis)
    return out, new_count[None], overflow


@operator("table.orderby", Abstraction.TABLE)
def orderby(dt: DistTable, by, *, ctx: HPTMTContext,
            ascending=True, out_capacity: Optional[int] = None,
            bucket_factor: float = 2.0, n_samples: int = 64,
            ) -> Tuple[DistTable, jnp.ndarray]:
    """Globally sort rows via multi-key sample sort (Table III OrderBy).

    ``by`` is one column name or a sequence; ``ascending`` one bool or one
    per key.  NaN keys sort LAST in BOTH directions (the monotone-lane
    transform of DESIGN.md §9 — the old float negation flipped NaNs to the
    front under ``ascending=False``).  Destination shards come from
    sampled splitters and the rows ride the same single packed AllToAll as
    a hash shuffle; rows with equal full keys never straddle a shard
    boundary.

    The output records ``("range", keys, ascending, n_shards)``
    partitioning metadata — the ordered counterpart of the §4 hash
    evidence: ``window`` / ``rank`` / ``quantile`` / another ``orderby``
    on the same keys then trace with ZERO additional AllToAll.  A call on
    an input already carrying exactly this layout is a traced no-op
    (unless it also resizes, mirroring ``shuffle``).
    """
    keys, asc = _normalize_order(by, ascending, dt.column_names, "by")
    n = ctx.n_shards
    part = range_partitioning(keys, asc, n)
    if dt.partitioning == part and (out_capacity is None
                                    or out_capacity == dt.capacity):
        return dt, jnp.zeros((), jnp.int32)
    impl = functools.partial(
        _orderby_impl, keys=keys, ascending=asc, n_shards=n,
        bucket=_bucket_capacity(dt.capacity, n, bucket_factor),
        out_capacity=out_capacity or dt.capacity,
        n_samples=min(n_samples, dt.capacity))
    cols, counts, overflow = _run_sharded(
        ctx, impl, (dt.columns, dt.counts),
        out_specs=(P(ctx.data_axis), P(ctx.data_axis), P()))
    return DistTable(cols, counts, part), overflow


def _local_sort_impl(cols: Cols, counts: jnp.ndarray, *, keys, ascending,
                     axis):
    local_cols, count = _local_parts(cols, counts)
    capacity = next(iter(local_cols.values())).shape[0]
    mask = _mask_for(count, capacity)
    order = lex_order(order_lanes(local_cols, keys, ascending), mask)
    return {k: v[order] for k, v in local_cols.items()}, count[None]


@operator("table.local_sort", Abstraction.TABLE)
def local_sort(dt: DistTable, by, *, ctx: HPTMTContext, ascending=True,
               partitioning: object = "auto"
               ) -> Tuple[DistTable, jnp.ndarray]:
    """Sort rows *within each shard* — a planner primitive, ZERO AllToAll.

    Rows never cross shards, so this is NOT a global sort on its own: the
    query planner (``repro.plan``) emits it when placement metadata already
    proves the cross-shard half of an ordering (e.g. shards hold disjoint
    contiguous key ranges after a range exchange upstream, so a local sort
    completes a global ``orderby``), or when only per-shard order matters
    (window evaluation over hash-co-located partitions).

    ``partitioning`` stamps the output metadata: ``"auto"`` keeps a hash
    layout (rows did not move) and drops anything else; an explicit value
    is trusted verbatim — callers must pass a layout they can prove.
    Same NaN-last key semantics as ``orderby`` (DESIGN.md §9).
    """
    keys, asc = _normalize_order(by, ascending, dt.column_names, "by")
    if partitioning == "auto":
        part = dt.partitioning if partitioning_kind(dt.partitioning) \
            == "hash" else None
    else:
        part = partitioning
    impl = functools.partial(_local_sort_impl, keys=keys, ascending=asc)
    cols, counts = _run_sharded(
        ctx, impl, (dt.columns, dt.counts),
        out_specs=(P(ctx.data_axis), P(ctx.data_axis)))
    return DistTable(cols, counts, part), jnp.zeros((), jnp.int32)


# ===========================================================================
# Windowed aggregation / rank / top-k / quantile (DESIGN.md §9)
# ===========================================================================
def _window_impl(cols: Cols, counts: jnp.ndarray, *, pkeys, okeys,
                 ascending, aggs, rows, n_shards, bucket, out_capacity,
                 n_samples, need_sort, axis):
    from repro.window import eval_window  # lazy: window imports core

    local_cols, count = _local_parts(cols, counts)
    ov = jnp.zeros((), jnp.int32)
    if need_sort:
        local_cols, count, ov = range_shuffle(
            local_cols, count, tuple(pkeys) + tuple(okeys), ascending,
            n_shards, bucket, out_capacity, axis, n_samples=n_samples)
    new_cols, o = eval_window(local_cols, count, pkeys=pkeys, okeys=okeys,
                              ascending=ascending, aggs=aggs, rows=rows,
                              n_shards=n_shards, axis=axis)
    overflow = ov + o
    if axis is not None:
        overflow = spmd_allreduce(overflow, axis)
    out = dict(local_cols)
    out.update(new_cols)
    return out, count[None], overflow


@operator("table.window", Abstraction.TABLE)
def window_aggregate(dt: DistTable, partition_by, order_by, aggs, *,
                     ctx: HPTMTContext, rows: Optional[int] = None,
                     ascending=True, bucket_factor: float = 2.0,
                     n_samples: int = 64) -> Tuple[DistTable, jnp.ndarray]:
    """SQL-style window functions over ``(PARTITION BY, ORDER BY)`` groups.

    ``aggs`` entries are ``(column, op)`` or ``(column, op, offset)`` with
    op in sum/mean/count/min/max (windowed by ``rows``: a trailing
    row-count window, ``None`` = cumulative/expanding), lag/lead (offset
    gathers, zero-filled outside the partition), and ``(None,
    "row_number")`` / ``(None, "rank")``.  Output = input columns plus one
    labeled column per agg (``{col}_{op}``, ``row_number``, ``rank``);
    rows never move or drop.  A window wider than its partition clips to
    the partition (SQL ROWS BETWEEN semantics); partition identity is the
    ordering identity (all-NaN keys form ONE partition, ±0.0 two).

    The input must be ordered by ``partition_by + order_by``: when its
    metadata already records exactly that range layout the sort is elided
    and the whole operator adds ZERO AllToAll and ZERO sort primitives to
    the trace (halo/carry state moves on ppermute/AllGather, DESIGN.md
    §9); otherwise one sample-sort exchange runs first — so an
    ``orderby -> window`` chain on the same keys costs exactly the
    orderby's single AllToAll.

    Overflow counts *truncated windows*: bounded-lookback lanes (rolling,
    lag/lead) that needed rows beyond what the cross-shard halo could
    prove.  Zero overflow certifies exact results (§2).
    """
    from repro.window import normalize_aggs

    pkeys = tuple(partition_by) if not isinstance(partition_by, str) \
        else (partition_by,)
    missing = [k for k in pkeys if k not in dt.column_names]
    if missing:
        raise ValueError(f"partition_by= names unknown column(s) "
                         f"{missing}; table has {sorted(dt.column_names)}")
    okeys, asc_o = _normalize_order(order_by, ascending, dt.column_names,
                                    "order_by")
    norm = normalize_aggs(aggs, dt.column_names, rows)
    n = ctx.n_shards
    max_off = max((p for _, _, op, p in norm if op in ("lag", "lead")),
                  default=0)
    lookback = max(rows - 1 if rows is not None else 0, max_off)
    if n > 1 and lookback > dt.capacity:
        raise ValueError(
            f"window lookback {lookback} (rows=/lag/lead offsets) exceeds "
            f"the per-shard capacity {dt.capacity}; raise the capacity or "
            f"repartition over fewer shards")
    keys = pkeys + okeys
    asc = (True,) * len(pkeys) + asc_o
    part = range_partitioning(keys, asc, n)
    impl = functools.partial(
        _window_impl, pkeys=pkeys, okeys=okeys, ascending=asc, aggs=norm,
        rows=rows, n_shards=n,
        bucket=_bucket_capacity(dt.capacity, n, bucket_factor),
        out_capacity=dt.capacity, n_samples=min(n_samples, dt.capacity),
        need_sort=dt.partitioning != part)
    cols, counts, overflow = _run_sharded(
        ctx, impl, (dt.columns, dt.counts),
        out_specs=(P(ctx.data_axis), P(ctx.data_axis), P()))
    return DistTable(cols, counts, part), overflow


def rank(dt: DistTable, partition_by, order_by, *, ctx: HPTMTContext,
         ascending=True, **kw) -> Tuple[DistTable, jnp.ndarray]:
    """Convenience: add SQL ``rank`` (+``row_number``) window columns."""
    return window_aggregate(
        dt, partition_by, order_by,
        [(None, "rank"), (None, "row_number")], ctx=ctx,
        ascending=ascending, **kw)


def _topk_impl(cols: Cols, counts: jnp.ndarray, *, keys, ascending, k,
               n_shards, axis):
    local_cols, count = _local_parts(cols, counts)
    capacity = next(iter(local_cols.values())).shape[0]
    mask = _mask_for(count, capacity)
    order = lex_order(order_lanes(local_cols, keys, ascending), mask)
    take = order[:k]
    cand = {name: v[take] for name, v in local_cols.items()}
    ccnt = jnp.minimum(count, k)

    # tree-reduce: log2(p) ppermute rounds, each merging two k-candidate
    # sets with a 2k-row local sort — no global sort, no AllToAll
    rounds = max(n_shards - 1, 0).bit_length()
    for t in range(rounds):
        stepsz = 1 << t
        perm = [(s + stepsz, s) for s in range(0, n_shards - stepsz,
                                               2 * stepsz)]
        recv = {name: spmd_ppermute(v, axis, perm)
                for name, v in cand.items()}
        rcnt = spmd_ppermute(ccnt, axis, perm)
        merged = {name: jnp.concatenate([v, recv[name]])
                  for name, v in cand.items()}
        mvalid = jnp.concatenate([jnp.arange(k) < ccnt,
                                  jnp.arange(k) < rcnt])
        morder = lex_order(order_lanes(merged, keys, ascending), mvalid)
        take = morder[:k]
        cand = {name: v[take] for name, v in merged.items()}
        ccnt = jnp.minimum(ccnt + rcnt, k)

    if axis is not None and n_shards > 1:
        mine = jax.lax.axis_index(axis) == 0
        keep = mine & (jnp.arange(k) < ccnt)
        cand = {name: _bcast(keep, v) for name, v in cand.items()}
        ccnt = jnp.where(mine, ccnt, 0)
    return cand, ccnt[None]


@operator("table.topk", Abstraction.TABLE)
def topk(dt: DistTable, by, k: int, *, ctx: HPTMTContext,
         largest: bool = True, ascending=None) -> DistTable:
    """The first ``k`` rows of the global sort order, WITHOUT a global
    sort: per-shard top-k candidates tree-reduce over ``log2(p)``
    ppermute rounds of 2k-row merges (DESIGN.md §9) — zero AllToAll, and
    local sorts touch at most ``max(capacity, 2k)`` rows.

    ``largest=True`` (default) means descending by ``by``; pass
    ``ascending=`` per-key directions to override.  The result lands on
    shard 0, globally sorted — it carries the corresponding range
    metadata, so a following window/quantile on the same keys elides.
    """
    if not isinstance(k, int) or k < 1:
        raise ValueError(f"k={k!r} must be a positive int")
    if ctx.n_shards > 1 and k > dt.capacity:
        # a shard can only surface `capacity` candidates, so a bigger k
        # would silently return fewer rows than asked — reject eagerly
        raise ValueError(
            f"k={k} exceeds the per-shard capacity {dt.capacity}; raise "
            f"the capacity or use orderby for a full sort")
    if ascending is None:
        ascending = not largest
    keys, asc = _normalize_order(by, ascending, dt.column_names, "by")
    n = ctx.n_shards
    impl = functools.partial(_topk_impl, keys=keys, ascending=asc,
                             k=min(k, dt.capacity), n_shards=n)
    cols, counts = _run_sharded(
        ctx, impl, (dt.columns, dt.counts),
        out_specs=(P(ctx.data_axis), P(ctx.data_axis)))
    return DistTable(cols, counts, range_partitioning(keys, asc, n))


def _quantile_impl(cols: Cols, counts: jnp.ndarray, *, column, qs, method,
                   n_shards, bucket, capacity, n_samples, need_sort, axis):
    local_cols, count = _local_parts(cols, counts)
    qarr = jnp.asarray(qs, jnp.float32)
    if capacity == 0:  # gathers on size-0 columns are ill-formed
        return jnp.full((len(qs),), jnp.nan, jnp.float32)

    if method == "approx":
        # splitter-style sketch: pooled regular sample, no exchange
        col = local_cols[column].astype(jnp.float32)
        cap = col.shape[0]
        mask = _mask_for(count, cap) & ~jnp.isnan(col)
        svals, scnt = compact_rows({"v": col}, mask, cap)[:2]
        stride = jnp.maximum(scnt // n_samples, 1)
        sidx = jnp.minimum(jnp.arange(n_samples, dtype=jnp.int32) * stride,
                           jnp.maximum(scnt - 1, 0))
        ok = sidx < scnt
        sample = jnp.where(ok, svals["v"][sidx], jnp.inf)
        nval = jnp.sum(ok, dtype=jnp.int32)
        if axis is not None:
            sample = spmd_allgather(sample, axis)
            nval = spmd_allreduce(nval, axis)
        sample = jnp.sort(sample)  # invalid (+inf) entries sort last
        t = qarr * jnp.maximum(nval - 1, 0).astype(jnp.float32)
        lo = jnp.floor(t).astype(jnp.int32)
        hi = jnp.ceil(t).astype(jnp.int32)
        vlo = sample[jnp.clip(lo, 0, sample.shape[0] - 1)]
        vhi = sample[jnp.clip(hi, 0, sample.shape[0] - 1)]
        out = vlo + (t - lo.astype(jnp.float32)) * (vhi - vlo)
        return jnp.where(nval > 0, out, jnp.nan)

    # exact: rows globally sorted by the column (sorted here if needed);
    # NaNs order last, so the non-NaN prefix is globally contiguous
    sort_ov = jnp.zeros((), jnp.int32)
    if need_sort:
        local_cols, count, sort_ov = range_shuffle(
            local_cols, count, (column,), (True,), n_shards, bucket,
            capacity, axis, n_samples=n_samples)
    col = local_cols[column].astype(jnp.float32)
    cap = col.shape[0]
    mask = _mask_for(count, cap)
    nn = jnp.sum(mask & ~jnp.isnan(col), dtype=jnp.int32)
    if axis is not None:
        nn_all = spmd_allgather(nn[None], axis)
        me = jax.lax.axis_index(axis)
        offset = jnp.sum(jnp.where(jnp.arange(n_shards) < me, nn_all, 0))
    else:
        nn_all = nn[None]
        offset = jnp.zeros((), jnp.int32)
    total = jnp.sum(nn_all)
    t = qarr * jnp.maximum(total - 1, 0).astype(jnp.float32)
    lo = jnp.floor(t).astype(jnp.int32)
    hi = jnp.ceil(t).astype(jnp.int32)

    def fetch(g):  # global rank → value, via one masked psum
        local = g - offset
        have = (local >= 0) & (local < nn)
        v = jnp.where(have, col[jnp.clip(local, 0, cap - 1)], 0.0)
        return spmd_allreduce(v, axis) if axis is not None else v

    vlo, vhi = fetch(lo), fetch(hi)
    out = vlo + (t - lo.astype(jnp.float32)) * (vhi - vlo)
    if axis is not None:
        sort_ov = spmd_allreduce(sort_ov, axis)
    # a skew-overflowed internal sort dropped rows: poison, never mislead
    return jnp.where((total > 0) & (sort_ov == 0), out, jnp.nan)


@operator("table.quantile", Abstraction.TABLE)
def quantile(dt: DistTable, column: str, qs, *, ctx: HPTMTContext,
             method: str = "auto", bucket_factor: float = 2.0,
             n_samples: int = 64) -> jnp.ndarray:
    """Quantiles of one column, numpy ``nanquantile`` semantics (linear
    interpolation, NaNs excluded).  Returns a ``(len(qs),)`` float32
    array (replicated).

    ``method="exact"`` reads the true order statistics off the range
    layout: already-sorted inputs (orderby/topk metadata on ``(column,)``
    ascending) cost ZERO AllToAll and ZERO sorts — rank→shard arithmetic
    plus one masked AllReduce per boundary; otherwise one sample-sort
    exchange runs first.  ``method="approx"`` is the splitter-style
    fallback: quantiles of a pooled per-shard regular sample (error
    bounded by the §9 sampling skew bound), never any exchange.
    ``"auto"`` picks exact when the layout is already there, else approx.
    """
    if column not in dt.column_names:
        raise ValueError(f"column= names unknown column {column!r}; "
                         f"table has {sorted(dt.column_names)}")
    if method not in ("auto", "exact", "approx"):
        raise ValueError(f"unknown quantile method={method!r}; expected "
                         f"'auto', 'exact' or 'approx'")
    if np.isscalar(qs) and not isinstance(qs, (str, bytes)):
        qs = (float(qs),)
    else:
        try:
            qs = tuple(float(q) for q in qs)
        except TypeError:
            raise ValueError(f"qs={qs!r} must be a probability or a "
                             f"sequence of probabilities") from None
    bad = [q for q in qs if not 0.0 <= q <= 1.0]
    if bad:
        raise ValueError(f"qs= values {bad} outside [0, 1]")
    n = ctx.n_shards
    # a range layout whose FIRST key is this column ascending proves the
    # global order the exact path reads ranks from
    asc = partitioning_ascending(dt.partitioning)
    sorted_on_col = (partitioning_kind(dt.partitioning) == "range"
                     and partitioning_keys(dt.partitioning)[:1] == (column,)
                     and bool(asc and asc[0]))
    if method == "auto":
        method = "exact" if (sorted_on_col or n == 1) else "approx"
    impl = functools.partial(
        _quantile_impl, column=column, qs=qs, method=method, n_shards=n,
        bucket=_bucket_capacity(dt.capacity, n, bucket_factor),
        capacity=dt.capacity, n_samples=min(n_samples, dt.capacity),
        need_sort=method == "exact" and not sorted_on_col)
    return _run_sharded(ctx, impl, (dt.columns, dt.counts), out_specs=P())


# ===========================================================================
# Join (Table III) — shuffle + local hash build/probe (or sort-merge oracle)
# ===========================================================================
_JOIN_HOWS = ("inner", "left", "right", "outer")


def _hash_slots(n_rows: int) -> int:
    """Power-of-two slot count with 4x head-room — the one sizing rule for
    every build table (join, set ops, groupby hash; DESIGN.md §8.1)."""
    return 1 << max(int(4 * n_rows - 1).bit_length(), 6)


def _bcast(mask: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """Broadcast a row mask over ``v``'s trailing dims; zero masked rows."""
    return jnp.where(mask.reshape((-1,) + (1,) * (v.ndim - 1)), v,
                     jnp.zeros_like(v))


def _emit_join_columns(lcols: Cols, rcols: Cols, keys, li, ri) -> Cols:
    """Late-materialized join output from ``(left_row, right_row)`` pairs.

    The probe/merge loops emit only the two int32 index lanes — ``li``
    and ``ri`` in each side's original row space, ``-1`` for an absent
    side — and every payload column is gathered here ONCE per side
    (DESIGN.md §8).  Key columns come from whichever side the pair has
    (left wins when both); absent sides zero-fill, so pure-padding pairs
    are zero rows.
    """
    has_l, has_r = li >= 0, ri >= 0
    li_s = jnp.where(has_l, li, 0)
    ri_s = jnp.where(has_r, ri, 0)
    out: Cols = {}
    for k in keys:
        out[k] = jnp.where(
            has_l.reshape((-1,) + (1,) * (lcols[k].ndim - 1)),
            lcols[k][li_s], _bcast(has_r, rcols[k][ri_s]))
    for k, v in lcols.items():
        if k in keys:
            continue
        out[k] = _bcast(has_l, v[li_s])
    for k, v in rcols.items():
        if k in keys:
            continue
        name = k if k not in lcols else f"{k}_r"
        out[name] = _bcast(has_r, v[ri_s])
    out["_matched"] = has_l & has_r
    return out


def _local_sorted_join(lcols: Cols, ln, rcols: Cols, rn, *, keys, how,
                       max_matches, window, out_capacity):
    # hashes carried through the shuffle (or computed here on the
    # single-shard path — same values either way)
    lcols, lh1, lh2 = take_hashes(lcols, keys)
    rcols, rh1, rh2 = take_hashes(rcols, keys)
    lcap = next(iter(lcols.values())).shape[0]
    rcap = next(iter(rcols.values())).shape[0]
    lmask, rmask = _mask_for(ln, lcap), _mask_for(rn, rcap)

    # invalid rows get MAX hash so the sorted array is truly sorted
    # (binary search requires global sortedness, including the tail).
    # Single-key stable sort: equal-h1 candidates are probed through the
    # bounded window below, so no secondary sort key is needed, and only the
    # probe-side arrays ride the sort gather — non-key output columns are
    # gathered once through ``rorder`` at emit time.
    rh1 = jnp.where(rmask, rh1, jnp.uint32(0xFFFFFFFF))
    rorder = jnp.argsort(rh1, stable=True)
    rh1s, rh2s = rh1[rorder], rh2[rorder]
    rvalid_s = rmask[rorder]
    rkey_s = {k: rcols[k][rorder] for k in keys}

    lo = jnp.searchsorted(rh1s, lh1, side="left").astype(jnp.int32)
    hi = jnp.searchsorted(rh1s, lh1, side="right").astype(jnp.int32)
    cnt = hi - lo

    def keys_equal(cand):
        # bitwise identity, matching the hash (NaN keys with equal bits
        # are equal, ±0.0 are not) — value ``==`` would contradict the
        # hash adjacency this path probes by (same fix as groupby PR 2)
        eq = lh2 == rh2s[cand]
        for k in keys:
            eq &= _key_bits_eq(lcols[k], rkey_s[k][cand])
        return eq

    rows = jnp.arange(lcap, dtype=jnp.int32)
    cnt_win = jnp.zeros((lcap,), jnp.int32)  # verified matches in window
    # right rows some left row verified against, even past the fan-out cap
    # (a capped pair must not resurface in the right/outer tail — same
    # rule as the hash path); only those modes pay the scatter
    track_touch = how in ("right", "outer")
    rtouched = jnp.zeros((rcap,), bool)

    def touch(rtouched, ok, cand):
        if not track_touch:
            return rtouched
        return rtouched.at[jnp.where(ok, cand, rcap)].set(True, mode="drop")

    if max_matches == 1:
        # scatter-free fast path: first match wins
        ridx = jnp.full((lcap,), -1, jnp.int32)
        found = jnp.zeros((lcap,), bool)
        for j in range(window):
            cand = jnp.clip(lo + j, 0, rcap - 1)
            ok = (j < cnt) & lmask & rvalid_s[cand] & keys_equal(cand)
            cnt_win += ok.astype(jnp.int32)
            rtouched = touch(rtouched, ok, cand)
            ok &= ~found
            ridx = jnp.where(ok, cand, ridx)
            found |= ok
        right_idx = ridx[:, None]
        matched = found.astype(jnp.int32)
    else:
        matched = jnp.zeros((lcap,), jnp.int32)
        right_idx = jnp.full((lcap, max_matches), -1, jnp.int32)
        for j in range(window):
            cand = jnp.clip(lo + j, 0, rcap - 1)
            ok = (j < cnt) & lmask & rvalid_s[cand] & keys_equal(cand)
            cnt_win += ok.astype(jnp.int32)
            rtouched = touch(rtouched, ok, cand)
            ok &= matched < max_matches
            slot = jnp.clip(matched, 0, max_matches - 1)
            cur = right_idx[rows, slot]
            right_idx = right_idx.at[rows, slot].set(jnp.where(ok, cand, cur))
            matched = matched + ok.astype(jnp.int32)

    # fan-out overflow (§2): matches verified but dropped by max_matches,
    # plus equal-h1 candidates beyond the probe window that could not even
    # be verified — never silently lost
    fanout_ov = jnp.sum(
        jnp.maximum(cnt_win - max_matches, 0)
        + jnp.where(lmask, jnp.maximum(cnt - window, 0), 0), dtype=jnp.int32)

    # expand to (lcap * max_matches) candidate output rows
    li = jnp.repeat(rows, max_matches)
    ri = right_idx.reshape(-1)
    has_match = ri >= 0
    first = (jnp.arange(lcap * max_matches) % max_matches) == 0
    keep_unmatched_l = first & lmask[li] & (matched[li] == 0)
    if how in ("inner", "right"):
        keep = has_match
    else:  # left / outer
        keep = has_match | keep_unmatched_l
    if how in ("right", "outer"):
        # tail block: right rows (in h1-sorted space) no left row verified
        tail_keep = rvalid_s & ~rtouched
        li = jnp.concatenate([li, jnp.full((rcap,), -1, jnp.int32)])
        ri = jnp.concatenate([ri, jnp.arange(rcap, dtype=jnp.int32)])
        keep = jnp.concatenate([keep, tail_keep])

    # ri indexes h1-sorted right space: compose sort + probe gathers so
    # every right column rides one gather through ``rorder``
    rsrc = jnp.where(ri >= 0, rorder[jnp.where(ri >= 0, ri, 0)], -1)
    out = _emit_join_columns(lcols, rcols, keys, li, rsrc)
    cols, n_out, trunc = _compact_cols(out, keep, out_capacity)
    return cols, n_out, trunc + fanout_ov


def _local_hash_join(lcols: Cols, ln, rcols: Cols, rn, *, keys, how,
                     max_matches, max_probes, out_capacity):
    """Sort-free local join: hash build over the right side, counted
    two-pass probe by the left, late-materialized payload gather.

    The build table is seeded by the ``(h1, h2)`` carried through the
    exchange (zero rehash); the probe hot loop touches only the two hash
    lanes plus the bitwise key lanes, and emits bare ``(left_row,
    right_row)`` index pairs at exclusive-scan offsets — output rows are
    born compacted, so the path contains zero ``sort`` primitives
    (DESIGN.md §8).  Overflow counts, per the §2 contract: verified
    matches dropped by ``max_matches``, probe/build rows that exhausted
    ``max_probes`` (their matches are unprovable), and rows past
    ``out_capacity``.
    """
    from repro.kernels.hash_join import ops as hjops

    lcols, lh1, lh2 = take_hashes(lcols, keys)
    rcols, rh1, rh2 = take_hashes(rcols, keys)
    lcap = next(iter(lcols.values())).shape[0]
    rcap = next(iter(rcols.values())).shape[0]
    lmask, rmask = _mask_for(ln, lcap), _mask_for(rn, rcap)
    lkeys = key_compare_u32(lcols, keys)
    rkeys = key_compare_u32(rcols, keys)

    slots = _hash_slots(rcap)
    table, n_unplaced = hjops.build_table(rh1, rh2, rmask, slots, max_probes)
    slot_h2, slot_keys = hjops.slot_payload(table, rh2, rkeys)
    cnt, rimat, exhausted = hjops.probe(table, slot_h2, slot_keys, lh1, lh2,
                                        lkeys, lmask, max_matches,
                                        max_probes)

    keep_all_left = how in ("left", "outer")
    emit_n = jnp.minimum(cnt, max_matches)
    if keep_all_left:
        emit_n = jnp.maximum(emit_n, 1)
    emit_n = jnp.where(lmask, emit_n, 0)
    base = jnp.cumsum(emit_n) - emit_n  # exclusive scan → packed offsets
    total = jnp.sum(emit_n, dtype=jnp.int32)
    li, ri = hjops.emit_lookup(rimat, base, emit_n, total, out_capacity)
    overflow = (jnp.sum(jnp.where(lmask, jnp.maximum(cnt - max_matches, 0),
                                  0), dtype=jnp.int32)
                + jnp.sum(exhausted, dtype=jnp.int32) + n_unplaced)
    if how in ("right", "outer"):
        # tail: right rows no left row's key matches, found by the reverse
        # membership probe (a unique-key table over the LEFT side) — a
        # right row whose pairs were all dropped by the fan-out cap stays
        # matched, so capped pairs never resurface as unmatched rows
        lslots = _hash_slots(lcap)
        lowner, _, l_unres = hjops.build_table_unique(
            lh1, lh2, lkeys, lmask, lslots, max_probes)
        lsh2, lskeys = hjops.slot_payload(lowner, lh2, lkeys)
        rcnt, _, rexh = hjops.probe(lowner, lsh2, lskeys, rh1, rh2,
                                    rkeys, rmask, 1, max_probes)
        tail = rmask & (rcnt == 0) & ~rexh
        tcum = jnp.cumsum(tail.astype(jnp.int32))
        tpos = jnp.where(tail, total + tcum - 1, out_capacity)
        ri = ri.at[tpos].set(jnp.arange(rcap, dtype=jnp.int32), mode="drop")
        total = total + jnp.sum(tail, dtype=jnp.int32)
        overflow = (overflow + jnp.sum(l_unres, dtype=jnp.int32)
                    + jnp.sum(rexh, dtype=jnp.int32))

    out = _emit_join_columns(lcols, rcols, keys, li, ri)
    overflow = overflow + jnp.maximum(total - out_capacity, 0)
    return out, jnp.minimum(total, out_capacity), overflow


def _join_impl(lc, lcnt, rc, rcnt, *, keys, how, method, max_matches,
               window, max_probes, n_shards, lbucket, rbucket, mid_cap_l,
               mid_cap_r, out_capacity, axis, shuffle_left, shuffle_right):
    lcols, ln = _local_parts(lc, lcnt)
    rcols, rn = _local_parts(rc, rcnt)
    ov = jnp.zeros((), jnp.int32)
    if n_shards > 1:
        # co-locate equal keys; carry (h1, h2) so the local join never
        # rehashes the shuffled rows — the hash path seeds its build table
        # straight from the carried hashes (DESIGN.md §3.3/§8).  A side
        # whose partitioning metadata already proves co-location skips its
        # exchange (DESIGN.md §4); its hashes are recomputed locally by
        # take_hashes.
        if shuffle_left:
            lcols, ln, o = hash_shuffle(lcols, ln, keys, n_shards, lbucket,
                                        mid_cap_l, axis, carry_hashes=True)
            ov = ov + o
        if shuffle_right:
            rcols, rn, o = hash_shuffle(rcols, rn, keys, n_shards, rbucket,
                                        mid_cap_r, axis, carry_hashes=True)
            ov = ov + o
    if method == "hash":
        out, cnt, ov_o = _local_hash_join(
            lcols, ln, rcols, rn, keys=keys, how=how,
            max_matches=max_matches, max_probes=max_probes,
            out_capacity=out_capacity)
    else:
        out, cnt, ov_o = _local_sorted_join(
            lcols, ln, rcols, rn, keys=keys, how=how,
            max_matches=max_matches, window=window,
            out_capacity=out_capacity)
    overflow = ov + ov_o
    if axis is not None:
        overflow = spmd_allreduce(overflow, axis)
    return out, cnt[None], overflow


@operator("table.join", Abstraction.TABLE)
def join(left: DistTable, right: DistTable, keys: Sequence[str], *,
         ctx: HPTMTContext, how: str = "inner", max_matches: int = 1,
         window: int = 4, out_capacity: Optional[int] = None,
         bucket_factor: float = 2.0, method: str = "auto",
         max_probes: Optional[int] = None
         ) -> Tuple[DistTable, jnp.ndarray]:
    """Distributed equi-join: shuffle-by-key + local hash build/probe
    (Table III); ``how`` is inner/left/right/outer.

    ``method`` selects the local kernel (DESIGN.md §8): ``"hash"`` — a
    sort-free open-addressing build over the right side plus a counted
    two-pass probe with late-materialized payload gathers; ``"sort"`` —
    the sort-merge oracle (argsort by carried hash + bounded probe
    window).  ``"auto"`` picks hash: it is sort-free, faster at every
    measured size, and reports rather than misses fan-out beyond its
    probe bound.  Put the smaller table on the right — it is the build
    side (conventional for both kernels: sort-merge orders the right side
    too, and swapping sides internally would silently change which side
    ``max_matches`` caps).

    ``max_matches`` bounds the join fan-out per left row (static shapes);
    matches beyond it — and, on the hash path, rows whose probe chain
    exceeds ``max_probes`` — are counted in the returned overflow, never
    silently lost.  A side already hash-partitioned on exactly ``keys``
    skips its shuffle; the output is itself partitioned on ``keys``
    (matched rows stay on the shard their key hashed to), so a following
    groupby/join on the same keys moves no data (DESIGN.md §4).
    """
    if how not in _JOIN_HOWS:
        raise ValueError(f"unknown join type how={how!r}; "
                         f"expected one of {_JOIN_HOWS}")
    if method not in ("auto", "hash", "sort"):
        raise ValueError(f"unknown join method={method!r}; "
                         f"expected 'auto', 'hash' or 'sort'")
    if max_matches < 1:
        raise ValueError(f"max_matches={max_matches} must be >= 1")
    if method == "auto":
        method = "hash"
    check_no_reserved(left.column_names)
    check_no_reserved(right.column_names)
    n = ctx.n_shards
    mid_l = max(left.capacity, 1)
    mid_r = max(right.capacity, 1)
    default_out = mid_l * max_matches + (
        mid_r if how in ("right", "outer") else 0)
    impl = functools.partial(
        _join_impl, keys=tuple(keys), how=how, method=method,
        max_matches=max_matches, window=window,
        max_probes=max_probes or max(64, 2 * max_matches), n_shards=n,
        lbucket=_bucket_capacity(left.capacity, n, bucket_factor),
        rbucket=_bucket_capacity(right.capacity, n, bucket_factor),
        mid_cap_l=mid_l, mid_cap_r=mid_r,
        out_capacity=out_capacity or default_out,
        shuffle_left=not _partitioned_on(left, keys, ctx),
        shuffle_right=not _partitioned_on(right, keys, ctx))
    cols, counts, overflow = _run_sharded(
        ctx, impl, (left.columns, left.counts, right.columns, right.counts),
        out_specs=(P(ctx.data_axis), P(ctx.data_axis), P()))
    return DistTable(cols, counts, (tuple(keys), n)), overflow


# ===========================================================================
# GroupBy + Aggregate (Table III)
# ===========================================================================
_SEGMENT_OPS = ("sum", "mean", "min", "max", "count")


def split_aggs(aggs):
    """Decompose aggregates into (map-side partial, merge) aggregates.

    sum/count/min/max combine associatively; mean decomposes into a sum and
    a count that are summed at the merge and divided at finalize (the mean
    decomposition rule, DESIGN.md §4).  Shared by the eager map-side combine
    and the dataflow combiner barrier.
    """
    partial, merge = [], []
    for col, op in aggs:
        if op in ("sum", "count"):
            partial.append((col, op))
            merge.append((f"{col}_{op}", "sum"))
        elif op in ("min", "max"):
            partial.append((col, op))
            merge.append((f"{col}_{op}", op))
        elif op == "mean":
            partial.append((col, "sum"))
            partial.append((col, "count"))
            merge.append((f"{col}_sum", "sum"))
            merge.append((f"{col}_count", "sum"))
        else:
            raise ValueError(op)
    return tuple(dict.fromkeys(partial)), tuple(dict.fromkeys(merge))


def finalize_agg_cols(cols: Cols, aggs, merge_aggs) -> Cols:
    """Rename merged partial-aggregate columns to the user's labels.

    ``cols`` holds key columns plus ``{col}_{partial}_{mergeop}`` outputs of
    the merge groupby; means are finalized as sum/count here (and only
    here — partials never divide).
    """
    merge_labels = {f"{c}_{o}" for c, o in merge_aggs}
    out = {k: v for k, v in cols.items() if k not in merge_labels}
    for col, op in aggs:
        if op == "mean":
            s, c = cols[f"{col}_sum_sum"], cols[f"{col}_count_sum"]
            out[f"{col}_mean"] = s / jnp.maximum(c, 1.0)
        elif op in ("sum", "count"):
            out[f"{col}_{op}"] = cols[f"{col}_{op}_sum"]
        else:
            out[f"{col}_{op}"] = cols[f"{col}_{op}_{op}"]
    return out


def _agg_outputs(aggs, seg_count, sums, minmax, out_capacity):
    """Assemble labeled aggregate columns from the shared reductions."""
    out: Cols = {}
    for col, agg in aggs:
        label = f"{col}_{agg}"
        if agg == "count":
            out[label] = seg_count[:out_capacity]
        elif agg == "sum":
            out[label] = sums[col][:out_capacity]
        elif agg == "mean":
            s = sums[col]
            cnt = seg_count.reshape((-1,) + (1,) * (s.ndim - 1))
            out[label] = (s / jnp.maximum(cnt, 1.0))[:out_capacity]
        else:
            out[label] = minmax[(col, agg)][:out_capacity]
    return out


def _segment_aggregates(cols: Cols, aggs, seg_id, n_segments: int):
    """All reductions for ``aggs`` over ``seg_id`` with minimal scatters.

    Every sum-combining lane (counts + sums, incl. both halves of mean)
    rides ONE fused segment reduction — trailing dims flatten to extra
    lanes and are reshaped back after; min/max reduce per column.
    Repeated (column, op) pairs are computed once.
    """
    from repro.kernels.segment_reduce import ops as segops

    cap = seg_id.shape[0]
    need_count = any(a in ("count", "mean") for _, a in aggs)
    sum_cols = list(dict.fromkeys(
        c for c, a in aggs if a in ("sum", "mean")))
    parts, spans = [], []  # spans: (col name | None=count, trailing, lanes)
    if need_count:
        parts.append(jnp.ones((1, cap), jnp.float32))
        spans.append((None, (), 1))
    for c in sum_cols:
        v = cols[c].astype(jnp.float32).reshape(cap, -1).T   # lanes-major
        parts.append(v)
        spans.append((c, tuple(cols[c].shape[1:]), v.shape[0]))
    seg_count, sums = None, {}
    if parts:
        fused = segops.segment_reduce_fused(
            jnp.concatenate(parts, axis=0), seg_id, n_segments)
        off = 0
        for name, trailing, lanes in spans:
            block = fused[off:off + lanes]
            off += lanes
            if name is None:
                seg_count = block[0]
            else:
                sums[name] = block.T.reshape((fused.shape[1],) + trailing)
    minmax = {}
    for col, agg in aggs:
        if agg in ("min", "max") and (col, agg) not in minmax:
            minmax[(col, agg)] = segops.segment_reduce(
                cols[col].astype(jnp.float32), seg_id, n_segments, op=agg)
    return seg_count, sums, minmax


def _local_groupby_sort(cols: Cols, count, *, keys, aggs, out_capacity):
    """Sort-based grouping: lexsort keys, segment-reduce runs."""
    cap = next(iter(cols.values())).shape[0]
    mask = _mask_for(count, cap)
    key_arrays = [cols[k] for k in keys]
    sorted_cols, order = _sort_cols(cols, key_arrays, mask)
    smask = mask[order]

    # a row opens a new segment when ANY key differs from its predecessor
    # (row 0 always does)
    new_seg = jnp.concatenate(
        [jnp.ones((1,), bool), jnp.zeros((cap - 1,), bool)])
    for k in keys:
        col = sorted_cols[k]
        new_seg = new_seg | jnp.concatenate(
            [jnp.ones((1,), bool), col[1:] != col[:-1]])
    new_seg = new_seg & smask
    seg_id = jnp.cumsum(new_seg.astype(jnp.int32)) - 1
    n_seg = jnp.maximum(jnp.max(jnp.where(smask, seg_id, -1)) + 1, 0)
    seg_id = jnp.where(smask, seg_id, cap)  # sentinel bucket for invalid

    out: Cols = {}
    # first row of each segment via counting scatter (segment ids of the
    # boundary rows are unique), no argsort
    first_idx = jnp.zeros((cap,), jnp.int32).at[
        jnp.where(new_seg, seg_id, cap)].set(
        jnp.arange(cap, dtype=jnp.int32), mode="drop")
    for k in keys:
        out[k] = sorted_cols[k][first_idx][:out_capacity]
    seg_count, sums, minmax = _segment_aggregates(
        sorted_cols, aggs, seg_id, cap + 1)
    out.update(_agg_outputs(aggs, seg_count, sums, minmax, out_capacity))
    # zero-fill rows beyond n_seg; pad when out_capacity exceeds the input
    # capacity (there can be at most ``cap`` groups, the rest is padding)
    m = _mask_for(jnp.minimum(n_seg, out_capacity), out_capacity)
    out = {k: jnp.where(m.reshape((-1,) + (1,) * (v.ndim - 1)),
                        _pad_axis0(v, out_capacity),
                        jnp.zeros(((out_capacity,) + v.shape[1:]), v.dtype))
           for k, v in out.items()}
    overflow = jnp.maximum(n_seg - out_capacity, 0)
    return out, jnp.minimum(n_seg, out_capacity).astype(jnp.int32), overflow


def _key_bits_eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Key equality by the same identity the hash uses.

    Float keys compare by f32 bit pattern, exactly matching
    ``table.hash_columns`` — so a row's key-compare verdict is always
    consistent with its probe sequence.  Value-compare (``==``) would
    deadlock NaN keys (NaN != NaN even against the row's own claimed slot,
    so each NaN row would claim a fresh slot every round) and would call
    ``-0.0 == +0.0`` equal while their hashes differ.  Consequence: the
    hash kernel groups float keys bitwise (equal-bit NaNs form one group,
    ±0.0 form two), where the sort kernel groups by value.
    """
    if jnp.issubdtype(a.dtype, jnp.floating):
        a = jax.lax.bitcast_convert_type(a.astype(jnp.float32), jnp.uint32)
        b = jax.lax.bitcast_convert_type(b.astype(jnp.float32), jnp.uint32)
    return a == b


def _local_groupby_hash(cols: Cols, count, *, keys, aggs, out_capacity,
                        max_probes: int = 64):
    """Sort-free grouping: claim hash-table slots, segment-reduce by slot.

    Each valid row double-hash-probes a power-of-two slot table via the
    shared ``build_table_unique`` primitive (``kernels/hash_join``, also
    under the join and set-op paths — DESIGN.md §8): the lowest row index
    probing a free slot claims it for its key (scatter-min), and rows
    match a slot only after comparing their ACTUAL bitwise key lanes
    against the claimant (hash equality is never trusted, DESIGN.md §4).
    The probe loop exits as soon as every valid row is resolved —
    typically 2-3 rounds at the ≤25% load factor implied by the 4x slot
    head-room.  Rows unresolved after ``max_probes`` (cardinality far
    beyond ``out_capacity``) are counted as overflow, per the §2
    contract.  O(n) per round, zero sorts.
    """
    from repro.kernels.hash_join import ops as hjops

    from .table import hash_columns

    cap = next(iter(cols.values())).shape[0]
    mask = _mask_for(count, cap)
    slots = _hash_slots(out_capacity)
    h1, h2 = hash_columns([cols[k] for k in keys])
    owner, seg, unresolved = hjops.build_table_unique(
        h1, h2, key_compare_u32(cols, keys), mask, slots, max_probes)

    occupied = owner >= 0
    claimant = jnp.where(occupied, owner, 0)
    slot_cols: Cols = {k: jnp.where(
        occupied.reshape((-1,) + (1,) * (cols[k].ndim - 1)),
        cols[k][claimant], jnp.zeros_like(cols[k][claimant])) for k in keys}
    seg_count, sums, minmax = _segment_aggregates(cols, aggs, seg, slots + 1)
    slot_cols.update(_agg_outputs(aggs, seg_count, sums, minmax, slots))
    out, n_seg, trunc = compact_rows(slot_cols, occupied, out_capacity)
    overflow = jnp.sum(unresolved, dtype=jnp.int32) + trunc
    return out, n_seg, overflow


def _local_groupby(cols: Cols, count, *, keys, aggs, out_capacity,
                   method: str = "auto"):
    """Local grouping, dispatching sort vs hash (DESIGN.md §4).

    ``auto`` picks the sort-free hash table when the caller declared low
    cardinality (``out_capacity`` at most a quarter of the row capacity —
    the slot table then still fits the 4x head-room), else the lexsort
    path.  Returns ``(columns, n_groups, overflow)``.  Overflow is a
    data-loss indicator (zero iff nothing was dropped); its unit is groups
    for capacity truncation and rows for hash-probe exhaustion, and which
    groups survive truncation is deterministic per kernel but differs
    between them (sorted-key order vs hash-slot order) — callers retrying
    per the §2 contract should grow capacity, not interpret the count.
    """
    cap = next(iter(cols.values())).shape[0]
    if method == "auto":
        method = "hash" if out_capacity * 4 <= cap else "sort"
    if method == "hash":
        return _local_groupby_hash(cols, count, keys=keys, aggs=aggs,
                                   out_capacity=out_capacity)
    return _local_groupby_sort(cols, count, keys=keys, aggs=aggs,
                               out_capacity=out_capacity)


def _groupby_impl(cols, counts, *, keys, aggs, n_shards, bucket,
                  mid_capacity, out_capacity, axis, elide, combine,
                  partial_cap, combine_bucket, method):
    local_cols, count = _local_parts(cols, counts)
    ov = jnp.zeros((), jnp.int32)
    if n_shards > 1 and not elide:
        if combine:
            # map-side combine: pre-aggregate locally so only distinct
            # (key, partial) rows enter the packed AllToAll
            partial_aggs, merge_aggs = split_aggs(aggs)
            pcols, pcount, o = _local_groupby(
                local_cols, count, keys=keys, aggs=partial_aggs,
                out_capacity=partial_cap, method=method)
            ov += o
            mid = n_shards * combine_bucket
            pcols, pcount, o = hash_shuffle(
                pcols, pcount, keys, n_shards, combine_bucket, mid, axis)
            ov += o
            out, n_seg, o = _local_groupby(
                pcols, pcount, keys=keys, aggs=merge_aggs,
                out_capacity=out_capacity, method=method)
            ov += o
            out = finalize_agg_cols(out, aggs, merge_aggs)
        else:
            local_cols, count, o = hash_shuffle(
                local_cols, count, keys, n_shards, bucket, mid_capacity,
                axis)
            out, n_seg, o2 = _local_groupby(
                local_cols, count, keys=keys, aggs=aggs,
                out_capacity=out_capacity, method=method)
            ov += o + o2
    else:
        # single shard, or rows already co-located on the keys: no exchange
        out, n_seg, o = _local_groupby(local_cols, count, keys=keys,
                                       aggs=aggs, out_capacity=out_capacity,
                                       method=method)
        ov += o
    if axis is not None:
        ov = spmd_allreduce(ov, axis)
    return out, n_seg[None], ov


@operator("table.groupby", Abstraction.TABLE)
def groupby_aggregate(dt: DistTable, keys: Sequence[str],
                      aggs: Sequence[Tuple[str, str]], *, ctx: HPTMTContext,
                      out_capacity: Optional[int] = None,
                      bucket_factor: float = 2.0,
                      combine: "bool | str" = "auto",
                      method: str = "auto",
                      ) -> Tuple[DistTable, jnp.ndarray]:
    """GroupBy + aggregate (Table III): shuffle-by-key + segment reduce.

    ``aggs`` is a list of ``(column, op)`` with op in sum/mean/min/max/count.

    Two data-movement optimisations (DESIGN.md §4):

      * **Shuffle elision** — when ``dt.partitioning`` records that rows are
        already hash-co-located on exactly these ``keys`` (e.g. the output
        of a join or shuffle on the same keys), the exchange is skipped
        entirely and grouping is purely local.
      * **Map-side combine** (``combine``) — pre-aggregate locally before
        the exchange so only distinct ``(key, partial)`` rows cross the
        network; mean decomposes into sum+count and is finalized after the
        merge.  ``"auto"`` enables it when ``out_capacity`` declares
        cardinality below the row capacity (which also shrinks the
        AllToAll frame itself).

    ``method`` selects the local grouping kernel: ``"sort"`` (lexsort +
    segment runs), ``"hash"`` (sort-free slot table), or ``"auto"``.
    """
    for _, a in aggs:
        if a not in _SEGMENT_OPS:
            raise ValueError(f"unknown aggregate {a!r}")
    if method not in ("auto", "sort", "hash"):
        raise ValueError(f"unknown groupby method {method!r}")
    if not isinstance(combine, bool) and combine != "auto":
        raise ValueError(f"combine must be a bool or 'auto', got {combine!r}")
    check_no_reserved(dt.column_names)
    n = ctx.n_shards
    out_cap = out_capacity or dt.capacity
    elide = _partitioned_on(dt, keys, ctx)
    do_combine = combine if isinstance(combine, bool) else (
        out_cap < dt.capacity)
    partial_cap = (dt.capacity if out_cap >= dt.capacity
                   else min(dt.capacity, out_cap * n))
    impl = functools.partial(
        _groupby_impl, keys=tuple(keys), aggs=tuple(aggs), n_shards=n,
        bucket=_bucket_capacity(dt.capacity, n, bucket_factor),
        mid_capacity=dt.capacity, out_capacity=out_cap, elide=elide,
        combine=do_combine, partial_cap=partial_cap,
        combine_bucket=_bucket_capacity(partial_cap, n, bucket_factor),
        method=method)
    cols, counts, overflow = _run_sharded(
        ctx, impl, (dt.columns, dt.counts),
        out_specs=(P(ctx.data_axis), P(ctx.data_axis), P()))
    return DistTable(cols, counts, (tuple(keys), n)), overflow


@operator("table.aggregate", Abstraction.TABLE)
def aggregate(dt: DistTable, column: str, op: str, *, ctx: HPTMTContext):
    """Global scalar aggregate of one column (Table III Aggregate)."""

    def impl(cols, counts, *, axis):
        local_cols, count = _local_parts(cols, counts)
        cap = next(iter(local_cols.values())).shape[0]
        mask = _mask_for(count, cap)
        col = local_cols[column].astype(jnp.float32)
        if op == "sum":
            v = jnp.sum(jnp.where(mask, col, 0.0))
        elif op == "count":
            v = jnp.sum(mask.astype(jnp.float32))
        elif op == "mean":
            v = jnp.sum(jnp.where(mask, col, 0.0))
        elif op == "min":
            v = jnp.min(jnp.where(mask, col, jnp.inf))
        elif op == "max":
            v = jnp.max(jnp.where(mask, col, -jnp.inf))
        else:
            raise ValueError(f"unknown aggregate {op!r}")
        if axis is not None:
            red = {"sum": "sum", "count": "sum", "mean": "sum",
                   "min": "min", "max": "max"}[op]
            v = spmd_allreduce(v, axis, op=red)
            if op == "mean":
                n = spmd_allreduce(jnp.sum(mask.astype(jnp.float32)), axis)
                v = v / jnp.maximum(n, 1.0)
        elif op == "mean":
            v = v / jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)
        return v

    return _run_sharded(ctx, impl, (dt.columns, dt.counts), out_specs=P())


# ===========================================================================
# set operators: Union / Difference / Intersect / Cartesian (Table II/III)
# ===========================================================================
def _dedup_hash(cols: Cols, h1, h2, mask, max_probes: int = 64):
    """Keep the lowest-index row of every bitwise-equal duplicate group.

    Sort-free: rows claim unique-key slots (``build_table_unique`` over
    the carried full-row hashes) and only slot claimants survive.  Rows
    whose probe chain exhausts are *kept* and counted — dropping them
    could lose a distinct row, keeping one can at worst leave a duplicate
    that the overflow count tells the caller to retry away (§2).
    Returns ``(keep, n_unresolved)``; row identity is bitwise (equal-bit
    NaNs deduplicate, ±0.0 stay distinct), consistent with the hashes.
    """
    from repro.kernels.hash_join import ops as hjops

    cap = h1.shape[0]
    keys_u32 = key_compare_u32(cols, tuple(sorted(cols)))
    owner, seg, unresolved = hjops.build_table_unique(
        h1, h2, keys_u32, mask, _hash_slots(cap), max_probes)
    rows = jnp.arange(cap, dtype=jnp.int32)
    claimant = owner[jnp.where(unresolved, 0, seg)] == rows
    keep = mask & (unresolved | claimant)
    return keep, jnp.sum(unresolved, dtype=jnp.int32)


def _membership_hash(a_cols: Cols, amask, ah1, ah2, b_cols: Cols, bmask,
                     bh1, bh2, names, max_probes: int = 64):
    """For each row of A: does a bitwise-equal row exist in B?

    Hash + verify over a unique-key slot table of B — the same build/probe
    primitives as the join, seeded by the carried hashes (zero rehash,
    zero sorts).  Returns ``(found, n_overflow)`` where the count covers B
    rows missing from the table and A probes that exhausted — for both,
    membership is unprovable, so the caller surfaces the count (§2).
    """
    from repro.kernels.hash_join import ops as hjops

    bkeys = key_compare_u32(b_cols, names)
    akeys = key_compare_u32(a_cols, names)
    owner, _, b_unres = hjops.build_table_unique(
        bh1, bh2, bkeys, bmask, _hash_slots(bh1.shape[0]), max_probes)
    slot_h2, slot_keys = hjops.slot_payload(owner, bh2, bkeys)
    cnt, _, exhausted = hjops.probe(owner, slot_h2, slot_keys, ah1, ah2,
                                    akeys, amask, 1, max_probes)
    found = amask & (cnt > 0)
    overflow = (jnp.sum(b_unres, dtype=jnp.int32)
                + jnp.sum(exhausted, dtype=jnp.int32))
    return found, overflow


def _setop_impl(ac, acnt, bc, bcnt, *, kind, names, n_shards, abucket,
                bbucket, mid_a, mid_b, out_capacity, axis, shuffle_a,
                shuffle_b):
    acols, an = _local_parts(ac, acnt)
    bcols, bn = _local_parts(bc, bcnt)
    ov = jnp.zeros((), jnp.int32)

    if n_shards > 1:
        # sides whose metadata proves co-location on the full schema skip
        # their exchange (DESIGN.md §4)
        if shuffle_a:
            acols, an, o = hash_shuffle(acols, an, names, n_shards, abucket,
                                        mid_a, axis, carry_hashes=True)
            ov += o
        if shuffle_b:
            bcols, bn, o = hash_shuffle(bcols, bn, names, n_shards, bbucket,
                                        mid_b, axis, carry_hashes=True)
            ov += o
    # hashes: popped from the shuffle carry, or computed once here — they
    # seed the set-op slot tables directly (build-side reuse, DESIGN.md §8)
    acols, ah1, ah2 = take_hashes(acols, names)
    bcols, bh1, bh2 = take_hashes(bcols, names)

    acap = next(iter(acols.values())).shape[0]
    bcap = next(iter(bcols.values())).shape[0]
    amask, bmask = _mask_for(an, acap), _mask_for(bn, bcap)

    if kind == "union":
        # concat then hash-dedup (hashes concatenate alongside the rows)
        cat = {k: jnp.concatenate([acols[k], bcols[k]]) for k in acols}
        cmask = jnp.concatenate([amask, bmask])
        h1 = jnp.concatenate([ah1, bh1])
        h2 = jnp.concatenate([ah2, bh2])
        keep, o_dedup = _dedup_hash(cat, h1, h2, cmask)
        out, cnt, o = _compact_cols(cat, keep, out_capacity)
    elif kind == "difference":
        found, o_dedup = _membership_hash(acols, amask, ah1, ah2, bcols,
                                          bmask, bh1, bh2, names)
        out, cnt, o = _compact_cols(acols, amask & ~found, out_capacity)
    elif kind == "intersect":
        found, o_mem = _membership_hash(acols, amask, ah1, ah2, bcols,
                                        bmask, bh1, bh2, names)
        keep, o_d = _dedup_hash(acols, ah1, ah2, found)
        o_dedup = o_mem + o_d
        out, cnt, o = _compact_cols(acols, keep, out_capacity)
    else:
        raise ValueError(kind)
    ov = ov + o + o_dedup
    if axis is not None:
        ov = spmd_allreduce(ov, axis)
    return out, cnt[None], ov


def _make_setop(kind: str, opname: str, doc: str):
    @operator(opname, Abstraction.TABLE)
    def op(a: DistTable, b: DistTable, *, ctx: HPTMTContext,
           out_capacity: Optional[int] = None, bucket_factor: float = 2.0,
           ) -> Tuple[DistTable, jnp.ndarray]:
        names = tuple(sorted(set(a.column_names) & set(b.column_names)))
        if names != a.column_names or names != b.column_names:
            raise ValueError("set operators require identical schemas")
        check_no_reserved(names)
        n = ctx.n_shards
        default_out = (a.capacity + b.capacity if kind == "union"
                       else a.capacity)
        impl = functools.partial(
            _setop_impl, kind=kind, names=names, n_shards=n,
            abucket=_bucket_capacity(a.capacity, n, bucket_factor),
            bbucket=_bucket_capacity(b.capacity, n, bucket_factor),
            mid_a=a.capacity, mid_b=b.capacity,
            out_capacity=out_capacity or default_out,
            shuffle_a=not _partitioned_on(a, names, ctx),
            shuffle_b=not _partitioned_on(b, names, ctx))
        cols, counts, overflow = _run_sharded(
            ctx, impl, (a.columns, a.counts, b.columns, b.counts),
            out_specs=(P(ctx.data_axis), P(ctx.data_axis), P()))
        # output rows keep the shard their full-row hash assigned
        return DistTable(cols, counts, (names, n)), overflow

    op.__doc__ = doc
    op.__name__ = kind
    return op


union = _make_setop("union", "table.union",
                    "Distributed Union with duplicate removal (Table II).")
difference = _make_setop(
    "difference", "table.difference",
    "Rows of A with no equal row in B (Table II Difference).")
intersect = _make_setop(
    "intersect", "table.intersect",
    "Deduplicated rows of A that also appear in B (Table III Intersect).")


@operator("table.cartesian", Abstraction.TABLE)
def cartesian(a: DistTable, b: DistTable, *, ctx: HPTMTContext,
              out_capacity: Optional[int] = None) -> DistTable:
    """Cartesian product (Table II): AllGather right, local cross join."""
    n = ctx.n_shards

    def impl(ac, acnt, bc, bcnt, *, axis):
        acols, an = _local_parts(ac, acnt)
        bcols, bn = _local_parts(bc, bcnt)
        acap = next(iter(acols.values())).shape[0]
        bcap = next(iter(bcols.values())).shape[0]
        if axis is not None:
            bcols = {k: spmd_allgather(v, axis) for k, v in bcols.items()}
            bns = spmd_allgather(bn[None], axis)
        else:
            bns = bn[None]
        bg = bcols[next(iter(bcols))].shape[0]
        # validity of gathered right rows
        pos = jnp.arange(bg, dtype=jnp.int32)
        bvalid = (pos % bcap) < bns[pos // bcap]
        li = jnp.repeat(jnp.arange(acap, dtype=jnp.int32), bg)
        ri = jnp.tile(jnp.arange(bg, dtype=jnp.int32), acap)
        keep = _mask_for(an, acap)[li] & bvalid[ri]
        out = {f"a_{k}": v[li] for k, v in acols.items()}
        out.update({f"b_{k}": v[ri] for k, v in bcols.items()})
        cols, cnt, _ = _compact_cols(out, keep, out_capacity or acap * bg)
        return cols, cnt[None]

    cols, counts = _run_sharded(
        ctx, impl, (a.columns, a.counts, b.columns, b.counts),
        out_specs=(P(ctx.data_axis), P(ctx.data_axis)))
    return DistTable(cols, counts)
