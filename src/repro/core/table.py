"""Columnar Table abstraction (paper §IV).

An Arrow-style struct-of-arrays table, adapted to XLA's static-shape world
(DESIGN.md §2 item 1):

  * every column is a fixed-dtype array of length ``capacity`` (static);
  * rows ``[0, num_rows)`` are valid and compacted to the front; rows beyond
    are padding (their contents are ignored by all operators);
  * heterogeneous dtypes across columns, homogeneous within a column — the
    paper's definition of a table;
  * variable-width data (strings) are dictionary-encoded into fixed-width
    integer id columns (the standard static-shape encoding).

``Table`` is a single-shard (local) table; :class:`DistTable` is the
row-partitioned distributed form (paper §IV-B: "most of the time, data
processing systems work on tables distributed with row-based partitioning").
Both are pytrees, so tables flow through ``jax.jit`` / ``shard_map`` like any
tensor — this is what lets table operators and tensor operators compose in a
single compiled program (the HPTMT thesis).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .context import HPTMTContext

Columns = Dict[str, jnp.ndarray]

# ---------------------------------------------------------------------------
# hashing (order must match kernels/hash_partition)
# ---------------------------------------------------------------------------
_H1_INIT = np.uint32(0x9E3779B9)
_H2_INIT = np.uint32(0x85EBCA6B)
_MUL1 = np.uint32(0xCC9E2D51)
_MUL2 = np.uint32(0x1B873593)


def _as_u32(col: jnp.ndarray) -> jnp.ndarray:
    """Bit-stable 32-bit view of a column for hashing."""
    if col.dtype == jnp.bool_:
        return col.astype(jnp.uint32)
    if jnp.issubdtype(col.dtype, jnp.floating):
        col = col.astype(jnp.float32)
        return jax.lax.bitcast_convert_type(col, jnp.uint32)
    return col.astype(jnp.uint32)


def _mix(h: jnp.ndarray, k: jnp.ndarray, mul: np.uint32) -> jnp.ndarray:
    k = (k * mul)
    k = (k << 15) | (k >> 17)
    h = h ^ k
    h = (h << 13) | (h >> 19)
    return h * np.uint32(5) + np.uint32(0xE6546B64)


def hash_columns(cols: Sequence[jnp.ndarray]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Two independent 32-bit hashes per row (≈64-bit identity)."""
    n = cols[0].shape[0]
    h1 = jnp.full((n,), _H1_INIT, dtype=jnp.uint32)
    h2 = jnp.full((n,), _H2_INIT, dtype=jnp.uint32)
    for c in cols:
        k = _as_u32(c)
        h1 = _mix(h1, k, _MUL1)
        h2 = _mix(h2, k ^ np.uint32(0xDEADBEEF), _MUL2)
    # final avalanche
    h1 = h1 ^ (h1 >> 16)
    h2 = h2 ^ (h2 >> 16)
    return h1, h2


# ---------------------------------------------------------------------------
# local Table
# ---------------------------------------------------------------------------
@jax.tree_util.register_pytree_node_class
class Table:
    """A local columnar table with static capacity and dynamic row count."""

    def __init__(self, columns: Columns, num_rows: jnp.ndarray):
        if not columns:
            raise ValueError("Table needs at least one column")
        caps = {v.shape[0] for v in columns.values()}
        if len(caps) != 1:
            raise ValueError(f"column capacities differ: {caps}")
        self.columns = dict(columns)
        self.num_rows = jnp.asarray(num_rows, dtype=jnp.int32)

    # -- construction ----------------------------------------------------
    @classmethod
    def from_arrays(cls, columns: Columns, num_rows=None,
                    capacity: Optional[int] = None) -> "Table":
        cols = {k: jnp.asarray(v) for k, v in columns.items()}
        n = next(iter(cols.values())).shape[0]
        if num_rows is None:
            num_rows = n
        if capacity is not None and capacity != n:
            if capacity < n:
                raise ValueError("capacity smaller than provided rows")
            cols = {k: _pad_axis0(v, capacity) for k, v in cols.items()}
        return cls(cols, jnp.asarray(num_rows, jnp.int32))

    # -- pytree ------------------------------------------------------------
    def tree_flatten(self):
        names = tuple(sorted(self.columns))
        children = tuple(self.columns[k] for k in names) + (self.num_rows,)
        return children, names

    @classmethod
    def tree_unflatten(cls, names, children):
        cols = dict(zip(names, children[:-1]))
        obj = object.__new__(cls)
        obj.columns = cols
        obj.num_rows = children[-1]
        return obj

    # -- properties --------------------------------------------------------
    @property
    def capacity(self) -> int:
        return next(iter(self.columns.values())).shape[0]

    @property
    def column_names(self) -> Tuple[str, ...]:
        return tuple(sorted(self.columns))

    def row_mask(self) -> jnp.ndarray:
        return jnp.arange(self.capacity, dtype=jnp.int32) < self.num_rows

    def key_arrays(self, keys: Sequence[str]) -> Tuple[jnp.ndarray, ...]:
        return tuple(self.columns[k] for k in keys)

    # -- basic local transforms ---------------------------------------------
    def take(self, idx: jnp.ndarray, num_rows) -> "Table":
        cols = {k: v[idx] for k, v in self.columns.items()}
        return Table(cols, num_rows)

    def compact(self, keep_mask: jnp.ndarray) -> "Table":
        """Keep rows where ``keep_mask`` (within valid range); re-compact.

        Sort-free: cumsum-scatter compaction (DESIGN.md §3), stable in row
        order; dropped slots are zero-filled padding.
        """
        from .exchange import compact_rows  # no import cycle: exchange
        # has no top-level dependency on table
        keep = keep_mask & self.row_mask()
        cols, n, _ = compact_rows(self.columns, keep, self.capacity)
        return Table(cols, n)

    def with_capacity(self, capacity: int) -> "Table":
        cols = {k: _pad_axis0(v[:capacity] if capacity < v.shape[0] else v,
                              capacity)
                for k, v in self.columns.items()}
        return Table(cols, jnp.minimum(self.num_rows, capacity))

    def head_np(self, n: int = 10) -> Dict[str, np.ndarray]:
        k = int(self.num_rows)
        return {name: np.asarray(col[:min(n, k)])
                for name, col in self.columns.items()}

    def to_numpy(self) -> Dict[str, np.ndarray]:
        """Materialize valid rows on host (paper Fig 17 interop bridge)."""
        k = int(self.num_rows)
        return {name: np.asarray(col[:k]) for name, col in self.columns.items()}


def _pad_axis0(x: jnp.ndarray, capacity: int) -> jnp.ndarray:
    if x.shape[0] == capacity:
        return x
    pad = [(0, capacity - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad)


# ---------------------------------------------------------------------------
# distributed Table
# ---------------------------------------------------------------------------
#: Partitioning metadata (DESIGN.md §4/§9) — static pytree aux data, one of:
#:
#:   * ``(hash_keys, n_shards)`` — rows hash-co-located: the ordered key
#:     columns whose murmur hash assigned each row to its shard, and the
#:     shard count the hash was taken modulo;
#:   * ``("range", keys, ascending, n_shards)`` — rows globally ordered by
#:     ``keys`` with per-key ``ascending`` directions (NaN-last): shard
#:     ``s`` holds the ``s``-th contiguous run of the global sort, each
#:     shard is locally sorted, and rows with equal full keys never
#:     straddle a shard boundary (the sample-sort splitter rule);
#:   * ``None`` — layout unknown.
#:
#: The hash form stays a 2-tuple for backward compatibility; the range form
#: is distinguished by its leading ``"range"`` marker (tuple equality can
#: never confuse the two).  Use the helpers below instead of destructuring.
Partitioning = Optional[tuple]

RANGE_MARKER = "range"


def range_partitioning(keys: Sequence[str], ascending: Sequence[bool],
                       n_shards: int) -> tuple:
    """Ordered-layout metadata produced by orderby / range repartition."""
    return (RANGE_MARKER, tuple(keys), tuple(bool(a) for a in ascending),
            int(n_shards))


def partitioning_kind(part: Partitioning) -> Optional[str]:
    """``"hash"`` / ``"range"`` / ``None`` for a metadata tuple."""
    if part is None:
        return None
    return RANGE_MARKER if part[0] == RANGE_MARKER else "hash"


def partitioning_keys(part: Partitioning) -> Tuple[str, ...]:
    """The ordered key columns the layout evidence depends on (() if None)."""
    if part is None:
        return ()
    return part[1] if part[0] == RANGE_MARKER else part[0]


def partitioning_ascending(part: Partitioning) -> Tuple[bool, ...]:
    """Per-key sort directions of a range layout (() for hash/None)."""
    if part is None or part[0] != RANGE_MARKER:
        return ()
    return part[2]


@jax.tree_util.register_pytree_node_class
class DistTable:
    """Row-partitioned table: ``n_shards`` blocks of ``capacity`` rows each.

    ``columns[k]`` has global shape ``(n_shards * capacity, ...)`` and is
    sharded over the context's data axis; ``counts`` has shape
    ``(n_shards,)`` giving each shard's valid-row count.  Inside a
    ``shard_map`` region each shard sees a local ``(capacity, ...)`` block —
    i.e. a plain :class:`Table`.

    ``partitioning`` records how rows were assigned to shards (DESIGN.md §4):
    ``(hash_keys, n_shards)`` after a hash exchange on ``hash_keys``, else
    ``None``.  It is static pytree aux data (part of the trace signature,
    not a traced value), so operators can skip a shuffle at Python level
    when equal keys are already co-located.  Constructors that cannot prove
    a layout (``from_local``, concatenation) leave it ``None``.
    """

    def __init__(self, columns: Columns, counts: jnp.ndarray,
                 partitioning: Partitioning = None):
        self.columns = dict(columns)
        self.counts = jnp.asarray(counts, jnp.int32)
        self.partitioning = partitioning

    # -- pytree ------------------------------------------------------------
    def tree_flatten(self):
        names = tuple(sorted(self.columns))
        children = tuple(self.columns[k] for k in names) + (self.counts,)
        return children, (names, self.partitioning)

    @classmethod
    def tree_unflatten(cls, aux, children):
        names, partitioning = aux
        obj = object.__new__(cls)
        obj.columns = dict(zip(names, children[:-1]))
        obj.counts = children[-1]
        obj.partitioning = partitioning
        return obj

    # -- properties ----------------------------------------------------------
    @property
    def n_shards(self) -> int:
        return self.counts.shape[0]

    @property
    def capacity(self) -> int:
        return next(iter(self.columns.values())).shape[0] // self.n_shards

    @property
    def column_names(self) -> Tuple[str, ...]:
        return tuple(sorted(self.columns))

    def num_rows(self) -> jnp.ndarray:
        return jnp.sum(self.counts)

    # -- construction ----------------------------------------------------------
    @classmethod
    def from_local(cls, table: Table, ctx: HPTMTContext,
                   capacity: Optional[int] = None) -> "DistTable":
        """Block-partition a local table's valid rows across shards.

        On a mesh the blocks are cut on the host and each goes straight
        to its own device (:meth:`placed`), so no device ever holds the
        whole table."""
        p = ctx.n_shards
        n = int(table.num_rows)
        per = (n + p - 1) // p  # rows per shard (last may be short)
        cap = capacity or -(-table.capacity // p)
        # row r goes to shard r // per at slot r % per
        idx = np.arange(p * cap, dtype=np.int64)
        shard, slot = idx // cap, idx % cap
        src = shard * per + slot
        valid = (slot < per) & (src < n)
        src = np.where(valid, src, 0)
        cols = {}
        for k, v in table.columns.items():
            h = np.asarray(v)[src]
            h[~valid] = 0
            cols[k] = h
        counts = np.clip(n - np.arange(p) * per, 0, per)
        counts = np.minimum(counts, cap).astype(np.int32)
        return cls.placed(cols, counts, ctx)

    @classmethod
    def from_shard_tables(cls, tables: Sequence[Table], ctx: HPTMTContext,
                          partitioning: Partitioning = None) -> "DistTable":
        """Assemble per-shard local tables into a DistTable.

        The inverse of :meth:`shard_table`: ``tables[i]`` becomes shard
        ``i``'s block (padded to the common capacity).  Used by the storage
        scan to place on-disk shard files back onto their shards —
        ``partitioning`` is attached verbatim, so callers assert the layout
        evidence truthfully (DESIGN.md §4/§5).  Columns may be host
        (numpy) arrays; the blocks are padded and joined on the host
        (:meth:`shard_blocks`) and each is placed on its own device
        (:meth:`placed`).
        """
        cols, counts = cls.shard_blocks(tables, ctx)
        return cls.placed(cols, counts, ctx, partitioning)

    @staticmethod
    def shard_blocks(tables: Sequence[Table], ctx: HPTMTContext
                     ) -> Tuple[Dict[str, np.ndarray], np.ndarray]:
        """The host half of :meth:`from_shard_tables`: every shard's
        columns padded to the common capacity and joined in shard order,
        and the shards' row counts."""
        if len(tables) != ctx.n_shards:
            raise ValueError(f"{len(tables)} shard tables for a "
                             f"{ctx.n_shards}-shard context")
        names = tables[0].column_names
        for i, t in enumerate(tables[1:], 1):
            if t.column_names != names:
                raise ValueError(f"shard {i} columns {t.column_names} != "
                                 f"shard 0 columns {names}")
        cap = max(t.capacity for t in tables)

        def pad(x):
            x = np.asarray(x)
            return np.pad(x, [(0, cap - x.shape[0])]
                          + [(0, 0)] * (x.ndim - 1))

        cols = {k: np.concatenate([pad(t.columns[k]) for t in tables])
                for k in names}
        counts = np.array([min(int(t.num_rows), cap) for t in tables],
                          np.int32)
        return cols, counts

    @classmethod
    def placed(cls, cols: Dict[str, np.ndarray], counts: np.ndarray,
               ctx: HPTMTContext, partitioning: Partitioning = None
               ) -> "DistTable":
        """Host columns → device arrays, each row block on its own device."""
        if ctx.mesh is None:
            return cls({k: jnp.asarray(v) for k, v in cols.items()},
                       jnp.asarray(counts), partitioning)
        return cls({k: jax.device_put(v, ctx.row_sharding(v.ndim))
                    for k, v in cols.items()},
                   jax.device_put(counts, ctx.row_sharding(1)), partitioning)

    def with_sharding(self, ctx: HPTMTContext) -> "DistTable":
        if ctx.mesh is None:
            return self
        cols = {k: jax.device_put(v, ctx.row_sharding(v.ndim))
                for k, v in self.columns.items()}
        counts = jax.device_put(self.counts, ctx.row_sharding(1))
        return DistTable(cols, counts, self.partitioning)

    # -- conversion ----------------------------------------------------------
    def shard_table(self, i: int) -> Table:
        c = self.capacity
        cols = {k: v[i * c:(i + 1) * c] for k, v in self.columns.items()}
        return Table(cols, self.counts[i])

    def to_local(self) -> Table:
        """Gather all shards into one compacted local table."""
        cols = self.to_numpy()
        n = int(np.asarray(self.counts).sum())
        return Table.from_arrays(
            {k: jnp.asarray(v) for k, v in cols.items()},
            num_rows=n, capacity=self.capacity * self.n_shards)

    def to_numpy(self) -> Dict[str, np.ndarray]:
        """Valid rows of every shard, in shard order, on the host.  Each
        device's block is copied straight to host memory: no device
        gathers the table on the way."""
        c = self.capacity
        counts = np.asarray(self.counts)
        out = {}
        for name in self.column_names:
            host = np.asarray(self.columns[name])
            out[name] = np.concatenate(
                [host[i * c:i * c + int(k)] for i, k in enumerate(counts)])
        return out
