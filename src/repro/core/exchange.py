"""Fused single-collective row-exchange engine (shuffle hot path, Fig 2).

Every distributed table operator (join, groupby, set ops, orderby) reduces
to the shuffle primitive — re-distributing rows so related keys land on the
same shard (paper §IV-B-1).  This module is the one implementation of that
primitive, replacing the seed's per-column exchange with three optimisations
(DESIGN.md §3):

  1. **Packed exchange** — every column is bit-cast to ``uint32`` lanes and
     packed into a single ``(n_shards * bucket, row_width)`` buffer, so each
     shuffle issues exactly **one** AllToAll regardless of column count.  The
     per-destination send counts travel in a metadata row fused into the same
     buffer — a shuffle is ONE collective, not ``n_cols + 1``.
  2. **Sort-free bucketing** — destination slots come from a counting-sort
     scatter (per-destination prefix ranks + the histogram that the Pallas
     ``hash_partition`` kernel already produces), not from ``argsort``.
     Compaction (``compact_rows``) is likewise a cumsum scatter.  The shuffle
     path is O(n) and contains zero ``sort`` primitives.
  3. **Hash carrying** — the row hashes ``(h1, h2)`` computed for destination
     assignment are threaded through the exchange as hidden columns
     (:data:`H1_NAME` / :data:`H2_NAME`), so join / set-op kernels never
     rehash rows after a shuffle — the carried pair directly seeds the
     hash-join / set-op slot tables (``h1`` = probe start, ``h2|1`` =
     stride; DESIGN.md §3.3/§8), with :func:`key_compare_u32` providing
     the matching bitwise verification lanes.

The static-shape overflow contract is unchanged from the seed: rows beyond a
destination bucket (send side) or beyond ``out_capacity`` (receive side) are
*counted and dropped*, never silently corrupted; callers surface the count so
the workflow layer can retry with larger capacities (paper §VII-F).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .array_ops import spmd_allgather, spmd_alltoall

Cols = Dict[str, jnp.ndarray]

#: Reserved hidden-column names for carried row hashes.  Operator impls pop
#: these after a shuffle instead of recomputing ``hash_columns``.
H1_NAME = "_h1"
H2_NAME = "_h2"
#: Reserved hidden-column name for carried order lanes: the spill engine
#: (``repro.spill``) persists :func:`order_lanes` in its on-disk runs so
#: re-ingested partitions re-sort on the host without recomputing the
#: directional transform (DESIGN.md §10).
LANES_NAME = "_lanes"


# ===========================================================================
# bit-exact uint32 packing
# ===========================================================================
@dataclasses.dataclass(frozen=True)
class ColSpec:
    """Static layout of one column inside the packed row (DESIGN.md §3.1)."""
    name: str
    dtype: np.dtype
    trailing: Tuple[int, ...]
    start: int
    lanes: int


def _col_to_u32(col: jnp.ndarray) -> jnp.ndarray:
    """Bit-exact reversible view of a column as ``(cap, lanes)`` uint32."""
    cap = col.shape[0]
    x = col.reshape(cap, -1) if col.ndim > 1 else col.reshape(cap, 1)
    size = jnp.dtype(x.dtype).itemsize
    if x.dtype == jnp.bool_:
        u = x.astype(jnp.uint32)
    elif size == 4:
        u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    elif size == 8:
        u = jax.lax.bitcast_convert_type(x, jnp.uint32)  # (cap, L, 2)
    elif size == 2:
        u = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
    elif size == 1:
        u = jax.lax.bitcast_convert_type(x, jnp.uint8).astype(jnp.uint32)
    else:
        raise TypeError(f"unsupported column dtype {col.dtype}")
    return u.reshape(cap, -1)


def _u32_to_col(u: jnp.ndarray, dtype, trailing: Tuple[int, ...]) -> jnp.ndarray:
    """Inverse of :func:`_col_to_u32`."""
    cap = u.shape[0]
    dt = jnp.dtype(dtype)
    if dt == jnp.bool_:
        x = u.astype(jnp.bool_)
    elif dt.itemsize == 4:
        x = jax.lax.bitcast_convert_type(u, dtype)
    elif dt.itemsize == 8:
        x = jax.lax.bitcast_convert_type(u.reshape(cap, -1, 2), dtype)
    elif dt.itemsize == 2:
        x = jax.lax.bitcast_convert_type(u.astype(jnp.uint16), dtype)
    else:
        x = jax.lax.bitcast_convert_type(u.astype(jnp.uint8), dtype)
    return x.reshape((cap,) + tuple(trailing))


def pack_columns(cols: Cols) -> Tuple[jnp.ndarray, Tuple[ColSpec, ...]]:
    """Pack all columns into one ``(row_width, cap)`` uint32 buffer.

    Lanes-major: one buffer row per lane, table rows along the last axis.
    A TPU tiles the last axis by 128, so a ``(cap, row_width)`` buffer
    with a row width of ~10 would occupy ~12x its bytes in HBM."""
    parts, specs, start = [], [], 0
    for name in sorted(cols):
        u = _col_to_u32(cols[name])
        specs.append(ColSpec(name, cols[name].dtype,
                             tuple(cols[name].shape[1:]), start, u.shape[1]))
        start += u.shape[1]
        parts.append(u.T)
    return jnp.concatenate(parts, axis=0), tuple(specs)


def unpack_columns(buf: jnp.ndarray, specs: Sequence[ColSpec]) -> Cols:
    """Recover original dtypes/shapes from a packed uint32 buffer."""
    return {s.name: _u32_to_col(buf[s.start:s.start + s.lanes].T,
                                s.dtype, s.trailing) for s in specs}


# ===========================================================================
# sort-free primitives
# ===========================================================================
def dest_ranks(dest: jnp.ndarray, n_parts: int,
               chunk: int = 16) -> jnp.ndarray:
    """Stable within-destination rank of each row (counting sort, no argsort).

    ``rank[i]`` = number of earlier rows with the same destination.  Rows with
    ``dest >= n_parts`` (invalid) get an arbitrary rank — callers mask them.

    Destinations are processed in chunks of ``chunk`` so the one-hot prefix
    buffer stays O(n * chunk) regardless of shard count (a full
    ``(n, n_parts)`` cumsum would be a memory blowup at pod-scale meshes).
    """
    n = dest.shape[0]
    rank = jnp.zeros((n,), jnp.int32)
    for c0 in range(0, n_parts, chunk):
        parts = jnp.arange(c0, min(c0 + chunk, n_parts), dtype=dest.dtype)
        # (chunk, n): rows along the last axis, the TPU's 128-wide one
        onehot = parts[:, None] == dest[None, :]
        prefix = jnp.cumsum(onehot.astype(jnp.int32), axis=1) - 1
        picked = jnp.sum(jnp.where(onehot, prefix, 0), axis=0)
        in_chunk = (dest >= c0) & (dest < c0 + parts.shape[0])
        rank = jnp.where(in_chunk, picked, rank)
    return rank


def compact_rows(cols: Cols, keep: jnp.ndarray,
                 out_capacity: int) -> Tuple[Cols, jnp.ndarray, jnp.ndarray]:
    """Move kept rows to the front (stable) via cumsum scatter; no sort.

    Returns ``(columns, new_count, n_truncated)`` — rows past ``out_capacity``
    are dropped and counted, matching the seed overflow contract.  Padding
    rows are zero-filled (operators never read them).
    """
    total = jnp.sum(keep, dtype=jnp.int32)
    pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
    slot = jnp.where(keep, pos, out_capacity)  # out-of-bounds ⇒ dropped
    out = {}
    for k, v in cols.items():
        buf = jnp.zeros((out_capacity,) + v.shape[1:], v.dtype)
        out[k] = buf.at[slot].set(v, mode="drop")
    new_count = jnp.minimum(total, out_capacity).astype(jnp.int32)
    return out, new_count, total - new_count


# ===========================================================================
# the packed single-collective exchange
# ===========================================================================
def exchange_rows(cols: Cols, dest: jnp.ndarray, n_shards: int, bucket: int,
                  axis: Optional[str], hist: Optional[jnp.ndarray] = None):
    """Bucket rows by destination shard and exchange them in ONE AllToAll.

    ``dest`` must be ``>= n_shards`` for invalid rows; ``hist`` is the
    per-destination valid-row histogram (recomputed by scatter-add when not
    supplied, e.g. by the fused ``hash_partition`` kernel).

    Frame layout (DESIGN.md §3.2): per destination, ``bucket`` packed data
    rows followed by one metadata row whose lane 0 holds the send count —
    so counts ride the same collective as the data.  The buffer is
    lanes-major (``pack_columns``), so the AllToAll splits its last axis.

    Returns ``(received_cols, received_valid_mask, n_overflowed_send)``;
    on a mesh the received columns include the metadata rows, which the
    mask never marks valid.
    """
    if hist is None:
        hist = jnp.zeros(n_shards + 1, jnp.int32).at[
            jnp.clip(dest, 0, n_shards)].add(1)[:n_shards]
    packed, specs = pack_columns(cols)
    width = packed.shape[0]
    sent = jnp.minimum(hist, bucket)
    overflow = jnp.sum(hist - sent)

    # frame per destination: ``bucket`` data rows (+ the metadata row when
    # the frame travels), all written by one scatter into one buffer
    frame = bucket + (axis is not None)
    rank = dest_ranks(dest, n_shards)
    ok = (dest < n_shards) & (rank < bucket)
    slot = jnp.where(ok, dest * frame + rank, n_shards * frame)
    buf = jnp.zeros((width, n_shards * frame), jnp.uint32
                    ).at[:, slot].set(packed, mode="drop")

    if axis is not None:
        meta = jnp.arange(n_shards, dtype=jnp.int32) * frame + bucket
        buf = buf.at[0, meta].set(sent.astype(jnp.uint32))
        buf = spmd_alltoall(buf, axis, split_axis=1, concat_axis=1)
        recv_cnt = buf[0, meta].astype(jnp.int32)
    else:
        recv_cnt = sent

    # metadata rows sit at offset ``bucket`` of their frame: never valid
    pos = jnp.arange(n_shards * frame, dtype=jnp.int32)
    valid = (pos % frame) < recv_cnt[pos // frame]
    return unpack_columns(buf, specs), valid, overflow


def hash_shuffle(cols: Cols, count: jnp.ndarray, key_names: Sequence[str],
                 n_shards: int, bucket: int, out_capacity: int,
                 axis: Optional[str], *, carry_hashes: bool = False):
    """Hash-partition + packed exchange + compaction in one call.

    Destination assignment and the send histogram come from the fused
    ``hash_partition`` dispatcher (Pallas on TPU, jnp elsewhere).  With
    ``carry_hashes`` the row hashes travel as hidden :data:`H1_NAME` /
    :data:`H2_NAME` columns so downstream kernels skip rehashing; pop them
    with :func:`take_hashes`.

    A completed call establishes the ``(key_names, n_shards)`` hash layout
    that operators record as ``DistTable.partitioning`` — the evidence the
    shuffle-elision machinery trusts (DESIGN.md §4).  Any exchange on other
    keys or a different shard count invalidates it.

    Returns ``(columns, new_count, overflow)``.
    """
    from repro.kernels.hash_partition import ops as hpops  # lazy: no cycle

    capacity = next(iter(cols.values())).shape[0]
    mask = jnp.arange(capacity, dtype=jnp.int32) < count
    key_cols = [cols[k] for k in key_names]
    if carry_hashes:
        check_no_reserved(cols)
        dest, hist, h1, h2 = hpops.hash_partition(
            key_cols, n_shards, mask, return_hashes=True)
        cols = dict(cols)
        cols[H1_NAME], cols[H2_NAME] = h1, h2
    else:
        dest, hist = hpops.hash_partition(key_cols, n_shards, mask)
    bufs, valid, ov_send = exchange_rows(cols, dest, n_shards, bucket, axis,
                                         hist=hist)
    out, new_count, ov_recv = compact_rows(bufs, valid, out_capacity)
    return out, new_count, ov_send + ov_recv


# ===========================================================================
# sample-sort range partitioning (DESIGN.md §9)
# ===========================================================================
def sort_key_lanes(col: jnp.ndarray, ascending: bool = True) -> jnp.ndarray:
    """Monotone ``(n, lanes)`` uint32 view of a key column for ordering.

    Unsigned lexicographic comparison of the lanes reproduces the column's
    value order exactly — the ordered twin of the §3.1 bit-packing:

      * floats narrow to f32 and map through the standard total-order
        transform (sign bit set for non-negatives, full complement for
        negatives), so ``-inf < -0.0 < +0.0 < +inf``;
      * signed integers flip their sign bit; unsigned/bool widen as-is;
      * ``ascending=False`` complements the lane, reversing the order.

    **NaN-last contract:** every NaN bit pattern is forced to the maximum
    lane value AFTER the direction flip, so NaNs form one deterministic
    block at the END of the order in BOTH directions.  (The old negation
    trick — ``sort by -x`` — flipped NaNs to the front under descending
    because complementing a NaN's transform does not commute with the
    override; this function is the fix, property-tested both ways.)

    64-bit key dtypes are rejected: with jax x64 disabled they cannot
    round-trip anyway — narrow the column first (``io.schema`` rules).
    """
    if jnp.dtype(col.dtype).itemsize == 8:
        raise TypeError(
            f"orderby/range-partition key dtype {col.dtype} is 64-bit; "
            f"narrow the column to a 32-bit type first")
    if col.ndim > 1:
        raise TypeError("orderby/range-partition keys must be 1-D columns")
    if jnp.issubdtype(col.dtype, jnp.floating):
        f = col.astype(jnp.float32)
        b = jax.lax.bitcast_convert_type(f, jnp.uint32)
        m = jnp.where(b >> 31 != 0, ~b, b | jnp.uint32(0x80000000))
        nan = jnp.isnan(f)
    elif col.dtype == jnp.bool_:
        m = col.astype(jnp.uint32)
        nan = None
    elif jnp.issubdtype(col.dtype, jnp.unsignedinteger):
        m = col.astype(jnp.uint32)
        nan = None
    else:  # signed integers
        m = jax.lax.bitcast_convert_type(
            col.astype(jnp.int32), jnp.uint32) ^ jnp.uint32(0x80000000)
        nan = None
    if not ascending:
        m = ~m
    if nan is not None:
        m = jnp.where(nan, jnp.uint32(0xFFFFFFFF), m)
    return m[:, None]


def order_lanes(cols: Cols, key_names: Sequence[str],
                ascending: Sequence[bool]) -> jnp.ndarray:
    """Concatenated directional lanes for multi-key ordering.

    Row ``i`` sorts before row ``j`` iff ``lanes[i]`` is lexicographically
    below ``lanes[j]`` (unsigned, lane 0 most significant) — so one uint32
    matrix carries the whole multi-key, per-key-direction, NaN-last order.
    """
    return jnp.concatenate(
        [sort_key_lanes(cols[k], asc)
         for k, asc in zip(key_names, ascending)], axis=1)


def lex_order(lanes: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Stable sort permutation for directional lanes; invalid rows last."""
    keys = tuple(lanes[:, lane] for lane in range(lanes.shape[1] - 1, -1, -1))
    return jnp.lexsort(keys + (~mask,))


def _lex_leq(splitters: jnp.ndarray, lanes: jnp.ndarray) -> jnp.ndarray:
    """``(S, n)`` bool: splitter ``s`` <= row lexicographically."""
    L = lanes.shape[1]
    res = jnp.ones((splitters.shape[0], lanes.shape[0]), bool)
    for lane in range(L - 1, -1, -1):
        sp = splitters[:, lane][:, None]
        rw = lanes[:, lane][None, :]
        res = (sp < rw) | ((sp == rw) & res)
    return res


def range_splitters(lanes: jnp.ndarray, mask: jnp.ndarray, n_shards: int,
                    n_samples: int, axis: Optional[str]) -> jnp.ndarray:
    """Per-shard regular sampling + AllGather → ``n_shards - 1`` splitters.

    Each shard samples ``n_samples`` valid rows at a regular stride (an
    even-spaced picture of its local distribution), all shards pool the
    samples with one AllGather, sort them lexicographically, and read the
    splitters at even positions.  Skew bound: with ``s`` samples per shard
    a destination receives at most ``~(1 + p/s)`` times its fair share of
    DISTINCT key positions (standard sample-sort bound) — duplicates of
    one key all land on one shard by the side="right" rule below, so heavy
    duplicate keys concentrate instead of splitting (DESIGN.md §9).
    """
    count = jnp.sum(mask, dtype=jnp.int32)
    stride = jnp.maximum(count // n_samples, 1)
    sidx = jnp.minimum(jnp.arange(n_samples, dtype=jnp.int32) * stride,
                       jnp.maximum(count - 1, 0))
    sample = jnp.where((sidx < count)[:, None], lanes[sidx],
                       jnp.uint32(0xFFFFFFFF))
    if axis is not None:
        sample = spmd_allgather(sample, axis)
    order = lex_order(sample, jnp.ones((sample.shape[0],), bool))
    sample = sample[order]
    total = sample.shape[0]
    spos = (jnp.arange(1, n_shards, dtype=jnp.int32) * total) // n_shards
    return sample[spos]


def range_shuffle(cols: Cols, count: jnp.ndarray, key_names: Sequence[str],
                  ascending: Sequence[bool], n_shards: int, bucket: int,
                  out_capacity: int, axis: Optional[str], *,
                  n_samples: int = 64, sort_local: bool = True):
    """Sample-sort range partitioning + packed exchange (+ local sort).

    The ordered twin of :func:`hash_shuffle`: destinations come from a
    lexicographic ``searchsorted`` against sampled splitters instead of a
    hash, and the rows ride the SAME single packed AllToAll
    (:func:`exchange_rows`).  Destination rule is side="right" — a row goes
    to ``#{splitters <= row}`` — so rows with equal full keys always share
    a shard (range metadata's boundary guarantee).  With ``sort_local``
    the received rows are lexsorted, completing the sample sort: the
    result is globally ordered by ``(key_names, ascending)`` with NaNs
    last.  A completed call establishes the layout that operators record
    as ``("range", keys, ascending, n_shards)`` partitioning metadata
    (DESIGN.md §9).

    Returns ``(columns, new_count, overflow)``.
    """
    capacity = next(iter(cols.values())).shape[0]
    mask = jnp.arange(capacity, dtype=jnp.int32) < count
    lanes = order_lanes(cols, key_names, ascending)

    if n_shards > 1:
        splitters = range_splitters(lanes, mask, n_shards, n_samples, axis)
        dest = jnp.sum(_lex_leq(splitters, lanes), axis=0, dtype=jnp.int32)
        dest = jnp.where(mask, dest, n_shards)
        bufs, valid, ov_send = exchange_rows(cols, dest, n_shards, bucket,
                                             axis)
        out, new_count, ov_recv = compact_rows(bufs, valid, out_capacity)
        overflow = ov_send + ov_recv
    else:
        out, new_count, overflow = compact_rows(cols, mask, out_capacity)
    if sort_local:
        m = jnp.arange(out_capacity, dtype=jnp.int32) < new_count
        order = lex_order(order_lanes(out, key_names, ascending), m)
        out = {k: v[order] for k, v in out.items()}
    return out, new_count, overflow


def key_compare_u32(cols: Cols, key_names: Sequence[str]) -> jnp.ndarray:
    """Bitwise key-comparison lanes, consistent with the hash identity.

    Builds the ``(N, L)`` uint32 matrix the hash-join / set-op kernels
    verify candidates against: float keys narrow to float32 and compare by
    bit pattern — exactly the identity ``hash_columns`` uses, so NaN keys
    with equal bits are equal and ``-0.0 != +0.0`` (DESIGN.md §8) — while
    integer/bool keys compare by their packed two's-complement lanes
    (identical to value equality).  The lane packing reuses the §3.1
    exchange layout (:func:`_col_to_u32`), so 64-bit integer keys keep both
    halves.  Comparing rows ``i`` and ``j`` is then
    ``jnp.all(m[i] == m[j])`` — two uint32 lane compares per key column,
    never a trip through the original dtypes.
    """
    parts = []
    for name in key_names:
        col = cols[name]
        if jnp.issubdtype(col.dtype, jnp.floating):
            col = jax.lax.bitcast_convert_type(
                col.astype(jnp.float32), jnp.uint32)
        parts.append(_col_to_u32(col))
    return jnp.concatenate(parts, axis=1)


def check_no_reserved(names: Sequence[str]) -> None:
    """Reject user tables that use the reserved hidden-column names."""
    clash = {H1_NAME, H2_NAME, LANES_NAME} & set(names)
    if clash:
        raise ValueError(
            f"column names {sorted(clash)} are reserved for carried row "
            f"hashes / order lanes (core/exchange.py); rename the column(s)")


def take_hashes(cols: Cols, key_names: Sequence[str]
                ) -> Tuple[Cols, jnp.ndarray, jnp.ndarray]:
    """Pop carried ``(h1, h2)`` from a shuffled table, or compute them.

    After a :func:`hash_shuffle` with ``carry_hashes=True`` this is a free
    dictionary pop; on the unshuffled (single-shard) path it falls back to
    ``hash_columns`` — same values either way.
    """
    from .table import hash_columns  # lazy: table does not import exchange

    cols = dict(cols)
    if H1_NAME in cols:
        return cols, cols.pop(H1_NAME), cols.pop(H2_NAME)
    h1, h2 = hash_columns([cols[k] for k in key_names])
    return cols, h1, h2


def strip_hidden(cols: Cols) -> Cols:
    """Drop carried-hash columns before handing a table back to the user."""
    return {k: v for k, v in cols.items()
            if k not in (H1_NAME, H2_NAME, LANES_NAME)}


# ===========================================================================
# seed reference implementation (oracle for parity tests)
# ===========================================================================
def exchange_rows_reference(cols: Cols, dest: jnp.ndarray, n_shards: int,
                            bucket: int, axis: Optional[str]):
    """The seed per-column argsort exchange, kept verbatim as a test oracle.

    Issues one AllToAll per column plus a count side-channel; bucketing via
    stable ``argsort``.  Bit-for-bit equal *valid rows* to
    :func:`exchange_rows` (padding differs: the reference leaves residual row
    data in padding slots, the packed engine zero-fills).
    """
    capacity = dest.shape[0]
    order = jnp.argsort(dest, stable=True)
    sdest = dest[order]
    first = jnp.searchsorted(sdest, sdest, side="left")
    rank = jnp.arange(capacity, dtype=jnp.int32) - first.astype(jnp.int32)
    ok = (sdest < n_shards) & (rank < bucket)
    slot = jnp.where(ok, sdest * bucket + rank, n_shards * bucket)

    send_cnt = jnp.zeros(n_shards + 1, jnp.int32).at[
        jnp.clip(dest, 0, n_shards)].add(1)[:n_shards]
    sent = jnp.minimum(send_cnt, bucket)
    overflow = jnp.sum(send_cnt - sent)

    bufs: Cols = {}
    for name, col in cols.items():
        buf = jnp.zeros((n_shards * bucket,) + col.shape[1:], col.dtype)
        bufs[name] = buf.at[slot].set(col[order], mode="drop")

    if axis is not None:
        recv_cnt = spmd_alltoall(sent, axis)
        bufs = {k: spmd_alltoall(v, axis) for k, v in bufs.items()}
    else:
        recv_cnt = sent

    pos = jnp.arange(n_shards * bucket, dtype=jnp.int32)
    valid = (pos % bucket) < recv_cnt[pos // bucket]
    return bufs, valid, overflow
