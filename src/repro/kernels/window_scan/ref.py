"""Pure-jnp oracle for the segmented windowed scan (DESIGN.md §9).

The windowed-aggregation hot loop: for every row ``i`` of a table sorted by
``(partition, order)`` keys, reduce the rows of the same partition inside a
trailing row-count window,

    out[i] = op( values[a .. i] ),   a = max(i - window + 1, seg_start[i]),

for ``op`` in sum/min/max — all sum-combining lanes ride ONE call with the
values stacked as ``(n, L)`` lanes, exactly like ``segment_reduce_fused``.
``seg_start[i]`` is the row index where ``i``'s segment (partition) begins;
segments are contiguous because the table is sorted, so no per-row hash or
grouping structure is needed.

The algorithm is segment-clipped doubling (:func:`_rolling`):

  1. ``P_0 = values``; ``P_{k+1}[j] = P_k[j - 2^k] ⊕ P_k[j]`` when row
     ``j - 2^k`` is still in ``j``'s segment, else ``P_k[j]`` — so
     ``P_k[j]`` reduces the last ``2^k`` rows up to ``j``, clipped at the
     segment start;
  2. the window is the binary decomposition of ``window``: walking the set
     bits from low to high, the piece of size ``2^k`` ending ``off`` rows
     back is ``P_k[i - off]``, kept while ``i - off`` is still in the
     segment.

Total work is O(n log window) shift-and-select steps, zero sorts, zero
scatters, zero gathers.  The Pallas kernel (``kernel.py``) runs the SAME
:func:`_rolling` helper on its VMEM blocks with a lane rotate for the
shift, so interpret-mode kernel output is bit-identical to this reference —
float summation order and all (tested in ``tests/test_window.py``).

:func:`segmented_cumulative` runs a Hillis–Steele ladder at chunk size = n
for expanding (cumulative) aggregates; lag/lead/row_number/rank need no
kernel at all (they are gathers off the same segment machinery) and live
in ``repro.window``.
"""
from __future__ import annotations

import jax.numpy as jnp

_IDENTITY = {"sum": 0.0, "min": float("inf"), "max": float("-inf")}


def _combine(op: str, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    if op == "sum":
        return a + b
    if op == "min":
        return jnp.minimum(a, b)
    return jnp.maximum(a, b)


def _chunk_scan(v: jnp.ndarray, f: jnp.ndarray, op: str) -> jnp.ndarray:
    """Segmented inclusive scan along axis 1 of ``v (m, c, L)``.

    ``f (m, c)`` flags rows that START a segment; the scan value at a row
    covers back to the nearest flagged row (or the chunk start).  A
    Hillis–Steele ladder: at offset ``d`` a row whose accumulated span is
    still open combines with the row ``d`` to its left and inherits its
    completion flag.  The combine ORDER is fixed (left operand is always
    the earlier span), so float results are deterministic.
    """
    c = v.shape[1]
    ident = jnp.asarray(_IDENTITY[op], v.dtype)
    d = 1
    while d < c:
        sv = jnp.concatenate(
            [jnp.full_like(v[:, :d], ident), v[:, :-d]], axis=1)
        sf = jnp.concatenate(
            [jnp.ones_like(f[:, :d]), f[:, :-d]], axis=1)
        v = jnp.where(f[..., None], v, _combine(op, sv, v))
        f = f | sf
        d *= 2
    return v


def _rolling(v, start, idx, window: int, op: str, shift):
    """Segment-clipped rolling reduction over the row axis of ``v``.

    ``start``/``idx`` (broadcastable to ``v``) hold each row's segment
    start and its own index; ``shift(x, d)`` moves ``x`` ``d`` rows toward
    higher indices (what fills the first ``d`` rows is never selected:
    there ``idx - d < start``).  Shared by the reference and the Pallas
    kernel — the same elementwise steps in the same order.
    """
    p, acc, off, k = v, None, 0, 0
    while (1 << k) <= window:
        size = 1 << k
        if window & size:
            if acc is None:
                acc = p
            else:
                keep = start <= idx - off
                acc = jnp.where(keep, _combine(op, shift(p, off), acc), acc)
            off += size
        if (2 << k) <= window:
            keep = start <= idx - size
            p = jnp.where(keep, _combine(op, shift(p, size), p), p)
        k += 1
    return acc


def windowed_scan(values: jnp.ndarray, seg_start: jnp.ndarray, window: int,
                  op: str = "sum") -> jnp.ndarray:
    """values (n, L) f32, seg_start (n,) i32 → (n, L) rolling reductions.

    ``out[i] = op(values[max(i - window + 1, seg_start[i]) .. i])`` — the
    trailing row-count window clipped at the segment start (so a window
    larger than its partition degrades to an expanding aggregate over the
    partition, the SQL ROWS BETWEEN semantics).  ``seg_start[i]`` must
    satisfy ``seg_start[i] <= i`` and be constant within each segment.
    """
    n = values.shape[0]
    ident = jnp.asarray(_IDENTITY[op], values.dtype)

    def shift(x, d):
        if d >= n:
            return jnp.full_like(x, ident)
        return jnp.concatenate([jnp.full_like(x[:d], ident), x[:n - d]])

    idx = jnp.arange(n, dtype=jnp.int32)[:, None]
    return _rolling(values, seg_start.astype(jnp.int32)[:, None], idx,
                    int(window), op, shift)


def segmented_cumulative(values: jnp.ndarray, seg_start: jnp.ndarray,
                         op: str = "sum") -> jnp.ndarray:
    """values (n, L), seg_start (n,) → expanding (cumulative) reductions.

    ``out[i] = op(values[seg_start[i] .. i])`` — the unbounded-window
    special case, computed as one segmented Hillis–Steele scan over all
    ``n`` rows.  No Pallas variant: the ladder is plain shift-combine XLA
    code with nothing for a kernel to fuse beyond what the compiler
    already does.
    """
    n = values.shape[0]
    f = (seg_start.astype(jnp.int32) == jnp.arange(n, dtype=jnp.int32))
    return _chunk_scan(values[None], f[None], op)[0]
