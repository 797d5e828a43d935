"""Pallas TPU kernel for the segmented windowed scan (DESIGN.md §9).

Layout: rows lie along the 128-wide lane axis — the values enter as an
``(L, n)`` array and segment starts as a ``(1, n)`` row — so every block
is a 2-D tile aligned to the (8, 128) tiling.  The grid walks blocks of
``block_n`` rows.  Each grid step loads its own block plus the PREVIOUS
block (two BlockSpecs over the same operand, the second with a clamped
``i-1`` index map): a window reaches at most ``window - 1`` rows back, and
``block_n >= window - 1``, so the previous block is the whole halo.

The step runs the reference's :func:`~.ref._rolling` helper over the
``(L, 2 * block_n)`` halo+block concatenation, with a lane rotate
(``pltpu.roll``) as the row shift, and writes the block's half.  A row's
result only reads rows back to ``i - window + 1``, which lie inside the
concatenation, so the wrapped-around lanes of the rotate are never
selected; the result is bit-identical to ``ref.windowed_scan`` (the oracle
IS the semantics, as with every kernel in this tree).  No gather, no
reversal, no scratch, no revisiting of output blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import _IDENTITY, _rolling


def _kernel(vals_ref, pvals_ref, seg_ref, pseg_ref, out_ref, *, w: int,
            op: str, block_n: int):
    base = pl.program_id(0) * block_n
    v = jnp.concatenate([pvals_ref[...], vals_ref[...]], axis=1)
    start = jnp.concatenate([pseg_ref[...], seg_ref[...]], axis=1)
    # absolute row index of every lane; the halo of block 0 gets negative
    # indices, below every segment start, so it is never selected
    idx = base - block_n + jax.lax.broadcasted_iota(
        jnp.int32, start.shape, 1)

    def shift(x, d):
        return pltpu.roll(x, d, 1)

    out = _rolling(v, start, idx, w, op, shift)
    out_ref[...] = out[:, block_n:]


def block_rows(window: int, target_block: int = 512) -> int:
    """Rows per block: lane-aligned and at least the halo ``window - 1``."""
    return max(target_block, -(-(window - 1) // 128) * 128)


def vmem_bytes(lanes: int, window: int) -> int:
    """VMEM the kernel holds: double-buffered value/start/output blocks
    plus about eight ``(lanes, 2 * block_n)`` temporaries."""
    bn = block_rows(window)
    sub = -(-lanes // 8) * 8
    io = 2 * 4 * bn * (2 * sub + 2 * 8 + sub)
    return io + 8 * 4 * sub * 2 * bn


def windowed_scan_pallas(values: jnp.ndarray, seg_start: jnp.ndarray,
                         window: int, op: str = "sum", *,
                         target_block: int = 512,
                         interpret: bool = False) -> jnp.ndarray:
    """values (n, L) f32, seg_start (n,) i32 → (n, L); see ref.windowed_scan."""
    n, lanes = values.shape
    w = int(window)
    block_n = block_rows(w, target_block)
    n_pad = -(-n // block_n) * block_n
    vals = jnp.pad(values.astype(jnp.float32), ((0, n_pad - n), (0, 0)),
                   constant_values=_IDENTITY[op]).T
    # padding rows are their own segments: they never contaminate a window
    segs = jnp.concatenate([seg_start.astype(jnp.int32),
                            jnp.arange(n, n_pad, dtype=jnp.int32)])[None, :]

    def cur(i):
        return (0, i)

    def prev(i):
        return (0, jnp.maximum(i - 1, 0))

    out = pl.pallas_call(
        functools.partial(_kernel, w=w, op=op, block_n=block_n),
        grid=(n_pad // block_n,),
        in_specs=[pl.BlockSpec((lanes, block_n), cur),
                  pl.BlockSpec((lanes, block_n), prev),
                  pl.BlockSpec((1, block_n), cur),
                  pl.BlockSpec((1, block_n), prev)],
        out_specs=pl.BlockSpec((lanes, block_n), cur),
        out_shape=jax.ShapeDtypeStruct((lanes, n_pad), jnp.float32),
        interpret=interpret,
    )(vals, vals, segs, segs)
    return out[:, :n].T
