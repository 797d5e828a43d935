"""Pallas TPU segment-reduce kernel.

TPU adaptation of the GroupBy-aggregate hot loop (paper Table III): rather
than scatter-adds (slow on TPU — no efficient random-access writes), each
(segment-block × value-block) grid cell builds a one-hot matrix
``onehot[s, n] = (segment_ids[n] == s)`` and reduces it against the value
block.  For ``sum`` this is a matmul that runs on the **MXU**; min/max use
masked VPU reductions.  Output blocks are revisited across the value-block
grid dimension (accumulation), so the value dimension must be the innermost
(fastest-varying) grid axis.

Layout: rows lie along the 128-wide lane axis.  Segment ids are a
``(1, N)`` row and the value lanes a ``(L, N)`` block, so every block is a
2-D tile aligned to the (8, 128) tiling; the kernel writes ``(S, L)``,
returned as ``(L, S)``.

The work is rows × padded segments, so ``ops.py`` sends only groupbys
with few segments here (DESIGN.md §7).  Block sizes default to 512×512:
one one-hot tile is 512*512*4B = 1 MiB of VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_INITS = {"sum": 0.0, "min": float("inf"), "max": float("-inf")}


def _kernel(seg_ref, val_ref, out_ref, *, op: str, block_s: int):
    s = pl.program_id(0)
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, _INITS[op])

    seg = seg_ref[...]            # (1, block_n) int32
    val = val_ref[...]            # (lanes, block_n) float32
    rows = s * block_s + jax.lax.broadcasted_iota(
        jnp.int32, (block_s, seg.shape[1]), 0)
    onehot = rows == seg          # (block_s, block_n)

    if op == "sum":
        # MXU path: one-hot matmul contracting the row axis of both
        out_ref[...] += jax.lax.dot_general(
            onehot.astype(jnp.float32), val, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    elif op == "min":
        cur = jnp.min(jnp.where(onehot, val, jnp.inf), axis=1, keepdims=True)
        out_ref[...] = jnp.minimum(out_ref[...], cur)
    else:  # max
        cur = jnp.max(jnp.where(onehot, val, -jnp.inf), axis=1,
                      keepdims=True)
        out_ref[...] = jnp.maximum(out_ref[...], cur)


def vmem_bytes(lanes: int, block_n: int = 512, block_s: int = 512) -> int:
    """VMEM the kernel holds: double-buffered id/value/output blocks plus
    the one-hot tile and its float copy."""
    sub = -(-lanes // 8) * 8
    io = 2 * 4 * (8 * block_n + sub * block_n + block_s * 128)
    return io + 2 * 4 * block_s * block_n


def segment_reduce_pallas(values: jnp.ndarray, segment_ids: jnp.ndarray,
                          num_segments: int, op: str = "sum", *,
                          block_n: int = 512, block_s: int = 512,
                          interpret: bool = False) -> jnp.ndarray:
    """values (N,) or lanes-major (L, N) f32, segment_ids (N,) i32 →
    (num_segments,) or (L, num_segments) f32.  min/max take one lane.

    N and num_segments are padded to block multiples internally; ids outside
    ``[0, num_segments)`` are dropped (they never match a one-hot row).
    """
    squeeze = values.ndim == 1
    vals = values.reshape(-1, values.shape[-1]).astype(jnp.float32)
    lanes, n = vals.shape
    if op != "sum" and lanes != 1:
        raise ValueError(f"segment {op} reduces one lane, got {lanes}")
    n_pad = -(-n // block_n) * block_n
    s_pad = -(-num_segments // block_s) * block_s
    vals = jnp.pad(vals, ((0, 0), (0, n_pad - n)))
    segs = segment_ids.astype(jnp.int32)
    segs = jnp.where((segs < 0) | (segs >= num_segments), s_pad, segs)
    segs = jnp.pad(segs, (0, n_pad - n), constant_values=s_pad)[None, :]

    out = pl.pallas_call(
        functools.partial(_kernel, op=op, block_s=block_s),
        grid=(s_pad // block_s, n_pad // block_n),
        in_specs=[
            pl.BlockSpec((1, block_n), lambda s, i: (0, i)),
            pl.BlockSpec((lanes, block_n), lambda s, i: (0, i)),
        ],
        out_specs=pl.BlockSpec((block_s, lanes), lambda s, i: (s, 0)),
        out_shape=jax.ShapeDtypeStruct((s_pad, lanes), jnp.float32),
        interpret=interpret,
    )(segs, vals)
    out = out[:num_segments].T                  # (L, S): S ≤ a few blocks
    return out[0] if squeeze else out
