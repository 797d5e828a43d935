"""Pallas TPU hash-partition kernel (shuffle hot loop, paper Fig 2).

Fuses, per row-block: (a) the multi-column murmur-style hash chain,
(b) destination-shard assignment ``h % P``, and (c) the per-destination
histogram — one HBM read of the key block instead of three.  The histogram
uses a one-hot VPU reduction with the histogram block revisited across the
row grid (accumulation), so the row dimension is the innermost grid axis.

With ``return_hashes`` the kernel also emits the full ``(h1, h2)`` row
hashes so the shuffle engine can carry them through the exchange
(DESIGN.md §3.3) — join and set-op kernels then never rehash post-shuffle.

The hash chain must match ``repro.core.table.hash_columns`` bit-for-bit —
the pure-jnp oracle in ``ref.py`` *is* that function.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

_H1_INIT = np.uint32(0x9E3779B9)
_H2_INIT = np.uint32(0x85EBCA6B)
_MUL1 = np.uint32(0xCC9E2D51)
_MUL2 = np.uint32(0x1B873593)
_K2_XOR = np.uint32(0xDEADBEEF)


def _mix(h, k, mul):
    k = k * mul
    k = (k << 15) | (k >> 17)
    h = h ^ k
    h = (h << 13) | (h >> 19)
    return h * np.uint32(5) + np.uint32(0xE6546B64)


def _kernel(keys_ref, valid_ref, *out_refs, n_parts: int, sentinel: int,
            n_cols: int, with_hashes: bool):
    if with_hashes:
        dest_ref, h1_ref, h2_ref, hist_ref = out_refs
    else:
        dest_ref, hist_ref = out_refs
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    shape = dest_ref.shape                   # (1, block_n): rows on lanes
    h1 = jnp.full(shape, _H1_INIT, jnp.uint32)
    h2 = jnp.full(shape, _H2_INIT, jnp.uint32)
    for c in range(n_cols):
        k = keys_ref[pl.ds(c, 1), :]
        h1 = _mix(h1, k, _MUL1)
        if with_hashes:
            h2 = _mix(h2, k ^ _K2_XOR, _MUL2)
    h1 = h1 ^ (h1 >> 16)

    dest = (h1 % np.uint32(n_parts)).astype(jnp.int32)
    dest = jnp.where(valid_ref[...] != 0, dest, sentinel)
    dest_ref[...] = dest
    if with_hashes:
        h1_ref[...] = h1
        h2_ref[...] = h2 ^ (h2 >> 16)

    p_pad = hist_ref.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (p_pad, shape[1]), 0)
    onehot = rows == dest
    hist_ref[...] += jnp.sum(onehot.astype(jnp.int32), axis=1,
                             keepdims=True)


def vmem_bytes(n_cols: int, n_parts: int, block_n: int = 1024) -> int:
    """VMEM the kernel holds: double-buffered key/valid/output blocks plus
    the ``(parts, block_n)`` one-hot histogram tile."""
    p_pad = max(8, -(-n_parts // 128) * 128)
    io = 2 * 4 * block_n * (-(-n_cols // 8) * 8 + 4 * 8) + 4 * p_pad * 128
    return io + 2 * 4 * p_pad * block_n


def hash_partition_pallas(keys_u32: jnp.ndarray, valid: jnp.ndarray,
                          n_parts: int, *, block_n: int = 1024,
                          interpret: bool = False,
                          return_hashes: bool = False):
    """keys_u32 (K, N) uint32 (one row per key column), valid (N,) →
    (dest (N,), hist (P,)) plus ``(h1 (N,), h2 (N,))`` uint32 when
    ``return_hashes``.  Rows lie along the lane axis of every block."""
    k, n = keys_u32.shape
    n_pad = -(-n // block_n) * block_n
    p_pad = max(8, -(-n_parts // 128) * 128)
    keys = jnp.pad(keys_u32, ((0, 0), (0, n_pad - n)))
    val = jnp.pad(valid.astype(jnp.int32), (0, n_pad - n))[None, :]

    row_spec = pl.BlockSpec((1, block_n), lambda i: (0, i))
    out_specs = [row_spec]
    out_shape = [jax.ShapeDtypeStruct((1, n_pad), jnp.int32)]
    if return_hashes:
        out_specs += [row_spec, row_spec]
        out_shape += [jax.ShapeDtypeStruct((1, n_pad), jnp.uint32)] * 2
    out_specs.append(pl.BlockSpec((p_pad, 1), lambda i: (0, 0)))
    out_shape.append(jax.ShapeDtypeStruct((p_pad, 1), jnp.int32))

    outs = pl.pallas_call(
        functools.partial(_kernel, n_parts=n_parts, sentinel=p_pad,
                          n_cols=k, with_hashes=return_hashes),
        grid=(n_pad // block_n,),
        in_specs=[pl.BlockSpec((k, block_n), lambda i: (0, i)), row_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(keys, val)
    dest, hist = outs[0][0, :n], outs[-1][:n_parts, 0]
    # sentinel rows → n_parts (match ref convention)
    d = jnp.where(dest == p_pad, n_parts, dest)
    if return_hashes:
        return d, hist, outs[1][0, :n], outs[2][0, :n]
    return d, hist
