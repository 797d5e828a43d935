"""Pallas TPU flash-attention kernel (forward).

Online-softmax tiling (Flash-Attention 2 schedule) adapted to the TPU memory
hierarchy: q/k/v tiles stream HBM→VMEM under BlockSpec control; the two
matmuls per tile run on the MXU with fp32 accumulation; running max / sum /
accumulator live in VMEM scratch that persists across the (innermost)
key-block grid dimension; the running max and sum are ``(block_q, 1)``
columns, so every vector in the kernel is 2-D.

Layout: heads are folded into the leading grid axis.  GQA never
materializes repeated KV heads — the kv BlockSpec index-maps query head
``h`` onto kv head ``h // group``, so each kv tile is fetched once per
query-head group.

Block sizes: (block_q=128, block_k=128) aligns both matmul contractions to
the 128×128 MXU; with D=128 the VMEM working set is
q(64KB) + k(64KB) + v(64KB) + acc(64KB) + O(1) vectors ≈ 0.3 MB.

Masking is done on absolute positions: ``q_offset`` places the query block
inside a longer KV context (decode), ``kv_len`` masks right-padding,
``window`` gives Mistral-style sliding-window attention.  Causal masking
also *skips* key blocks strictly above the diagonal (they are revisits of
the output block, so skipping is just an early-exit ``pl.when``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            sm_scale: float, block_q: int, block_k: int, causal: bool,
            window: Optional[int], kv_len: int, q_offset: int,
            n_kblocks: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # absolute positions of this tile
    q_pos0 = q_offset + qi * block_q
    k_pos0 = kj * block_k

    # causal early-exit: whole key block above the diagonal
    block_needed = True
    if causal:
        block_needed = k_pos0 <= q_pos0 + block_q - 1
    if window is not None:
        # skip only if the newest key is outside the *oldest* query's window
        block_needed = jnp.logical_and(
            block_needed,
            q_pos0 - (k_pos0 + block_k - 1) < window)

    @pl.when(block_needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0].astype(jnp.float32)          # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # (bq, bk)

        rows = q_pos0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = k_pos0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        allow = cols < kv_len
        if causal:
            allow &= cols <= rows
        if window is not None:
            allow &= (rows - cols) < window
        s = jnp.where(allow, s, _NEG_INF)

        m_prev = m_ref[...]                       # (bq, 1)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(allow, p, 0.0)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(kj == n_kblocks - 1)
    def _finish():
        l = l_ref[...]                            # (bq, 1)
        out = acc_ref[...] / jnp.maximum(l, 1e-30)
        out = jnp.where(l > 0, out, 0.0)
        o_ref[0] = out.astype(o_ref.dtype)


def vmem_bytes(d: int, block_q: int = 128, block_k: int = 128) -> int:
    """VMEM the kernel holds: double-buffered q/k/v/o blocks, the f32
    accumulator and running columns, and the f32 score tiles."""
    lanes = -(-d // 128) * 128
    io = 2 * 4 * lanes * (2 * block_q + 2 * block_k)
    scratch = 4 * (block_q * lanes + 2 * block_q * 128)
    return io + scratch + 3 * 4 * block_q * block_k


def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           causal: bool = True, window: Optional[int] = None,
                           kv_len: Optional[int] = None, q_offset: int = 0,
                           sm_scale: Optional[float] = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False) -> jnp.ndarray:
    """q (B, Hq, Sq, D); k, v (B, Hkv, Sk, D) → (B, Hq, Sq, D)."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    group = hq // hkv
    if sm_scale is None:
        sm_scale = d ** -0.5
    if kv_len is None:
        kv_len = sk

    block_q = min(block_q, max(sq, 8))
    block_k = min(block_k, max(sk, 8))
    sq_pad = -(-sq // block_q) * block_q
    sk_pad = -(-sk // block_k) * block_k

    qr = jnp.pad(q.reshape(b * hq, sq, d), ((0, 0), (0, sq_pad - sq), (0, 0)))
    kr = jnp.pad(k.reshape(b * hkv, sk, d), ((0, 0), (0, sk_pad - sk), (0, 0)))
    vr = jnp.pad(v.reshape(b * hkv, sk, d), ((0, 0), (0, sk_pad - sk), (0, 0)))

    n_kblocks = sk_pad // block_k
    grid = (b * hq, sq_pad // block_q, n_kblocks)

    def kv_index(bh, qi, kj):
        return ((bh // hq) * hkv + (bh % hq) // group, kj, 0)

    out = pl.pallas_call(
        functools.partial(
            _kernel, sm_scale=sm_scale, block_q=block_q, block_k=block_k,
            causal=causal, window=window, kv_len=kv_len, q_offset=q_offset,
            n_kblocks=n_kblocks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi, kj: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq_pad, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out[:, :sq].reshape(b, hq, sq, d)
