"""Int8 error-feedback gradient compression for the pure-DP (pod) axis.

Distributed-optimization trick for multi-pod scale: the inter-pod gradient
all-reduce crosses the slowest links (DCN/optical), so its volume dominates.
We compress to int8 with error feedback (1-bit-Adam / EF-SGD lineage):

    q  = quantize(g + e)          # int8, per-leaf max-abs scale
    ĝ  = allreduce_int8(q)        # reduce-scatter + all-gather in int8
    e' = (g + e) - dequant(q)     # residual carried to the next step

The int8 exchange is two ``all_to_all``/``all_gather`` rounds on one quarter
of the fp32 volume.  Exact when every pod sees identical data (q identical);
otherwise standard EF convergence applies.  Exposed as a standalone operator
(HPTMT array-operator, usable on any mesh axis) and unit-tested on a host
mesh; the trainer enables it on meshes with a ``pod`` axis.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp


def _quantize(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def ef_allreduce_mean(x: jnp.ndarray, err: jnp.ndarray, axis: str,
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Inside shard_map: int8 mean-allreduce of ``x`` with error feedback.

    Returns (averaged value, new error state). x/err are the local shard's
    full gradient leaf (replicated shape across the axis).
    """
    n = jax.lax.axis_size(axis)
    xe = x.astype(jnp.float32) + err
    # pad flat length to a multiple of the axis size
    flat = xe.reshape(-1)
    pad = (-flat.shape[0]) % n
    flat_p = jnp.pad(flat, (0, pad))

    q, scale = _quantize(flat_p)
    # stage 1: reduce-scatter in int8 — each member sums one chunk
    chunks = q.reshape(n, -1)
    mine = jax.lax.all_to_all(chunks, axis, split_axis=0, concat_axis=0,
                              tiled=False)                      # (n, chunk)
    scales = jax.lax.all_gather(scale, axis)                    # (n,)
    part = jnp.sum(mine.astype(jnp.float32) * scales[:, None], axis=0) / n

    # stage 2: all-gather the reduced chunk in int8
    q2, scale2 = _quantize(part)
    full_q = jax.lax.all_gather(q2, axis, axis=0, tiled=True)
    scale2_all = jax.lax.all_gather(scale2, axis)               # (n,)
    per_chunk = full_q.reshape(n, -1).astype(jnp.float32) \
        * scale2_all[:, None]
    result = per_chunk.reshape(-1)[:flat.shape[0]].reshape(x.shape)

    # error feedback on the local quantization
    dq_local = (q.astype(jnp.float32) * scale)[:flat.shape[0]].reshape(x.shape)
    new_err = xe - dq_local
    return result.astype(x.dtype), new_err


def init_error_state(params) -> Any:
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def tree_ef_allreduce(grads, err_state, axis: str):
    """Apply ef_allreduce_mean leaf-wise (inside shard_map over ``axis``)."""
    flat_g, treedef = jax.tree.flatten(grads)
    flat_e = treedef.flatten_up_to(err_state)
    out = [ef_allreduce_mean(g, e, axis) for g, e in zip(flat_g, flat_e)]
    return (treedef.unflatten([o[0] for o in out]),
            treedef.unflatten([o[1] for o in out]))
