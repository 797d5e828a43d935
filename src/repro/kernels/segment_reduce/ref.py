"""Pure-jnp oracle for segment reduction (GroupBy-aggregate hot loop)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

_INITS = {"sum": 0.0, "min": jnp.inf, "max": -jnp.inf}


def segment_reduce(values: jnp.ndarray, segment_ids: jnp.ndarray,
                   num_segments: int, op: str = "sum") -> jnp.ndarray:
    """Reduce ``values`` by ``segment_ids`` into ``num_segments`` buckets.

    ids outside ``[0, num_segments)`` are dropped. Empty segments hold the
    reduction identity (0 / +inf / -inf), matching ``jax.ops.segment_*``.
    """
    if op == "sum":
        return jax.ops.segment_sum(values, segment_ids, num_segments)
    if op == "min":
        return jax.ops.segment_min(values, segment_ids, num_segments)
    if op == "max":
        return jax.ops.segment_max(values, segment_ids, num_segments)
    raise ValueError(f"unknown op {op!r}")


def segment_reduce_fused(values: jnp.ndarray, segment_ids: jnp.ndarray,
                         num_segments: int) -> jnp.ndarray:
    """Sum-reduce lanes-major ``(L, N)`` values by segment → ``(L, S)``.

    One 1-D scatter-add per lane (the GroupBy map-side-combine hot loop,
    DESIGN.md §4).  Rows stay on the last axis throughout: a TPU tiles
    that axis by 128, so an ``(N, L)`` operand or update block with a
    handful of lanes — what one batched scatter over all lanes lowers
    to — would occupy ~128/L times its bytes.
    """
    return jnp.stack([jax.ops.segment_sum(v, segment_ids, num_segments)
                      for v in values])
