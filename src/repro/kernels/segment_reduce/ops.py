"""Public entry point for segment reduction.

Dispatch (``kernels/dispatch.py``): the one-hot Pallas kernel on TPU when
the padded segment count is at most :data:`MAX_ONEHOT_SEGMENTS` — its work
is rows × segments, so a groupby with many groups takes the XLA scatter
reduction instead; the pure-jnp reference elsewhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import dispatch

from . import kernel as _kernel
from . import ref as _ref

#: Largest segment count the one-hot kernel takes: two 512-wide segment
#: blocks, so each row is compared against at most 1024 segments.
MAX_ONEHOT_SEGMENTS = 1024


def _impl(num_segments: int, lanes: int) -> str:
    return dispatch.choose("segment_reduce",
                           fits=num_segments <= MAX_ONEHOT_SEGMENTS,
                           vmem_bytes=_kernel.vmem_bytes(lanes))


@functools.partial(jax.jit, static_argnums=(2,), static_argnames=("op",))
def segment_reduce(values: jnp.ndarray, segment_ids: jnp.ndarray,
                   num_segments: int, op: str = "sum") -> jnp.ndarray:
    impl = _impl(num_segments, 1)
    if impl != "xla":
        return _kernel.segment_reduce_pallas(
            values, segment_ids, num_segments, op,
            interpret=impl == "interpret")
    return _ref.segment_reduce(values, segment_ids, num_segments, op)


@functools.partial(jax.jit, static_argnums=(2,))
def segment_reduce_fused(values: jnp.ndarray, segment_ids: jnp.ndarray,
                         num_segments: int) -> jnp.ndarray:
    """Sum-reduce lanes-major ``(L, N)`` values by segment → ``(L, S)``.

    The GroupBy fast path: every sum-combining aggregate (sum, count, the
    sum/count halves of mean) rides one scatter (XLA) or one one-hot
    matmul sweep (TPU Pallas) instead of one reduction per column.
    """
    impl = _impl(num_segments, values.shape[0])
    if impl != "xla":
        return _kernel.segment_reduce_pallas(
            values, segment_ids, num_segments, "sum",
            interpret=impl == "interpret")
    return _ref.segment_reduce_fused(values, segment_ids, num_segments)
