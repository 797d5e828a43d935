"""Public entry points for the windowed-scan engine (DESIGN.md §9).

Dispatch (``kernels/dispatch.py``): the compiled Pallas kernel on TPU when
its blocks fit the scoped-VMEM limit, the pure-jnp reference elsewhere
(itself fast XLA code).  Interpret-mode kernel output must match the
reference bit-for-bit (shared rolling helper).  The expanding
(cumulative) scan has no Pallas variant — it is one ladder of
shift-combines with nothing extra for a kernel to fuse — and always takes
the reference path.

``windowed_scan`` accepts ``(n,)`` or ``(n, L)`` values; all sum-combining
window lanes of one operator call ride a single ``(n, L)`` invocation
(count and both halves of mean derive from it), min/max reduce per column —
the same lane-fusion contract as the groupby segment reduction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import dispatch

from . import kernel as _kernel
from . import ref as _ref

segmented_cumulative = _ref.segmented_cumulative

_OPS = ("sum", "min", "max")


@functools.partial(jax.jit, static_argnums=(2, 3), static_argnames=("op",))
def windowed_scan(values: jnp.ndarray, seg_start: jnp.ndarray, window: int,
                  op: str = "sum") -> jnp.ndarray:
    """Rolling segment-clipped reduction; see ``ref.windowed_scan``.

    ``out[i] = op(values[max(i - window + 1, seg_start[i]) .. i])``.
    """
    if op not in _OPS:
        raise ValueError(f"unknown windowed_scan op {op!r}; expected "
                         f"one of {_OPS}")
    squeeze = values.ndim == 1
    v = values[:, None] if squeeze else values
    v = v.astype(jnp.float32)
    impl = dispatch.choose("window_scan",
                           vmem_bytes=_kernel.vmem_bytes(v.shape[1], window))
    if impl != "xla":
        out = _kernel.windowed_scan_pallas(v, seg_start, window, op,
                                           interpret=impl == "interpret")
    else:
        out = _ref.windowed_scan(v, seg_start, window, op)
    return out[:, 0] if squeeze else out
