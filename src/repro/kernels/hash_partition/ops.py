"""Public entry point for hash-partitioning (shuffle destination compute).

Dispatch (``kernels/dispatch.py``): the compiled Pallas kernel on TPU,
the pure-jnp reference elsewhere.  This is the single hash site of the
shuffle engine (``core/exchange.py``): with ``return_hashes`` the fused
kernel also hands back ``(h1, h2)`` so the exchange can carry them and
downstream operators never rehash.
"""
from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp

from repro.core.table import _as_u32
from repro.kernels import dispatch

from . import kernel as _kernel
from . import ref as _ref


def hash_partition(key_cols: Sequence[jnp.ndarray], n_parts: int,
                   valid: jnp.ndarray, return_hashes: bool = False):
    """Row destinations + histogram (+ row hashes when ``return_hashes``).

    Returns ``(dest, hist)`` or ``(dest, hist, h1, h2)``.
    """
    impl = dispatch.choose(
        "hash_partition",
        vmem_bytes=_kernel.vmem_bytes(len(key_cols), n_parts))
    if impl != "xla":
        keys = jnp.stack([_as_u32(c) for c in key_cols])
        return _kernel.hash_partition_pallas(
            keys, valid, n_parts, interpret=impl == "interpret",
            return_hashes=return_hashes)
    if return_hashes:
        return _ref.hash_partition_full(key_cols, n_parts, valid)
    return _ref.hash_partition(key_cols, n_parts, valid)
