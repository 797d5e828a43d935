"""The one rule that picks a Pallas kernel or its XLA reference (DESIGN.md §7).

Every kernel entry point (``kernels/*/ops.py`` and the attention layer)
asks :func:`choose`, which decides from what the code can observe:

  * the backend — the compiled kernel exists only for a TPU; anywhere
    else the XLA reference runs;
  * a shape rule the caller passes as ``fits`` — e.g. a one-hot segment
    reduction only for few segments (its work is rows × segments), or
    no flash kernel under autodiff (it has no backward pass);
  * a VMEM estimate the caller passes as ``vmem_bytes`` — admitted only
    within the scoped-VMEM limit the TPU compiler applies to a kernel on
    this device kind (``core.device.PEAKS``).

``want`` is an explicit caller choice (tests, and the model config's
``use_flash``): ``True`` runs the kernel — compiled on a TPU, in
interpret mode elsewhere — and ``False`` runs the reference.

Each decision is counted under ``(kernel, impl)``; a path that has only
one implementation counts itself with :func:`record`.  Decisions happen
while a program is traced, so the counts say how many traced call sites
took each implementation, not how many times a compiled program ran.
"""
from __future__ import annotations

import collections
from typing import Dict, Optional, Tuple

import jax

from repro.core.device import peaks

_COUNTS: Dict[Tuple[str, str], int] = collections.Counter()


def _platform() -> str:
    """The platform the rule decides for: the device this process runs."""
    return jax.devices()[0].platform


def vmem_limit() -> int:
    """Scoped-VMEM bytes a kernel may use on the device this process runs."""
    return peaks(jax.devices()[0].device_kind).scoped_vmem_bytes


def choose(kernel: str, *, fits: bool = True, vmem_bytes: int = 0,
           want: Optional[bool] = None) -> str:
    """``"pallas"`` (compiled), ``"interpret"`` or ``"xla"`` for one call.

    A kernel the rule picks compiles where the backend is a TPU and runs
    in interpret mode anywhere else."""
    if want is None:
        want = (_platform() == "tpu" and fits
                and vmem_bytes <= vmem_limit())
    if not want:
        impl = "xla"
    else:
        impl = "pallas" if jax.default_backend() == "tpu" else "interpret"
    _COUNTS[(kernel, impl)] += 1
    return impl


def record(kernel: str, impl: str) -> None:
    """Count a decision the caller made itself, so that a path with no
    alternative to choose from still shows in :func:`counts`."""
    _COUNTS[(kernel, impl)] += 1


def counts() -> Dict[str, Dict[str, int]]:
    """``{kernel: {impl: decisions}}`` since the last :func:`reset_counts`."""
    out: Dict[str, Dict[str, int]] = {}
    for (kernel, impl), n in sorted(_COUNTS.items()):
        out.setdefault(kernel, {})[impl] = n
    return out


def reset_counts() -> None:
    _COUNTS.clear()
