"""HPTMT execution context.

The paper's principle (c) — *independence of the parallel execution
environment* — requires operators that never reach for global runtime state.
Every operator in this framework takes an :class:`HPTMTContext` describing the
device mesh and the named axes it may use.  The same operator code runs on

  * a single device (``mesh=None``) — "excellent performance even in
    non-parallel environments" (paper §II),
  * a host-local test mesh (``xla_force_host_platform_device_count``),
  * a production pod / multi-pod TPU mesh,

without modification — principle (d), *same operator on different hardware*.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(shape: Sequence[int], names: Sequence[str], devices=None) -> Mesh:
    """Create a mesh with ``Auto`` axis types (shard_map-compatible)."""
    if devices is None:
        devices = jax.devices()
    n = math.prod(shape)
    if n > len(devices):
        raise ValueError(f"mesh shape {tuple(shape)} needs {n} devices, have {len(devices)}")
    dev_array = np.asarray(devices[:n]).reshape(tuple(shape))
    return Mesh(dev_array, tuple(names))


@dataclasses.dataclass(frozen=True)
class HPTMTContext:
    """Binding of HPTMT logical axes onto a concrete mesh.

    Attributes:
      mesh: the device mesh, or ``None`` for single-device execution.
      data_axis: mesh axis over which table rows / batch entries are
        partitioned (the paper's row-decomposition, §II).
      model_axis: mesh axis for tensor (model) parallelism / expert
        parallelism, if present.
      pod_axis: outer axis spanning pods (multi-pod DP), if present.
    """

    mesh: Optional[Mesh] = None
    data_axis: str = "data"
    model_axis: Optional[str] = None
    pod_axis: Optional[str] = None

    # ---- introspection -------------------------------------------------
    @property
    def is_distributed(self) -> bool:
        return self.mesh is not None and self.n_shards > 1

    @property
    def n_shards(self) -> int:
        """Number of row-partitions (size of the data axis)."""
        if self.mesh is None:
            return 1
        return self.mesh.shape[self.data_axis]

    @property
    def model_size(self) -> int:
        if self.mesh is None or self.model_axis is None:
            return 1
        return self.mesh.shape[self.model_axis]

    @property
    def n_pods(self) -> int:
        if self.mesh is None or self.pod_axis is None:
            return 1
        return self.mesh.shape[self.pod_axis]

    @property
    def dp_axes(self) -> Tuple[str, ...]:
        """All data-parallel axes, outermost first."""
        axes: Tuple[str, ...] = ()
        if self.pod_axis is not None:
            axes += (self.pod_axis,)
        axes += (self.data_axis,)
        return axes

    # ---- sharding helpers ----------------------------------------------
    def row_sharding(self, ndim: int = 1) -> Optional[NamedSharding]:
        """Sharding that row-partitions a leading axis over the data axis."""
        if self.mesh is None:
            return None
        spec = P(self.data_axis, *([None] * (ndim - 1)))
        return NamedSharding(self.mesh, spec)

    def replicated(self) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, P())

    def shard_map(self, fn, in_specs, out_specs, check_vma: bool = False):
        """shard_map over this context's mesh (identity when single-device)."""
        if self.mesh is None:
            raise ValueError("shard_map requires a mesh-backed context")
        return jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=check_vma)


def local_context() -> HPTMTContext:
    """Single-device context: operators degrade to local execution."""
    return HPTMTContext(mesh=None)


def host_test_context(n_shards: int = 1, model: int = 1) -> HPTMTContext:
    """Context over host devices, for tests (requires enough devices)."""
    if n_shards * model == 1:
        return local_context()
    if model > 1:
        mesh = make_mesh((n_shards, model), ("data", "model"))
        return HPTMTContext(mesh=mesh, model_axis="model")
    mesh = make_mesh((n_shards,), ("data",))
    return HPTMTContext(mesh=mesh)
