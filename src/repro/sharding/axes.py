"""Logical-axis sharding: rules mapping logical tensor axes → mesh axes.

Model code annotates activations with *logical* axes (``batch``, ``seq``,
``heads``, ``ff`` …); the launcher binds a mesh + rule set, and
:func:`constrain` lowers the annotation to ``with_sharding_constraint``.
Unbound (test / single-device) execution makes ``constrain`` a no-op — the
same model code runs everywhere (HPTMT principle (c)/(d)).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: F401

MeshAxes = Union[None, str, Tuple[str, ...]]

# default logical→mesh rules for the production mesh (pod, data, model)
DEFAULT_RULES: Dict[str, MeshAxes] = {
    "batch": ("pod", "data"),     # DP over pods × data axis
    "seq": None,
    "embed": None,
    "heads": "model",             # TP: attention heads
    "kv_heads": "model",
    "q_lora": None,
    "kv_lora": None,
    "ff": "model",                # TP: FFN hidden
    "vocab": "model",             # TP: vocab / logits
    "embed_d": "model",           # embedding table: shard d_model, NOT vocab
                                  # (vocab-sharded gather forces involuntary
                                  # replication in the SPMD partitioner)
    "expert": "model",            # EP: routed experts
    "moe_ff": None,               # expert-internal hidden (TP fallback: model)
    "fsdp": "data",               # parameter sharding (ZeRO-3 style)
    "ssm_inner": "model",
    "kv_seq": "model",            # sequence-sharded KV (decode)
    "state": None,
}


class _Binding(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: Dict[str, MeshAxes] = dict(DEFAULT_RULES)


_BINDING = _Binding()


@contextlib.contextmanager
def logical_binding(mesh: Optional[Mesh], rules: Optional[Dict] = None):
    """Bind mesh + rules for ``constrain``/``spec_for`` inside the block."""
    old = (_BINDING.mesh, _BINDING.rules)
    _BINDING.mesh = mesh
    merged = dict(DEFAULT_RULES)
    if rules:
        merged.update(rules)
    _BINDING.rules = merged
    try:
        yield
    finally:
        _BINDING.mesh, _BINDING.rules = old


def current_mesh() -> Optional[Mesh]:
    return _BINDING.mesh


def spec_for(logical_axes: Sequence[Optional[str]]) -> P:
    """Translate logical axis names to a PartitionSpec under current rules."""
    rules = _BINDING.rules
    mesh = _BINDING.mesh
    used = set()
    parts = []
    for ax in logical_axes:
        mapped = rules.get(ax) if ax is not None else None
        if mapped is None:
            parts.append(None)
            continue
        axes = (mapped,) if isinstance(mapped, str) else tuple(mapped)
        if mesh is not None:
            axes = tuple(a for a in axes if a in mesh.axis_names)
        axes = tuple(a for a in axes if a not in used)
        used.update(axes)
        if not axes:
            parts.append(None)
        elif len(axes) == 1:
            parts.append(axes[0])
        else:
            parts.append(axes)
    return P(*parts)


def constrain(x, *logical_axes: Optional[str]):
    """with_sharding_constraint by logical axes; no-op when unbound."""
    mesh = _BINDING.mesh
    if mesh is None:
        return x
    spec = spec_for(logical_axes)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def embed_lookup(embed, tokens):
    """Embedding gather that sidesteps the SPMD partitioner.

    With the table sharded (vocab replicated, d_model over ``model``) and
    token ids sharded over the DP axes, the gather is *local* per device —
    but the auto-partitioner mis-handles gather-from-sharded-operand (it
    either replicates the output or emits invalid dynamic-slices).  A
    ``shard_map`` pins the obvious strategy: every shard gathers its own
    d-slice for its own batch rows; backward is the matching local
    scatter-add.  Unbound contexts use the plain gather.
    """
    mesh = _BINDING.mesh
    if mesh is None:
        return embed[tokens]
    rules = _BINDING.rules
    d_axis = rules.get("embed_d")
    if isinstance(d_axis, tuple):
        d_axis = d_axis[0] if d_axis else None
    if d_axis is not None and d_axis not in mesh.axis_names:
        d_axis = None
    if d_axis is not None and embed.shape[1] % mesh.shape[d_axis]:
        d_axis = None
    b_spec = spec_for(["batch"])[0]

    def local(e, t):
        return e[t]

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, d_axis), P(b_spec, None)),
        out_specs=P(b_spec, None, d_axis))
    return fn(embed, tokens)


def divisible(n: int, axis: MeshAxes) -> bool:
    """Can dimension ``n`` be sharded over the mapped mesh axes?"""
    mesh = _BINDING.mesh
    if mesh is None or axis is None:
        return True
    axes = (axis,) if isinstance(axis, str) else axis
    size = 1
    for a in axes:
        size *= mesh.shape.get(a, 1)
    return n % size == 0
