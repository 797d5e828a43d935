"""Static collective audits: what a traced/compiled program WILL do.

The second metrics source (DESIGN.md §12): instead of timing, these
functions read collectives out of program artifacts at two levels —

  * **jaxpr** (:func:`jaxpr_collectives`, :func:`jaxpr_exchanges`):
    counts and payload bytes of ``all_to_all`` / ``all_gather`` /
    ``ppermute`` / ``sort`` equations, walked recursively through
    ``shard_map``/``pjit`` sub-jaxprs in program order.  This is the
    "traced" layer — the exact program jax will hand to XLA.
  * **compiled HLO** (:func:`hlo_collectives`, :func:`top_collectives`):
    the post-optimization executable, parsed with the roofline HLO
    collective parser.  This is the "observed" layer — what actually
    runs, after XLA has had every chance to fuse, split or elide.

The lazy planner's plan-vs-observed audit compares its own prediction
against BOTH (``LazyFrame.collect(telemetry=...)``); the perf CLI and
the benchmark harness reuse the same parsers for their reports.
"""
from __future__ import annotations

import collections
import re
from typing import Any, Dict, List, Optional, Tuple

#: jaxpr primitives worth counting — the exchange (all_to_all), the
#: splitter/broadcast collectives, and the sort the paper's operators
#: are built from.
JAXPR_PRIMITIVES = ("all_to_all", "all_gather", "ppermute", "psum", "sort")


def _iter_eqns(jaxpr):
    """Every equation of a (Closed)Jaxpr, recursing into sub-jaxprs
    carried in params (pjit/shard_map/scan/cond), in program order."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)  # ClosedJaxpr -> Jaxpr
    for eqn in inner.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from _iter_eqns(sub)


def _sub_jaxprs(value):
    if hasattr(value, "eqns") or hasattr(value, "jaxpr"):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
                yield v


def jaxpr_collectives(closed_jaxpr) -> Dict[str, int]:
    """Counts of :data:`JAXPR_PRIMITIVES` in a traced program."""
    counts = {name: 0 for name in JAXPR_PRIMITIVES}
    for eqn in _iter_eqns(closed_jaxpr):
        name = eqn.primitive.name
        if name in counts:
            counts[name] += 1
    return counts


def _eqn_bytes(eqn) -> int:
    total = 0
    for var in eqn.invars:
        aval = getattr(var, "aval", None)
        if aval is not None and hasattr(aval, "size"):
            total += int(aval.size) * aval.dtype.itemsize
    return total


def jaxpr_exchanges(closed_jaxpr, n_shards: int = 1) -> List[Dict[str, Any]]:
    """Program-order ``all_to_all`` payloads.

    Bytes are GLOBAL: inside ``shard_map`` an equation sees the
    per-shard operand, so the per-shard payload is scaled by
    ``n_shards`` — the total volume the exchange moves across the mesh.
    """
    out = []
    for eqn in _iter_eqns(closed_jaxpr):
        if eqn.primitive.name == "all_to_all":
            out.append({"primitive": "all_to_all",
                        "bytes": _eqn_bytes(eqn) * n_shards})
    return out


def trace_collectives(fn, *args, n_shards: int = 1) -> Dict[str, Any]:
    """Trace ``fn`` (no execution) → jaxpr counts + exchange payloads."""
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    return {"counts": jaxpr_collectives(closed),
            "exchanges": jaxpr_exchanges(closed, n_shards)}


# ---------------------------------------------------------------------------
# compiled-HLO layer (generalized from the launch/perf.py CLI)
# ---------------------------------------------------------------------------
def hlo_collectives(hlo_text: str):
    """Counts/bytes/ring-cost of every collective in compiled HLO text
    (a :class:`~repro.launch.roofline.CollectiveStats`)."""
    from repro.launch.roofline import parse_collectives

    return parse_collectives(hlo_text)


def top_collectives(hlo_text: str, k: int = 12
                    ) -> List[Tuple[int, str, str]]:
    """The ``k`` largest collectives by total bytes, aggregated by
    (kind, shape) — the perf CLI's contributor table."""
    from repro.launch.roofline import _shape_bytes

    rows = []
    for line in hlo_text.splitlines():
        m = re.match(
            r"\s*%?\S+ = (.+?)\s+(all-gather|all-reduce|reduce-scatter"
            r"|all-to-all|collective-permute)(?:-start)?\(", line)
        if not m:
            continue
        b = _shape_bytes(m.group(1))
        if b:
            rows.append((b, m.group(2), m.group(1)[:70]))
    agg = collections.Counter()
    for b, kind, shape in rows:
        agg[(kind, shape)] += b
    return sorted(((b, kind, shape) for (kind, shape), b in agg.items()),
                  reverse=True)[:k]


def compiled_collectives(fn, *args) -> Dict[str, Any]:
    """Compile ``fn`` (no execution) → observed HLO collective stats.

    ``ring_cost_s`` prices the ring model at the link bandwidth of the
    device the program compiled for (``core.device.peaks``); it is
    ``None`` off a TPU, where there are no links to price."""
    import jax

    from repro.core.device import peaks
    from repro.launch.roofline import DRYRUN_KIND

    compiled = jax.jit(fn).lower(*args).compile()
    stats = hlo_collectives(compiled.as_text())
    dev = jax.devices()[0]
    ring_cost_s = None
    if dev.platform == "tpu":
        # hlo_collectives prices links at the dry-run chip's bandwidth
        ring_cost_s = stats.cost_s * (peaks(DRYRUN_KIND).ici_bw
                                      / peaks(dev.device_kind).ici_bw)
    return {"counts": dict(stats.counts),
            "bytes_by_kind": dict(stats.bytes_by_kind),
            "total_bytes": stats.total_bytes,
            "ring_cost_s": ring_cost_s}


def program_audit(fn, *args, n_shards: int = 1,
                  predicted_a2a: Optional[int] = None) -> Dict[str, Any]:
    """Full two-layer audit of one program: traced jaxpr + compiled HLO.

    ``traced_a2a`` counts ``all_to_all`` equations; ``observed_a2a``
    counts ``all-to-all`` ops in the optimized executable.  When the
    caller supplies its planner prediction, ``consistent`` states
    whether all three layers agree — the runtime form of the
    plan-contract CI assertion.
    """
    traced = trace_collectives(fn, *args, n_shards=n_shards)
    observed = compiled_collectives(fn, *args)
    audit: Dict[str, Any] = {
        "n_shards": n_shards,
        "traced": traced["counts"],
        "traced_a2a": traced["counts"]["all_to_all"],
        "exchanges": traced["exchanges"],
        "observed": observed["counts"],
        "observed_a2a": observed["counts"].get("all-to-all", 0),
        "observed_bytes_by_kind": observed["bytes_by_kind"],
        "observed_total_bytes": observed["total_bytes"],
    }
    if predicted_a2a is not None:
        audit["predicted_a2a"] = predicted_a2a
        audit["consistent"] = (predicted_a2a == audit["traced_a2a"]
                               == audit["observed_a2a"])
    return audit
