"""Public entry points for the hash-join build/probe engine.

All four primitives run the pure-jnp reference everywhere (DESIGN.md §7):
build (contended scatter-min), the probe walk and emit (binary search +
gather walk) lower through XLA.  The probe's random walk over a slot table
has no Pallas form on a TPU — Mosaic gathers only within a vreg — so there
is no probe kernel to dispatch to.

These primitives serve three operators (DESIGN.md §8): join
(``build_table`` + two-pass probe), set-op membership/dedup and the
groupby hash kernel (``build_table_unique``).
"""
from __future__ import annotations

from . import ref as _ref

build_table = _ref.build_table
build_table_unique = _ref.build_table_unique
slot_payload = _ref.slot_payload
emit_lookup = _ref.emit_lookup
probe = _ref.probe
