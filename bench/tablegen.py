"""Tables of a table configuration, generated from a seed.

A configuration file lists each table's columns with their distribution;
``<table>_rows`` at its top level gives the table's rows.  One generator
reads every configuration, so a new deployment is a new file:

    {"dtype": "int32", "dist": "uniform_int", "low": 0, "high": 100}
        integers uniform over ``[low, high)``
    {"dtype": "float32", "dist": "uniform", "low": 0.0, "high": 1.0}
        reals uniform over ``[low, high)``
    {"dtype": "int32", "dist": "permutation"}
        ``0 .. rows-1`` in a drawn order: a unique key
    {"dtype": "int32", "dist": "key_of", "table": "t", "column": "c"}
        values of ``t.c`` drawn uniformly with replacement: a foreign key

A column marked ``"fixed": true`` is drawn the same whatever the seed.
A configuration fixes the columns that decide the work, such as join and
group keys, their order and filtered columns (a hash join's or a hash
groupby's time depends on how its keys collide), and lets the seed draw
the values aggregated.  Each column draws from a stream of its own,
named by table and column.

Tables are made in the file's order, so a ``key_of`` names a table
listed before it.
"""
from __future__ import annotations

import zlib

import numpy as np


def rows_of(cfg: dict, table: str) -> int:
    return int(cfg[f"{table}_rows"])


def make_tables(cfg: dict, seed: int) -> dict:
    """``{table: {column: numpy array}}`` of the configuration for
    ``seed``."""
    out = {}
    for table, spec in cfg["tables"].items():
        n = rows_of(cfg, table)
        cols = {}
        for name, c in spec["columns"].items():
            stream = zlib.crc32(f"{table}.{name}".encode())
            rng = np.random.default_rng(
                [stream] if c.get("fixed") else [seed, stream])
            dist, dtype = c["dist"], np.dtype(c["dtype"])
            if dist == "uniform_int":
                v = rng.integers(c["low"], c["high"], n)
            elif dist == "uniform":
                v = rng.uniform(c["low"], c["high"], n)
            elif dist == "permutation":
                v = rng.permutation(n)
            elif dist == "key_of":
                src = out[c["table"]][c["column"]]
                v = src[rng.integers(0, len(src), n)]
            else:
                raise ValueError(f"unknown distribution {dist!r} of "
                                 f"{table}.{name}")
            cols[name] = v.astype(dtype)
        out[table] = cols
    return out
