"""Driver of table cells: one planned query, back to back, from one client.

Set-up generates the configuration's tables from the seed
(``bench/tablegen.py``), writes them with the program's dataset writer
into a temporary directory, and runs the query once, which warms every
program the window runs.  A unit of work is the user's whole query:
build the ``LazyFrame``, ``collect()`` it, check its overflow report and
bring the result to the host with ``to_numpy()``.  Its work is the
scanned table's rows.

The traffic file's ``query`` is data: ``scan``, ``filter`` (pushed-down
predicates), ``join`` (inner, on a key, with another table), ``groupby``
and ``topk``.
"""
from __future__ import annotations

import os
import tempfile

from limits import limits_of
from tablegen import make_tables, rows_of


class Driver:
    unit_name = "query"

    def __init__(self, cfg: dict, traffic: dict, seed: int, ref, cell: str):
        self.cfg, self.traffic, self.seed, self.ref = cfg, traffic, seed, ref
        self.cell = cell
        self.query = traffic["query"]
        self.outputs = []           # (result columns, overflow) per query
        self._tmp = None

    # -- set-up --------------------------------------------------------------
    def setup(self) -> None:
        from repro import telemetry as T
        from repro.core import HPTMTContext
        from repro.io import write_dataset

        self.data = make_tables(self.cfg, self.seed)
        self._tmp = tempfile.TemporaryDirectory(prefix="bench-table-")
        for name, cols in self.data.items():
            n = len(next(iter(cols.values())))
            frags = self.cfg["tables"][name].get("fragments", 1)
            write_dataset(os.path.join(self._tmp.name, name), [(cols, n)],
                          format=self.cfg["format"],
                          rows_per_group=max(n // frags, 1))
        self.ctx = HPTMTContext()
        self.rec = T.Collector("bench")
        self._T = T
        self.unit()                 # warm-up: compiles, or loads the cache
        self.outputs.clear()
        self.rec = T.Collector("bench")

    def lazy(self):
        """The traffic file's query as a ``LazyFrame``."""
        from repro.io.scan import pred
        from repro.plan import LazyFrame

        q, root = self.query, self._tmp.name
        scan = self.cfg["scan_headroom"]
        lf = LazyFrame.read_parquet(os.path.join(root, q["scan"]), self.ctx,
                                    bucket_factor=scan)
        if q.get("filter"):
            lf = lf.filter([pred(c, op, v) for c, op, v in q["filter"]])
        if q.get("join"):
            j = q["join"]
            dim = LazyFrame.read_parquet(os.path.join(root, j["table"]),
                                         self.ctx, bucket_factor=scan)
            lf = lf.join(dim, list(j["on"]),
                         bucket_factor=self.cfg["join_headroom"])
        g = q["groupby"]
        lf = lf.groupby(list(g["keys"]), [tuple(a) for a in g["aggs"]],
                        out_capacity=g["out_capacity"])
        if q.get("topk"):
            lf = lf.topk(q["topk"]["by"], q["topk"]["k"])
        return lf

    # -- the window ----------------------------------------------------------
    def unit(self) -> int:
        with self._T.using(self.rec):
            df = self.lazy().collect(strict=False)
        overflow = sum(int(v) for _, v in df.overflow_report)
        self.outputs.append((df.to_numpy(), overflow))
        return rows_of(self.cfg, self.query["scan"])

    def spans(self):
        """Program spans as ``(name, start, end)`` on ``time.perf_counter``."""
        ep = self.rec.epoch
        return [(s.name, ep + s.t0_us / 1e6, ep + (s.t0_us + s.dur_us) / 1e6)
                for s in self.rec.all_spans() if s.dur_us is not None]

    def notes(self):
        return [f"table: outputs kept={len(self.outputs)} overflow per query="
                f"{[o for _, o in self.outputs]}"]

    # -- after the window ----------------------------------------------------
    def release(self) -> None:
        self.ctx = None
        if self._tmp is not None:
            self._tmp.cleanup()
            self._tmp = None

    def compare(self, results) -> dict:
        """The compared numbers of ``results`` (column dicts with their
        overflow) against the reference."""
        lim = limits_of(self.cell)
        exp = self.ref.expected(self.data, self.query)
        mismatches, err = 0, 0.0
        for got, overflow in results:
            bad, e = self.ref.compare(got, exp)
            mismatches += bad + overflow
            err = max(err, e)
        return {"mismatches": {"value": mismatches, "limit": lim["mismatches"]},
                "agg_err": {"value": err, "limit": lim["agg_err"]}}

    def check(self) -> dict:
        """Every query of the window compared."""
        return self.compare(self.outputs)

    def control(self) -> dict:
        """The compared numbers of the control: the reference in bfloat16
        in the program's place."""
        got = self.ref.control(self.data, self.query)
        return self.compare([(got, 0)])
