"""The readers of the program's query spans: ``io.upload_share.table`` and
``plan.host_share.table``, on a synthetic run worked out by hand and on
the spans of a real planned query."""
import os
import sys
import time
import types

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for _p in (os.path.join(ROOT, "src"), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import harness  # noqa: E402


def reader(name):
    return harness.load_module(os.path.join(BENCH, "metrics", name + ".py"))


def synthetic_run(spans, window=(10.0, 20.0)):
    cell = types.SimpleNamespace(config={}, traffic={}, chips=1)
    run = harness.Run(cell, seed=1, seconds=window[1] - window[0])
    run.window = window
    run.spans = spans
    return run


#: one query over a [10, 20) s window: the root, its phases, two scans
SPANS = [
    ("plan.collect", 10.5, 19.5),
    ("plan.optimize", 10.5, 11.0),
    ("io.scan.materialize", 11.0, 12.5),
    ("io.scan.read", 11.0, 11.2),
    ("io.scan.assemble", 11.2, 11.5),
    ("io.scan.upload", 11.5, 12.5),
    ("io.scan.materialize", 12.5, 13.0),
    ("io.scan.upload", 12.75, 13.0),
    ("plan.jit", 13.0, 14.0),
    ("plan.wait", 14.0, 19.5),
    # the previous query's upload, half inside the window
    ("io.scan.upload", 9.0, 10.5),
]


@pytest.mark.parametrize("name, expected", [
    # [10, 10.5) + [11.5, 12.5) + [12.75, 13): 1.75 s of 10
    ("io.upload_share.table", 17.5),
    # [10.5, 11) + [13, 14): 1.5 s of 10; plan.wait and the root not
    ("plan.host_share.table", 15.0),
])
def test_share_of_the_window(name, expected):
    assert reader(name).read(synthetic_run(SPANS)) == pytest.approx(expected)


def test_overlapping_spans_count_once():
    run = synthetic_run([("plan.jit", 11.0, 13.0), ("plan.optimize", 12.0,
                                                     14.0)])
    assert reader("plan.host_share.table").read(run) == pytest.approx(30.0)


@pytest.mark.parametrize("name", ["io.upload_share.table",
                                  "plan.host_share.table"])
def test_a_program_without_the_spans_reports_nothing(name):
    run = synthetic_run([("io.scan.read", 11.0, 12.0),
                         ("io.scan.materialize", 11.0, 13.0)])
    assert reader(name).read(run) is None


def test_shares_of_a_planned_query(tmp_path):
    """The spans of one real query, as the table cell hands them to the
    readers: both shares are read, and the upload is part of the scan."""
    from repro import telemetry
    from repro.core import HPTMTContext
    from repro.io import write_dataset
    from repro.plan import LazyFrame

    rng = np.random.default_rng(3)
    n = 256
    write_dataset(str(tmp_path / "t"),
                  [({"k": rng.integers(0, 16, n).astype(np.int32),
                     "v": rng.random(n).astype(np.float32)}, n)],
                  format="hpt", rows_per_group=64)
    rec = telemetry.Collector("bench")
    t0 = time.perf_counter()
    with telemetry.using(rec):
        (LazyFrame.read_parquet(str(tmp_path / "t"), HPTMTContext(),
                                bucket_factor=1.1)
         .groupby(["k"], [("v", "sum")], out_capacity=32)
         .collect(strict=False))
    run = synthetic_run(None, (t0, time.perf_counter()))
    table_query = harness.load_module(os.path.join(BENCH, "drivers",
                                                   "table_query.py"))
    impl = table_query.Driver.__new__(table_query.Driver)
    impl.rec = rec
    run.spans = impl.spans()
    upload = reader("io.upload_share.table").read(run)
    scan = reader("io.scan_share.table").read(run)
    host = reader("plan.host_share.table").read(run)
    assert 0 < upload <= scan <= 100
    assert 0 < host <= 100 and scan + host <= 100
