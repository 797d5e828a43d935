"""A planned query traced from inside ``collect()`` (DESIGN.md §12.5).

  * one ``plan.collect`` root per query, on every activation, holding its
    phases in order: ``plan.optimize``, the scans (each read, assembled
    and uploaded), ``plan.jit``, ``plan.wait``;
  * the spans are profiler annotations too, on the profiler's clock, and
    nothing at all records with no collector active;
  * every step that puts work in the jitted program names it with a
    ``plan.<index>.<op>`` scope.
"""
import os
import re

import jax
import numpy as np
import pytest

from repro import telemetry
from repro.core import local_context
from repro.io import write_dataset
from repro.io.scan import pred
from repro.plan import LazyFrame

VISITS, PAGES = 512, 64


@pytest.fixture(scope="module")
def datasets(tmp_path_factory):
    root = tmp_path_factory.mktemp("query-trace")
    rng = np.random.default_rng(7)
    visits = {"url": rng.integers(0, PAGES, VISITS).astype(np.int32),
              "ip": rng.integers(0, 32, VISITS).astype(np.int32),
              "day": rng.integers(0, 100, VISITS).astype(np.int32),
              "rev": rng.random(VISITS).astype(np.float32)}
    pages = {"url": np.arange(PAGES, dtype=np.int32),
             "rank": rng.random(PAGES).astype(np.float32)}
    for name, cols in (("visits", visits), ("pages", pages)):
        n = len(next(iter(cols.values())))
        write_dataset(str(root / name), [(cols, n)], format="hpt",
                      rows_per_group=n // 4)
    return str(root)


def _query(root, ctx):
    """scan -> filter -> join -> groupby -> topk, the benchmark's shape."""
    visits = LazyFrame.read_parquet(os.path.join(root, "visits"), ctx,
                                    bucket_factor=1.1)
    pages = LazyFrame.read_parquet(os.path.join(root, "pages"), ctx,
                                   bucket_factor=1.1)
    return (visits.filter([pred("day", ">=", 20)])
            .join(pages, ["url"], bucket_factor=1.25)
            .groupby(["ip"], [("rev", "sum"), ("rank", "mean")],
                     out_capacity=64)
            .topk("rev_sum", 1))


def _inside(child, parent):
    return (parent.t0_us <= child.t0_us
            and child.t0_us + child.dur_us <= parent.t0_us + parent.dur_us
            and parent.wall_ns <= child.wall_ns)


def test_one_root_per_query_with_its_phases_in_order(datasets):
    ctx = local_context()
    rec = telemetry.Collector("query")
    with telemetry.using(rec):
        for _ in range(2):
            _query(datasets, ctx).collect(strict=False)
    assert [r.name for r in rec.spans] == ["plan.collect"] * 2
    assert [r.attrs["query"] for r in rec.spans] == [1, 2]
    for root in rec.spans:
        names = [c.name for c in root.children]
        assert names == ["plan.optimize", "io.scan.materialize",
                         "io.scan.materialize", "plan.jit", "plan.wait"]
        scans = [c for c in root.children if c.name == "io.scan.materialize"]
        assert root.attrs["rows_scanned"] == sum(
            r.attrs["rows_scanned"] for s in scans for r in s.children
            if r.name == "io.scan.read") > 0
        for c in root.children:
            assert _inside(c, root), c
        for s in scans:
            kids = [c.name for c in s.children]
            assert kids[-2:] == ["io.scan.assemble", "io.scan.upload"]
            assert set(kids[:-2]) == {"io.scan.read"}
            for c in s.children:
                assert _inside(c, s), c
            assert s.children[-1].attrs["bytes"] > 0
        # children follow one another
        ends = [c.t0_us + c.dur_us for c in root.children]
        starts = [c.t0_us for c in root.children[1:]]
        assert all(e <= s for e, s in zip(ends, starts))


def test_every_activation_opens_the_root(datasets):
    ctx = local_context()
    with telemetry.trace("eager") as rec:
        _query(datasets, ctx).collect(strict=False, jit=False)
    root, = rec.spans
    names = [c.name for c in root.children]
    assert root.name == "plan.collect" and root.attrs["jit"] is False
    assert names[0] == "plan.optimize" and names[-1] == "plan.wait"
    assert "plan.jit" not in names
    # op-by-op, the steps are spans of their own under the root
    assert any(n.startswith("plan.") and n.split(".")[1].isdigit()
               for n in names)
    audited = telemetry.Collector("audited")
    _query(datasets, ctx).collect(strict=False, telemetry=audited)
    assert [r.name for r in audited.spans] == ["plan.collect"]
    assert sum(1 for s in audited.all_spans()
               if s.name == "plan.collect") == 1
    assert audited.audits[-1]["consistent"] is True


def _host_events(trace_dir):
    """``(name, start, end)`` of the trace's host events in ns since the
    profiler session's start, and that start on the wall clock."""
    from jax.profiler import ProfileData

    path = next(os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
                for f in fs if f.endswith(".xplane.pb"))
    planes = list(ProfileData.from_file(path).planes)
    start = next(v for p in planes if p.name == "Task Environment"
                 for k, v in p.stats if k == "profile_start_time")
    events = []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events.extend((ev.name, ev.start_ns,
                               ev.start_ns + ev.duration_ns)
                              for ev in line.events)
    return events, start


def test_spans_are_profiler_annotations_on_its_clock(datasets, tmp_path):
    ctx = local_context()
    lf = _query(datasets, ctx)
    lf.collect(strict=False)                # compiled before the trace
    rec = telemetry.Collector("profiled")
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.using(rec):
            lf.collect(strict=False)
    finally:
        jax.profiler.stop_trace()
    events, start = _host_events(str(tmp_path))
    t0 = min(s for _, s, _ in events)
    t1 = max(e for _, _, e in events)
    for name in ("plan.collect", "io.scan.upload", "plan.jit", "plan.wait"):
        found = [(s, e) for n, s, e in events if n == name]
        spans = [sp for sp in rec.all_spans() if sp.name == name]
        assert len(found) == len(spans) > 0, name
        for (s, e), sp in zip(sorted(found), spans):
            assert t0 <= s <= e <= t1
            # the span's wall-clock stamp lies inside its annotation
            assert s <= sp.wall_ns - start <= e, name


def test_nothing_records_with_no_collector(datasets, tmp_path):
    ctx = local_context()
    lf = _query(datasets, ctx)
    lf.collect(strict=False)
    assert telemetry.current() is None
    jax.profiler.start_trace(str(tmp_path))
    try:
        lf.collect(strict=False)
    finally:
        jax.profiler.stop_trace()
    names = {n for n, _, _ in _host_events(str(tmp_path))[0]}
    ours = {n for n in names if n.startswith(("plan.", "io.scan."))}
    assert ours == set()
    assert telemetry.span("plan.collect") is telemetry.span("io.scan.read")


def test_jitted_program_names_every_step(datasets):
    ctx = local_context()
    plan = _query(datasets, ctx).physical_plan()
    text = jax.jit(plan.fn).lower(*plan.inputs()).as_text(debug_info=True)
    assert {"join", "groupby", "topk"} <= {s.op for s in plan.steps}
    # a scan is read on the host and puts no op in the program
    steps = {f"plan.{s.index}.{s.op}" for s in plan.steps if s.op != "scan"}
    assert set(re.findall(r"plan\.\d+\.[a-z]+", text)) == steps
