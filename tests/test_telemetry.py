"""Telemetry-layer tests (DESIGN.md §12).

Four contracts:

  * **off by default** — no collector active means no spans, no metrics,
    and the shared no-op span object at every instrumentation site.
  * **honest spans** — eager registered-operator calls become spans with
    rows in/out; calls inside a jit trace emit NOTHING (host clocks lie
    there), so instrumentation can never perturb a traced program.
  * **one metrics story** — OverflowReport/ScanStats/spill facts all
    surface under their dotted labels through the active collector, from
    DataFrame, TSet and the planner alike.
  * **plan-vs-observed audit** — ``collect(telemetry=...)`` records
    predicted (planner) == traced (jaxpr) == observed (compiled HLO)
    AllToAll counts; the 4-device subprocess leg asserts all three on
    the representative scan→filter→join→groupby→window chain, with
    payload bytes, and ``explain(analyze=True)`` annotates every
    physical node.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np

from repro import telemetry
from repro.core import local_context, table_ops
from repro.core.dataflow import TSet
from repro.core.report import OverflowReport
from repro.dataframe.frame import DataFrame
from repro.plan import LazyFrame

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _df(ctx, n=64, seed=0, n_keys=8):
    rng = np.random.default_rng(seed)
    return DataFrame.from_dict(
        {"k": rng.integers(0, n_keys, n).astype(np.float32),
         "v": rng.normal(size=n).astype(np.float32)}, ctx,
        bucket_factor=4.0)


# ---------------------------------------------------------------------------
# off by default
# ---------------------------------------------------------------------------
def test_off_by_default_is_one_shared_noop():
    assert telemetry.current() is None
    sp = telemetry.span("anything", tagged=1)
    assert telemetry.span("else") is sp, "off path must reuse ONE object"
    with sp as s:
        s.attrs["x"] = 1
        s.block(None)
    ctx = local_context()
    out = _df(ctx).select(lambda c: c["v"] > 0)
    assert len(out) >= 0
    assert telemetry.current() is None


def test_eager_operator_calls_become_spans_with_rows():
    ctx = local_context()
    df = _df(ctx)
    with telemetry.trace("t") as rec:
        df.groupby(["k"], [("v", "sum")])
    names = [s.name for s in rec.all_spans()]
    assert "table.groupby" in names
    g = next(s for s in rec.all_spans() if s.name == "table.groupby")
    assert g.attrs["rows_in"] == 64
    assert g.attrs["rows_out"] == 8
    assert rec.metrics.counters["table.groupby.calls"] == 1
    assert rec.metrics.counters["table.groupby.rows_in"] == 64
    assert telemetry.current() is None, "trace() must deactivate on exit"


def test_jit_internal_operator_calls_emit_nothing():
    ctx = local_context()
    df = _df(ctx)
    jfn = jax.jit(lambda t: table_ops.shuffle(t, ["k"], ctx=ctx))
    with telemetry.trace("t") as rec:
        jax.block_until_ready(jfn(df.table))
        jax.block_until_ready(jfn(df.table))
    assert not any(s.name.startswith("table.") for s in rec.all_spans()), \
        "operator calls inside a jit trace must not materialize spans"
    assert "table.shuffle.calls" not in rec.metrics.counters


def test_tracing_is_false_eagerly_and_true_under_every_transform():
    def probe(seen):
        def f(x):
            seen.append(telemetry.tracing())
            return x
        return f

    x = np.float32(1.0)
    transforms = {
        "jit": lambda f: jax.jit(f)(x),
        "make_jaxpr": lambda f: jax.make_jaxpr(f)(x),
        "eval_shape": lambda f: jax.eval_shape(f, x),
        "grad": lambda f: jax.grad(f)(x),
        "vmap": lambda f: jax.vmap(f)(np.ones(2, np.float32)),
    }
    assert not telemetry.tracing()
    for name, run in transforms.items():
        seen = []
        run(probe(seen))
        assert seen == [True], name
    assert not telemetry.tracing()


def test_nested_traces_stack():
    with telemetry.trace("outer") as outer:
        with outer.span("a"):
            with telemetry.trace("inner") as inner:
                with telemetry.span("b"):
                    pass
        with telemetry.span("c"):
            pass
    assert [s.name for s in outer.all_spans()] == ["a", "c"]
    assert [s.name for s in inner.all_spans()] == ["b"]


# ---------------------------------------------------------------------------
# the one metrics story: OverflowReport / scan / TSet bridges
# ---------------------------------------------------------------------------
def test_overflow_report_to_metrics_and_gauge_idempotence():
    rep = (OverflowReport().add("join.fanout", 3)
           .add_recovered("spill.join", 7))
    assert rep.to_metrics() == {"overflow.join.fanout": 3,
                                "overflow.recovered.spill.join": 7}
    rec = telemetry.Collector()
    rec.record_overflow(rep)
    rec.record_overflow(rep)  # lineage reports are cumulative → gauges
    assert rec.metrics.gauges["overflow.join.fanout"] == 3
    assert rec.metrics.gauges["overflow.recovered.spill.join"] == 7


def test_scan_overflow_and_stats_reach_collector(tmp_path):
    ctx = local_context()
    data = {"a": np.arange(32, dtype=np.float32),
            "b": np.arange(32, dtype=np.float32)}
    path = str(tmp_path / "tele_ds")
    DataFrame.from_dict(data, ctx).to_hpt(path, rows_per_group=8)
    with telemetry.trace("scan") as rec:
        df = DataFrame.read_parquet(path, ctx, capacity=8, strict=False)
    lost = df.overflow_report.entries["scan.capacity"]
    assert lost > 0
    assert rec.metrics.gauges["overflow.scan.capacity"] == lost
    assert rec.metrics.counters["scan.rows_overflowed"] == lost
    assert rec.metrics.counters["scan.rows_scanned"] > 0
    names = [s.name for s in rec.all_spans()]
    assert "io.scan.materialize" in names
    assert "io.scan.read" in names
    read = next(s for s in rec.all_spans() if s.name == "io.scan.read")
    assert read.attrs["rows_scanned"] > 0


def test_tset_publishes_reports_through_collector():
    ctx = local_context()
    dt = _df(ctx).table
    ts = TSet.from_table(dt, ctx).select(lambda c: c["v"] > 0)
    with telemetry.trace("tset") as rec:
        ts.collect()
        assert any(s.name == "table.select" for s in rec.all_spans())
        # fabricate a lossy lineage: the publish path is the same one
        # collect()/reduce()/quantile() call after _execute
        ts._last_report = OverflowReport().add("window.truncated", 5)
        ts._publish_report()
    assert rec.metrics.gauges["overflow.window.truncated"] == 5


def test_spill_spans_and_gauges():
    from repro.spill import spill_join

    ctx = local_context()
    rng = np.random.default_rng(2)
    n = 4096
    lk = rng.integers(0, n // 4, n).astype(np.int32)
    rk = np.arange(n // 4, dtype=np.int32)
    left = DataFrame.from_dict(
        {"k": lk, "v": lk.astype(np.float32)}, ctx).table
    right = DataFrame.from_dict(
        {"k": rk, "w": rk.astype(np.float32)}, ctx).table
    with telemetry.trace("spill") as rec:
        res = spill_join(left, right, ("k",), ctx=ctx, budget_rows=512)
        rows = sum(int(c.num_rows()) for c in res.chunks())
        res.close()
    assert rows == n
    names = [s.name for s in rec.all_spans()]
    assert "spill.write" in names
    assert "spill.read" in names
    assert "spill.reentry" in names
    re_sp = next(s for s in rec.all_spans() if s.name == "spill.reentry")
    assert re_sp.attrs["op"] == "table.join"
    assert rec.metrics.gauges["spill.bytes_spilled"] > 0
    assert rec.metrics.gauges["spill.rows_in"] == n + n // 4
    assert rec.metrics.gauges["overflow.recovered.spill.join"] > 0


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------
def test_chrome_trace_and_metrics_export(tmp_path):
    with telemetry.trace("export") as rec:
        with rec.span("parent", kind="demo"):
            with rec.span("child"):
                pass
        rec.metrics.count("demo.calls", 2)
        rec.metrics.gauge("demo.level", 7)
    tpath = str(tmp_path / "trace.json")
    telemetry.export_chrome_trace(rec, tpath)
    with open(tpath) as f:
        data = json.load(f)
    evs = data["traceEvents"]
    # ts 0 on the wall clock, to line the trace up with a profiler trace
    assert data["otherData"]["epoch_wall_ns"] == rec.epoch_wall_ns
    spans = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in spans} == {"parent", "child"}
    parent = next(e for e in spans if e["name"] == "parent")
    child = next(e for e in spans if e["name"] == "child")
    assert parent["ts"] <= child["ts"], "child opens inside parent"
    assert parent["args"]["kind"] == "demo"
    # metadata names the process + every used lane (Perfetto grouping)
    meta = [e for e in evs if e["ph"] == "M"]
    assert any(e["name"] == "process_name"
               and e["args"]["name"] == "export" for e in meta)
    tids = {e["tid"] for e in spans}
    named = {e["tid"] for e in meta if e["name"] == "thread_name"}
    assert tids <= named, "every span lane must carry a thread_name"
    # gauges become counter tracks stamped at trace end
    counters = [e for e in evs if e["ph"] == "C"]
    level = next(e for e in counters if e["name"] == "demo.level")
    assert level["args"]["value"] == 7
    assert level["ts"] >= max(e["ts"] + e["dur"] for e in spans)

    snap = telemetry.metrics_snapshot(rec)
    assert snap["metrics"]["counters"]["demo.calls"] == 2
    assert snap["metrics"]["gauges"]["demo.level"] == 7
    assert snap["n_spans"] == 2
    mpath = str(tmp_path / "metrics.json")
    telemetry.export_metrics(rec, mpath)
    with open(mpath) as f:
        assert json.load(f)["metrics"]["counters"]["demo.calls"] == 2


# ---------------------------------------------------------------------------
# explain: determinism + analyze annotations + the audit
# ---------------------------------------------------------------------------
def _chain(ctx):
    big = _df(ctx, n=96, seed=0)
    small = DataFrame.from_dict(
        {"k": np.arange(8, dtype=np.float32),
         "w": 10.0 + np.arange(8, dtype=np.float32)}, ctx,
        bucket_factor=4.0)
    return (big.lazy()
            .join(small.lazy(), ["k"], max_matches=4)
            .groupby(["k"], [("v", "sum"), ("w", "max")])
            .sort_values("k"))


def test_explain_is_byte_identical_across_runs():
    ctx = local_context()
    first = _chain(ctx).explain()
    second = _chain(ctx).explain()
    assert first == second
    # analyze output is measured (times vary) but must not change the
    # deterministic render
    assert _chain(ctx).explain() == first


def test_explain_analyze_annotates_every_node():
    ctx = local_context()
    lf = _chain(ctx)
    plan = lf.physical_plan()
    txt = lf.explain(analyze=True)
    phys = txt.split("== physical plan ==")[1].splitlines()
    for s in plan.steps:
        line = next(ln for ln in phys
                    if ln.strip().startswith(f"{s.index}. "))
        assert "time=" in line, f"step {s.index} missing measured time"
        assert "rows=" in line, f"step {s.index} missing rows"
    assert "audit: predicted=" in txt
    assert "traced=" in txt and "observed=" in txt


def test_collect_with_telemetry_records_consistent_audit():
    ctx = local_context()
    lf = _chain(ctx)
    with telemetry.trace("audit") as rec:
        out = lf.collect(telemetry=rec, jit=False)
    assert out.overflow_report.is_exact()
    audit = rec.audits[-1]
    assert audit["consistent"] is True
    assert (audit["predicted_a2a"] == audit["traced_a2a"]
            == audit["observed_a2a"])
    assert rec.metrics.gauges["plan.predicted_a2a"] == audit["predicted_a2a"]
    # every physical step carries its predicted facts
    plan = lf.physical_plan()
    for s in plan.steps:
        assert rec.plan_steps[s.index]["strategy"] == s.strategy
        assert rec.plan_steps[s.index]["time_us"] > 0
    # the jitted path records the audit too (no per-node spans: only
    # the query's phases)
    with telemetry.trace("audit-jit") as rec2:
        lf.collect(telemetry=rec2, jit=True)
    assert rec2.audits[-1]["consistent"] is True
    assert {s.name for s in rec2.all_spans()
            if s.name.startswith("plan.")} == {
        "plan.collect", "plan.optimize", "plan.jit", "plan.wait"}


# ---------------------------------------------------------------------------
# satellite: importing the perf CLI must not mutate the process
# ---------------------------------------------------------------------------
def test_perf_import_is_side_effect_free():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    r = subprocess.run([sys.executable, "-c", textwrap.dedent("""
        import os
        before = os.environ["XLA_FLAGS"]
        import repro.launch.perf as perf
        assert os.environ["XLA_FLAGS"] == before, os.environ["XLA_FLAGS"]
        from repro.telemetry.audit import top_collectives
        assert perf._top_collectives is top_collectives
        print("PERF-IMPORT-PURE")
        """)], capture_output=True, text=True, timeout=120, env=env)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-2000:]}"
    assert "PERF-IMPORT-PURE" in r.stdout


# ---------------------------------------------------------------------------
# the 4-device contract: predicted == traced == observed, with bytes
# ---------------------------------------------------------------------------
def _run_devices(script: str, n: int = 4, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={n}")
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-4000:]}"
    return r.stdout


def test_telemetry_contract_4way(tmp_path):
    out = _run_devices(f"""
        import numpy as np
        from repro import telemetry
        from repro.core import host_test_context
        from repro.dataframe.frame import DataFrame
        from repro.io.scan import pred
        from repro.plan import LazyFrame

        ctx = host_test_context(n_shards=4)
        rng = np.random.default_rng(0)
        nb = 320
        big = {{"k1": rng.integers(0, 10, nb).astype(np.float32),
               "k2": rng.integers(0, 4, nb).astype(np.float32),
               "v": rng.normal(size=nb).astype(np.float32)}}
        small = {{"k1": np.repeat(np.arange(10), 4).astype(np.float32),
                 "k2": np.tile(np.arange(4), 10).astype(np.float32),
                 "w": rng.normal(size=40).astype(np.float32)}}
        path = {str(tmp_path / 'tele4_ds')!r}
        DataFrame.from_dict(big, ctx, bucket_factor=4.0).to_hpt(
            path, rows_per_group=40)
        sf = DataFrame.from_dict(small, ctx, bucket_factor=4.0)

        # the representative chain: scan -> filter -> join -> groupby
        # -> window (acceptance shape, DESIGN.md §12)
        lf = (LazyFrame.read_parquet(path, ctx, bucket_factor=4.0)
              .filter([pred("k1", "<", 8.0)])
              .join(sf.lazy(), ["k1", "k2"], max_matches=64)
              .groupby(["k2", "k1"], [("v", "sum"), ("w", "max")])
              .window(["k2", "k1"], ["v_sum"]).agg([("v_sum", "sum")]))
        plan = lf.physical_plan()
        with telemetry.trace("contract") as rec:
            # strict cardinality audit rides the representative chain:
            # the distinct-combo bound must keep every q-error under the
            # contract threshold (observed max ~1.25; margin to 2.0)
            out = lf.collect(telemetry=rec, jit=False, qerror_threshold=2.0)
        audit = rec.audits[-1]
        print("AUDIT predicted=%d traced=%d observed=%d" % (
            audit["predicted_a2a"], audit["traced_a2a"],
            audit["observed_a2a"]))
        assert audit["consistent"] is True, audit
        assert audit["predicted_a2a"] > 0, "chain must exchange"
        assert audit["observed_bytes_by_kind"]["all-to-all"] > 0
        assert all(e["bytes"] > 0 for e in audit["exchanges"])

        # every exchanging step got its traced payload bytes; every step
        # got measured time and rows, plus the observatory facts:
        # predicted (est_rows/est_bytes) and observed (qerr/rss delta)
        for s in plan.steps:
            facts = rec.plan_steps[s.index]
            assert facts["time_us"] > 0, (s.index, facts)
            assert facts["rows_out"] is not None
            assert facts["est_rows"] is not None, (s.index, facts)
            assert facts["est_bytes"] > 0, (s.index, facts)
            assert 1.0 <= facts["qerr"] <= 2.0, (s.index, facts)
            assert facts["peak_rss_delta_kb"] >= 0, (s.index, facts)
            if s.a2a:
                assert facts["a2a_bytes"] > 0, (s.index, facts)
        assert rec.metrics.gauges["cardinality.steps_audited"] == len(
            plan.steps)
        assert rec.metrics.gauges["cardinality.max_qerror"] <= 2.0

        txt = lf.explain(analyze=True)
        want = ("audit: predicted=%d traced=%d observed=%d"
                % ((audit["predicted_a2a"],) * 3))
        assert want in txt, txt
        assert txt.count("time=") >= len(plan.steps)
        print("TELEMETRY-CONTRACT-4DEV-OK")
        """)
    assert "TELEMETRY-CONTRACT-4DEV-OK" in out
    assert "AUDIT predicted=2 traced=2 observed=2" in out
