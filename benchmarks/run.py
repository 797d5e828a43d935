"""Benchmark harness — one function per paper table/figure.

  bench_array_ops     → paper Table I   (array collectives)
  bench_table_ops     → paper Tables II/III (relational operators)
  bench_shuffle       → paper Fig 2     (shuffle primitive)
  bench_join_scaling  → paper Fig 16    (Cylon join scaling study)
  bench_join_highdup  → high-duplication join: hash vs sort-merge
                        (fan-out ≈ 8, DESIGN.md §8)
  bench_orderby       → multi-key sample sort (DESIGN.md §9)
  bench_window_rolling→ rolling windows off the range layout vs a
                        gather-then-numpy-sort oracle (DESIGN.md §9)
  bench_topk          → tree-reduced top-k, no global sort
  bench_setop_union   → set-op union on the hash dedup path
  bench_mds           → paper Figs 14/15 (MDS composition pipeline)
  bench_lm_step       → framework: LM train/decode step (tokens/s)
  bench_kernels       → Pallas kernel interpret-mode vs ref overhead
  bench_scan_ingest   → storage scan (DESIGN.md §5): full vs pushdown,
                        native .hpt always, Parquet when pyarrow present
  bench_planned_pipeline → lazy planner (DESIGN.md §11): whole-pipeline
                        scan→filter→groupby, planned vs eager wall time
  bench_spill_join    → out-of-core join beyond budget_rows (DESIGN.md
                        §10): chunk-streamed, exactness- and RSS-gated
  bench_telemetry_overhead → collector on vs off around the 500k
                        shuffle, gated < 2% (DESIGN.md §12)

Methodology: every operator case is jitted ONCE and the compiled function is
timed with a ``block_until_ready`` per iteration — numbers are steady-state
execution, not retrace time.  Prints ``name,us_per_call,derived,peak_rss_mb``
CSV (derived = rows/s, tokens/s, …) and writes ``BENCH_shuffle.json`` next to
this file so the perf trajectory is tracked across PRs.

Wall times are single-host CPU numbers — scaling behaviour at pod size is
covered by the dry-run collective analysis (EXPERIMENTS.md §Roofline).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import DistTable, Table, local_context, table_ops
from repro.core import array_ops

CTX = local_context()
ROWS = []
DEFAULT_JSON = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_shuffle.json")

#: committed peak-RSS cap for the out-of-core spill case (DESIGN.md §10):
#: the bounded-memory promise as a number.  The spill bench joins an input
#: far larger than its budget_rows working set; if its peak RSS climbs past
#: this, the engine stopped being out-of-core and main() exits non-zero.
SPILL_RSS_BUDGET_MB = 4096.0
RSS_VIOLATIONS = []

#: committed ceiling on what the telemetry machinery may add around a
#: jitted operator call (DESIGN.md §12): collector-on vs collector-off
#: on the 500k shuffle, best-of interleaved legs.  Violations fail
#: main() exactly like the RSS budget.
TELEMETRY_OVERHEAD_BUDGET_PCT = 2.0
TELEMETRY_VIOLATIONS = []

#: per-case static collective audits (compiled-HLO counts/bytes +
#: achieved fraction of the ICI roofline), keyed by bench name; rides
#: into the JSON record and the --telemetry-out artifact
TELEMETRY = {}


def _attach_telemetry(name: str, jfn, *args, us: float = None) -> None:
    """Audit one jitted bench case: compiled-HLO collective counts and
    payload bytes, plus — when the wall time is known and was taken on a
    TPU — the achieved exchange bandwidth against that chip's link
    bandwidth (``core.device.peaks``).  A CPU wall time is no device metric,
    so it gets no fraction."""
    from repro.core.device import peaks
    from repro.telemetry import compiled_collectives

    rec = compiled_collectives(jfn, *args)
    entry = {"collectives": rec["counts"],
             "bytes_by_kind": rec["bytes_by_kind"],
             "total_bytes": rec["total_bytes"],
             "ring_cost_s": rec["ring_cost_s"]}
    dev = jax.devices()[0]
    if us and rec["total_bytes"] and dev.platform == "tpu":
        achieved = rec["total_bytes"] / (us * 1e-6)
        entry["achieved_bytes_per_s"] = round(achieved)
        entry["ici_roofline_frac"] = round(
            achieved / peaks(dev.device_kind).ici_bw, 4)
    TELEMETRY[name] = entry


def _peak_rss_mb() -> float:
    """Process peak RSS in MB — VmHWM (resettable) with a rusage fallback."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmHWM:"):
                    return int(line.split()[1]) / 1024.0
    except OSError:
        pass
    try:
        import resource

        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
    except Exception:
        return float("nan")


def _reset_peak_rss() -> None:
    """Reset the kernel's VmHWM watermark so per-case peaks are isolated
    (Linux /proc/self/clear_refs; silently a no-op elsewhere — then VmHWM
    is a process-lifetime high-water mark and per-case numbers only ever
    over-report, never under-report)."""
    try:
        with open("/proc/self/clear_refs", "w") as f:
            f.write("5")
    except OSError:
        pass


def _timeit(fn, *args, warmup: int = 2, iters: int = 5) -> float:
    """µs per call of an already-jitted ``fn``, blocking every iteration."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6  # µs


def _emit(name: str, us: float, derived: str):
    rss = _peak_rss_mb()
    ROWS.append((name, us, derived, rss))
    print(f"{name},{us:.1f},{derived},{rss:.0f}", flush=True)


def _table(n: int, n_keys: int = None, seed: int = 0) -> DistTable:
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, n_keys or max(n // 4, 2), n).astype(np.int32)
    vals = rng.normal(size=n).astype(np.float32)
    return DistTable.from_local(
        Table.from_arrays({"k": jnp.asarray(keys), "v": jnp.asarray(vals)}),
        CTX)


# ---------------------------------------------------------------------------
def bench_array_ops(n: int = 1 << 20):
    """Paper Table I: array collective operators."""
    x = jnp.ones((8, n // 8), jnp.float32)
    flat = jnp.ones((n,), jnp.float32)
    for name, fn, arg in [
        ("allreduce", lambda v: array_ops.allreduce(v, ctx=CTX), x),
        ("allgather", lambda v: array_ops.allgather(v, ctx=CTX), flat),
        ("broadcast", lambda v: array_ops.broadcast(v, ctx=CTX), x),
        ("alltoall", lambda v: array_ops.alltoall(v, ctx=CTX), flat),
        ("reduce_scatter",
         lambda v: array_ops.reduce_scatter(v, ctx=CTX), flat),
    ]:
        jfn = jax.jit(fn)
        us = _timeit(jfn, arg)
        gbps = n * 4 / (us * 1e-6) / 1e9
        _emit(f"tab1_array_{name}", us, f"{gbps:.2f}GB/s")


def bench_table_ops(n: int = 200_000):
    """Paper Tables II/III: relational operators at n rows (pre-jitted)."""
    dt = _table(n)
    dt2 = _table(n, seed=1)

    unary = [
        ("select", jax.jit(lambda t: table_ops.select(
            t, lambda c: c["v"] > 0, ctx=CTX))),
        ("project", jax.jit(lambda t: table_ops.project(t, ["v"], ctx=CTX))),
        ("orderby", jax.jit(lambda t: table_ops.orderby(t, "v", ctx=CTX))),
        ("groupby", jax.jit(lambda t: table_ops.groupby_aggregate(
            t, ["k"], [("v", "sum"), ("v", "mean")], ctx=CTX))),
        ("aggregate", jax.jit(lambda t: table_ops.aggregate(
            t, "v", "sum", ctx=CTX))),
    ]
    binary = [
        ("union", jax.jit(lambda a, b: table_ops.union(a, b, ctx=CTX))),
        ("difference", jax.jit(lambda a, b: table_ops.difference(
            a, b, ctx=CTX))),
        ("intersect", jax.jit(lambda a, b: table_ops.intersect(
            a, b, ctx=CTX))),
    ]
    for name, jfn in unary:
        us = _timeit(jfn, dt)
        _emit(f"tab23_table_{name}", us, f"{n / (us * 1e-6) / 1e6:.1f}Mrow/s")
    for name, jfn in binary:
        us = _timeit(jfn, dt, dt2)
        _emit(f"tab23_table_{name}", us, f"{n / (us * 1e-6) / 1e6:.1f}Mrow/s")


def bench_shuffle(n: int = 500_000):
    """Paper Fig 2: hash shuffle (one packed AllToAll per exchange)."""
    dt = _table(n)
    jfn = jax.jit(lambda t: table_ops.shuffle(t, ["k"], ctx=CTX))
    us = _timeit(jfn, dt)
    _emit("fig2_shuffle", us, f"{n / (us * 1e-6) / 1e6:.1f}Mrow/s")
    _attach_telemetry("fig2_shuffle", jfn, dt, us=us)


def bench_groupby_lowcard(n: int = 200_000, n_keys: int = 1_000):
    """Low-cardinality GroupBy: the map-side-combine / hash-slot regime.

    ``out_capacity`` declares the bounded key cardinality, which selects
    the sort-free hash grouping kernel (and, on multi-shard meshes, the
    shrunken combine exchange) — DESIGN.md §4.
    """
    dt = _table(n, n_keys=n_keys)
    out_cap = 1 << (2 * n_keys - 1).bit_length()
    jfn = jax.jit(lambda t: table_ops.groupby_aggregate(
        t, ["k"], [("v", "sum"), ("v", "mean")], out_capacity=out_cap,
        ctx=CTX))
    us = _timeit(jfn, dt)
    _emit("groupby_lowcard", us, f"{n / (us * 1e-6) / 1e6:.1f}Mrow/s")


def bench_join_then_groupby(n: int = 200_000):
    """Operator chain: join + groupby on the join keys.

    The groupby consumes the join's partitioning metadata, so on meshes the
    chain issues shuffles only for the join inputs (zero for pre-partitioned
    ones) and none for the groupby — jaxpr-asserted in
    tests/test_partitioning.py; here the steady-state wall time is tracked.
    """
    rng = np.random.default_rng(0)
    lk = rng.permutation(n).astype(np.int32)
    rk = rng.permutation(n).astype(np.int32)
    l = DistTable.from_local(Table.from_arrays(
        {"k": jnp.asarray(lk), "a": jnp.asarray(lk, jnp.float32)}), CTX)
    r = DistTable.from_local(Table.from_arrays(
        {"k": jnp.asarray(rk), "b": jnp.asarray(rk, jnp.float32)}), CTX)

    def chain(a, b):
        j, ov1 = table_ops.join(a, b, ["k"], out_capacity=n, ctx=CTX)
        g, ov2 = table_ops.groupby_aggregate(
            j, ["k"], [("a", "sum"), ("b", "mean")], ctx=CTX)
        return g, ov1 + ov2

    jfn = jax.jit(chain)
    us = _timeit(jfn, l, r, iters=3)
    _emit("join_then_groupby", us, f"{n / (us * 1e-6) / 1e6:.2f}Mrow/s")


def bench_join_scaling(sizes=(50_000, 100_000, 200_000, 400_000)):
    """Paper Fig 16: join wall time while load grows (weak scaling proxy:
    rows double, per-row time should stay ~flat).  Runs the default path
    (``method="auto"`` → the sort-free hash build/probe, DESIGN.md §8)."""
    for n in sizes:
        rng = np.random.default_rng(0)
        lk = rng.permutation(n).astype(np.int32)
        rk = rng.permutation(n).astype(np.int32)
        l = DistTable.from_local(Table.from_arrays(
            {"k": jnp.asarray(lk), "a": jnp.asarray(lk, jnp.float32)}), CTX)
        r = DistTable.from_local(Table.from_arrays(
            {"k": jnp.asarray(rk), "b": jnp.asarray(rk, jnp.float32)}), CTX)
        jfn = jax.jit(lambda a, b, n=n: table_ops.join(
            a, b, ["k"], out_capacity=n, ctx=CTX))
        us = _timeit(jfn, l, r, iters=3)
        _emit(f"fig16_join_{n}", us, f"{n / (us * 1e-6) / 1e6:.2f}Mrow/s")


def bench_join_highdup(n: int = 200_000, n_keys: int = 1_000,
                       fanout: int = 8):
    """High-duplication join (fan-out ≈ ``fanout``): sort-merge's worst
    regime, and the case the hash engine's counted two-pass scheme is
    built for (DESIGN.md §8).

    Left: ``n`` rows with keys uniform over ``n_keys``; right: every key
    exactly ``fanout`` times — each left row emits ``fanout`` pairs.  Both
    kernels run on identical inputs; the sort path's probe window is set
    to the duplicate depth it needs to find every match.
    """
    rng = np.random.default_rng(0)
    lk = rng.integers(0, n_keys, n).astype(np.int32)
    rk = np.repeat(np.arange(n_keys, dtype=np.int32), fanout)
    l = DistTable.from_local(Table.from_arrays(
        {"k": jnp.asarray(lk), "a": jnp.asarray(lk, jnp.float32)}), CTX)
    r = DistTable.from_local(Table.from_arrays(
        {"k": jnp.asarray(rk),
         "b": jnp.arange(len(rk), dtype=jnp.float32)}), CTX)
    out_cap = n * fanout
    jhash = jax.jit(lambda a, b: table_ops.join(
        a, b, ["k"], max_matches=fanout, out_capacity=out_cap, ctx=CTX))
    jsort = jax.jit(lambda a, b: table_ops.join(
        a, b, ["k"], max_matches=fanout, window=fanout,
        out_capacity=out_cap, method="sort", ctx=CTX))
    us = _timeit(jhash, l, r, iters=3)
    _emit("join_highdup", us, f"{n / (us * 1e-6) / 1e6:.2f}Mrow/s")
    us_sort = _timeit(jsort, l, r, iters=3)
    _emit("join_highdup_sort", us_sort,
          f"hash_{us_sort / us:.2f}x_faster")


def bench_orderby(n: int = 500_000):
    """Multi-key sample sort (DESIGN.md §9): monotone-lane directional
    keys, splitter AllGather, one packed AllToAll, local lexsort."""
    rng = np.random.default_rng(0)
    dt = DistTable.from_local(Table.from_arrays({
        "g": jnp.asarray(rng.integers(0, 1_000, n).astype(np.int32)),
        "t": jnp.asarray(rng.integers(0, 1 << 20, n).astype(np.int32)),
        "v": jnp.asarray(rng.normal(size=n).astype(np.float32))}), CTX)
    jfn = jax.jit(lambda t: table_ops.orderby(t, ["g", "t"], ctx=CTX))
    us = _timeit(jfn, dt, iters=3)
    _emit("orderby_500k", us, f"{n / (us * 1e-6) / 1e6:.1f}Mrow/s")
    _attach_telemetry("orderby_500k", jfn, dt, us=us)


def bench_window_rolling(n: int = 200_000, n_part: int = 1_000,
                         w: int = 32):
    """Rolling windows off the range layout (DESIGN.md §9) vs the
    numpy-style recompute an un-layouted system pays.

    The subsystem path: the table already carries orderby's range
    metadata (the steady state of an ordered pipeline), so `window`
    evaluates sum+mean+count via the fused blocked scan with zero
    exchanges and zero sorts.  The oracle: gather to host (`to_numpy`),
    np.lexsort by (partition, order), vectorized cumsum-diff rolling —
    the honest fast-numpy recompute.  Acceptance: ≥ 1.5x."""
    rng = np.random.default_rng(0)
    g = rng.integers(0, n_part, n).astype(np.int32)
    t = rng.integers(0, 1 << 20, n).astype(np.int32)
    v = rng.normal(size=n).astype(np.float32)
    dt = DistTable.from_local(Table.from_arrays(
        {"g": jnp.asarray(g), "t": jnp.asarray(t),
         "v": jnp.asarray(v)}), CTX)
    srt, _ = table_ops.orderby(dt, ["g", "t"], ctx=CTX)
    aggs = [("v", "sum"), ("v", "mean"), (None, "count")]
    jfn = jax.jit(lambda d: table_ops.window_aggregate(
        d, ["g"], ["t"], aggs, rows=w, ctx=CTX))
    us = _timeit(jfn, srt, iters=3)
    _emit("window_rolling_200k", us, f"{n / (us * 1e-6) / 1e6:.1f}Mrow/s")

    def oracle():
        cols = srt.to_numpy()  # the gather an un-layouted system pays
        og, ot, ov = cols["g"], cols["t"], cols["v"]
        order = np.lexsort((ot, og))
        sg, sv = og[order], ov[order]
        m = len(sv)
        new_seg = np.r_[True, sg[1:] != sg[:-1]]
        seg_start = np.maximum.accumulate(
            np.where(new_seg, np.arange(m), 0))
        c = np.cumsum(sv)
        a = np.maximum(np.arange(m) - w + 1, seg_start)
        s = c - np.where(a > 0, c[a - 1], 0.0)
        cnt = np.arange(m) - a + 1
        return s, s / cnt, cnt

    us_o = _timeit(oracle, iters=3)
    _emit("window_rolling_200k_oracle", us_o,
          f"window_{us_o / us:.2f}x_faster")


def bench_topk(n: int = 500_000, k: int = 64):
    """Top-k via per-shard candidates + tree-reduce merge — no global
    sort of the 500k rows ever happens off a single shard's lexsort."""
    dt = _table(n)
    jfn = jax.jit(lambda t: table_ops.topk(t, "v", k, ctx=CTX))
    us = _timeit(jfn, dt, iters=3)
    _emit("topk_500k", us, f"{n / (us * 1e-6) / 1e6:.1f}Mrow/s")
    _attach_telemetry("topk_500k", jfn, dt, us=us)


def bench_setop_union(n: int = 200_000):
    """Set-op union at ``n`` rows per side: concat + sort-free hash dedup
    over the carried full-row hashes (DESIGN.md §8)."""
    dt = _table(n)
    dt2 = _table(n, seed=1)
    jfn = jax.jit(lambda a, b: table_ops.union(a, b, ctx=CTX))
    us = _timeit(jfn, dt, dt2, iters=3)
    _emit("setop_union_200k", us, f"{2 * n / (us * 1e-6) / 1e6:.1f}Mrow/s")


def bench_mds():
    """Paper Figs 14/15: table-prep + SMACOF MDS composition."""
    from repro.apps.mds import mds_pipeline
    for n in (64, 128, 256):
        t0 = time.perf_counter()
        path, emb = mds_pipeline(n_points=n, dim=2, iters=20, ctx=CTX)
        dt = (time.perf_counter() - t0) * 1e6
        _emit(f"fig15_mds_{n}pts", dt, f"stress={path[-1]:.3f}")


def bench_lm_step():
    """Framework: LM train + decode step at reduced config (CPU)."""
    from repro.configs import get_config, reduced_config
    from repro.train.optimizer import OptimizerConfig
    from repro.train.train_step import (TrainConfig, init_train_state,
                                        make_train_step)

    for arch in ("smollm-360m", "mixtral-8x7b", "xlstm-125m"):
        cfg = reduced_config(get_config(arch))
        state = init_train_state(jax.random.PRNGKey(0), cfg)
        step = jax.jit(make_train_step(
            cfg, TrainConfig(optimizer=OptimizerConfig())))
        b, s = 4, 128
        rng = jax.random.PRNGKey(1)
        batch = {"tokens": jax.random.randint(rng, (b, s), 0,
                                              cfg.vocab_size)}
        batch["labels"] = batch["tokens"]
        us = _timeit(lambda: step(state, batch)[1]["loss"], iters=3)
        _emit(f"lm_train_step_{arch}", us,
              f"{b * s / (us * 1e-6):.0f}tok/s")


def bench_kernels():
    """jnp reference wall time of the kernels' XLA twins."""
    from repro.kernels.flash_attention import ref as fref
    from repro.kernels.segment_reduce import ref as sref

    q = jnp.ones((1, 4, 256, 64), jnp.float32)
    k = v = jnp.ones((1, 2, 256, 64), jnp.float32)
    us_ref = _timeit(jax.jit(fref.flash_attention), q, k, v)
    _emit("kernel_flash_ref_xla", us_ref, "256x256")

    vals = jnp.ones((1 << 16,), jnp.float32)
    segs = jnp.zeros((1 << 16,), jnp.int32)
    us = _timeit(jax.jit(lambda a, b: sref.segment_reduce(a, b, 512)),
                 vals, segs)
    _emit("kernel_segreduce_ref_xla", us, "65k_rows")


def bench_scan_ingest(n: int = 500_000):
    """Storage-layer ingest (DESIGN.md §5): cold scan of an on-disk
    dataset, full vs projection+predicate pushdown.

    Host I/O + table assembly is the measured path (no jit): this is the
    realistic "data lands on disk, enters the operator world" cost the
    paper's §VI interop argument is about.  The pushdown case projects 2
    of 6 columns and prunes ~2/3 of the fragments via min/max stats.
    """
    import shutil
    import sys
    import tempfile

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "..", "scripts"))
    from make_dataset import make_events_dataset

    from repro.io import ScanSource, has_pyarrow, pred

    fmts = ["hpt"] + (["parquet"] if has_pyarrow() else [])
    for fmt in fmts:
        root = tempfile.mkdtemp(prefix=f"hptmt_bench_{fmt}_")
        try:
            make_events_dataset(root, n_rows=n, fmt=fmt,
                                rows_per_group=max(n // 16, 1))
            events = os.path.join(root, "events")

            def full_scan():
                src = ScanSource(events, ctx=CTX)
                return src.to_dist_table()[0].counts

            def pushdown_scan():
                src = ScanSource(events, ctx=CTX,
                                 columns=["user_id", "value"],
                                 predicate=pred("day", "<", 10))
                return src.to_dist_table()[0].counts

            us = _timeit(full_scan, iters=3)
            _emit(f"ingest_scan_{fmt}", us,
                  f"{n / (us * 1e-6) / 1e6:.1f}Mrow/s")
            us = _timeit(pushdown_scan, iters=3)
            _emit(f"ingest_scan_{fmt}_pushdown", us,
                  f"{n / (us * 1e-6) / 1e6:.1f}Mrow/s")
        finally:
            shutil.rmtree(root, ignore_errors=True)



def bench_planned_pipeline(n: int = 500_000):
    """Planned vs eager pipeline (DESIGN.md §11): scan → filter → groupby.

    Both cases run the same user chain over the same on-disk events
    dataset.  The eager API executes each call as issued — a full-width
    scan of every fragment, then the filter, then the groupby exchange.
    The lazy API plans the whole pipeline first: the day-range predicate
    lands in the scan (fragment pruning via manifest min/max + residual
    mask), the scan reads only the 3 of 6 columns the pipeline touches,
    and the groupby runs on what is left.  End-to-end host wall time
    (I/O included, no jit of the I/O path) — the planner's win is the
    work it never does.  Acceptance: planned ≥ 1.3x, recorded in the
    derived field; wall time rides the regression gate like every case.
    """
    import shutil
    import sys as _sys
    import tempfile

    _sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "scripts"))
    from make_dataset import make_events_dataset

    from repro.dataframe.frame import DataFrame
    from repro.io import pred
    from repro.plan import LazyFrame

    root = tempfile.mkdtemp(prefix="hptmt_bench_plan_")
    try:
        make_events_dataset(root, n_rows=n, fmt="hpt",
                            rows_per_group=max(n // 16, 1))
        events = os.path.join(root, "events")
        aggs = [("value", "sum"), ("value", "count")]

        def eager():
            df = DataFrame.read_parquet(events, CTX)
            return (df.select(lambda c: c["day"] < 10)
                    .groupby(["user_id"], aggs).table.counts)

        def planned():
            return (LazyFrame.read_parquet(events, CTX)
                    .filter([pred("day", "<", 10)])
                    .groupby(["user_id"], aggs)
                    .collect(jit=False).table.counts)

        us_p = _timeit(planned, iters=3)
        _emit("planned_pipeline", us_p,
              f"{n / (us_p * 1e-6) / 1e6:.1f}Mrow/s")
        us_e = _timeit(eager, iters=3)
        _emit("planned_pipeline_eager", us_e,
              f"planned_{us_e / us_p:.2f}x_faster")
    finally:
        shutil.rmtree(root, ignore_errors=True)


def bench_spill_join(n: int = 2_000_000, budget_rows: int = 262_144):
    """Out-of-core join: input far beyond the committed per-step budget.

    The acceptance case for DESIGN.md §10: an ``n``-row probe side joined
    at a ``budget_rows`` working-set cap — the spill engine must complete
    it exactly (row count cross-checked against a numpy membership oracle,
    zero residual overflow) while peak RSS stays under the committed
    ``SPILL_RSS_BUDGET_MB``.  The result is consumed chunk-wise, never
    materialized whole.  Wall time rides the regression gate like every
    other case; the RSS cap failure is collected in ``RSS_VIOLATIONS``
    and fails the run at the end of main().
    """
    from repro.spill import spill_join

    rng = np.random.default_rng(3)
    n_keys = n // 4
    lk = rng.integers(0, n_keys, n).astype(np.int32)
    rk = rng.permutation(n_keys)[: n_keys // 2].astype(np.int32)  # unique
    left = DistTable.from_local(Table.from_arrays(
        {"k": jnp.asarray(lk), "v": jnp.asarray(lk, jnp.float32)}), CTX)
    right = DistTable.from_local(Table.from_arrays(
        {"k": jnp.asarray(rk), "w": jnp.asarray(rk, jnp.float32)}), CTX)
    expected = int(np.isin(lk, rk).sum())  # right keys unique: 1 match/row

    _reset_peak_rss()
    t0 = time.perf_counter()
    res = spill_join(left, right, ("k",), ctx=CTX, budget_rows=budget_rows)
    rows_out = 0
    for chunk in res.chunks():  # chunk-wise consumption, bounded memory
        rows_out += int(chunk.num_rows())
    report, stats = res.report, res.stats
    res.close()
    us = (time.perf_counter() - t0) * 1e6
    peak = _peak_rss_mb()

    name = f"spill_join_{n // 1000}k_budget{budget_rows // 1024}k"
    assert report.is_exact(), f"residual overflow: {report}"
    assert rows_out == expected, (rows_out, expected)
    _emit(name, us, f"{n / (us * 1e-6) / 1e6:.1f}Mrow/s "
                    f"parts={stats.n_parts} "
                    f"spilled={stats.bytes_spilled >> 20}MB")
    if peak > SPILL_RSS_BUDGET_MB:
        RSS_VIOLATIONS.append((name, peak))
        print(f"# RSS VIOLATION: {name} peaked at {peak:.0f}MB "
              f"> committed {SPILL_RSS_BUDGET_MB:.0f}MB budget", flush=True)


def bench_telemetry_overhead(n: int = 500_000, rounds: int = 15):
    """Telemetry overhead contract (DESIGN.md §12): collector on vs off.

    Both legs run the identical pre-jitted 500k shuffle, blocking every
    call; the ON leg additionally activates a collector and wraps each
    call in a span (open, ``block_until_ready``, close — everything the
    instrumentation adds around a jit boundary).  Legs are interleaved
    and compared best-of-``rounds`` so runner noise cancels instead of
    deciding the gate; a trip re-measures once at double rounds before
    counting (a genuine per-span cost reproduces; a one-off scheduler /
    page-cache spike right after the spill bench does not).  The ratio
    must stay under ``TELEMETRY_OVERHEAD_BUDGET_PCT`` or main() exits
    non-zero.
    """
    from repro import telemetry

    dt = _table(n)
    jfn = jax.jit(lambda t: table_ops.shuffle(t, ["k"], ctx=CTX))
    for _ in range(3):
        jax.block_until_ready(jfn(dt))

    def leg_off() -> float:
        t0 = time.perf_counter()
        jax.block_until_ready(jfn(dt))
        return time.perf_counter() - t0

    def leg_on() -> float:
        with telemetry.trace("bench-overhead") as rec:
            t0 = time.perf_counter()
            with rec.span("bench.shuffle") as sp:
                sp.block(jfn(dt))
            return time.perf_counter() - t0

    def measure(k: int):
        offs, ons = [], []
        for _ in range(k):
            offs.append(leg_off())
            ons.append(leg_on())
        return min(offs), min(ons)

    best_off, best_on = measure(rounds)
    overhead = best_on / best_off - 1.0
    if overhead * 100 > TELEMETRY_OVERHEAD_BUDGET_PCT:
        off2, on2 = measure(rounds * 2)
        best_off, best_on = min(best_off, off2), min(best_on, on2)
        overhead = best_on / best_off - 1.0
    name = "telemetry_overhead_500k"
    _emit(name, best_off * 1e6, f"overhead_{overhead * 100:.2f}pct")
    if overhead * 100 > TELEMETRY_OVERHEAD_BUDGET_PCT:
        TELEMETRY_VIOLATIONS.append((name, overhead * 100))
        print(f"# TELEMETRY OVERHEAD VIOLATION: {name} on/off = "
              f"{overhead:+.2%} > {TELEMETRY_OVERHEAD_BUDGET_PCT:.0f}% "
              f"budget", flush=True)


def write_ledger(path: str) -> None:
    """Append each case's record to the cross-run JSONL ledger
    (``scripts/perf_report.py`` renders the per-fingerprint deltas)."""
    from repro.telemetry import ledger

    for name, us, derived, rss in ROWS:
        rec = ledger.bench_record(
            name, us, derived=derived,
            peak_rss_mb=None if rss != rss else round(rss, 1),
            telemetry=TELEMETRY.get(name))
        ledger.append(path, rec)
    print(f"# appended {len(ROWS)} record(s) to {path}", flush=True)


def write_telemetry(path: str) -> None:
    """The per-bench collective audits as one JSON artifact (CI uploads
    this next to the perf record)."""
    with open(path, "w") as f:
        json.dump(TELEMETRY, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"# wrote {path}", flush=True)


def write_json(path: str, merge: bool = False) -> None:
    """Machine-readable perf record (name → µs + derived metric).

    ``merge=True`` updates only the cases that ran into an existing file
    (the ``--spill-only`` job must not clobber the committed baseline's
    other entries)."""
    data = {}
    if merge and os.path.exists(path):
        with open(path) as f:
            data = json.load(f)
    for name, us, derived, rss in ROWS:
        rec = {"us_per_call": round(us, 1), "derived": derived,
               "peak_rss_mb": round(rss, 1)}
        if name in TELEMETRY:
            rec["telemetry"] = TELEMETRY[name]
        data[name] = rec
    with open(path, "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"# wrote {path}", flush=True)


def compare_json(base: dict, baseline_name: str, threshold: float,
                 min_delta_us: float = 1000.0) -> int:
    """Regression gate: fail when any case slows >threshold vs baseline.

    ``base`` is the PRELOADED baseline record — callers read it before any
    ``write_json`` so that ``--compare X --out X`` (or the default ``--out``
    pointing at the committed baseline) can never compare a run against
    its own freshly-written copy.

    Only cases present in both the fresh run and the committed baseline are
    compared (quick-mode runs a subset at smaller sizes, so a quick number
    beating a full-size baseline is expected; what the gate catches is the
    catastrophic class — retrace-per-call, lost fusion, accidental
    quadratic paths — which blow far past the margin in either mode).
    A slowdown must exceed the relative threshold AND ``min_delta_us`` of
    absolute regression: overhead-dominated microsecond cases (project,
    scalar aggregate) jitter past 30% from dispatch noise alone on slower
    runners, while every real regression class costs milliseconds.
    Returns the number of regressions; prints a per-case delta table.
    """
    regressions = []
    print(f"# compare vs {baseline_name} "
          f"(fail > {threshold:+.0%} and > {min_delta_us:.0f}us)")
    for name, us, *_ in ROWS:
        if name not in base:
            print(f"# {name}: no baseline, skipped")
            continue
        ref = base[name]["us_per_call"]
        delta = us / ref - 1.0
        regressed = delta > threshold and us - ref > min_delta_us
        flag = " REGRESSION" if regressed else ""
        print(f"# {name}: {us:.1f}us vs {ref:.1f}us ({delta:+.1%}){flag}")
        if regressed:
            regressions.append(name)
    if regressions:
        print(f"# FAILED: {len(regressions)} case(s) regressed "
              f">{threshold:.0%}: {', '.join(regressions)}")
    else:
        print("# regression gate passed")
    return len(regressions)


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--quick", action="store_true",
                   help="small sizes, shuffle-relevant benches only (CI)")
    p.add_argument("--out", default=DEFAULT_JSON,
                   help="path for the JSON perf record")
    p.add_argument("--compare", metavar="BASELINE.json",
                   help="fail when any case regresses vs this record")
    p.add_argument("--threshold", type=float, default=0.30,
                   help="relative slowdown tolerated by --compare")
    p.add_argument("--min-delta-us", type=float, default=1000.0,
                   help="absolute slowdown (us) below which --compare "
                        "treats a relative regression as noise")
    p.add_argument("--spill-only", action="store_true",
                   help="run only the memory-capped out-of-core spill "
                        "case at full size (the CI spill job)")
    p.add_argument("--telemetry-out", metavar="TELEMETRY.json",
                   help="also write the per-bench collective audits "
                        "(compiled-HLO counts/bytes, roofline fraction) "
                        "as a standalone JSON artifact")
    p.add_argument("--ledger", metavar="LEDGER.jsonl",
                   help="append one run-history record per case to this "
                        "JSONL ledger (keyed bench:<case>) for "
                        "scripts/perf_report.py cross-run deltas")
    p.add_argument("--compare-files", nargs=2, metavar=("FRESH", "BASELINE"),
                   help="compare two existing records (no benches run): "
                        "the like-for-like gate — both sides same sizes, "
                        "same machine (CI runs the PR base for BASELINE)")
    args = p.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.compare_files:
        fresh_path, baseline_path = args.compare_files
        with open(fresh_path) as f:
            for name, rec in json.load(f).items():
                ROWS.append((name, rec["us_per_call"], rec["derived"],
                             rec.get("peak_rss_mb", float("nan"))))
        with open(baseline_path) as f:
            base = json.load(f)
        if compare_json(base, baseline_path, args.threshold,
                        args.min_delta_us):
            raise SystemExit(1)
        return

    # read the baseline BEFORE running/writing anything: with the default
    # --out both paths may name the committed baseline, and comparing a
    # run against its own fresh copy would make the gate vacuous
    base = None
    if args.compare:
        with open(args.compare) as f:
            base = json.load(f)

    print("name,us_per_call,derived,peak_rss_mb")
    if args.spill_only:
        bench_spill_join()
        write_json(args.out, merge=True)
        if args.ledger:
            write_ledger(args.ledger)
        if RSS_VIOLATIONS:
            print(f"# FAILED: peak RSS over the {SPILL_RSS_BUDGET_MB:.0f}MB "
                  "budget: " + ", ".join(f"{n}={p:.0f}MB"
                                         for n, p in RSS_VIOLATIONS))
            raise SystemExit(1)
        return
    if args.quick:
        bench_table_ops(n=20_000)
        bench_shuffle(n=50_000)
        bench_groupby_lowcard(n=20_000, n_keys=200)
        bench_join_then_groupby(n=20_000)
        bench_join_scaling(sizes=(20_000, 40_000))
        bench_join_highdup(n=20_000, n_keys=200)
        bench_orderby(n=50_000)
        bench_window_rolling(n=20_000, n_part=200)
        bench_topk(n=50_000)
        bench_setop_union(n=20_000)
        bench_scan_ingest(n=50_000)
        bench_planned_pipeline(n=50_000)
        bench_spill_join(n=400_000, budget_rows=65_536)
        bench_telemetry_overhead()  # full 500k: the committed contract
    else:
        bench_array_ops()
        bench_table_ops()
        bench_shuffle()
        bench_groupby_lowcard()
        bench_join_then_groupby()
        bench_join_scaling()
        bench_join_highdup()
        bench_orderby()
        bench_window_rolling()
        bench_topk()
        bench_setop_union()
        bench_mds()
        bench_lm_step()
        bench_kernels()
        bench_scan_ingest()
        bench_planned_pipeline()
        bench_spill_join()
        bench_telemetry_overhead()
    write_json(args.out)
    if args.telemetry_out:
        write_telemetry(args.telemetry_out)
    if args.ledger:
        write_ledger(args.ledger)
    print(f"# {len(ROWS)} benchmarks complete")
    failures = 0
    if base is not None:
        failures += compare_json(base, args.compare, args.threshold,
                                 args.min_delta_us)
    if RSS_VIOLATIONS:
        print(f"# FAILED: {len(RSS_VIOLATIONS)} case(s) over the "
              f"{SPILL_RSS_BUDGET_MB:.0f}MB RSS budget: "
              + ", ".join(f"{n}={p:.0f}MB" for n, p in RSS_VIOLATIONS))
        failures += len(RSS_VIOLATIONS)
    if TELEMETRY_VIOLATIONS:
        print(f"# FAILED: {len(TELEMETRY_VIOLATIONS)} case(s) over the "
              f"{TELEMETRY_OVERHEAD_BUDGET_PCT:.0f}% telemetry overhead "
              "budget: " + ", ".join(f"{n}={p:+.2f}%"
                                     for n, p in TELEMETRY_VIOLATIONS))
        failures += len(TELEMETRY_VIOLATIONS)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
