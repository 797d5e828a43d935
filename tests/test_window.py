"""Ordered-analytics subsystem (DESIGN.md §9): multi-key orderby, range
partitioning metadata, windowed aggregation, rank/top-k/quantile.

Four layers of guarantees:

  * parity — every ordered operator against a numpy oracle, including
    duplicate keys, NaN keys, descending directions, and windows larger
    than their partition;
  * the NaN-last contract — NaNs are one deterministic block at the END
    of the sort in BOTH directions (the old ``-x`` negation flipped them
    to the front under descending);
  * kernel fidelity — the Pallas windowed scan in interpret mode is
    bit-identical to the jnp reference;
  * elision — orderby produces range metadata, window/rank/quantile
    consume it, and the traced jaxpr of the chain really contains the
    promised AllToAll/sort counts (4-device subprocess leg).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # tier-1 env may lack hypothesis: skip only @given tests
    from conftest import given, settings, st

from repro.core import (DistTable, Table, local_context, partitioning_kind,
                        range_partitioning, table_ops)
from repro.core.dataflow import TSet
from repro.dataframe.frame import DataFrame

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
RNG = np.random.default_rng(23)
CTX = local_context()


def make_dt(d, capacity=None):
    t = Table.from_arrays({k: jnp.asarray(v) for k, v in d.items()},
                          capacity=capacity)
    return DistTable.from_local(t, CTX)


# ---------------------------------------------------------------------------
# numpy oracle for the ordering contract (monotone lanes, NaN-last)
# ---------------------------------------------------------------------------
def np_lane(col, asc=True):
    """The DESIGN.md §9 monotone-u32 transform, in numpy."""
    a = np.asarray(col)
    if a.dtype.kind == "f":
        b = a.astype(np.float32).view(np.uint32)
        m = np.where(b >> 31 != 0, ~b, b | np.uint32(0x80000000))
        if not asc:
            m = ~m
        return np.where(np.isnan(a), np.uint32(0xFFFFFFFF), m)
    if a.dtype.kind == "b" or a.dtype.kind == "u":
        m = a.astype(np.uint32)
    else:
        m = a.astype(np.int32).view(np.uint32) ^ np.uint32(0x80000000)
    return m if asc else ~m


def np_order(cols, ascending):
    """Oracle sort permutation: stable lexsort of the monotone lanes."""
    lanes = [np_lane(c, a) for c, a in zip(cols, ascending)]
    return np.lexsort(tuple(lanes[::-1][i] for i in range(len(lanes))))


def np_groups(cols):
    """Partition ids under the ordering identity (NaNs one group)."""
    lanes = np.stack([np_lane(c, True) for c in cols], axis=1) \
        if cols else np.zeros((len(cols[0]) if cols else 0, 0), np.uint32)
    _, ids = np.unique(lanes, axis=0, return_inverse=True)
    return ids


# ---------------------------------------------------------------------------
# multi-key orderby
# ---------------------------------------------------------------------------
def test_orderby_multikey_vs_numpy():
    n = 300
    g = RNG.integers(-5, 5, n).astype(np.int32)
    x = RNG.normal(size=n).astype(np.float32)
    dt = make_dt({"g": g, "x": x})
    for asc in ((True, True), (False, True), (True, False), (False, False)):
        out, ov = table_ops.orderby(dt, ["g", "x"], ascending=list(asc),
                                    ctx=CTX)
        assert int(ov) == 0
        got = out.to_numpy()
        order = np_order([g, x], asc)
        np.testing.assert_array_equal(got["g"], g[order], err_msg=str(asc))
        np.testing.assert_array_equal(got["x"], x[order], err_msg=str(asc))
        assert out.partitioning == range_partitioning(("g", "x"), asc, 1)
    # full-row multiset is preserved
    srt, _ = table_ops.orderby(dt, ["g", "x"], ctx=CTX)
    got = srt.to_numpy()
    assert sorted(zip(got["g"].tolist(), got["x"].tolist())) == \
        sorted(zip(g.tolist(), x.tolist()))


def test_orderby_nan_last_both_directions():
    """The satellite fix: descending float sorts keep NaNs LAST (the seed
    ``_negate`` flipped them to the front)."""
    x = np.array([3.0, np.nan, -1.0, np.nan, 2.0, -np.inf, np.inf, -0.0,
                  0.0], np.float32)
    dt = make_dt({"x": x})
    nn = (~np.isnan(x)).sum()
    for asc in (True, False):
        out, ov = table_ops.orderby(dt, "x", ascending=asc, ctx=CTX)
        assert int(ov) == 0
        got = out.to_numpy()["x"]
        assert np.all(np.isnan(got[nn:])), (asc, got)
        assert not np.any(np.isnan(got[:nn])), (asc, got)
        exp = np.sort(x[~np.isnan(x)])
        np.testing.assert_allclose(got[:nn], exp if asc else exp[::-1])
    # the total order separates -0.0 / +0.0 deterministically
    asc_got = table_ops.orderby(dt, "x", ctx=CTX)[0].to_numpy()["x"]
    signs = np.signbit(asc_got[np.where(asc_got[:nn] == 0.0)[0]])
    np.testing.assert_array_equal(signs, [True, False])


@settings(max_examples=30, deadline=None)
@given(vals=st.lists(st.one_of(st.floats(-100, 100, width=32),
                               st.just(float("nan"))),
                     min_size=1, max_size=48),
       keys=st.lists(st.integers(0, 5), min_size=1, max_size=48),
       asc_k=st.booleans(), asc_v=st.booleans())
def test_orderby_property(vals, keys, asc_k, asc_v):
    n = min(len(vals), len(keys))
    k = np.array(keys[:n], np.int32)
    v = np.array(vals[:n], np.float32)
    dt = make_dt({"k": k, "v": v})
    out, ov = table_ops.orderby(dt, ["k", "v"], ascending=[asc_k, asc_v],
                                ctx=CTX)
    assert int(ov) == 0
    got = out.to_numpy()
    order = np_order([k, v], (asc_k, asc_v))
    np.testing.assert_array_equal(got["k"], k[order])
    np.testing.assert_array_equal(
        np.isnan(got["v"]), np.isnan(v[order]))
    np.testing.assert_array_equal(
        np.nan_to_num(got["v"]), np.nan_to_num(v[order]))


# ---------------------------------------------------------------------------
# windowed aggregation vs a brute-force numpy oracle
# ---------------------------------------------------------------------------
def np_window_oracle(g_cols, o_cols, v, rows):
    """Brute-force rolling/cumulative windows, ranks, lag/lead."""
    n = len(v)
    order = np_order(list(g_cols) + list(o_cols),
                     (True,) * (len(g_cols) + len(o_cols)))
    gid = np_groups([c[order] for c in g_cols]) if g_cols else \
        np.zeros(n, np.int64)
    rid = np_groups([c[order] for c in list(g_cols) + list(o_cols)])
    sv = v[order]
    out = {k: np.zeros(n) for k in ("sum", "mean", "count", "min", "max",
                                    "row_number", "rank", "lag", "lead")}
    for i in range(n):
        s0 = i
        while s0 > 0 and gid[s0 - 1] == gid[i]:
            s0 -= 1
        a = s0 if rows is None else max(i - rows + 1, s0)
        win = sv[a:i + 1]
        out["sum"][i] = win.sum()
        out["mean"][i] = win.mean()
        out["count"][i] = i - a + 1
        out["min"][i] = win.min()
        out["max"][i] = win.max()
        out["row_number"][i] = i - s0 + 1
        r0 = i
        while r0 > 0 and rid[r0 - 1] == rid[i]:
            r0 -= 1
        out["rank"][i] = r0 - s0 + 1
        out["lag"][i] = sv[i - 1] if i - 1 >= s0 else 0.0
        seg_end = i
        while seg_end + 1 < n and gid[seg_end + 1] == gid[i]:
            seg_end += 1
        out["lead"][i] = sv[i + 1] if i + 1 <= seg_end else 0.0
    return order, out


AGGS = [("v", "sum"), ("v", "mean"), (None, "count"), ("v", "min"),
        ("v", "max"), (None, "row_number"), (None, "rank"), ("v", "lag"),
        ("v", "lead")]
LABELS = {"v_sum": "sum", "v_mean": "mean", "count": "count",
          "v_min": "min", "v_max": "max", "row_number": "row_number",
          "rank": "rank", "v_lag": "lag", "v_lead": "lead"}


def check_window(g, t, v, rows):
    dt = make_dt({"g": g, "t": t, "v": v})
    out, ov = table_ops.window_aggregate(dt, ["g"], ["t"], AGGS, rows=rows,
                                         ctx=CTX)
    assert int(ov) == 0
    got = out.to_numpy()
    _, exp = np_window_oracle([g], [t], v, rows)
    for lbl, key in LABELS.items():
        np.testing.assert_allclose(got[lbl], exp[key], rtol=1e-4, atol=1e-4,
                                   err_msg=f"rows={rows} {lbl}")


def test_window_rolling_and_cumulative_vs_numpy():
    n = 257
    g = RNG.integers(0, 6, n).astype(np.int32)
    t = RNG.integers(0, 30, n).astype(np.int32)  # duplicate order keys
    v = RNG.normal(size=n).astype(np.float32)
    for rows in (1, 4, 32, None):
        check_window(g, t, v, rows)


def test_window_larger_than_partition_and_nan_keys():
    # windows clip at partition starts; NaN partition keys form ONE
    # partition (the ordering identity, DESIGN.md §9)
    n = 80
    g = RNG.normal(size=n).astype(np.float32)
    g[RNG.random(n) < 0.3] = np.nan
    g[RNG.random(n) < 0.3] = 1.5  # duplicates
    t = RNG.integers(0, 9, n).astype(np.int32)
    v = RNG.normal(size=n).astype(np.float32)
    check_window(g, t, v, rows=50)
    check_window(g, t, v, rows=None)


def test_window_multi_partition_and_order_keys():
    n = 120
    g1 = RNG.integers(0, 3, n).astype(np.int32)
    g2 = RNG.integers(0, 3, n).astype(np.int32)
    t = RNG.integers(0, 8, n).astype(np.int32)
    v = RNG.normal(size=n).astype(np.float32)
    dt = make_dt({"a": g1, "b": g2, "t": t, "v": v})
    out, ov = table_ops.window_aggregate(
        dt, ["a", "b"], ["t"], [("v", "sum"), (None, "rank")], rows=5,
        ctx=CTX)
    assert int(ov) == 0
    got = out.to_numpy()
    order, exp = np_window_oracle([g1, g2], [t], v, 5)
    np.testing.assert_allclose(got["v_sum"], exp["sum"], rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_array_equal(got["rank"], exp["rank"])


def test_window_lag_lead_offsets():
    n = 64
    g = RNG.integers(0, 4, n).astype(np.int32)
    t = np.arange(n, dtype=np.int32)
    v = RNG.normal(size=n).astype(np.float32)
    dt = make_dt({"g": g, "t": t, "v": v})
    out, ov = table_ops.window_aggregate(
        dt, ["g"], ["t"], [("v", "lag", 3), ("v", "lead", 2)], rows=4,
        ctx=CTX)
    assert int(ov) == 0
    got = out.to_numpy()
    order = np_order([g, t], (True, True))
    sg, sv = g[order], v[order]
    for i in range(n):
        s0 = i
        while s0 > 0 and sg[s0 - 1] == sg[i]:
            s0 -= 1
        exp_lag = sv[i - 3] if i - 3 >= s0 else 0.0
        in_seg = i + 2 < n and np.all(sg[i:i + 3] == sg[i])
        exp_lead = sv[i + 2] if in_seg else 0.0
        np.testing.assert_allclose(got["v_lag3"][i], exp_lag, rtol=1e-6)
        np.testing.assert_allclose(got["v_lead2"][i], exp_lead, rtol=1e-6)


@settings(max_examples=25, deadline=None)
@given(keys=st.lists(st.integers(0, 4), min_size=1, max_size=40),
       vals=st.lists(st.floats(-50, 50, width=32), min_size=1, max_size=40),
       rows=st.one_of(st.none(), st.integers(1, 8)))
def test_window_property(keys, vals, rows):
    n = min(len(keys), len(vals))
    g = np.array(keys[:n], np.int32)
    t = np.arange(n, dtype=np.int32)
    v = np.array(vals[:n], np.float32)
    dt = make_dt({"g": g, "t": t, "v": v})
    out, ov = table_ops.window_aggregate(
        dt, ["g"], ["t"], [("v", "sum"), (None, "count"), (None, "rank")],
        rows=rows, ctx=CTX)
    assert int(ov) == 0
    got = out.to_numpy()
    _, exp = np_window_oracle([g], [t], v, rows)
    np.testing.assert_allclose(got["v_sum"], exp["sum"], rtol=1e-3,
                               atol=1e-3)
    np.testing.assert_array_equal(got["count"], exp["count"])
    np.testing.assert_array_equal(got["rank"], exp["rank"])


# ---------------------------------------------------------------------------
# Pallas windowed scan: interpret mode is bit-identical to the reference
# ---------------------------------------------------------------------------
def test_windowed_scan_pallas_bit_equality():
    from repro.kernels.window_scan import kernel as wk, ops as wops

    n = 1111
    vals = jnp.asarray(RNG.normal(size=(n, 3)).astype(np.float32))
    flags = np.zeros(n, bool)
    flags[0] = True
    flags[np.sort(RNG.choice(np.arange(1, n), 40, replace=False))] = True
    seg = jnp.asarray(np.maximum.accumulate(
        np.where(flags, np.arange(n), 0)).astype(np.int32))
    for w in (1, 7, 64, 512, 1500):
        for op in ("sum", "min", "max"):
            ref = wops.windowed_scan(vals, seg, w, op)
            pal = wk.windowed_scan_pallas(vals, seg, w, op, interpret=True)
            np.testing.assert_array_equal(np.asarray(ref), np.asarray(pal),
                                          err_msg=f"w={w} op={op}")


def test_windowed_scan_semantics_vs_bruteforce():
    from repro.kernels.window_scan import ops as wops

    n, w = 203, 9
    vals = RNG.normal(size=(n, 1)).astype(np.float32)
    flags = np.zeros(n, bool)
    flags[0] = True
    flags[np.sort(RNG.choice(np.arange(1, n), 11, replace=False))] = True
    seg = np.maximum.accumulate(np.where(flags, np.arange(n), 0))
    got = np.asarray(wops.windowed_scan(
        jnp.asarray(vals), jnp.asarray(seg, np.int32), w, "sum"))[:, 0]
    exp = np.array([vals[max(i - w + 1, seg[i]):i + 1, 0].sum()
                    for i in range(n)])
    np.testing.assert_allclose(got, exp, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# top-k and quantile
# ---------------------------------------------------------------------------
def test_topk_vs_numpy():
    n = 500
    v = RNG.normal(size=n).astype(np.float32)
    g = RNG.integers(0, 50, n).astype(np.int32)  # duplicates
    dt = make_dt({"g": g, "v": v})
    top = table_ops.topk(dt, "v", 12, ctx=CTX)
    np.testing.assert_allclose(top.to_numpy()["v"],
                               np.sort(v)[::-1][:12], rtol=1e-6)
    assert partitioning_kind(top.partitioning) == "range"
    # smallest-k via largest=False; multi-key with duplicate primaries
    bot = table_ops.topk(dt, ["g", "v"], 7, largest=False, ctx=CTX)
    got = bot.to_numpy()
    order = np_order([g, v], (True, True))
    np.testing.assert_array_equal(got["g"], g[order][:7])
    np.testing.assert_allclose(got["v"], v[order][:7], rtol=1e-6)
    # k beyond the row count returns everything
    small = make_dt({"v": np.array([3., 1., 2.], np.float32)})
    allk = table_ops.topk(small, "v", 64, ctx=CTX)
    np.testing.assert_allclose(np.sort(allk.to_numpy()["v"]), [1., 2., 3.])


def test_quantile_exact_and_approx():
    n = 4000
    v = RNG.normal(size=n).astype(np.float32)
    v[RNG.random(n) < 0.05] = np.nan
    dt = make_dt({"v": v})
    qs = (0.0, 0.1, 0.5, 0.9, 1.0)
    exact = np.asarray(table_ops.quantile(dt, "v", qs, method="exact",
                                          ctx=CTX))
    np.testing.assert_allclose(exact, np.nanquantile(v, qs), rtol=1e-5,
                               atol=1e-6)
    # exact off a pre-sorted input elides the internal sort, same numbers
    srt, _ = table_ops.orderby(dt, "v", ctx=CTX)
    exact2 = np.asarray(table_ops.quantile(srt, "v", qs, ctx=CTX))
    np.testing.assert_allclose(exact2, exact, rtol=1e-6)
    # approx: rank error bounded by the sampling density (~sqrt(q(1-q)/m))
    approx = np.asarray(table_ops.quantile(dt, "v", qs, method="approx",
                                           n_samples=512, ctx=CTX))
    valid = np.sort(v[~np.isnan(v)])
    ranks = np.searchsorted(valid, approx) / len(valid)
    assert np.all(np.abs(ranks - np.asarray(qs)) < 0.06), (ranks, qs)


def test_quantile_empty_and_scalar_frame_api():
    df = DataFrame.from_dict({"v": np.arange(10, dtype=np.float32)}, CTX)
    assert df.quantile("v", 0.5) == pytest.approx(4.5)
    arr = df.quantile("v", [0.0, 1.0])
    np.testing.assert_allclose(arr, [0.0, 9.0])
    empty = make_dt({"v": np.zeros(4, np.float32)})
    empty = DistTable(empty.columns, jnp.zeros(1, jnp.int32))
    out = np.asarray(table_ops.quantile(empty, "v", (0.5,), method="exact",
                                        ctx=CTX))
    assert np.isnan(out).all()


# ---------------------------------------------------------------------------
# metadata contract (§4 rules extended to range layouts) + frame/TSet API
# ---------------------------------------------------------------------------
def test_range_metadata_contract():
    n = 64
    dt = make_dt({"k": RNG.integers(0, 9, n).astype(np.int32),
                  "t": RNG.integers(0, 9, n).astype(np.int32),
                  "v": RNG.normal(size=n).astype(np.float32)})
    srt, _ = table_ops.orderby(dt, ["k", "t"], ctx=CTX)
    part = range_partitioning(("k", "t"), (True, True), 1)
    assert srt.partitioning == part
    # select keeps rows in place (stable compaction) -> preserved
    sel = table_ops.select(srt, lambda c: c["v"] > -10, ctx=CTX)
    assert sel.partitioning == part
    # project: keeping every key preserves, dropping one drops
    assert table_ops.project(srt, ["k", "t"], ctx=CTX).partitioning == part
    assert table_ops.project(srt, ["k", "v"], ctx=CTX).partitioning is None
    # window adds columns without moving rows -> output carries the layout
    w, _ = table_ops.window_aggregate(srt, ["k"], ["t"], [("v", "sum")],
                                      rows=4, ctx=CTX)
    assert w.partitioning == part
    # hash operators overwrite with hash evidence
    gb, _ = table_ops.groupby_aggregate(srt, ["k"], [("v", "sum")], ctx=CTX)
    assert gb.partitioning == (("k",), 1)
    # TSet: row-chunking preserves a range layout; multi-chunk concat and
    # key-rewriting maps drop it
    chunks = TSet.from_table(srt, CTX, chunk_rows=16)
    for c in chunks._node.payload["chunks"]:
        assert c.partitioning == part
    assert chunks.collect().partitioning is None  # interleaved concat
    kept = TSet.from_table(srt, CTX).map_columns(
        lambda c: {"v": c["v"] * 2}).collect()
    assert kept.partitioning == part
    dropped = TSet.from_table(srt, CTX).map_columns(
        lambda c: {"t": c["t"] + 1}).collect()
    assert dropped.partitioning is None


def test_frame_api_and_validation():
    df = DataFrame.from_dict({
        "g": RNG.integers(0, 4, 60).astype(np.int32),
        "t": RNG.integers(0, 60, 60).astype(np.int32),
        "v": RNG.normal(size=60).astype(np.float32)}, CTX)
    assert df.partitioning_kind is None
    rp = df.repartition(["g"])
    assert rp.partitioning_kind == "hash"
    rr = df.repartition(["g", "t"], mode="range")
    assert rr.partitioning_kind == "range"
    # the sorted frame windows with no further exchange, columns added
    w = rr.window(["g"], ["t"]).agg([("v", "mean"), (None, "row_number")],
                                    rows=8)
    assert set(w.columns) >= {"g", "t", "v", "v_mean", "row_number"}
    assert len(w) == len(df)
    rk = df.rank(["g"], ["t"])
    assert "rank" in rk.columns and "row_number" in rk.columns
    top = df.topk("v", 5)
    assert len(top) == 5
    # eager validation names the offending kwarg/entry
    with pytest.raises(ValueError, match="mode="):
        df.repartition(["g"], mode="sideways")
    with pytest.raises(ValueError, match="keys="):
        df.repartition(["nope"])
    with pytest.raises(ValueError, match="by="):
        df.sort_values(["g", "nope"])
    with pytest.raises(ValueError, match="ascending="):
        df.sort_values(["g", "t"], ascending=[True])
    with pytest.raises(ValueError, match="unknown window op"):
        df.window(["g"], ["t"]).agg([("v", "median")])
    with pytest.raises(ValueError, match="rows="):
        df.window(["g"], ["t"]).agg([("v", "sum")], rows=0)
    with pytest.raises(ValueError, match="offset"):
        df.window(["g"], ["t"]).agg([("v", "lag", 0)])
    with pytest.raises(ValueError, match="collides"):
        df.window(["g"], ["t"]).agg([("v", "sum"), ("v", "sum")])
    with pytest.raises(ValueError, match="partition_by="):
        df.window(["nope"], ["t"]).agg([("v", "sum")])
    with pytest.raises(ValueError, match="method="):
        df.quantile("v", 0.5, method="guess")
    with pytest.raises(ValueError, match="qs="):
        df.quantile("v", [0.5, 1.5])
    with pytest.raises(ValueError, match="column="):
        table_ops.quantile(df.table, "nope", 0.5, ctx=CTX)
    with pytest.raises(ValueError, match="k="):
        df.topk("v", 0)


def test_tset_window_and_topk_match_eager():
    n = 128
    g = RNG.integers(0, 5, n).astype(np.int32)
    t = RNG.integers(0, 40, n).astype(np.int32)
    v = RNG.normal(size=n).astype(np.float32)
    dt = make_dt({"g": g, "t": t, "v": v})
    ts = TSet.from_table(dt, CTX, chunk_rows=32)
    got = ts.window(["g"], ["t"], [("v", "sum")], rows=6).collect()
    exp, _ = table_ops.window_aggregate(dt, ["g"], ["t"], [("v", "sum")],
                                        rows=6, ctx=CTX)
    np.testing.assert_allclose(got.to_numpy()["v_sum"],
                               exp.to_numpy()["v_sum"], rtol=1e-5)
    topc = ts.topk("v", 9).collect()
    np.testing.assert_allclose(topc.to_numpy()["v"],
                               np.sort(v)[::-1][:9], rtol=1e-6)
    q = np.asarray(ts.quantile("v", (0.5,), method="exact"))
    np.testing.assert_allclose(q, np.quantile(v, 0.5), rtol=1e-5)


# ---------------------------------------------------------------------------
# 4-shard subprocess leg: parity + the AllToAll/sort elision contract
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


def test_ordered_chain_4way():
    """The acceptance chain: orderby = ONE AllToAll; window/rank/quantile
    on the same keys add ZERO AllToAll and ZERO sorts; values match the
    single-device oracle bit-for-bit where exact."""
    out = _run_devices("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.core import (Table, DistTable, HPTMTContext, make_mesh,
                                local_context, table_ops,
                                range_partitioning)
        mesh = make_mesh((4,), ("data",))
        ctx = HPTMTContext(mesh=mesh)
        one = local_context()
        rng = np.random.default_rng(11)
        n = 512
        g = rng.integers(0, 11, n).astype(np.int32)
        t = rng.integers(0, 60, n).astype(np.int32)
        v = rng.normal(size=n).astype(np.float32)
        mk = lambda c: Table.from_arrays(
            {k: jnp.asarray(x) for k, x in c.items()})
        dt = DistTable.from_local(mk({"g": g, "t": t, "v": v}), ctx,
                                  capacity=256)
        dt1 = DistTable.from_local(mk({"g": g, "t": t, "v": v}), one)

        # orderby: exactly ONE AllToAll, zero for the elided re-sort
        jx = str(jax.make_jaxpr(lambda d: table_ops.orderby(
            d, ["g", "t"], ctx=ctx))(dt))
        assert jx.count("all_to_all") == 1, jx.count("all_to_all")
        srt, ov = table_ops.orderby(dt, ["g", "t"], ctx=ctx)
        assert int(ov) == 0
        assert srt.partitioning == range_partitioning(
            ("g", "t"), (True, True), 4)
        jx0 = str(jax.make_jaxpr(lambda d: table_ops.orderby(
            d, ["g", "t"], ctx=ctx))(srt))
        assert jx0.count("all_to_all") == 0

        # window on the range layout: ZERO AllToAll, ZERO sorts
        aggs = [("v", "sum"), ("v", "mean"), ("v", "min"), ("v", "count"),
                (None, "rank"), (None, "row_number"), ("v", "lag"),
                ("v", "lead")]
        jw = str(jax.make_jaxpr(lambda d: table_ops.window_aggregate(
            d, ["g"], ["t"], aggs, rows=8, ctx=ctx))(srt))
        assert jw.count("all_to_all") == 0, jw.count("all_to_all")
        assert "sort[" not in jw, "window must stay sort-free"

        # the full chain costs exactly the orderby's single AllToAll
        def chain(d):
            s, o1 = table_ops.orderby(d, ["g", "t"], ctx=ctx)
            w, o2 = table_ops.window_aggregate(
                s, ["g"], ["t"], aggs, rows=8, ctx=ctx)
            return w, o1 + o2
        jc = str(jax.make_jaxpr(chain)(dt))
        assert jc.count("all_to_all") == 1, jc.count("all_to_all")

        # parity: rolling AND cumulative vs the 1-shard oracle
        ref, _ = table_ops.orderby(dt1, ["g", "t"], ctx=one)
        for rows in (8, None):
            w4, ov4 = table_ops.window_aggregate(
                srt, ["g"], ["t"], aggs, rows=rows, ctx=ctx)
            assert int(ov4) == 0, (rows, int(ov4))
            r1, _ = table_ops.window_aggregate(
                ref, ["g"], ["t"], aggs, rows=rows, ctx=one)
            a, b = w4.to_numpy(), r1.to_numpy()
            for lbl in ("v_sum", "v_mean", "v_min", "v_count", "rank",
                        "row_number", "v_lag", "v_lead"):
                np.testing.assert_allclose(
                    a[lbl], b[lbl], rtol=1e-4, atol=1e-5,
                    err_msg=f"rows={rows} {lbl}")

        # topk: zero AllToAll, parity
        jt = str(jax.make_jaxpr(lambda d: table_ops.topk(
            d, "v", 16, ctx=ctx))(dt))
        assert jt.count("all_to_all") == 0
        np.testing.assert_allclose(
            table_ops.topk(dt, "v", 16, ctx=ctx).to_numpy()["v"],
            table_ops.topk(dt1, "v", 16, ctx=one).to_numpy()["v"],
            rtol=1e-6)

        # quantile off the range layout: zero AllToAll, zero sorts, and
        # numpy parity; approx stays within the sampling rank bound
        sv, _ = table_ops.orderby(dt, "v", ctx=ctx)
        jq = str(jax.make_jaxpr(lambda d: table_ops.quantile(
            d, "v", (0.5,), ctx=ctx))(sv))
        assert jq.count("all_to_all") == 0 and "sort[" not in jq
        qs = (0.1, 0.5, 0.9)
        np.testing.assert_allclose(
            np.asarray(table_ops.quantile(sv, "v", qs, ctx=ctx)),
            np.quantile(v, qs), rtol=1e-5, atol=1e-6)
        qa = np.asarray(table_ops.quantile(dt, "v", qs, method="approx",
                                           ctx=ctx))
        ranks = np.searchsorted(np.sort(v), qa) / n
        assert np.all(np.abs(ranks - np.asarray(qs)) < 0.05), ranks
        print("ORDERED-4WAY-OK")
        """)
    assert "ORDERED-4WAY-OK" in out


def test_window_truncation_counted_4way():
    """A rolling window deeper than a mid-partition shard's rows cannot be
    proven from the one-shard halo: it must COUNT truncations (§2), never
    return silently wrong windows."""
    out = _run_devices("""
        import numpy as np, jax.numpy as jnp
        from repro.core import (Table, DistTable, HPTMTContext, make_mesh,
                                table_ops)
        mesh = make_mesh((4,), ("data",))
        ctx = HPTMTContext(mesh=mesh)
        n = 64
        # ONE partition spanning every shard, ~16 rows per shard
        t = np.arange(n, dtype=np.int32)
        v = np.ones(n, np.float32)
        dt = DistTable.from_local(Table.from_arrays(
            {"g": jnp.zeros(n, jnp.int32), "t": jnp.asarray(t),
             "v": jnp.asarray(v)}), ctx, capacity=32)
        srt, _ = table_ops.orderby(dt, ["g", "t"], ctx=ctx)
        # window of 28 needs up to 27 rows back: beyond one shard's ~16
        w, ov = table_ops.window_aggregate(
            srt, ["g"], ["t"], [("v", "sum")], rows=28, ctx=ctx)
        assert int(ov) > 0, "deep cross-shard windows must count"
        # a window within the halo is exact and counts zero
        w2, ov2 = table_ops.window_aggregate(
            srt, ["g"], ["t"], [("v", "sum")], rows=8, ctx=ctx)
        assert int(ov2) == 0
        got = w2.to_numpy()["v_sum"]
        exp = np.minimum(np.arange(n) + 1, 8).astype(np.float32)
        np.testing.assert_allclose(got, exp)
        # topk beyond what a shard can surface is rejected, not clamped
        try:
            table_ops.topk(srt, "t", 33, ctx=ctx)
        except ValueError as e:
            assert "per-shard capacity" in str(e)
        else:
            raise AssertionError("k > capacity must raise")
        print("TRUNCATION-4WAY-OK")
        """)
    assert "TRUNCATION-4WAY-OK" in out
