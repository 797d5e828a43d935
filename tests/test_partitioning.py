"""Partitioning metadata, shuffle elision, and map-side combine (DESIGN.md §4).

Three layers of guarantees:

  * metadata propagation — which operators preserve, produce, or drop the
    ``(hash_keys, n_shards)`` layout record;
  * elision correctness — skipping the shuffle on pre-partitioned inputs
    yields bit-identical aggregates to the always-shuffle oracle, and the
    traced jaxpr really contains zero AllToAll;
  * map-side combine — pre-aggregated shuffles match the direct path for
    every aggregate, including the mean sum/count decomposition.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DistTable, Table, local_context, partitioning_kind,
                        range_partitioning, table_ops)
from repro.core.dataflow import TSet
from repro.dataframe.frame import DataFrame

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
RNG = np.random.default_rng(11)
CTX = local_context()


def make_dt(d):
    return DistTable.from_local(
        Table.from_arrays({k: jnp.asarray(v) for k, v in d.items()}), CTX)


# ---------------------------------------------------------------------------
# metadata propagation (single shard: pure bookkeeping)
# ---------------------------------------------------------------------------
def test_partitioning_lifecycle():
    dt = make_dt({"k": np.arange(8, dtype=np.int32),
                  "v": np.arange(8, dtype=np.float32)})
    assert dt.partitioning is None  # from_local proves nothing

    sh, _ = table_ops.shuffle(dt, ["k"], ctx=CTX)
    assert sh.partitioning == (("k",), 1)

    # select keeps rows on their shard -> preserved
    sel = table_ops.select(sh, lambda c: c["v"] >= 0, ctx=CTX)
    assert sel.partitioning == (("k",), 1)

    # project keeps the layout only while the hash keys survive
    assert table_ops.project(sh, ["k"], ctx=CTX).partitioning == (("k",), 1)
    assert table_ops.project(sh, ["v"], ctx=CTX).partitioning is None

    # orderby range-partitions: the hash layout is REPLACED by range
    # evidence (DESIGN.md §9), never silently dropped
    srt, _ = table_ops.orderby(sh, "v", ctx=CTX)
    assert srt.partitioning == range_partitioning(("v",), (True,), 1)
    assert partitioning_kind(srt.partitioning) == "range"
    # ...and hash-elision sites can never confuse it with hash evidence
    assert srt.partitioning != (("v",), 1)

    # keyed operators stamp their output
    g, _ = table_ops.groupby_aggregate(dt, ["k"], [("v", "sum")], ctx=CTX)
    assert g.partitioning == (("k",), 1)
    j, _ = table_ops.join(dt, dt, ["k"], ctx=CTX)
    assert j.partitioning == (("k",), 1)
    u, _ = table_ops.union(
        table_ops.project(dt, ["k"], ctx=CTX),
        table_ops.project(dt, ["k"], ctx=CTX), ctx=CTX)
    assert u.partitioning == (("k",), 1)

    # pytree round trip keeps the aux metadata
    leaves, treedef = jax.tree_util.tree_flatten(sh)
    assert jax.tree_util.tree_unflatten(
        treedef, leaves).partitioning == (("k",), 1)


def test_partitioning_exact_match_only():
    dt = make_dt({"a": np.arange(6, dtype=np.int32),
                  "b": np.arange(6, dtype=np.int32)})
    sh, _ = table_ops.shuffle(dt, ["a", "b"], ctx=CTX)
    # the murmur chain is order-sensitive: ("b","a") is a different layout
    assert sh.partitioning == (("a", "b"), 1)
    assert sh.partitioning != (("b", "a"), 1)


def test_tset_chunking_preserves_and_map_invalidates():
    dt = make_dt({"k": np.arange(16, dtype=np.int32),
                  "v": np.arange(16, dtype=np.float32)})
    sh, _ = table_ops.shuffle(dt, ["k"], ctx=CTX)
    chunks = TSet.from_table(sh, CTX, chunk_rows=4)
    for c in chunks._node.payload["chunks"]:
        assert c.partitioning == (("k",), 1)
    # a map over a non-key column keeps the layout; touching the key drops it
    kept = chunks.map_columns(lambda c: {"v": c["v"] * 2}).collect()
    assert kept.partitioning == (("k",), 1)
    dropped = chunks.map_columns(lambda c: {"k": c["k"] + 1}).collect()
    assert dropped.partitioning is None


def test_groupby_hash_method_matches_sort():
    n = 4096
    keys = RNG.integers(0, 37, n).astype(np.int32)
    keys2 = RNG.integers(0, 5, n).astype(np.int32)
    vals = RNG.normal(size=n).astype(np.float32)
    dt = make_dt({"k": keys, "k2": keys2, "v": vals})
    aggs = [("v", "sum"), ("v", "mean"), ("v", "min"), ("v", "max"),
            ("v", "count")]
    hs, ovh = table_ops.groupby_aggregate(dt, ["k", "k2"], aggs, ctx=CTX,
                                          out_capacity=512, method="hash")
    st, ovs = table_ops.groupby_aggregate(dt, ["k", "k2"], aggs, ctx=CTX,
                                          out_capacity=512, method="sort")
    assert int(ovh) == 0 and int(ovs) == 0
    a, b = hs.to_numpy(), st.to_numpy()
    oa = np.lexsort((a["k2"], a["k"]))
    ob = np.lexsort((b["k2"], b["k"]))
    np.testing.assert_array_equal(a["k"][oa], b["k"][ob])
    np.testing.assert_array_equal(a["k2"][oa], b["k2"][ob])
    for lbl in ("v_sum", "v_mean", "v_min", "v_max", "v_count"):
        np.testing.assert_allclose(a[lbl][oa], b[lbl][ob], rtol=1e-4,
                                   atol=1e-4, err_msg=lbl)


def test_groupby_out_capacity_above_input_capacity():
    # more output room than input rows: both kernels pad instead of crashing
    keys = RNG.integers(0, 40, 64).astype(np.int32)
    vals = RNG.normal(size=64).astype(np.float32)
    dt = make_dt({"k": keys, "v": vals})
    exp = {k: vals[keys == k].sum() for k in set(keys.tolist())}
    for method in ("sort", "hash"):
        out, ov = table_ops.groupby_aggregate(
            dt, ["k"], [("v", "sum")], ctx=CTX, out_capacity=130,
            method=method)
        got = out.to_numpy()
        assert int(ov) == 0 and len(got["k"]) == len(exp), method
        for k, s in zip(got["k"], got["v_sum"]):
            np.testing.assert_allclose(s, exp[int(k)], rtol=1e-4, atol=1e-4,
                                       err_msg=method)


def test_groupby_hash_nan_keys_do_not_corrupt():
    # NaN != NaN must not let NaN rows claim a fresh slot every probe
    # round and crowd out real groups: the hash kernel compares keys by
    # bit pattern, so equal-bit NaNs form ONE group and 1.0/2.0 survive
    keys = np.array([1.0, np.nan, 1.0, np.nan, 2.0], np.float32)
    vals = np.array([1.0, 10.0, 1.0, 10.0, 4.0], np.float32)
    dt = make_dt({"k": keys, "v": vals})
    out, ov = table_ops.groupby_aggregate(dt, ["k"], [("v", "sum")], ctx=CTX,
                                          out_capacity=8, method="hash")
    assert int(ov) == 0
    got = out.to_numpy()
    assert len(got["k"]) == 3
    by_key = {("nan" if np.isnan(k) else float(k)): s
              for k, s in zip(got["k"], got["v_sum"])}
    assert by_key[1.0] == 2.0
    assert by_key[2.0] == 4.0
    assert by_key["nan"] == 20.0


def test_groupby_hash_overflow_counted():
    # 64 distinct keys forced through an 8-group output: the surplus is
    # counted, never silently merged
    dt = make_dt({"k": np.arange(64, dtype=np.int32),
                  "v": np.ones(64, np.float32)})
    out, ov = table_ops.groupby_aggregate(dt, ["k"], [("v", "sum")], ctx=CTX,
                                          out_capacity=8, method="hash")
    assert int(out.counts.sum()) == 8
    assert int(ov) == 64 - 8


def test_from_dict_capacity_validation_and_headroom():
    data = {"k": np.arange(10, dtype=np.int32)}
    with pytest.raises(ValueError, match="cannot hold"):
        DataFrame.from_dict(data, CTX, capacity=4)
    df = DataFrame.from_dict(data, CTX, bucket_factor=2.0)
    assert df.table.capacity == 20  # headroom for later shuffle skew
    assert len(df) == 10
    assert df.partitioning is None
    assert df.repartition(["k"]).partitioning == (("k",), 1)


def test_groupby_trailing_dim_column_with_scalar_lanes():
    # a (n, 3) sum column fused alongside count/mean lanes: trailing dims
    # flatten to extra lanes and reshape back
    n = 256
    keys = RNG.integers(0, 9, n).astype(np.int32)
    emb = RNG.normal(size=(n, 3)).astype(np.float32)
    vals = RNG.normal(size=n).astype(np.float32)
    dt = make_dt({"k": keys, "e": emb, "v": vals})
    for method in ("sort", "hash"):
        out, ov = table_ops.groupby_aggregate(
            dt, ["k"], [("e", "sum"), ("v", "mean"), ("k", "count")],
            ctx=CTX, out_capacity=32, method=method)
        assert int(ov) == 0
        got = out.to_numpy()
        order = np.argsort(got["k"])
        for i, k in enumerate(got["k"][order]):
            sel = keys == k
            np.testing.assert_allclose(got["e_sum"][order][i],
                                       emb[sel].sum(axis=0), rtol=1e-4,
                                       atol=1e-4, err_msg=method)
            np.testing.assert_allclose(got["v_mean"][order][i],
                                       vals[sel].mean(), rtol=1e-4,
                                       atol=1e-4, err_msg=method)
            assert got["k_count"][order][i] == sel.sum()


def test_segment_reduce_fused_matches_per_column():
    from repro.kernels.segment_reduce import ops as segops

    n, s = 999, 64
    seg = jnp.asarray(RNG.integers(0, s + 2, n).astype(np.int32))  # + oob
    vals = jnp.asarray(RNG.normal(size=(3, n)).astype(np.float32))
    fused = segops.segment_reduce_fused(vals, seg, s)
    for lane in range(3):
        exp = segops.segment_reduce(vals[lane], seg, s, op="sum")
        np.testing.assert_allclose(fused[lane], exp, rtol=1e-5,
                                   atol=1e-5)
    # Pallas interpret-mode kernel vs the jnp reference
    from repro.kernels.segment_reduce import kernel as sk
    interp = sk.segment_reduce_pallas(vals, seg, s, "sum", interpret=True)
    np.testing.assert_allclose(interp, fused, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# 4-shard: elision vs always-shuffle oracle + jaxpr AllToAll counts
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


def test_elision_and_combine_4way():
    out = _run_devices("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.core import (Table, DistTable, HPTMTContext, make_mesh,
                                local_context, table_ops)
        mesh = make_mesh((4,), ("data",))
        ctx = HPTMTContext(mesh=mesh)
        one = local_context()
        rng = np.random.default_rng(5)
        n = 256
        t = Table.from_arrays(
            {"id": jnp.asarray(rng.integers(0, 24, n).astype(np.int32)),
             "v": jnp.asarray(rng.normal(size=n).astype(np.float32))})
        dt = DistTable.from_local(t, ctx, capacity=128)
        aggs = [("v", "sum"), ("v", "mean"), ("v", "min"), ("v", "count")]
        ref, _ = table_ops.groupby_aggregate(
            DistTable.from_local(t, one), ["id"], aggs, ctx=one)
        rg = ref.to_numpy(); ro = np.argsort(rg["id"])

        def check(got, ov, what):
            assert int(ov) == 0, (what, int(ov))
            gg = got.to_numpy(); go = np.argsort(gg["id"])
            np.testing.assert_array_equal(gg["id"][go], rg["id"][ro], what)
            for lbl in ("v_sum", "v_mean", "v_min", "v_count"):
                np.testing.assert_allclose(gg[lbl][go], rg[lbl][ro],
                                           rtol=1e-4, atol=1e-4,
                                           err_msg=f"{what}:{lbl}")

        # map-side combine == direct shuffle == single-device oracle
        check(*table_ops.groupby_aggregate(dt, ["id"], aggs, ctx=ctx,
                                           combine=False), "direct")
        check(*table_ops.groupby_aggregate(dt, ["id"], aggs, ctx=ctx,
                                           combine=True), "combine")
        check(*table_ops.groupby_aggregate(dt, ["id"], aggs, ctx=ctx,
                                           combine=True, out_capacity=64),
              "combine-lowcard")

        # elision: pre-partitioned input, zero AllToAll, same numbers
        sh, ov = table_ops.shuffle(dt, ["id"], ctx=ctx)
        assert int(ov) == 0
        assert sh.partitioning == (("id",), 4)
        check(*table_ops.groupby_aggregate(sh, ["id"], aggs, ctx=ctx),
              "elided")
        jx = str(jax.make_jaxpr(lambda d: table_ops.groupby_aggregate(
            d, ["id"], aggs, ctx=ctx))(sh))
        assert jx.count("all_to_all") == 0, jx.count("all_to_all")

        # re-shuffle on the same keys is a traced no-op
        jx = str(jax.make_jaxpr(lambda d: table_ops.shuffle(
            d, ["id"], ctx=ctx))(sh))
        assert jx.count("all_to_all") == 0

        # groupby on OTHER keys must still shuffle (metadata mismatch)
        dt2 = DistTable.from_local(Table.from_arrays(
            {"id": t.columns["id"], "g": t.columns["id"] % 3,
             "v": t.columns["v"]}), ctx, capacity=128)
        sh2, _ = table_ops.shuffle(dt2, ["id"], ctx=ctx)
        jx = str(jax.make_jaxpr(lambda d: table_ops.groupby_aggregate(
            d, ["g"], [("v", "sum")], ctx=ctx))(sh2))
        assert jx.count("all_to_all") >= 1

        # set ops elide per side and stamp their output
        pa = table_ops.project(sh, ["id"], ctx=ctx)
        jx = str(jax.make_jaxpr(lambda x: table_ops.union(
            x, x, ctx=ctx))(pa))
        assert jx.count("all_to_all") == 0
        u, ov = table_ops.union(pa, pa, ctx=ctx)
        assert u.partitioning == (("id",), 4)
        got = sorted(u.to_numpy()["id"].tolist())
        assert got == sorted(set(np.asarray(t.columns["id"]).tolist()))
        print("ELISION-4WAY-OK")
        """)
    assert "ELISION-4WAY-OK" in out


def test_join_then_groupby_single_alltoall_4way():
    """The acceptance chain: join with a pre-partitioned left lowers to ONE
    AllToAll (right side only), and the following groupby on the join keys
    lowers to ZERO — verified on the traced jaxpr AND for values."""
    out = _run_devices("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.core import (Table, DistTable, HPTMTContext, make_mesh,
                                local_context, table_ops)
        mesh = make_mesh((4,), ("data",))
        ctx = HPTMTContext(mesh=mesh)
        one = local_context()
        rng = np.random.default_rng(9)
        lk = rng.permutation(96).astype(np.int32)
        rk = rng.permutation(96).astype(np.int32)[:64]
        lt = Table.from_arrays({"k": jnp.asarray(lk),
                                "a": jnp.asarray(lk, jnp.float32)})
        rt = Table.from_arrays({"k": jnp.asarray(rk),
                                "b": jnp.asarray(rk, jnp.float32)})
        l = DistTable.from_local(lt, ctx, capacity=48)
        r = DistTable.from_local(rt, ctx, capacity=32)
        lp, ov = table_ops.shuffle(l, ["k"], ctx=ctx)
        assert int(ov) == 0

        def chain(left, right):
            j, o1 = table_ops.join(left, right, ["k"], out_capacity=96,
                                   ctx=ctx)
            g, o2 = table_ops.groupby_aggregate(
                j, ["k"], [("a", "sum"), ("b", "mean")], ctx=ctx)
            return g, o1 + o2

        jx = str(jax.make_jaxpr(chain)(lp, r))
        assert jx.count("all_to_all") == 1, jx.count("all_to_all")

        # fully pre-partitioned chain: ZERO AllToAll
        rp, ov = table_ops.shuffle(r, ["k"], ctx=ctx)
        assert int(ov) == 0
        jx0 = str(jax.make_jaxpr(chain)(lp, rp))
        assert jx0.count("all_to_all") == 0, jx0.count("all_to_all")

        # and the values are the single-device truth either way
        g4, ov4 = chain(lp, r)
        g0, ov0 = chain(lp, rp)
        lo = DistTable.from_local(lt, one)
        roo = DistTable.from_local(rt, one)
        j1, _ = table_ops.join(lo, roo, ["k"], out_capacity=96, ctx=one)
        gr, _ = table_ops.groupby_aggregate(
            j1, ["k"], [("a", "sum"), ("b", "mean")], ctx=one)
        eg = gr.to_numpy(); eo = np.argsort(eg["k"])
        for got, ov in ((g4, ov4), (g0, ov0)):
            assert int(ov) == 0
            gg = got.to_numpy(); go = np.argsort(gg["k"])
            np.testing.assert_array_equal(gg["k"][go], eg["k"][eo])
            np.testing.assert_allclose(gg["a_sum"][go], eg["a_sum"][eo],
                                       rtol=1e-5)
            np.testing.assert_allclose(gg["b_mean"][go], eg["b_mean"][eo],
                                       rtol=1e-5)
        print("JOIN-GROUPBY-1A2A-OK")
        """)
    assert "JOIN-GROUPBY-1A2A-OK" in out


def test_dataflow_combiner_elides_merge_shuffle_4way():
    """The chunked combiner barrier: per-chunk partials are partitioned on
    the keys, so the merge groupby at the barrier issues no extra
    AllToAll beyond the per-chunk exchanges."""
    out = _run_devices("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.core import (Table, DistTable, HPTMTContext, make_mesh,
                                local_context, table_ops)
        from repro.core.dataflow import TSet
        mesh = make_mesh((4,), ("data",))
        ctx = HPTMTContext(mesh=mesh)
        one = local_context()
        rng = np.random.default_rng(2)
        n = 256
        t = Table.from_arrays(
            {"k": jnp.asarray(rng.integers(0, 13, n).astype(np.int32)),
             "v": jnp.asarray(rng.normal(size=n).astype(np.float32))})
        dt = DistTable.from_local(t, ctx, capacity=128)
        got = (TSet.from_table(dt, ctx, chunk_rows=32)
               .groupby(["k"], [("v", "sum"), ("v", "mean")]).collect())
        assert got.partitioning == (("k",), 4)
        ref, _ = table_ops.groupby_aggregate(
            DistTable.from_local(t, one), ["k"],
            [("v", "sum"), ("v", "mean")], ctx=one)
        a, b = got.to_numpy(), ref.to_numpy()
        oa, ob = np.argsort(a["k"]), np.argsort(b["k"])
        np.testing.assert_array_equal(a["k"][oa], b["k"][ob])
        np.testing.assert_allclose(a["v_sum"][oa], b["v_sum"][ob],
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(a["v_mean"][oa], b["v_mean"][ob],
                                   rtol=1e-4, atol=1e-4)
        print("DATAFLOW-COMBINER-OK")
        """)
    assert "DATAFLOW-COMBINER-OK" in out
