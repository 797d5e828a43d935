"""Storage subsystem tests (repro.io, DESIGN.md §5).

Four layers of guarantees:

  * schema ↔ ColSpec mapping — the schema model computes the exact packed
    layout ``pack_columns`` produces, bidirectionally;
  * round-trip bit-exactness — native ``.hpt`` and Arrow paths preserve
    every packed dtype bit-for-bit, including ``-0.0``/``inf``/``nan``;
    nulls and ragged inputs are rejected eagerly with names;
  * pushdown — projection + predicate scans materialize only projected
    columns and skip prunable fragments (observable via reader stats),
    with results identical to a full scan + post-filter, and overflow
    obeying the §2 count-and-drop contract;
  * partitioned re-entry — a dataset written with ``partition_by`` scans
    back with ``DistTable.partitioning`` attached, so a join on the
    partition keys traces with zero left-side AllToAll (4-device
    subprocess, jaxpr-asserted).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from conftest import HAS_PYARROW, requires_pyarrow

import jax.numpy as jnp

from repro.core import local_context, table_ops
from repro.core.exchange import pack_columns, unpack_columns
from repro.dataframe.frame import DataFrame
from repro.io import (ColumnPredicate, Field, ScanSource, Schema,
                      open_dataset, pred, read_dataset, read_hpt,
                      write_dataset, write_hpt)

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
RNG = np.random.default_rng(7)
CTX = local_context()

WEIRD_F32 = np.array([-0.0, 0.0, np.inf, -np.inf, np.nan, -np.nan,
                      np.float32(1e-40), 3.5], np.float32)

#: one column per packed dtype (§3.1), with adversarial payloads
ALL_DTYPE_COLS = {
    "f16": WEIRD_F32.astype(np.float16),
    "f32": WEIRD_F32,
    "f64": WEIRD_F32.astype(np.float64),
    "i8": np.array([-128, 127, 0, -1, 5, 6, 7, 8], np.int8),
    "i16": np.array([-32768, 32767, 0, -1, 5, 6, 7, 8], np.int16),
    "i32": np.array([-2**31, 2**31 - 1, 0, -1, 5, 6, 7, 8], np.int32),
    "i64": np.array([-2**63, 2**63 - 1, 0, -1, 5, 6, 7, 8], np.int64),
    "u8": np.array([0, 255, 1, 2, 3, 4, 5, 6], np.uint8),
    "u16": np.array([0, 65535, 1, 2, 3, 4, 5, 6], np.uint16),
    "u32": np.array([0, 2**32 - 1, 1, 2, 3, 4, 5, 6], np.uint32),
    "u64": np.array([0, 2**64 - 1, 1, 2, 3, 4, 5, 6], np.uint64),
    "b": np.array([1, 0, 1, 1, 0, 0, 1, 0], bool),
    "emb": np.arange(24, dtype=np.float32).reshape(8, 3) * -0.5,
}


def bit_equal(a: np.ndarray, b: np.ndarray, msg=""):
    """Bitwise equality — distinguishes -0.0 from 0.0 and NaN payloads."""
    assert a.dtype == b.dtype and a.shape == b.shape, \
        f"{msg}: {a.dtype}{a.shape} vs {b.dtype}{b.shape}"
    assert np.ascontiguousarray(a).tobytes() == \
        np.ascontiguousarray(b).tobytes(), msg


def make_events(n=1200, n_days=30, seed=3):
    rng = np.random.default_rng(seed)
    return {
        "user_id": rng.integers(0, 40, n).astype(np.int32),
        "day": np.sort(rng.integers(0, n_days, n)).astype(np.int32),
        "value": rng.normal(size=n).astype(np.float32),
        "score": rng.uniform(0, 1, n).astype(np.float32),
        "clicks": rng.integers(0, 9, n).astype(np.int32),
        "flag": rng.uniform(size=n) < 0.5,
    }


FORMATS = ["hpt"] + (["parquet"] if HAS_PYARROW else [])


# ===========================================================================
# schema ↔ ColSpec
# ===========================================================================
def test_schema_matches_packer_layout():
    # jax-resident columns (32-bit world): the schema's computed layout
    # must equal what pack_columns actually records
    cols = {"v": jnp.asarray(WEIRD_F32), "k": jnp.arange(8, dtype=jnp.int32),
            "b": jnp.asarray(ALL_DTYPE_COLS["b"]),
            "h": jnp.asarray(ALL_DTYPE_COLS["f16"]),
            "e": jnp.asarray(ALL_DTYPE_COLS["emb"])}
    buf, specs = pack_columns(cols)
    schema = Schema.from_columns(cols)
    assert schema.to_colspecs() == specs
    assert schema.row_width == buf.shape[0]
    # bidirectional: specs -> schema -> specs round trip
    assert Schema.from_colspecs(specs).to_colspecs() == specs
    # and unpack still inverts under the schema-derived specs
    back = unpack_columns(buf, schema.to_colspecs())
    for k in cols:
        bit_equal(np.asarray(back[k]), np.asarray(cols[k]), k)


def test_schema_lane_math_64bit_and_trailing():
    schema = Schema([Field("a", "int64"), Field("b", "float64", (3,)),
                     Field("c", "uint8", (2, 2)), Field("d", "bool")])
    by = {f.name: f for f in schema}
    assert by["a"].lanes == 2          # 8-byte -> 2 lanes
    assert by["b"].lanes == 6          # 3 elements x 2 lanes
    assert by["c"].lanes == 4          # 4 elements x 1 widened lane
    assert by["d"].lanes == 1
    assert schema.row_width == 13
    specs = schema.to_colspecs()
    assert [s.start for s in specs] == [0, 2, 8, 12]  # sorted-name order
    assert Schema.from_colspecs(specs) == schema


def test_schema_rejects_unsupported_dtype():
    with pytest.raises(TypeError, match="dictionary-encode"):
        Schema.from_columns({"s": np.array(["a", "b"])})


def test_schema_json_round_trip():
    schema = Schema.from_columns(ALL_DTYPE_COLS)
    assert Schema.from_json(schema.to_json()) == schema


# ===========================================================================
# round-trip bit-exactness
# ===========================================================================
def test_native_round_trip_bit_exact(tmp_path):
    path = str(tmp_path / "all.hpt")
    write_hpt(path, ALL_DTYPE_COLS)
    back, n = read_hpt(path)
    assert n == 8
    assert set(back) == set(ALL_DTYPE_COLS)
    for k, v in ALL_DTYPE_COLS.items():
        bit_equal(back[k], v, k)


def test_native_projection_reads_requested_only(tmp_path):
    path = str(tmp_path / "t.hpt")
    write_hpt(path, ALL_DTYPE_COLS)
    back, _ = read_hpt(path, columns=["f32", "emb"])
    assert set(back) == {"f32", "emb"}
    bit_equal(back["f32"], ALL_DTYPE_COLS["f32"])
    with pytest.raises(KeyError, match="nope"):
        read_hpt(path, columns=["nope"])


def test_native_ragged_rejected(tmp_path):
    with pytest.raises(ValueError, match="ragged"):
        write_hpt(str(tmp_path / "r.hpt"),
                  {"a": np.arange(3), "b": np.arange(4)})


@requires_pyarrow
def test_arrow_round_trip_bit_exact():
    from repro.io import from_arrow, to_arrow

    at = to_arrow(ALL_DTYPE_COLS)
    back, n = from_arrow(at)
    assert n == 8
    for k, v in ALL_DTYPE_COLS.items():
        bit_equal(back[k], v, k)


@requires_pyarrow
def test_arrow_schema_round_trip():
    schema = Schema.from_columns(ALL_DTYPE_COLS)
    assert Schema.from_arrow(schema.to_arrow()) == schema


@requires_pyarrow
def test_arrow_nulls_rejected_with_names():
    import pyarrow as pa

    from repro.io import from_arrow

    at = pa.table({"ok": pa.array([1, 2, 3], pa.int32()),
                   "holes": pa.array([1.0, None, 3.0], pa.float32())})
    with pytest.raises(ValueError, match="holes"):
        from_arrow(at)


@requires_pyarrow
def test_parquet_round_trip_bit_exact(tmp_path):
    from repro.io.parquet import read_row_groups, write_parquet

    path = str(tmp_path / "all.parquet")
    write_parquet(path, ALL_DTYPE_COLS)
    back, n = read_row_groups(path, [0])
    assert n == 8
    for k, v in ALL_DTYPE_COLS.items():
        bit_equal(back[k], v, k)


@requires_pyarrow
def test_dataframe_arrow_bridge():
    import pyarrow as pa

    df = DataFrame.from_dict({"k": np.arange(6, dtype=np.int32),
                              "v": WEIRD_F32[:6]}, CTX)
    at = df.to_arrow()
    assert isinstance(at, pa.Table)
    back = DataFrame.from_arrow(at, CTX)
    bit_equal(back.to_numpy()["v"], np.asarray(df.to_numpy()["v"]))


# ===========================================================================
# pushdown scans
# ===========================================================================
@pytest.mark.parametrize("fmt", FORMATS)
def test_pushdown_parity_and_stats(tmp_path, fmt):
    """Acceptance: scanning 2 of 6 columns with a selective predicate
    materializes only the projected columns, skips >=1 row group (reader
    stats), and matches the full scan + post-filter exactly."""
    cols = make_events()
    root = str(tmp_path / f"events_{fmt}")
    write_dataset(root, [(cols, 1200)], format=fmt, rows_per_group=150)

    src = ScanSource(root, ctx=CTX, columns=["user_id", "value"],
                     predicate=[pred("day", ">=", 5), pred("day", "<", 9)])
    dt, overflow = src.to_dist_table()
    st = src.stats
    assert overflow == 0
    assert st.columns_total == 6 and st.columns_read == 3  # proj + pred col
    assert st.row_groups_total == 8
    assert st.row_groups_skipped >= 1
    assert st.rows_scanned < st.rows_on_disk  # pruning really read less
    got = dt.to_numpy()
    assert set(got) == {"user_id", "value"}  # pred col not materialized out

    full, ov_full, st_full = read_dataset(root, ctx=CTX)
    assert ov_full == 0 and st_full.row_groups_skipped == 0
    fn = full.to_numpy()
    mask = (fn["day"] >= 5) & (fn["day"] < 9)
    # row order is preserved by the scan, so parity is positional
    bit_equal(got["user_id"], fn["user_id"][mask])
    bit_equal(got["value"], fn["value"][mask])
    assert st.rows_selected == int(mask.sum())


@pytest.mark.parametrize("fmt", FORMATS)
def test_pushdown_operator_coverage(tmp_path, fmt):
    """Every predicate op against the full-scan oracle."""
    cols = make_events(n=600)
    root = str(tmp_path / f"ev_{fmt}")
    write_dataset(root, [(cols, 600)], format=fmt, rows_per_group=100)
    full = read_dataset(root, ctx=CTX)[0].to_numpy()
    ops = {"<": np.less, "<=": np.less_equal, ">": np.greater,
           ">=": np.greater_equal, "==": np.equal, "!=": np.not_equal}
    for op, npop in ops.items():
        dt, ov, _ = read_dataset(root, ctx=CTX, predicate=pred("day", op, 7))
        assert ov == 0
        bit_equal(dt.to_numpy()["value"],
                  full["value"][npop(full["day"], 7)], op)


def test_predicate_validation(tmp_path):
    root = str(tmp_path / "v")
    write_dataset(root, [(ALL_DTYPE_COLS, 8)], format="hpt")
    with pytest.raises(KeyError, match="missing"):
        ScanSource(root, ctx=CTX, predicate=pred("missing", "<", 1))
    with pytest.raises(ValueError, match="trailing"):
        ScanSource(root, ctx=CTX, predicate=pred("emb", "<", 1))
    with pytest.raises(ValueError, match="unknown predicate op"):
        ColumnPredicate("f32", "~", 1)


def test_nan_stats_never_prune(tmp_path):
    # NaNs poison min/max: the fragment must stay scannable, and the
    # residual filter gives the exact (NaN-excluding) comparison result
    root = str(tmp_path / "nan")
    write_dataset(root, [({"x": WEIRD_F32,
                           "i": np.arange(8, dtype=np.int32)}, 8)],
                  format="hpt")
    ds = open_dataset(root)
    assert ds.fragments[0].stats["x"] is None
    assert ds.fragments[0].stats["i"] == (0, 7)
    dt, ov, st = read_dataset(root, ctx=CTX, predicate=pred("x", ">", 0))
    assert st.row_groups_skipped == 0
    got = dt.to_numpy()
    assert got["i"].tolist() == [2, 6, 7]  # inf, 1e-40 and 3.5


@pytest.mark.parametrize("fmt", FORMATS)
def test_float_ne_predicate_never_prunes(tmp_path, fmt):
    # Parquet computes min/max ignoring NaNs, so min==max==v does NOT
    # prove all rows equal v — "!=" on float columns must skip pruning
    # and let the residual filter keep the NaN rows
    root = str(tmp_path / f"ne_{fmt}")
    x = np.array([1.0, 1.0, np.nan, 1.0], np.float32)
    write_dataset(root, [({"x": x, "i": np.arange(4, dtype=np.int32)}, 4)],
                  format=fmt)
    dt, ov, st = read_dataset(root, ctx=CTX, predicate=pred("x", "!=", 1.0))
    assert ov == 0 and st.row_groups_skipped == 0
    got = dt.to_numpy()
    assert got["i"].tolist() == [2]  # exactly the NaN row survives
    # int columns still prune on "!=" when stats prove uniformity
    root2 = str(tmp_path / f"ne_int_{fmt}")
    write_dataset(root2, [({"k": np.full(4, 7, np.int32),
                            "i": np.arange(4, dtype=np.int32)}, 4)],
                  format=fmt)
    _, _, st2 = read_dataset(root2, ctx=CTX, predicate=pred("k", "!=", 7))
    assert st2.row_groups_skipped == 1


def test_scan_stats_reset_per_materialization(tmp_path):
    cols = make_events(n=300)
    root = str(tmp_path / "stats")
    write_dataset(root, [(cols, 300)], format="hpt", rows_per_group=60)
    src = ScanSource(root, ctx=CTX)
    src.to_dist_table()
    first = src.stats.rows_scanned
    src.to_dist_table()  # a second run must not double-count
    assert src.stats.rows_scanned == first == 300
    list(src.chunks())
    assert src.stats.rows_scanned == 300


@pytest.mark.parametrize("fmt", FORMATS)
def test_scan_overflow_count_and_drop(tmp_path, fmt):
    """§2 contract: rows beyond an explicit capacity are counted and
    dropped in original row order — never silently corrupted."""
    cols = make_events(n=500)
    root = str(tmp_path / f"ovf_{fmt}")
    write_dataset(root, [(cols, 500)], format=fmt, rows_per_group=100)
    dt, overflow, st = read_dataset(root, ctx=CTX, capacity=120)
    assert overflow == 500 - 120
    assert st.rows_overflowed == 380
    assert int(dt.num_rows()) == 120
    # deterministic prefix in original row order
    bit_equal(dt.to_numpy()["value"], cols["value"][:120])


def test_scan_plans_capacity_from_metadata(tmp_path):
    cols = make_events(n=321)
    root = str(tmp_path / "cap")
    write_dataset(root, [(cols, 321)], format="hpt", rows_per_group=64)
    src = ScanSource(root, ctx=CTX)
    assert src.shard_capacity == 321  # exact plan, no load needed
    dt, ov = src.to_dist_table()
    assert ov == 0 and int(dt.num_rows()) == 321


def test_scan_bucket_factor_headroom(tmp_path):
    # mirrors DataFrame.from_dict: head-room so a later shuffle's hash
    # skew does not overflow a 100%-occupancy scanned table
    cols = make_events(n=200)
    root = str(tmp_path / "bf")
    write_dataset(root, [(cols, 200)], format="hpt")
    assert ScanSource(root, ctx=CTX).shard_capacity == 200
    src = ScanSource(root, ctx=CTX, bucket_factor=1.5)
    assert src.shard_capacity == 300
    dt, ov = src.to_dist_table()
    assert ov == 0 and int(dt.num_rows()) == 200 and dt.capacity == 300


def test_scan_64bit_narrowing_guard(tmp_path):
    import jax

    if jax.config.jax_enable_x64:
        pytest.skip("x64 enabled: no narrowing to guard")
    root = str(tmp_path / "wide")
    write_dataset(root, [({"big": np.array([1, 2**40], np.int64),
                           "ok64": np.array([1, 2], np.int64)}, 2)],
                  format="hpt")
    with pytest.raises(ValueError, match="big"):
        read_dataset(root, ctx=CTX)
    dt, _, _ = read_dataset(root, ctx=CTX, columns=["ok64"])  # values fit
    assert dt.to_numpy()["ok64"].tolist() == [1, 2]
    dt, _, _ = read_dataset(root, ctx=CTX, allow_narrowing=True)
    assert dt.to_numpy()["ok64"].tolist() == [1, 2]


@pytest.mark.parametrize("fmt", FORMATS)
def test_scan_chunks_to_tset_out_of_core(tmp_path, fmt):
    """Fragment-round chunk stream through the dataflow combiner matches
    the eager whole-table groupby."""
    from repro.core.dataflow import TSet

    cols = make_events(n=800)
    root = str(tmp_path / f"tset_{fmt}")
    write_dataset(root, [(cols, 800)], format=fmt, rows_per_group=128)
    src = ScanSource(root, ctx=CTX, columns=["user_id", "value"])
    chunks = list(src.chunks())  # lazy generator: one round per next()
    assert len(chunks) == 7  # ceil(800/128) fragment rounds
    got = (TSet.from_scan(ScanSource(root, ctx=CTX,
                                     columns=["user_id", "value"]))
           .groupby(["user_id"], [("value", "sum"), ("value", "count")])
           .collect())
    eager, _ = table_ops.groupby_aggregate(
        read_dataset(root, ctx=CTX)[0], ["user_id"],
        [("value", "sum"), ("value", "count")], ctx=CTX)
    a, b = got.to_numpy(), eager.to_numpy()
    oa, ob = np.argsort(a["user_id"]), np.argsort(b["user_id"])
    np.testing.assert_array_equal(a["user_id"][oa], b["user_id"][ob])
    np.testing.assert_allclose(a["value_sum"][oa], b["value_sum"][ob],
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(a["value_count"][oa],
                                  b["value_count"][ob])


# ===========================================================================
# partitioning manifest & re-entry
# ===========================================================================
@pytest.mark.parametrize("fmt", FORMATS)
def test_partitioned_write_read_reattaches_metadata(tmp_path, fmt):
    df = DataFrame.from_dict(make_events(n=400), CTX)
    root = str(tmp_path / f"part_{fmt}")
    df.to_parquet(root, partition_by=["user_id"], format=fmt)
    assert open_dataset(root).partitioning == (("user_id",), 1)

    back = DataFrame.read_parquet(root, CTX)
    assert back.partitioning == (("user_id",), 1)
    # dropping a key column in the projection drops the evidence
    proj = DataFrame.read_parquet(root, CTX, columns=["day", "value"])
    assert proj.partitioning is None
    # a predicate is a select: rows never change shards, evidence survives
    filt = DataFrame.read_parquet(root, CTX, predicate=pred("day", "<", 9))
    assert filt.partitioning == (("user_id",), 1)


def test_unpartitioned_dataset_has_no_evidence(tmp_path):
    df = DataFrame.from_dict(make_events(n=100), CTX)
    root = str(tmp_path / "plain")
    df.to_parquet(root, format="hpt")
    assert open_dataset(root).partitioning is None
    assert DataFrame.read_parquet(root, CTX).partitioning is None


def test_roundtrip_values_through_partitioned_dataset(tmp_path):
    cols = make_events(n=300)
    df = DataFrame.from_dict(cols, CTX)
    root = str(tmp_path / "pv")
    df.to_parquet(root, partition_by=["user_id"], format="hpt")
    back = DataFrame.read_parquet(root, CTX).to_numpy()
    # single shard: the shuffle is an intra-shard permutation; compare as
    # multisets keyed by (user_id, value) bits
    order = np.lexsort((cols["value"].view(np.uint32), cols["user_id"]))
    border = np.lexsort((back["value"].view(np.uint32), back["user_id"]))
    for k in cols:
        bit_equal(back[k][border], cols[k][order], k)


# ===========================================================================
# 4-device mesh: zero left-side AllToAll on partitioned read → join
# ===========================================================================
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


def test_partitioned_read_join_elision_4way(tmp_path):
    """Acceptance: read_parquet of a hash-partitioned dataset -> join on
    the partition keys traces with zero left-side all_to_all equations
    (1 total for the unpartitioned right, 0 when both sides re-enter)."""
    fmt = "parquet" if HAS_PYARROW else "hpt"
    out = _run_devices(f"""
        import os, numpy as np, jax, jax.numpy as jnp
        from repro.core import HPTMTContext, make_mesh, table_ops, local_context
        from repro.dataframe.frame import DataFrame
        fmt = {fmt!r}
        root = {str(tmp_path)!r}
        mesh = make_mesh((4,), ("data",))
        ctx = HPTMTContext(mesh=mesh)
        rng = np.random.default_rng(9)
        n = 96
        lk = rng.permutation(n).astype(np.int32)
        rk = rng.permutation(n).astype(np.int32)[:64]
        left = DataFrame.from_dict(
            {{"k": lk, "a": lk.astype(np.float32)}}, ctx, bucket_factor=2.0)
        right = DataFrame.from_dict(
            {{"k": rk, "b": rk.astype(np.float32)}}, ctx, bucket_factor=2.0)
        lroot = os.path.join(root, "left_ds")
        left.to_parquet(lroot, partition_by=["k"], format=fmt)
        lp = DataFrame.read_parquet(lroot, ctx)
        assert lp.partitioning == (("k",), 4), lp.partitioning

        def chain(l, r):
            return table_ops.join(l, r, ["k"], out_capacity=48, ctx=ctx)

        jx = str(jax.make_jaxpr(chain)(lp.table, right.table))
        assert jx.count("all_to_all") == 1, jx.count("all_to_all")

        rroot = os.path.join(root, "right_ds")
        right.to_parquet(rroot, partition_by=["k"], format=fmt)
        rp = DataFrame.read_parquet(rroot, ctx)
        jx0 = str(jax.make_jaxpr(chain)(lp.table, rp.table))
        assert jx0.count("all_to_all") == 0, jx0.count("all_to_all")

        # values match the single-device truth
        one = local_context()
        exp = (DataFrame.from_dict({{"k": lk, "a": lk.astype(np.float32)}}, one)
               .join(DataFrame.from_dict(
                   {{"k": rk, "b": rk.astype(np.float32)}}, one),
                   on=["k"], out_capacity=96).to_numpy())
        got = lp.join(rp, on=["k"], out_capacity=48).to_numpy()
        eo, go = np.argsort(exp["k"]), np.argsort(got["k"])
        np.testing.assert_array_equal(got["k"][go], exp["k"][eo])
        np.testing.assert_allclose(got["b"][go], exp["b"][eo])
        np.testing.assert_allclose(got["a"][go], exp["a"][eo])

        # mismatched shard count: evidence must NOT attach on a 2-shard read
        mesh2 = make_mesh((2,), ("data",), devices=jax.devices()[:2])
        ctx2 = HPTMTContext(mesh=mesh2)
        lp2 = DataFrame.read_parquet(lroot, ctx2)
        assert lp2.partitioning is None, lp2.partitioning
        assert int(lp2.table.num_rows()) == n
        print("IO-ELISION-4WAY-OK")
        """)
    assert "IO-ELISION-4WAY-OK" in out


# ===========================================================================
# satellites: from_dict validation, pyarrow-absent leg
# ===========================================================================
def test_from_dict_ragged_names_offenders():
    with pytest.raises(ValueError) as ei:
        DataFrame.from_dict({"a": np.arange(4), "b": np.arange(4),
                             "short": np.arange(2)}, CTX)
    assert "short has 2 rows" in str(ei.value)
    assert "4 rows" in str(ei.value)


def test_pyarrow_absent_leg_native_works(tmp_path):
    """With pyarrow force-disabled, auto-format falls back to .hpt, scans
    work, and parquet asks fail with an actionable error."""
    script = textwrap.dedent(f"""
        import os
        os.environ["HPTMT_DISABLE_PYARROW"] = "1"
        import numpy as np
        from repro.core import local_context
        from repro.dataframe.frame import DataFrame
        from repro.io import has_pyarrow, pred
        assert not has_pyarrow()
        ctx = local_context()
        df = DataFrame.from_dict(
            {{"k": np.arange(50, dtype=np.int32),
              "v": np.arange(50, dtype=np.float32)}}, ctx)
        root = os.path.join({str(tmp_path)!r}, "ds")
        df.to_parquet(root, format=None, rows_per_group=10,
                      partition_by=["k"])
        back = DataFrame.read_parquet(root, ctx, predicate=pred("k", "<", 20))
        assert len(back) == 20
        assert back.partitioning == (("k",), 1)
        try:
            df.to_parquet(os.path.join({str(tmp_path)!r}, "pq"),
                          format="parquet")
        except RuntimeError as e:
            assert "pyarrow" in str(e)
        else:
            raise AssertionError("parquet write should have raised")
        print("ABSENT-LEG-OK")
        """)
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    assert "ABSENT-LEG-OK" in r.stdout


def test_disk_corpus_matches_synthetic(tmp_path):
    """The training-data ingest path: corpus written to disk and scanned
    back yields the same curated token stream as the in-memory corpus."""
    sys.path.insert(0, os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "scripts")))
    from make_dataset import make_corpus_dataset

    from repro.data.pipeline import (CorpusConfig, disk_corpus, preprocess,
                                     synthetic_corpus)

    ccfg = CorpusConfig(n_docs=16, mean_doc_len=24, vocab_size=64, seed=4)
    root = str(tmp_path / "corpus")
    make_corpus_dataset(root, n_docs=16, mean_doc_len=24, vocab_size=64,
                        fmt="hpt", seed=4)
    mem = preprocess(synthetic_corpus(ccfg, CTX), ccfg, CTX)
    disk = preprocess(disk_corpus(root, CTX), ccfg, CTX)
    np.testing.assert_array_equal(mem, disk)
