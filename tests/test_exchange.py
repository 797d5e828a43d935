"""Parity tests for the packed single-collective exchange engine.

The new engine (``core/exchange.py``) must produce row-for-row identical
tables — columns, counts, overflow — to the seed per-column argsort path
(kept as ``exchange_rows_reference``) across dtypes, shard counts, and
overflow-triggering capacities; plus the fused Pallas ``hash_partition``
kernel (interpret mode) must match the jnp oracle bit-for-bit.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DistTable, Table, local_context, table_ops
from repro.core import exchange as ex
from repro.core.table import hash_columns

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
RNG = np.random.default_rng(7)
CTX = local_context()


def _mixed_cols(n, rng=RNG):
    return {
        "i": jnp.asarray(rng.integers(-1000, 1000, n).astype(np.int32)),
        "u": jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint64
                                      ).astype(np.uint32)),
        "f": jnp.asarray(rng.normal(size=n).astype(np.float32)),
        "b": jnp.asarray(rng.random(n) < 0.5),
        "m": jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32)),
    }


# ---------------------------------------------------------------------------
# packing
# ---------------------------------------------------------------------------
def test_pack_unpack_roundtrip_bit_exact():
    cols = _mixed_cols(97)
    # adversarial float bit patterns must survive the round trip
    cols["f"] = cols["f"].at[0].set(-0.0).at[1].set(jnp.inf).at[2].set(
        jnp.nan)
    buf, specs = ex.pack_columns(cols)
    assert buf.dtype == jnp.uint32
    assert buf.shape == (1 + 1 + 1 + 1 + 3, 97)     # lanes-major
    back = ex.unpack_columns(buf, specs)
    assert set(back) == set(cols)
    for k in cols:
        assert back[k].dtype == cols[k].dtype, k
        np.testing.assert_array_equal(
            np.asarray(back[k]).view(np.uint8).reshape(-1),
            np.asarray(cols[k]).view(np.uint8).reshape(-1), err_msg=k)


def test_dest_ranks_matches_argsort_rank():
    n, p = 513, 7
    dest = jnp.asarray(RNG.integers(0, p + 1, n).astype(np.int32))
    got = np.asarray(ex.dest_ranks(dest, p))
    # oracle: stable-argsort-based rank (the seed algorithm)
    order = np.argsort(np.asarray(dest), kind="stable")
    sdest = np.asarray(dest)[order]
    first = np.searchsorted(sdest, sdest, side="left")
    rank_sorted = np.arange(n) - first
    rank = np.empty(n, np.int64)
    rank[order] = rank_sorted
    valid = np.asarray(dest) < p
    np.testing.assert_array_equal(got[valid], rank[valid])


def test_compact_rows_matches_argsort_compaction():
    n = 200
    cols = _mixed_cols(n)
    keep = jnp.asarray(RNG.random(n) < 0.6)
    for out_cap in (n, 50):  # 50 triggers truncation overflow
        got, cnt, trunc = ex.compact_rows(cols, keep, out_cap)
        order = np.argsort(~np.asarray(keep), kind="stable")
        total = int(np.asarray(keep).sum())
        exp_cnt = min(total, out_cap)
        assert int(cnt) == exp_cnt
        assert int(trunc) == total - exp_cnt
        for k in cols:
            exp = np.asarray(cols[k])[order][:out_cap][:exp_cnt]
            np.testing.assert_array_equal(
                np.asarray(got[k])[:exp_cnt], exp, err_msg=k)


# ---------------------------------------------------------------------------
# packed exchange vs seed per-column reference (local, n_shards simulated)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_shards,bucket", [(1, 64), (4, 16), (4, 5)])
def test_exchange_parity_vs_reference(n_shards, bucket):
    """bucket=5 forces send-side overflow; valid rows must still agree."""
    n = 64
    cols = _mixed_cols(n)
    dest = jnp.asarray(RNG.integers(0, n_shards + 1, n).astype(np.int32))
    got, gvalid, gov = ex.exchange_rows(cols, dest, n_shards, bucket, None)
    exp, evalid, eov = ex.exchange_rows_reference(cols, dest, n_shards,
                                                  bucket, None)
    assert int(gov) == int(eov)
    np.testing.assert_array_equal(np.asarray(gvalid), np.asarray(evalid))
    v = np.asarray(evalid)
    for k in cols:
        np.testing.assert_array_equal(np.asarray(got[k])[v],
                                      np.asarray(exp[k])[v], err_msg=k)


@pytest.mark.parametrize("dtype", [np.int32, np.uint32, np.float32])
def test_shuffle_parity_single_shard_dtypes(dtype):
    n = 50
    vals = RNG.integers(0, 100, n).astype(dtype)
    dt = DistTable.from_local(
        Table.from_arrays({"x": jnp.asarray(vals)}), CTX)
    out, ov = table_ops.shuffle(dt, ["x"], ctx=CTX)
    assert int(ov) == 0
    got = out.to_numpy()["x"]
    assert got.dtype == dtype
    np.testing.assert_array_equal(np.sort(got), np.sort(vals))


def test_reserved_hash_column_names_rejected():
    l = DistTable.from_local(Table.from_arrays(
        {"k": jnp.arange(4, dtype=jnp.int32),
         "_h1": jnp.arange(4, dtype=jnp.uint32)}), CTX)
    r = DistTable.from_local(Table.from_arrays(
        {"k": jnp.arange(4, dtype=jnp.int32),
         "b": jnp.arange(4, dtype=jnp.float32)}), CTX)
    with pytest.raises(ValueError, match="reserved"):
        table_ops.join(l, r, ["k"], ctx=CTX)
    bad = DistTable.from_local(Table.from_arrays(
        {"_h1": jnp.arange(4, dtype=jnp.uint32),
         "_h2": jnp.arange(4, dtype=jnp.uint32)}), CTX)
    with pytest.raises(ValueError, match="reserved"):
        table_ops.union(bad, bad, ctx=CTX)


def test_dest_ranks_chunked_many_partitions():
    # more partitions than the chunk size exercises the chunk loop
    n, p = 257, 50
    dest = jnp.asarray(RNG.integers(0, p + 1, n).astype(np.int32))
    got = np.asarray(ex.dest_ranks(dest, p, chunk=16))
    d = np.asarray(dest)
    exp = np.array([int((d[:i] == d[i]).sum()) for i in range(n)])
    valid = d < p
    np.testing.assert_array_equal(got[valid], exp[valid])


def test_shuffle_overflow_counted_not_corrupted():
    n = 40
    dt = DistTable.from_local(Table.from_arrays(
        {"x": jnp.arange(n, dtype=jnp.int32)}), CTX)
    out, ov = table_ops.shuffle(dt, ["x"], out_capacity=25, ctx=CTX)
    assert int(ov) == n - 25
    got = out.to_numpy()["x"]
    assert len(got) == 25
    assert len(set(got.tolist())) == 25  # no duplicated/corrupted rows


# ---------------------------------------------------------------------------
# fused hash_partition kernel: hashes out of the Pallas path
# ---------------------------------------------------------------------------
def test_hash_partition_return_hashes_bit_equal():
    from repro.core.table import _as_u32
    from repro.kernels.hash_partition import kernel as hk, ref as hr

    n, p = 300, 8
    cols = [jnp.asarray(RNG.integers(0, 1000, n), jnp.int32),
            jnp.asarray(RNG.normal(size=n), jnp.float32)]
    valid = jnp.asarray(RNG.random(n) < 0.8)
    keys = jnp.stack([_as_u32(c) for c in cols])
    dg, hg, h1g, h2g = hk.hash_partition_pallas(
        keys, valid, p, interpret=True, block_n=128, return_hashes=True)
    de, he, h1e, h2e = hr.hash_partition_full(cols, p, valid)
    np.testing.assert_array_equal(dg, de)
    np.testing.assert_array_equal(hg, he)
    np.testing.assert_array_equal(h1g, h1e)
    np.testing.assert_array_equal(h2g, h2e)
    # and against the user-facing hash
    h1, h2 = hash_columns(cols)
    np.testing.assert_array_equal(h1g, h1)
    np.testing.assert_array_equal(h2g, h2)


def test_hash_partition_ops_dispatcher_force_pallas():
    from repro.core.table import _as_u32
    from repro.kernels import dispatch
    from repro.kernels.hash_partition import kernel as hk, ops as hpops

    n, p = 100, 4
    col = jnp.asarray(RNG.integers(0, 50, n), jnp.int32)
    valid = jnp.ones((n,), bool)
    dispatch.reset_counts()
    d1, h1 = hpops.hash_partition([col], p, valid)
    # off a TPU the one dispatch rule takes the XLA reference
    assert dispatch.counts()["hash_partition"] == {"xla": 1}
    d2, h2, a, b = hk.hash_partition_pallas(
        jnp.stack([_as_u32(col)]), valid, p, interpret=True,
        return_hashes=True)
    np.testing.assert_array_equal(d1, d2)
    np.testing.assert_array_equal(h1, h2)
    e1, e2 = hash_columns([col])
    np.testing.assert_array_equal(a, e1)
    np.testing.assert_array_equal(b, e2)


# ---------------------------------------------------------------------------
# multi-shard: operator-level parity vs single-device + collective count
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


def test_exchange_4way_parity_and_single_collective():
    out = _run_devices("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.core import (Table, DistTable, HPTMTContext, make_mesh,
                                local_context, table_ops)
        mesh = make_mesh((4,), ("data",))
        ctx = HPTMTContext(mesh=mesh)
        one = local_context()
        rng = np.random.default_rng(3)
        n = 128
        cols = {"id": jnp.asarray(rng.integers(0, 40, n).astype(np.int32)),
                "v": jnp.asarray(rng.normal(size=n).astype(np.float32)),
                "w": jnp.asarray(rng.integers(0, 2**31, n).astype(np.uint32))}
        t = Table.from_arrays(cols)

        # shuffle: same row multiset as the single-device identity, 0 overflow
        # (capacity 2x the per-shard row count absorbs hash skew)
        dt = DistTable.from_local(t, ctx, capacity=64)
        sh, ov = table_ops.shuffle(dt, ["id"], ctx=ctx)
        assert int(ov) == 0 and int(sh.num_rows()) == n
        got = sh.to_numpy()
        rows = sorted(zip(got["id"].tolist(), got["w"].tolist(),
                          got["v"].tolist()))
        exp = sorted(zip(np.asarray(cols["id"]).tolist(),
                         np.asarray(cols["w"]).tolist(),
                         np.asarray(cols["v"]).tolist()))
        assert rows == exp, "shuffled row multiset differs"

        # groupby on 4 shards == groupby on 1 device
        g4, _ = table_ops.groupby_aggregate(dt, ["id"], [("v", "sum")],
                                            ctx=ctx)
        g1, _ = table_ops.groupby_aggregate(
            DistTable.from_local(t, one), ["id"], [("v", "sum")], ctx=one)
        a, b = g4.to_numpy(), g1.to_numpy()
        oa, ob = np.argsort(a["id"]), np.argsort(b["id"])
        np.testing.assert_array_equal(a["id"][oa], b["id"][ob])
        np.testing.assert_allclose(a["v_sum"][oa], b["v_sum"][ob],
                                   rtol=1e-5)

        # overflow-triggering bucket: counted, survivors intact
        tiny, ov = table_ops.shuffle(dt, ["id"], bucket_factor=0.25,
                                     ctx=ctx)
        assert int(ov) > 0
        assert int(tiny.num_rows()) + int(ov) == n

        # the traced shuffle contains exactly ONE all_to_all, zero sorts
        jaxpr = str(jax.make_jaxpr(
            lambda d: table_ops.shuffle(d, ["id"], ctx=ctx))(dt))
        assert jaxpr.count("all_to_all") == 1, jaxpr.count("all_to_all")
        assert jaxpr.count("sort[") == 0
        print("PARITY-4WAY-OK")
        """)
    assert "PARITY-4WAY-OK" in out


def test_join_carries_hashes_no_rehash_4way():
    """Post-shuffle join must not re-run the hash chain: the traced join
    jaxpr contains exactly the two pre-shuffle hash sites (left + right),
    each a fused hash_partition, and exactly 2 data AllToAlls."""
    out = _run_devices("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.core import Table, DistTable, HPTMTContext, make_mesh
        from repro.core import table_ops
        mesh = make_mesh((4,), ("data",))
        ctx = HPTMTContext(mesh=mesh)
        rng = np.random.default_rng(0)
        lk = rng.permutation(64).astype(np.int32)
        rk = rng.permutation(64).astype(np.int32)[:48]
        l = DistTable.from_local(Table.from_arrays(
            {"k": jnp.asarray(lk), "a": jnp.asarray(lk, jnp.float32)}),
            ctx, capacity=32)
        r = DistTable.from_local(Table.from_arrays(
            {"k": jnp.asarray(rk), "b": jnp.asarray(rk, jnp.float32)}),
            ctx, capacity=32)
        res, ov = table_ops.join(l, r, ["k"], out_capacity=64, ctx=ctx)
        assert int(ov) == 0
        got = sorted(res.to_numpy()["k"].tolist())
        assert got == sorted(set(lk.tolist()) & set(rk.tolist()))
        jaxpr = str(jax.make_jaxpr(
            lambda a, b: table_ops.join(a, b, ["k"], out_capacity=64,
                                        ctx=ctx))(l, r))
        assert jaxpr.count("all_to_all") == 2  # one per side
        # the murmur mix multiplier appears once per hash site: 2 shuffles
        # (h1+h2 fused) and nothing post-shuffle
        assert jaxpr.count("0xcc9e2d51") <= 2, jaxpr.count("0xcc9e2d51")
        print("JOIN-CARRY-OK")
        """)
    assert "JOIN-CARRY-OK" in out
