"""Sort-free hash-join engine vs the sort-merge oracle (DESIGN.md §8).

Three layers of guarantees:

  * parity — ``method="hash"`` output equals ``method="sort"`` bit-exactly
    on valid rows (as multisets) for all four ``how`` modes, duplicate
    keys, NaN/±0.0 float keys, and fan-out overflow at ``max_matches``,
    with equal overflow counts;
  * sort-freedom — the traced jaxpr of the hash join path and of every
    set operator contains zero ``sort`` primitives;
  * overflow contract — fan-out beyond ``max_matches``/``max_probes`` is
    counted, never silently dropped (§2).
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

from repro.core import DistTable, Table, local_context, table_ops
from repro.core.table import hash_columns
from repro.dataframe.frame import DataFrame
from repro.kernels.hash_join import ops as hjops

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
CTX = local_context()
RNG = np.random.default_rng(7)

#: float key pool exercising the bitwise identity: NaN (equal bits match),
#: -0.0 vs +0.0 (distinct), and plain values
KEY_POOL = np.array([0.0, -0.0, 1.0, 2.0, 3.5, np.nan, np.nan, 7.25],
                    np.float32)


def make_dt(cols, capacity=None):
    t = Table.from_arrays({k: jnp.asarray(v) for k, v in cols.items()},
                          capacity=capacity)
    return DistTable.from_local(t, CTX)


def canon_rows(got):
    """Canonical bitwise row multiset: every column viewed as bits, rows
    lexsorted — NaN-safe, ±0.0-distinguishing comparisons."""
    names = sorted(got)
    bits = []
    for k in names:
        a = np.asarray(got[k])
        bits.append(a.view(np.uint32) if a.dtype == np.float32
                    else a.astype(np.int64))
    order = np.lexsort(tuple(reversed(bits)))
    return {k: b[order] for k, b in zip(names, bits)}


def assert_rows_equal(a, b, msg=""):
    ca, cb = canon_rows(a), canon_rows(b)
    assert set(ca) == set(cb), (msg, sorted(ca), sorted(cb))
    for k in ca:
        np.testing.assert_array_equal(ca[k], cb[k], err_msg=f"{msg}:{k}")


def _join_both(l, r, how, mm, out_capacity, window=40):
    h, ovh = table_ops.join(l, r, ["k"], how=how, max_matches=mm,
                            out_capacity=out_capacity, method="hash",
                            ctx=CTX)
    s, ovs = table_ops.join(l, r, ["k"], how=how, max_matches=mm,
                            out_capacity=out_capacity, method="sort",
                            window=window, ctx=CTX)
    return h, int(ovh), s, int(ovs)


# ---------------------------------------------------------------------------
# hash-vs-sort parity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("how", ["inner", "left", "right", "outer"])
def test_hash_join_matches_sort_dup_keys(how):
    lk = np.array([1, 2, 2, 3, 5, 2, 7, 1], np.int32)
    rk = np.array([2, 2, 1, 9, 2, 2], np.int32)
    l = make_dt({"k": lk, "a": np.arange(8, dtype=np.float32)})
    r = make_dt({"k": rk, "b": 10 * np.arange(6, dtype=np.float32)})
    for mm in (1, 2, 4):
        h, ovh, s, ovs = _join_both(l, r, how, mm, 8 * mm + 8)
        assert ovh == ovs, (how, mm)
        assert_rows_equal(h.to_numpy(), s.to_numpy(), f"{how}/mm={mm}")


def test_hash_join_right_outer_semantics():
    l = make_dt({"k": np.array([1, 2, 3], np.int32),
                 "a": np.array([10., 20., 30.], np.float32)})
    r = make_dt({"k": np.array([2, 4], np.int32),
                 "b": np.array([200., 400.], np.float32)})
    right, ov = table_ops.join(l, r, ["k"], how="right", ctx=CTX)
    assert int(ov) == 0
    got = right.to_numpy()
    order = np.argsort(got["k"])
    np.testing.assert_array_equal(got["k"][order], [2, 4])
    np.testing.assert_array_equal(got["b"][order], [200., 400.])
    np.testing.assert_array_equal(got["a"][order], [20., 0.])  # unmatched→0
    np.testing.assert_array_equal(got["_matched"][order], [True, False])

    outer, ov = table_ops.join(l, r, ["k"], how="outer", ctx=CTX)
    assert int(ov) == 0
    got = outer.to_numpy()
    order = np.argsort(got["k"])
    np.testing.assert_array_equal(got["k"][order], [1, 2, 3, 4])
    np.testing.assert_array_equal(got["_matched"][order],
                                  [False, True, False, False])


def test_nan_and_signed_zero_keys_regression():
    """NaN join keys match bitwise; -0.0 and +0.0 never match — on BOTH
    kernels, consistent with the hash identity (the PR 2 groupby fix class:
    value ``==`` would drop NaN matches and cross-match ±0.0)."""
    l = make_dt({"k": np.array([np.nan, -0.0, 1.0], np.float32),
                 "a": np.array([1., 2., 3.], np.float32)})
    r = make_dt({"k": np.array([np.nan, 0.0, 1.0], np.float32),
                 "b": np.array([10., 20., 30.], np.float32)})
    for method in ("hash", "sort"):
        out, ov = table_ops.join(l, r, ["k"], method=method, ctx=CTX)
        assert int(ov) == 0
        got = out.to_numpy()
        # NaN row matched NaN row; 1.0 matched 1.0; -0.0 did NOT match +0.0
        assert len(got["k"]) == 2, method
        assert np.isnan(got["k"]).sum() == 1, method
        np.testing.assert_array_equal(np.sort(got["b"]), [10., 30.])


def test_fanout_beyond_max_matches_is_counted():
    """Matches dropped by the fan-out cap are overflow, never silent (§2)."""
    l = make_dt({"k": np.array([1, 2], np.int32),
                 "a": np.array([1., 2.], np.float32)})
    r = make_dt({"k": np.array([2, 2, 2], np.int32),
                 "b": np.array([5., 6., 7.], np.float32)})
    for method in ("hash", "sort"):
        out, ov = table_ops.join(l, r, ["k"], max_matches=1, out_capacity=8,
                                 method=method, ctx=CTX)
        assert int(ov) == 2, method  # 3 matches, 1 kept
        got = out.to_numpy()
        # deterministic survivor: the FIRST duplicate in right-row order
        np.testing.assert_array_equal(got["b"], [5.])


def test_hash_join_max_probes_exhaustion_counted():
    """Probe chains longer than max_probes surface as overflow."""
    l = make_dt({"k": np.zeros(4, np.int32),
                 "a": np.arange(4, dtype=np.float32)})
    r = make_dt({"k": np.zeros(16, np.int32),
                 "b": np.arange(16, dtype=np.float32)})
    out, ov = table_ops.join(l, r, ["k"], max_matches=16, out_capacity=64,
                             method="hash", max_probes=4, ctx=CTX)
    assert int(ov) > 0  # 16-deep duplicate chain cannot build/probe in 4


@settings(max_examples=40, deadline=None)
@given(lidx=st.lists(st.integers(0, len(KEY_POOL) - 1), min_size=1,
                     max_size=24),
       ridx=st.lists(st.integers(0, len(KEY_POOL) - 1), min_size=1,
                     max_size=24),
       how=st.sampled_from(["inner", "left", "right", "outer"]),
       mm=st.integers(1, 4))
def test_hash_join_parity_property(lidx, ridx, how, mm):
    """Bit-exact hash-vs-sort parity: duplicate keys, NaN/±0.0 keys, all
    four how modes, fan-out overflow at max_matches — equal row multisets
    (bitwise) and equal overflow counts.  Payloads are key-derived so the
    surviving rows under fan-out truncation are comparable as multisets
    regardless of which equal-key duplicate was kept."""
    lk, rk = KEY_POOL[lidx], KEY_POOL[ridx]
    l = make_dt({"k": lk, "a": np.arange(len(lk), dtype=np.float32)})
    r = make_dt({"k": rk,
                 "b": rk.view(np.uint32).astype(np.float32)})
    out_cap = len(lk) * mm + len(rk) + 4
    h, ovh, s, ovs = _join_both(l, r, how, mm, out_cap)
    assert ovh == ovs
    assert_rows_equal(h.to_numpy(), s.to_numpy(), f"{how}/mm={mm}")


# ---------------------------------------------------------------------------
# sort-freedom (jaxpr-asserted)
# ---------------------------------------------------------------------------
def _sort_count(fn, *args) -> int:
    return str(jax.make_jaxpr(fn)(*args)).count("sort[")


def test_hash_join_jaxpr_has_zero_sorts():
    l = make_dt({"k": np.arange(64, dtype=np.int32),
                 "a": np.ones(64, np.float32)})
    r = make_dt({"k": np.arange(64, dtype=np.int32),
                 "b": np.ones(64, np.float32)})
    for how in ("inner", "left", "right", "outer"):
        assert _sort_count(
            lambda a, b, how=how: table_ops.join(
                a, b, ["k"], how=how, method="hash", ctx=CTX), l, r) == 0
    # the oracle really does sort — the assertion above is not vacuous
    assert _sort_count(
        lambda a, b: table_ops.join(a, b, ["k"], method="sort", ctx=CTX),
        l, r) > 0


def test_setops_jaxpr_have_zero_sorts():
    a = make_dt({"x": np.arange(32, dtype=np.int32)})
    b = make_dt({"x": np.arange(16, 48, dtype=np.int32)})
    for op in (table_ops.union, table_ops.difference, table_ops.intersect):
        assert _sort_count(lambda u, v, op=op: op(u, v, ctx=CTX), a, b) == 0


def test_groupby_hash_jaxpr_has_zero_sorts():
    dt = make_dt({"k": np.arange(64, dtype=np.int32),
                  "v": np.ones(64, np.float32)})
    assert _sort_count(
        lambda t: table_ops.groupby_aggregate(
            t, ["k"], [("v", "sum")], method="hash", ctx=CTX), dt) == 0


# ---------------------------------------------------------------------------
# set ops on the hash primitives
# ---------------------------------------------------------------------------
def test_setops_nan_rows_bitwise():
    """Set-op row identity is bitwise (consistent with the hashes):
    equal-bit NaN rows deduplicate and subtract; ±0.0 stay distinct."""
    a = make_dt({"x": np.array([np.nan, np.nan, 1.0, -0.0], np.float32)})
    b = make_dt({"x": np.array([np.nan, 0.0], np.float32)})
    u, ov = table_ops.union(a, b, ctx=CTX)
    assert int(ov) == 0
    bits = np.sort(u.to_numpy()["x"].view(np.uint32))
    # {nan, 1.0, -0.0, +0.0} — one NaN (deduped), both zero signs
    assert len(bits) == 4
    d, _ = table_ops.difference(a, b, ctx=CTX)
    got = d.to_numpy()["x"]
    # NaN rows removed (present in b bitwise); -0.0 kept (+0.0 != -0.0)
    assert len(got) == 2
    assert np.sort(got.view(np.uint32)).tolist() == np.sort(
        np.array([1.0, -0.0], np.float32).view(np.uint32)).tolist()
    i, _ = table_ops.intersect(a, b, ctx=CTX)
    got = i.to_numpy()["x"]
    assert len(got) == 1 and np.isnan(got[0])


def test_build_table_every_valid_row_has_a_slot():
    n = 500
    cols = {"k": jnp.asarray(RNG.integers(0, 40, n).astype(np.int32))}
    h1, h2 = hash_columns([cols["k"]])
    valid = jnp.arange(n) < 450
    table, unplaced = hjops.build_table(h1, h2, valid, 4096, 64)
    t = np.asarray(table)
    assert int(unplaced) == 0
    placed = np.sort(t[t >= 0])
    np.testing.assert_array_equal(placed, np.arange(450))  # own slot each


# ---------------------------------------------------------------------------
# DataFrame surface
# ---------------------------------------------------------------------------
def test_dataframe_join_kwargs():
    df = DataFrame.from_dict({"k": np.array([1, 2, 3], np.int32),
                              "a": np.ones(3, np.float32)}, CTX)
    other = DataFrame.from_dict({"k": np.array([2, 3, 4], np.int32),
                                 "b": np.ones(3, np.float32)}, CTX)
    with pytest.raises(ValueError, match="method='bogus'"):
        df.join(other, on=["k"], method="bogus")
    with pytest.raises(ValueError, match="how='sideways'"):
        df.join(other, on=["k"], how="sideways")
    with pytest.raises(ValueError, match="max_matches"):
        df.join(other, on=["k"], max_matches=0)
    got = df.join(other, on=["k"], how="outer", method="hash",
                  max_matches=2).to_numpy()
    assert sorted(got["k"].tolist()) == [1, 2, 3, 4]
    # the sort oracle stays reachable through the same surface
    got = df.join(other, on=["k"], method="sort", window=8).to_numpy()
    assert sorted(got["k"].tolist()) == [2, 3]


# ---------------------------------------------------------------------------
# 4-device mesh: parity vs single-shard oracle + collective/sort counts
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


def test_hash_join_and_setops_4way():
    _run_devices("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.core import (Table, DistTable, HPTMTContext, make_mesh,
                                local_context, table_ops)
        mesh = make_mesh((4,), ("data",))
        ctx = HPTMTContext(mesh=mesh)
        one = local_context()
        rng = np.random.default_rng(9)
        n = 256
        lk = rng.integers(0, 64, n).astype(np.int32)
        rk = rng.integers(0, 64, n).astype(np.int32)
        lt = Table.from_arrays({"k": jnp.asarray(lk),
                                "a": jnp.asarray(lk * 2, jnp.float32)})
        rt = Table.from_arrays({"k": jnp.asarray(rk),
                                "b": jnp.asarray(rk * 3, jnp.float32)})

        def rows(dt, cols):
            g = dt.to_numpy()
            return sorted(zip(*(g[c].tolist() for c in cols)))

        for how in ("inner", "left", "right", "outer"):
            got, ovd = table_ops.join(
                DistTable.from_local(lt, ctx, capacity=128),
                DistTable.from_local(rt, ctx, capacity=128),
                ["k"], how=how, max_matches=8, out_capacity=2048,
                method="hash", ctx=ctx)
            ref, ovo = table_ops.join(
                DistTable.from_local(lt, one), DistTable.from_local(rt, one),
                ["k"], how=how, max_matches=8, out_capacity=8192,
                method="hash", ctx=one)
            assert int(ovd) == 0 and int(ovo) == 0, (how, int(ovd), int(ovo))
            cols = ("k", "a", "b", "_matched")
            assert rows(got, cols) == rows(ref, cols), how

        # one packed AllToAll per join side, zero sorts, on the mesh too
        jaxpr = str(jax.make_jaxpr(lambda a, b: table_ops.join(
            a, b, ["k"], method="hash", ctx=ctx))(
            DistTable.from_local(lt, ctx, capacity=128),
            DistTable.from_local(rt, ctx, capacity=128)))
        assert jaxpr.count("all_to_all") == 2, jaxpr.count("all_to_all")
        assert jaxpr.count("sort[") == 0

        # set ops: 4-shard == 1-shard, sort-free on the mesh
        at = Table.from_arrays({"x": jnp.asarray(
            rng.integers(0, 40, n).astype(np.int32))})
        bt = Table.from_arrays({"x": jnp.asarray(
            rng.integers(20, 60, n).astype(np.int32))})
        for op in (table_ops.union, table_ops.difference,
                   table_ops.intersect):
            got, _ = op(DistTable.from_local(at, ctx, capacity=128),
                        DistTable.from_local(bt, ctx, capacity=128),
                        ctx=ctx, out_capacity=1024)
            ref, _ = op(DistTable.from_local(at, one),
                        DistTable.from_local(bt, one), ctx=one)
            assert (sorted(got.to_numpy()["x"].tolist())
                    == sorted(ref.to_numpy()["x"].tolist())), op.__name__
        print("4way hash join + set ops OK")
    """)
