"""The table generator and the query-3 reference on hand-made tables."""
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for _p in (os.path.join(ROOT, "src"), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from tablegen import make_tables  # noqa: E402

CFG = {
    "pages_rows": 64, "visits_rows": 1000,
    "tables": {
        "pages": {"columns": {
            "url": {"dtype": "int32", "dist": "permutation", "fixed": True},
            "rank": {"dtype": "int32", "dist": "uniform_int", "low": 1,
                     "high": 5}}},
        "visits": {"columns": {
            "url": {"dtype": "int32", "dist": "key_of", "table": "pages",
                    "column": "url"},
            "revenue": {"dtype": "float32", "dist": "uniform", "low": 2.0,
                        "high": 3.0}}},
    },
}
QUERY = {"scan": "visits", "filter": [["day", ">=", 1]],
         "join": {"table": "pages", "on": ["url"]},
         "groupby": {"keys": ["ip"], "aggs": [["revenue", "sum"],
                                              ["rank", "mean"]]},
         "topk": {"by": "revenue_sum", "k": 1}}


def test_generator_follows_each_distribution():
    t = make_tables(CFG, 7)
    pages, visits = t["pages"], t["visits"]
    assert sorted(pages["url"]) == list(range(64))
    assert pages["rank"].min() >= 1 and pages["rank"].max() <= 4
    assert len(visits["url"]) == 1000
    assert np.isin(visits["url"], pages["url"]).all()
    assert visits["revenue"].dtype == np.float32
    assert ((visits["revenue"] >= 2) & (visits["revenue"] < 3)).all()


def test_generator_is_a_function_of_the_seed():
    a, b, c = (make_tables(CFG, s) for s in (2**31 + 5, 2**31 + 5, 3))
    assert all(np.array_equal(a["visits"][k], b["visits"][k])
               for k in a["visits"])
    assert not np.array_equal(a["visits"]["revenue"], c["visits"]["revenue"])


def test_fixed_columns_are_the_same_for_every_seed():
    a, c = make_tables(CFG, 2**31 + 5), make_tables(CFG, 3)
    assert np.array_equal(a["pages"]["url"], c["pages"]["url"])
    assert not np.array_equal(a["pages"]["rank"], c["pages"]["rank"])


def test_unknown_distribution_is_an_error():
    bad = {"x_rows": 3, "tables": {"x": {"columns": {
        "c": {"dtype": "int32", "dist": "zipf"}}}}}
    with pytest.raises(ValueError):
        make_tables(bad, 0)


@pytest.fixture(scope="module")
def ref():
    import harness

    return harness.load_module(os.path.join(BENCH, "configs",
                                            "amplab-bdb-s25_ref.py"))


@pytest.fixture(scope="module")
def exp(ref):
    # ip 1: revenues 1 + 2 (day 0 cut, so 2 only), ranks of url 0: 10;
    # ip 2: revenues 4 + 5 at urls 0, 1 (ranks 10, 30): sum 9, mean 20;
    # ip 3: revenue 6 at url 9, which no page has: joined away
    tables = {
        "visits": {"ip": np.array([1, 1, 2, 2, 3], np.int32),
                   "day": np.array([0, 1, 1, 1, 1], np.int32),
                   "url": np.array([0, 0, 0, 1, 9], np.int32),
                   "revenue": np.array([1, 2, 4, 5, 6], np.float32)},
        "pages": {"url": np.array([1, 0], np.int32),
                  "rank": np.array([30, 10], np.int32)},
    }
    return ref.expected(tables, QUERY)


def test_reference_groups_and_top(ref, exp):
    _, groups = exp
    assert list(groups["ip"]) == [1, 2]
    assert list(groups["revenue_sum"]) == [2.0, 9.0]
    assert list(groups["rank_mean"]) == [10.0, 20.0]
    top = ref.top(exp)
    assert list(top["ip"]) == [2] and list(top["revenue_sum"]) == [9.0]


def test_the_right_answer_compares_clean(ref, exp):
    got = {"ip": np.array([2]), "revenue_sum": np.array([9.0], np.float32),
           "rank_mean": np.array([20.0], np.float32)}
    assert ref.compare(got, exp) == (0, 0.0)


def test_an_outranked_answer_is_an_error(ref, exp):
    # ip 1 has its own sums right, but ip 2 outranks it by 9 - 2 = 7,
    # over ip 2's root sum of squares sqrt(16 + 25)
    got = {"ip": np.array([1]), "revenue_sum": np.array([2.0]),
           "rank_mean": np.array([10.0])}
    bad, err = ref.compare(got, exp)
    assert bad == 0 and err == pytest.approx(7 / np.sqrt(41))


def test_a_wrong_aggregate_is_an_error(ref, exp):
    # a mean off by 1 over 2 rows, against ranks' root sum of squares
    got = {"ip": np.array([2]), "revenue_sum": np.array([9.0]),
           "rank_mean": np.array([21.0])}
    bad, err = ref.compare(got, exp)
    assert bad == 0 and err == pytest.approx(2 / np.sqrt(100 + 900))


def test_a_missing_row_or_group_is_a_mismatch(ref, exp):
    none = {"ip": np.array([], np.int32), "revenue_sum": np.array([]),
            "rank_mean": np.array([])}
    assert ref.compare(none, exp)[0] == 1
    ghost = {"ip": np.array([3]), "revenue_sum": np.array([6.0]),
             "rank_mean": np.array([0.0])}
    assert ref.compare(ghost, exp)[0] == 1
