"""Plain numpy reference of the Big Data Benchmark's query 3.

Evaluates a query of a traffic file (scan → filter → inner join on a key
→ groupby-aggregate → the top ``k`` groups by one aggregate) straight
from the generator's arrays, in float64, and compares a result with it.
Nothing of the program is imported.

``precision="bfloat16"`` rounds the values an aggregate reads, integer
or real, to bfloat16 first: the control, the reference one precision
below the float32 the configuration states.

A result is judged by what it says: each row names a group and gives its
aggregates, and claims that no group outranks it.  Two numbers come out:

  * ``mismatches`` — rows too many or too few, and rows naming a group
    that does not exist: exact, so its limit is 0;
  * ``err`` — the largest error of a row's aggregate against the
    reference's value of that group, and the largest amount by which a
    group left out outranks the last row, each as a share of the root sum
    of squares of the values aggregated (the scale of the rounding error
    of a sum of those values; a mean's error is taken times its count).
"""
from __future__ import annotations

import numpy as np

OPS = {"<": np.less, "<=": np.less_equal, ">": np.greater,
       ">=": np.greater_equal, "==": np.equal, "!=": np.not_equal}


def _round(x: np.ndarray, precision: str) -> np.ndarray:
    if precision == "bfloat16":
        import ml_dtypes

        return x.astype(ml_dtypes.bfloat16).astype(np.float64)
    return x.astype(np.float64)


def joined(tables: dict, query: dict) -> dict:
    """Rows of scan → filter → inner join, in the fact table's order."""
    fact = tables[query["scan"]]
    keep = np.ones(len(next(iter(fact.values()))), bool)
    for col, op, val in query.get("filter", []):
        keep &= OPS[op](fact[col], val)
    rows = {k: v[keep] for k, v in fact.items()}
    j = query.get("join")
    if j:
        dim = tables[j["table"]]
        (key,) = j["on"]
        order = np.argsort(dim[key], kind="stable")
        pos = np.searchsorted(dim[key][order], rows[key])
        pos = np.minimum(pos, len(order) - 1)
        hit = dim[key][order][pos] == rows[key]
        rows = {k: v[hit] for k, v in rows.items()}
        idx = order[pos[hit]]
        for k, v in dim.items():
            if k not in rows:
                rows[k] = v[idx]
    return rows


def groupby(rows: dict, spec: dict, precision: str = "float64") -> dict:
    """Every group: its key, and for each ``(col, agg)`` its value, the
    root sum of squares of what it aggregates and their count.  One key
    column."""
    (key,) = spec["keys"]
    uniq, inv = np.unique(rows[key], return_inverse=True)
    inv = inv.reshape(-1)
    g = len(uniq)
    cnt = np.bincount(inv, minlength=g)
    out = {key: uniq, "n": cnt}
    for col, agg in spec["aggs"]:
        v = _round(rows[col], precision)
        s = np.bincount(inv, weights=v, minlength=g)
        out[f"{col}_{agg}"] = {"sum": s, "count": cnt.astype(np.float64),
                               "mean": s / np.maximum(cnt, 1)}[agg]
        out[f"{col}_{agg}.scale"] = np.sqrt(np.bincount(
            inv, weights=v * v, minlength=g))
    return out


def expected(tables: dict, query: dict, precision: str = "float64"):
    """Every group of the query before its top ``k``, worked out once per
    run."""
    return query, groupby(joined(tables, query), query["groupby"], precision)


def top(exp) -> dict:
    """The reference's own answer: its top ``k`` rows."""
    query, groups = exp
    t = query["topk"]
    order = np.argsort(-groups[t["by"]], kind="stable")[:t["k"]]
    return {k: v[order] for k, v in groups.items() if "." not in k
            and k != "n"}


def compare(got: dict, exp) -> tuple:
    """``(mismatches, err)`` of a result against the float64 reference
    ``exp`` (:func:`expected`)."""
    query, groups = exp
    spec, t = query["groupby"], query["topk"]
    (key,) = spec["keys"]
    k = min(t["k"], len(groups[key]))
    ids = np.asarray(got[key])
    bad = abs(len(ids) - k)
    pos = np.searchsorted(groups[key], ids)
    pos = np.minimum(pos, len(groups[key]) - 1)
    real = groups[key][pos] == ids
    bad += int(np.sum(~real))
    pos = pos[real]
    err = 0.0
    for col, agg in spec["aggs"]:
        name = f"{col}_{agg}"
        g = np.asarray(got[name], np.float64)[real]
        d = np.abs(g - groups[name][pos])
        if agg == "mean":
            d = d * groups["n"][pos]
        scale = np.maximum(groups[name + ".scale"][pos], 1e-30)
        err = max(err, float(np.max(d / scale)) if len(d) else 0.0)
    # no group left out outranks the last row
    if len(pos):
        by = groups[t["by"]]
        left_out = np.ones(len(by), bool)
        left_out[pos] = False
        if left_out.any():
            best = int(np.argmax(np.where(left_out, by, -np.inf)))
            last = float(np.min(np.asarray(got[t["by"]], np.float64)[real]))
            err = max(err, (by[best] - last)
                      / max(groups[t["by"] + ".scale"][best], 1e-30))
    return bad, err


def control(tables: dict, query: dict) -> dict:
    """The control's result: the reference computed in bfloat16."""
    return top(expected(tables, query, precision="bfloat16"))
