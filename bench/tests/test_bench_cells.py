"""Each cell driven end to end on the CPU at a tiny size, past the
harness's look for a chip: the system agrees with the plain reference, and
with the timed path broken underneath, ``correct`` comes out false."""
import dataclasses
import os
import sys
import time

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for _p in (os.path.join(ROOT, "src"), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)


#: a tiny deployment: rows, and a sourceIP pool small enough that groups
#: hold several rows
TABLE = {"uservisits_rows": 1 << 16, "rankings_rows": 1 << 12,
         "sourceIP": 1 << 12, "out_capacity": 1 << 13}
#: the published widths, one layer
LM_LAYERS = 1
TRAFFIC = {
    "amplab-bdb-s25.q3c": {},
    "smollm-360m.train_b8_s2048": {
        "batch": 2, "seq": 128, "reference_rows": 1,
        "corpus": {"n_docs": 64, "mean_doc_len": 64,
                   "quality_threshold": 0.3}},
    "smollm-360m.decode_b64": {"batch": 2, "prompt": 64, "generate": 16,
                               "checked_per_call": 2, "reference_rows": 1},
}
SEED = 2**31 + 11


def tiny_cell(name, monkeypatch, traffic=None):
    import harness
    import peaks

    monkeypatch.setitem(peaks.PEAKS, "cpu", {"bf16_flops": 1e12,
                                             "int8_ops": 1e12,
                                             "hbm_bw": 1e11,
                                             "hbm_bytes": 1e10})
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = harness.Cell(bench, name)
    if cell.config["system"] == "table":
        cfg = cell.config
        cfg["uservisits_rows"] = TABLE["uservisits_rows"]
        cfg["rankings_rows"] = TABLE["rankings_rows"]
        cfg["tables"]["uservisits"]["columns"]["sourceIP"]["high"] = \
            TABLE["sourceIP"]
        cell.traffic["query"]["groupby"]["out_capacity"] = \
            TABLE["out_capacity"]
    else:
        from repro.configs import get_config

        cell.config["num_hidden_layers"] = LM_LAYERS
        pcfg = dataclasses.replace(get_config(cell.config["arch"]),
                                   n_layers=LM_LAYERS)
        monkeypatch.setattr(cell.driver(), "program_config", lambda c: pcfg)
    cell.traffic.update(TRAFFIC[name] if traffic is None else traffic)
    return cell


def run_cell(cell, trace=False):
    import jax

    import harness

    return harness.execute(cell, SEED, 0.5, trace, jax.devices(),
                           time.time())


@pytest.mark.parametrize("name", sorted(TRAFFIC))
def test_cell_agrees_with_reference(name, monkeypatch):
    cell = tiny_cell(name, monkeypatch)
    res = run_cell(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {cell.traffic["metric"], "setup_s"}
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1


def _alter_table_answer(monkeypatch):
    from repro.dataframe.frame import DataFrame

    real = DataFrame.to_numpy

    def altered(self):
        out = real(self)
        out["adRevenue_sum"] = out["adRevenue_sum"] * np.float32(1.01)
        return out

    monkeypatch.setattr(DataFrame, "to_numpy", altered)


def _drop_half_the_rows(monkeypatch):
    import repro.io

    real = repro.io.write_dataset

    def half(root, shards, **kw):
        if root.endswith("uservisits"):
            (cols, n), = shards
            shards = [({k: v[:n // 2] for k, v in cols.items()}, n // 2)]
        return real(root, shards, **kw)

    monkeypatch.setattr(repro.io, "write_dataset", half)


def _state_unchanged(monkeypatch):
    import repro.train.train_step as ts

    real = ts.make_train_step

    def make(cfg, tcfg):
        step = real(cfg, tcfg)

        def unchanged(state, batch):
            _, metrics = step(state, batch)
            return state, metrics

        return unchanged

    monkeypatch.setattr(ts, "make_train_step", make)


def _half_batch(monkeypatch):
    import repro.train.train_step as ts

    real = ts.make_train_step

    def make(cfg, tcfg):
        step = real(cfg, tcfg)

        def half(state, batch):
            n = batch["tokens"].shape[0] // 2
            return step(state, {k: v[:n] for k, v in batch.items()})

        return half

    monkeypatch.setattr(ts, "make_train_step", make)


def _alter_loss(monkeypatch):
    import repro.train.train_step as ts

    real = ts.make_train_step

    def make(cfg, tcfg):
        step = real(cfg, tcfg)

        def altered(state, batch):
            state, metrics = step(state, batch)
            return state, {**metrics, "loss": metrics["loss"] + 0.1}

        return altered

    monkeypatch.setattr(ts, "make_train_step", make)


def _alter_token(monkeypatch):
    from repro.serve.engine import Engine

    real = Engine.generate

    def altered(self, prompts, n_tokens, **kw):
        out = np.array(real(self, prompts, n_tokens, **kw))
        out[:, -1] = (out[:, -1] + 1) % self.cfg.vocab_size
        return out

    monkeypatch.setattr(Engine, "generate", altered)


FAULTS = [
    ("amplab-bdb-s25.q3c", _alter_table_answer),
    ("amplab-bdb-s25.q3c", _drop_half_the_rows),
    ("smollm-360m.train_b8_s2048", _state_unchanged),
    ("smollm-360m.train_b8_s2048", _half_batch),
    ("smollm-360m.train_b8_s2048", _alter_loss),
    ("smollm-360m.decode_b64", _alter_token),
]


@pytest.mark.parametrize("name,fault", FAULTS,
                         ids=[f"{n}-{f.__name__.strip('_')}" for n, f in FAULTS])
def test_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    cell = tiny_cell(name, monkeypatch)
    fault(monkeypatch)
    res = run_cell(cell)
    assert res["correct"] is False, res["checks"]
