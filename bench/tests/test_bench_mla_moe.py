"""The DeepSeek-V2-Lite serving cell driven end to end on the CPU at a
tiny size, past the harness's look for a chip: the system agrees with the
plain reference, and with the timed path broken underneath (a served
token altered, a held expert's tokens dropped, the held experts' output
zeroed, their weights shifted by one expert) ``correct`` comes out false,
through the check that each fault is for.  Also the cell's counts against values worked out by hand."""
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

CELL = "deepseek-v2-lite-ep8.decode_b32_p2048"
#: the block at a tiny size: every kind of layer, 4 of 16 experts held
TINY = {"hidden_size": 64, "intermediate_size": 128,
        "moe_intermediate_size": 32, "num_attention_heads": 4,
        "num_key_value_heads": 4, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "num_hidden_layers": 3, "vocab_size": 256, "n_routed_experts": 4,
        "published": {"n_routed_experts": 16}, "experts_held": [0, 4]}
TRAFFIC = {"batch": 2, "prompt": 48, "generate": 12, "checked_per_call": 2,
           "reference_rows": 1}
SEED = 2**31 + 23


def tiny_cell(monkeypatch):
    import harness
    import lmcheck_mla_moe
    import peaks
    from repro.configs import get_config

    monkeypatch.setitem(peaks.PEAKS, "cpu", {"bf16_flops": 1e12,
                                             "int8_ops": 1e12,
                                             "hbm_bw": 1e11,
                                             "hbm_bytes": 1e10})
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = harness.Cell(bench, CELL)
    cell.config.update(TINY)
    pcfg = dataclasses.replace(get_config(cell.config["arch"]),
                               **lmcheck_mla_moe.fields(cell.config))
    monkeypatch.setattr(cell.driver(), "program_config", lambda c: pcfg)
    cell.traffic.update(TRAFFIC)
    return cell


def run_cell(cell, trace=False):
    import jax

    import harness

    return harness.execute(cell, SEED, 0.5, trace, jax.devices(),
                           time.time())


def test_cell_agrees_with_reference(monkeypatch):
    from repro.kernels import dispatch

    cell = tiny_cell(monkeypatch)
    counts = {}     # the timed path's, before the checks run their own
    drv = cell.driver().Driver
    release = drv.release
    monkeypatch.setattr(drv, "release", lambda self: (
        counts.update(dispatch.counts()), release(self)))
    res = run_cell(cell)
    assert res["correct"], res["checks"]
    assert res["checks"]["moe_dropped"]["value"] == 0
    assert set(res["metrics"]) == {"decode_tokens_per_s", "setup_s"}
    assert counts["decode_attention"] == {"latent": 2}   # dense + scanned
    assert counts["moe"] == {"held_dropless": 2}         # prefill + decode


def test_traced_cell_reports_its_layer_metrics(monkeypatch):
    cell = tiny_cell(monkeypatch)
    res = run_cell(cell, trace=True)
    assert res["correct"], res["checks"]
    got = res["metrics"]
    # the CPU trace has no device plane: the idle share reads nothing
    assert set(got) == {"serve.prefill_share.mla_moe",
                        "hbm.decode_roofline.mla_moe", "mfu.decode.mla_moe"}
    for name in ("serve.prefill_share.mla_moe", "hbm.decode_roofline.mla_moe",
                 "mfu.decode.mla_moe"):
        assert got[name]["value"] > 0 and got[name]["unit"] == "%"


def _alter_token(monkeypatch):
    from repro.serve.engine import Engine

    real = Engine.generate

    def altered(self, prompts, n_tokens, **kw):
        out = np.array(real(self, prompts, n_tokens, **kw))
        out[:, -1] = (out[:, -1] + 1) % self.cfg.vocab_size
        return out

    monkeypatch.setattr(Engine, "generate", altered)


def _drop_held_tokens(monkeypatch):
    """A held layer whose buffer has one row per token: pairs past it
    are dropped."""
    from repro.models import moe

    monkeypatch.setattr(moe, "_held_rows", lambda tokens, k, n_held: tokens)


def _zero_routed(monkeypatch):
    """A held layer that computes its pairs and adds nothing of them:
    the counters read as if all were well."""
    import jax.numpy as jnp

    from repro.models import moe

    real = moe._held_chunk

    def zeroed(*args):
        y, *rest = real(*args)
        return (jnp.zeros_like(y), *rest)

    monkeypatch.setattr(moe, "_held_chunk", zeroed)


def _shift_experts(monkeypatch):
    """A held layer that reads each expert's weights from its neighbour."""
    import jax.numpy as jnp

    from repro.models import moe

    real = moe._held_chunk

    def shifted(params, cfg, *args):
        p = {**params, **{w: jnp.roll(params[w], 1, axis=0)
                          for w in ("w_gate", "w_in", "w_out")}}
        return real(p, cfg, *args)

    monkeypatch.setattr(moe, "_held_chunk", shifted)


@pytest.mark.parametrize("fault,caught_by", [
    (_alter_token, "served_gap"), (_drop_held_tokens, "moe_dropped"),
    (_zero_routed, "routed_gap"), (_shift_experts, "routed_gap")],
    ids=["alter_token", "drop_held_tokens", "zero_routed", "shift_experts"])
def test_broken_timed_path_is_not_correct(fault, caught_by, monkeypatch):
    cell = tiny_cell(monkeypatch)
    fault(monkeypatch)
    res = run_cell(cell)
    assert res["correct"] is False, res["checks"]
    check = res["checks"][caught_by]
    assert check["value"] > check["limit"], res["checks"]
    if caught_by == "routed_gap":       # no counter sees these faults
        assert res["checks"]["moe_dropped"]["value"] == 0


def test_generate_counts_of_the_published_cell():
    import json

    import counts_mla_moe as C

    with open(os.path.join(BENCH, "configs", "deepseek-v2-lite-ep8.json")) as f:
        cfg = json.load(f)
    # attention per layer: q 2048*3072, down-kv 2048*576, o 2048*2048,
    # up-kv 512*4096; 27 layers, bf16
    attn = 27 * (6_291_456 + 1_179_648 + 4_194_304 + 2_097_152)
    assert attn == 371_589_120
    # one decode step at B 32 over a context of 2304: the weights once
    # (held experts at the 1 - (58/64)^32 they reach), the cache read
    reach = 1 - (58 / 64) ** 32
    weights = (attn + 27 * (2048 + 512) + 3 * 2048 * 10944 + 2048
               + 26 * (2048 * 64 + 3 * 2048 * 2816 + 2048
                       + reach * 8 * 3 * 2048 * 1408)
               + 2048 * 102400 + 2048 + 32 * 2048)
    cache = 27 * 576 * 32 * 2305
    assert C.decode_step_bytes(cfg, 32, 2304) == pytest.approx(
        2 * (weights + cache))
    assert 7.7e9 < C.decode_step_bytes(cfg, 32, 2304) < 8.1e9
    # a call: 511 steps at the mean context 2048 + 1 + 255
    assert C.decode_bytes(cfg, 32, 2048, 512) == pytest.approx(
        511 * C.decode_step_bytes(cfg, 32, 2304))
    # prefill FLOPs: ~157 TFLOP for 32 x 2048 tokens
    assert 1.4e14 < C.prefill_flops(cfg, 32, 2048) < 1.7e14
