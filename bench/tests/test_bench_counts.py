"""FLOP and byte counts against values worked out by hand."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for _p in (os.path.join(ROOT, "src"), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import counts  # noqa: E402

@pytest.fixture(scope="module")
def smollm():
    with open(os.path.join(BENCH, "configs", "smollm-360m.json")) as f:
        return json.load(f)


def test_matmul_params_of_smollm(smollm):
    # per layer: q 960*960, k and v 960*320 each, o 960*960, SwiGLU 3*960*2560
    layer = 921_600 + 2 * 307_200 + 921_600 + 7_372_800
    assert layer == 9_830_400
    # 32 layers and the tied head 49152*960
    assert counts.lm_matmul_params(smollm) == 32 * layer + 47_185_920 \
        == 361_758_720


def test_train_flops_per_token(smollm):
    # forward: 2 * 361,758,720 + attention 32 layers * 4 * 960 * 1024.5
    fwd = 723_517_440 + 125_890_560
    assert counts.lm_train_flops_per_token(smollm, 2048) == 3 * fwd \
        == 2_548_224_000


def test_generate_flops(smollm):
    # batch 2, prompt 4, 3 tokens: prefill of 8 tokens at mean context 2.5,
    # then 2 decode steps of 2 rows at contexts 5 and 6 (mean 5.5)
    p = 2 * 361_758_720
    a = 32 * 4 * 960
    want = 8 * (p + a * 2.5) + 4 * (p + a * 5.5)
    assert counts.lm_generate_flops(smollm, 2, 4, 3) == pytest.approx(want)
