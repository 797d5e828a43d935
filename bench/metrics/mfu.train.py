"""Model FLOP/s utilization of training: forward and backward FLOPs per
token counted from the configuration's shapes (nothing recomputed
counted, ``bench/counts.py``) times the tokens per second of the window,
over the chips' bf16 peak."""
from counts import lm_train_flops_per_token


def read(run):
    rate = run.work / run.window_s
    flops = lm_train_flops_per_token(run.config, run.traffic["seq"]) * rate
    return 100.0 * flops / (run.chips * run.peak["bf16_flops"])
