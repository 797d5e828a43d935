"""Model FLOP/s utilization of generation: the forward FLOPs of every
``generate`` call of the window (the causal prefill of the prompts and
each decode step's attention over the cache, ``bench/counts.py``) over the
window and the chips' bf16 peak."""
from counts import lm_generate_flops


def read(run):
    t = run.traffic
    flops = run.units * lm_generate_flops(run.config, t["batch"], t["prompt"],
                                          t["generate"])
    return 100.0 * flops / (run.window_s * run.chips * run.peak["bf16_flops"])
