"""Model FLOP/s utilization of generation on a chip's share of the routed
experts: the FLOPs of every ``generate`` call of the window (the prefill
in the expanded attention form, each decode step in the latent form,
routed experts at the held share; ``bench/counts_mla_moe.py``) over the
window and the chips' bf16 peak."""
from counts_mla_moe import generate_flops


def read(run):
    t = run.traffic
    flops = run.units * generate_flops(run.config, t["batch"], t["prompt"],
                                       t["generate"])
    return 100.0 * flops / (run.window_s * run.chips * run.peak["bf16_flops"])
