"""The decode steps' share of their memory roofline: the bytes every
decode step of a call must move (weights once, the held experts at the
share the batch reaches, the latent cache; ``bench/counts_mla_moe.py``)
over the seconds inside the engine's ``serve.decode`` spans times the
chip's HBM bandwidth, in percent."""
from counts_mla_moe import decode_bytes

SPANS = ("serve.decode",)


def read(run):
    spans = [(s, e) for name, s, e in run.spans if name in SPANS]
    if not spans:
        return None
    t = run.traffic
    moved = len(spans) * decode_bytes(run.config, t["batch"], t["prompt"],
                                      t["generate"])
    seconds = sum(e - s for s, e in spans)
    return 100.0 * moved / (seconds * run.chips * run.peak["hbm_bw"])
