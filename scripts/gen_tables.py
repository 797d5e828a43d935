"""Generate EXPERIMENTS.md tables from dry-run JSON results."""
import json
import sys


def fmt_s(x):
    return f"{x:8.2f}" if x >= 0.01 else f"{x*1e3:6.1f}m"


def _tpu_adjusted(r):
    """Post-hoc TPU-adjusted terms from a JSON record (see roofline.py)."""
    import os
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    from repro.configs import SHAPES, get_config
    from repro.launch import roofline as rl
    roof = r["roofline"]
    if "tpu_adjusted" in roof:
        return roof["tpu_adjusted"]
    cfg = get_config(r["arch"])
    cell = SHAPES[r["shape"]]
    chip = rl.peaks(rl.DRYRUN_KIND)
    meas = rl.Roofline(
        flops=roof["compute_s"] * chip.bf16_flops,
        hbm_bytes=roof["memory_s"] * chip.hbm_bw,
        collectives=rl.CollectiveStats({}, {}, roof["collective_s"]),
        n_chips=r["n_chips"], model_flops=roof["model_flops"])
    return rl.tpu_adjusted_terms(cfg, cell, r["n_chips"], meas)


def tpu_table(path):
    data = json.load(open(path))
    rows = []
    for r in data:
        if r.get("status") != "ok" or "roofline" not in r:
            continue
        roof = r["roofline"]
        adj = _tpu_adjusted(r)
        rows.append(
            "| {arch} | {shape} | {c:.2f} | {m:.2f} | {k:.2f} "
            "| {step:.2f} | {mfu:.1f}% |".format(
                arch=r["arch"], shape=r["shape"], c=roof["compute_s"],
                m=adj["memory_s_tpu"], k=adj["collective_s_tpu"],
                step=adj["step_s_tpu"], mfu=adj["mfu_tpu"] * 100))
    return "\n".join(rows)


def roofline_table(path):
    data = json.load(open(path))
    rows = []
    for r in data:
        if r.get("status") == "skipped":
            rows.append(f"| {r['arch']} | {r['shape']} | — | — | — | — | — "
                        f"| — | skip (full attention) |")
            continue
        if r.get("status") != "ok" or "roofline" not in r:
            continue
        roof = r["roofline"]
        peak = r["memory"]["peak_bytes_per_device"] / 2**30
        rows.append(
            "| {arch} | {shape} | {c:.2f} | {m:.2f} | {k:.2f} | {b} "
            "| {uf:.2f} | {mfu:.1f}% | {peak:.1f} |".format(
                arch=r["arch"], shape=r["shape"],
                c=roof["compute_s"], m=roof["memory_s"],
                k=roof["collective_s"], b=roof["bottleneck"],
                uf=roof["useful_flops_frac"],
                mfu=roof["mfu_at_roofline"] * 100, peak=peak))
    return "\n".join(rows)


def memory_table(path):
    data = json.load(open(path))
    rows = []
    for r in data:
        if r.get("status") == "skipped":
            rows.append(f"| {r['arch']} | {r['shape']} | — | — | skip |")
            continue
        if r.get("status") != "ok":
            continue
        m = r["memory"]
        rows.append(
            "| {arch} | {shape} | {peak:.2f} | {arg:.2f} | ok ({t:.0f}s) |"
            .format(arch=r["arch"], shape=r["shape"],
                    peak=m["peak_bytes_per_device"] / 2**30,
                    arg=m["argument_bytes_per_device"] / 2**30,
                    t=r.get("compile_s", 0)))
    return "\n".join(rows)


if __name__ == "__main__":
    kind, path = sys.argv[1], sys.argv[2]
    print({"roofline": roofline_table, "memory": memory_table,
           "tpu": tpu_table}[kind](path))
