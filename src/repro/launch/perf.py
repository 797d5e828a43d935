"""Perf-iteration tool: lower one cell (with config/rule overrides), report
the three roofline terms and the largest collective/memory contributors.

    python -m repro.launch.perf --arch smollm-360m --shape prefill_32k \
        [--set key=value ...] [--rule axis=meshaxis ...] [--top 10]

Each hypothesis→change→measure cycle in EXPERIMENTS.md §Perf is one
invocation of this tool.

Importing this module is side-effect free: the 512-host-device XLA flag
the CLI needs is set under ``__main__`` only (before jax initializes),
never at import time — ``import repro.launch.perf`` from a test or a
library must not reconfigure the process's device topology.
"""
import argparse
import dataclasses
import sys

from repro.telemetry.audit import top_collectives as _top_collectives


def measure(arch, shape_name, set_overrides=None, rule_overrides=None,
            top=10, show_mem=False, micro=None):
    import jax
    from repro.configs import SHAPES, get_config
    from repro.launch import roofline as rl
    from repro.launch.cells import lower_cell, roofline_config, \
        slstm_flops_correction
    from repro.launch.dryrun import _extrapolated_roofline
    from repro.launch.mesh import make_production_mesh

    cfg = get_config(arch)
    if set_overrides:
        cfg = dataclasses.replace(cfg, **set_overrides)
    cell = SHAPES[shape_name]
    mesh = make_production_mesh()

    # full compile for memory analysis
    lc = lower_cell(arch, cell, mesh, rule_overrides, cfg=cfg,
                    micro_batches=micro)
    co = lc.lowered.compile()
    mem = co.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"peak/dev: {peak/2**30:.2f} GiB  (args {mem.argument_size_in_bytes/2**30:.2f} "
          f"out {mem.output_size_in_bytes/2**30:.2f} temp {mem.temp_size_in_bytes/2**30:.2f} "
          f"alias {mem.alias_size_in_bytes/2**30:.2f})")

    # extrapolated roofline on the modified config
    def lower_with_cfg(a, c, m, r, cfg=None, micro_batches=None):
        return lower_cell(a, c, m, r, cfg=cfg, micro_batches=micro_batches)

    meas = {}
    for k in (1, 2):
        rcfg = roofline_config(cfg, k)
        lck = lower_cell(arch, cell, mesh, rule_overrides, cfg=rcfg,
                         micro_batches=1)
        cok = lck.lowered.compile()
        ca = cok.cost_analysis()
        text = cok.as_text()
        meas[k] = (float(ca.get("flops", 0)),
                   float(ca.get("bytes accessed", 0)),
                   rl.parse_collectives(text), text)

    g = cfg.n_groups

    def extr(a1, a2):
        return max((2 * a1 - a2) + g * (a2 - a1), max(a1, a2))

    dp = mesh.devices.size // mesh.shape.get("model", 1)
    flops = extr(meas[1][0], meas[2][0]) + slstm_flops_correction(cfg, cell,
                                                                  dp)
    byts = extr(meas[1][1], meas[2][1])
    coll = extr(meas[1][2].cost_s, meas[2][2].cost_s)
    coll_b = extr(meas[1][2].total_bytes, meas[2][2].total_bytes)
    mf = rl.model_flops_for(cfg, cell)
    chip = rl.peaks(rl.DRYRUN_KIND)
    compute_s = flops / chip.bf16_flops
    memory_s = byts / chip.hbm_bw
    step = max(compute_s, memory_s, coll)
    print(f"compute {compute_s:.3f}s | memory {memory_s:.3f}s | "
          f"collective {coll:.3f}s  → step {step:.3f}s  "
          f"mfu {mf/(step*256*chip.bf16_flops)*100:.1f}%  "
          f"useful_frac {mf/(flops*256):.2f}  coll {coll_b/1e9:.0f}GB")

    print("top collectives (k=2 variant, per-layer-group ×%d):" % g)
    for b, kind, shape in _top_collectives(meas[2][3], top):
        print(f"  {b/2**30:8.3f} GiB  {kind:20s} {shape}")
    return {"peak": peak, "compute_s": compute_s, "memory_s": memory_s,
            "collective_s": coll, "step_s": step,
            "mfu": mf / (step * 256 * chip.bf16_flops)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (python literal)")
    ap.add_argument("--rule", action="append", default=[],
                    help="logical rule override axis=meshaxis|none")
    ap.add_argument("--micro", type=int, default=None)
    ap.add_argument("--top", type=int, default=10)
    args = ap.parse_args(argv)

    import ast
    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        try:
            overrides[k] = ast.literal_eval(v)
        except (ValueError, SyntaxError):
            overrides[k] = v
    rules = {}
    for kv in args.rule:
        k, v = kv.split("=", 1)
        rules[k] = None if v.lower() == "none" else (
            tuple(v.split("+")) if "+" in v else v)
    measure(args.arch, args.shape, overrides or None, rules or None,
            args.top, micro=args.micro)
    return 0


if __name__ == "__main__":
    # the CLI wants a 512-device host platform; set it HERE (jax has not
    # initialized yet — measure() imports it lazily), not at import time
    import os

    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=512")
    sys.exit(main())
