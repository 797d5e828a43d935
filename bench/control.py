"""Readings that set a cell's limits: the program's on many seeds, and the
control's (and, for training, a planted fault's) on some, in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--seconds 2]

For each seed this sets the cell up as a run does, measures a short
window at the cell's own load, and prints one JSON line: the program's
compared numbers, and on a control seed the numbers of the control (the
plain reference one precision below what the configuration states, in
the program's place) and of each fault the driver can plant.  The
benchmark's own runs never run this; ``PERF.md`` records the readings
and the limits set from them.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402

#: driver methods that plant a fault in the reference put in the
#: program's place, by the name printed for them
FAULTS = ("half_batch",)


def readings(cell, seed: int, seconds: float, control: bool) -> dict:
    import jax

    run = harness.Run(cell, seed, seconds)
    impl = cell.driver().Driver(cell.config, cell.traffic, seed,
                                cell.reference(), cell.name)
    impl.setup()
    harness.measure(run, impl, trace=False)
    impl.release()
    jax.clear_caches()
    out = {"seed": seed, "units": run.units, "failed": run.failed,
           "program": {k: c["value"] for k, c in impl.check().items()}}
    if control:
        out["control"] = {k: c["value"] for k, c in impl.control().items()}
        for fault in FAULTS:
            if hasattr(impl, fault):
                out[fault] = {k: c["value"]
                              for k, c in getattr(impl, fault)().items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cell = harness.Cell(bench, args.workload)
    harness.prepare_jax(cell.chips)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    seeds = [int(s) for s in args.seeds.split(",") if s]
    for seed in seeds + sorted(controls - set(seeds)):
        t0 = time.perf_counter()
        out = readings(cell, seed, args.seconds, seed in controls)
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
