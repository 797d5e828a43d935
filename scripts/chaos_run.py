"""Chaos harness: seeded fault schedules against the resilient runtime.

Drives the three recovery contracts of DESIGN.md §13 end to end, each
under a seeded deterministic fault schedule, and verifies that the
observable result is **bit-exact** against an uninterrupted oracle and
that the retry budget actually bounded the damage:

  1. ``scan``  — a transient read fault (``scan.read``) injected into a
     planned scan→filter→groupby→sort pipeline running under a
     :class:`~repro.resilience.FaultPolicy`; the retry must absorb it.
  2. ``spill`` — a write fault (``spill.write``: disk-full or partial
     write) injected into an out-of-core groupby with a policy-carrying
     :class:`~repro.spill.SpillStore`; the retry must leave no torn
     run files and a bit-exact aggregate.
  3. ``commit`` — a ``SIGKILL`` injected mid stage-checkpoint commit
     (``checkpoint.commit:crash``) in a child process; a second child
     must resume from the committed prefix and reproduce the oracle
     bit-for-bit (the kill-and-resume contract).

Run:  PYTHONPATH=src python scripts/chaos_run.py --seeds 11,23,37
Exits non-zero on the first violated contract; prints one summary line
per (scenario, seed) so CI logs show exactly what was injected.
"""
from __future__ import annotations

import argparse
import os
import shutil
import subprocess
import sys
import tempfile
import zlib

import numpy as np

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _crc_rows(df) -> str:
    d = df.to_numpy()
    crc = 0
    for k in sorted(d):
        crc = zlib.crc32(np.ascontiguousarray(d[k]).tobytes(), crc)
    return f"{crc:08x}"


def _events(root: str, n: int = 96) -> str:
    from repro.io.dataset import write_dataset

    rng = np.random.default_rng(5)
    cols = {"k": (np.arange(n) % 12).astype(np.float32),
            "u": np.arange(n, dtype=np.float32),
            "v": rng.normal(size=n).astype(np.float32)}
    write_dataset(root, [(cols, n)], format="hpt", rows_per_group=12)
    return root


def _pipeline(ds: str, ctx):
    from repro.io.scan import pred
    from repro.plan.frame import LazyFrame

    return (LazyFrame.read_parquet(ds, ctx)
            .filter([pred("u", "<", 72.0)])
            .groupby(["k"], [("v", "sum"), ("v", "count")])
            .sort_values("v_sum"))


def scenario_scan(seed: int, work: str) -> str:
    """Transient scan faults under a seeded schedule; retry absorbs."""
    from repro import telemetry as T
    from repro.core import local_context
    from repro.resilience import FaultPolicy, arm_schedule, faults

    ctx = local_context()
    ds = _events(os.path.join(work, "ds"))
    oracle = _crc_rows(_pipeline(ds, ctx).collect(strict=False))
    faults.reset()
    sched = arm_schedule(seed, ["scan.read"], kinds=("io_error",
                                                     "disk_full"),
                         n_faults=1, max_nth=3)
    rec = T.Collector("chaos-scan")
    pol = FaultPolicy(max_retries=3, backoff_base=0.0, backoff_max=0.0)
    got = _crc_rows(_pipeline(ds, ctx).collect(strict=False, policy=pol,
                                               telemetry=rec))
    assert got == oracle, f"scan: {got} != oracle {oracle}"
    retries = rec.metrics.counters.get("retry.scan.read", 0)
    injected = faults.fires("scan.read")
    assert retries <= pol.max_retries, f"retry budget blown: {retries}"
    assert injected >= 1 or all(nth > 8 for _, _, nth in sched), sched
    faults.reset()
    return f"injected={sched} fired={injected} retries={retries}"


def scenario_spill(seed: int, work: str) -> str:
    """Spill write faults; policy retry leaves no torn runs, bit-exact."""
    from repro import telemetry as T
    from repro.core import local_context
    from repro.dataframe.frame import DataFrame
    from repro.resilience import FaultPolicy, arm_schedule, faults
    from repro.spill import spill_groupby

    ctx = local_context()
    rng = np.random.default_rng(seed)
    n = 4096
    cols = {"k": rng.integers(0, 64, n).astype(np.int32),
            "v": rng.standard_normal(n).astype(np.float32)}
    df = DataFrame.from_dict(cols, ctx, bucket_factor=2.0)
    aggs = (("v", "sum"), ("v", "count"))
    want = df.groupby(["k"], list(aggs)).to_numpy()
    faults.reset()
    sched = arm_schedule(seed, ["spill.write"],
                         kinds=("disk_full", "partial_write"),
                         n_faults=1, max_nth=2)
    rec = T.Collector("chaos-spill")
    pol = FaultPolicy(max_retries=2, backoff_base=0.0, backoff_max=0.0)
    spill_dir = os.path.join(work, "spill")
    with T.using(rec):
        with spill_groupby(df.table, ("k",), aggs, ctx=ctx,
                           budget_rows=256, workdir=spill_dir,
                           policy=pol) as res:
            got = res.collect()
    order_w, order_g = np.argsort(want["k"]), np.argsort(got["k"])
    for c in want:
        a, b = want[c][order_w], got[c][order_g]
        assert np.array_equal(a, b), f"spill: column {c} diverged"
    leftovers = []
    if os.path.isdir(spill_dir):
        leftovers = [f for f in os.listdir(spill_dir)
                     if f.endswith(".tmp")]
    assert not leftovers, f"torn run files left behind: {leftovers}"
    retries = rec.metrics.counters.get("retry.spill.write", 0)
    assert retries <= pol.max_retries, f"retry budget blown: {retries}"
    fired = faults.fires("spill.write")
    faults.reset()
    return f"injected={sched} fired={fired} retries={retries}"


_CHILD = """
import os, sys, zlib
import numpy as np
sys.path.insert(0, {src!r})
from repro import telemetry as T
from repro.core import local_context
from repro.io.scan import pred
from repro.plan.frame import LazyFrame
from repro.resilience import FaultPolicy

ds, ckdir = sys.argv[1], sys.argv[2]
ctx = local_context()
lf = (LazyFrame.read_parquet(ds, ctx)
      .filter([pred("u", "<", 72.0)])
      .groupby(["k"], [("v", "sum"), ("v", "count")])
      .sort_values("v_sum"))
rec = T.Collector("chaos-child")
if ckdir == "-":  # the uninterrupted oracle: no policy, no stages
    out = lf.collect(strict=False)
else:
    pol = FaultPolicy(max_retries=1, checkpoint_dir=ckdir,
                      keep_checkpoints=True)
    out = lf.collect(strict=False, policy=pol, telemetry=rec)
d = out.to_numpy()
crc = 0
for k in sorted(d):
    crc = zlib.crc32(np.ascontiguousarray(d[k]).tobytes(), crc)
print("RESTORED", rec.metrics.counters.get("recovery.stages_restored", 0))
print("CRC", f"{{crc:08x}}")
"""


def _child(args, env, expect_rc: int = 0) -> dict:
    r = subprocess.run([sys.executable] + args, capture_output=True,
                       text=True, timeout=560, env=env)
    assert r.returncode == expect_rc, (
        f"expected rc={expect_rc}, got rc={r.returncode}\n"
        f"{r.stderr[-2000:]}")
    pairs = (line.split(None, 1) for line in r.stdout.splitlines())
    return dict(p for p in pairs if len(p) == 2)


def scenario_commit_crash(seed: int, work: str) -> str:
    """SIGKILL mid stage-commit in a child; resume is bit-exact.

    Runs in the parent without touching JAX: the dataset, the oracle and
    both attempts each run in a child, so a device is only ever held by
    one process at a time."""
    ds = os.path.join(work, "ds")
    ckdir = os.path.join(work, "stages")
    child = _CHILD.format(src=SRC)
    env = dict(os.environ)
    env.pop("HPTMT_FAULTS", None)
    _child([os.path.abspath(__file__), "--make-events", ds], env)
    oracle = _child(["-c", child, ds, "-"], env)["CRC"]
    # the pipeline commits two stages; the seed picks which commit dies
    nth = 1 + (seed >> 1) % 2
    env1 = dict(env, HPTMT_FAULTS=f"checkpoint.commit:crash:{nth}")
    _child(["-c", child, ds, ckdir], env1, expect_rc=-9)
    lines = _child(["-c", child, ds, ckdir], env)
    assert lines["CRC"] == oracle, (
        f"resumed run diverged: {lines['CRC']} != oracle {oracle}")
    restored = int(lines["RESTORED"])
    if nth == 2:
        assert restored >= 1, "crash after commit 1 but nothing restored"
    return f"killed_at_commit={nth} restored={restored} crc=ok"


def _in_child(name: str, seed: int, work: str) -> str:
    """Run one in-process scenario in a child, so the parent never holds
    a device while later scenarios start their own children."""
    out = _child([os.path.abspath(__file__), "--run", name, "--seeds",
                  str(seed), "--work", work], dict(os.environ))
    if "FAIL" in out:
        raise AssertionError(out["FAIL"])
    return out["PASS"]


IN_PROCESS = {"scan": scenario_scan, "spill": scenario_spill}
SCENARIOS = ("scan", "spill", "commit-crash")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", default="11,23,37",
                    help="comma-separated chaos schedule seeds")
    ap.add_argument("--only", default=None,
                    help="run one scenario: scan | spill | commit-crash")
    ap.add_argument("--run", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--work", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--make-events", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    sys.path.insert(0, SRC)
    if args.make_events:
        _events(args.make_events)
        return 0
    seeds = [int(s) for s in args.seeds.split(",") if s]
    if args.run:  # child side of _in_child
        try:
            print("PASS", IN_PROCESS[args.run](seeds[0], args.work))
        except AssertionError as e:
            print("FAIL", " ".join(str(e).split()))
        return 0
    failures = 0
    for seed in seeds:
        for name in SCENARIOS:
            if args.only and name != args.only:
                continue
            work = tempfile.mkdtemp(prefix=f"chaos-{name}-{seed}-")
            try:
                if name in IN_PROCESS:
                    detail = _in_child(name, seed, work)
                else:
                    detail = scenario_commit_crash(seed, work)
                print(f"PASS {name:>12} seed={seed:<3} {detail}")
            except AssertionError as e:
                failures += 1
                print(f"FAIL {name:>12} seed={seed:<3} {e}")
            finally:
                shutil.rmtree(work, ignore_errors=True)
    if failures:
        print(f"{failures} chaos contract violation(s)")
        return 1
    print("all chaos contracts held")
    return 0


if __name__ == "__main__":
    sys.exit(main())
