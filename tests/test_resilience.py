"""Fault-tolerant execution tests (DESIGN.md §13).

Five layers, mirroring the resilience contract:

  * chaos registry — arm/fire/disarm one-shots, seeded schedules, env
    back-compat with ``HPTMT_SPILL_FAULT``;
  * retry policy — deterministic backoff, typed fatal-vs-transient
    split, budget exhaustion as :class:`RetryBudgetExceeded`;
  * hardened IO — typed :class:`CorruptFragmentError` for inconsistent
    ``.hpt`` headers, scan quarantine with sidecar manifest, checkpoint
    manifest CRC/dtype validation;
  * workflow — policy-routed retries, fatal fail-fast, journal content
    hashes that refuse a stale-DAG resume;
  * lineage stage checkpoints — fingerprinted commit/restore round
    trips, bit-exact resumed collects, suffix-only re-execution
    (jaxpr-asserted in the 4-device leg), and a real SIGKILL
    kill-and-resume subprocess.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from conftest import SRC

import jax
import jax.numpy as jnp

from repro import telemetry as T
from repro.checkpoint.manager import (CheckpointIntegrityError,
                                      CheckpointManager)
from repro.core import local_context
from repro.dataframe.frame import DataFrame
from repro.io.dataset import write_dataset
from repro.io.native import (CorruptFragmentError, HptIntegrityError,
                             read_hpt, write_hpt)
from repro.io.scan import pred
from repro.plan.frame import LazyFrame
from repro.resilience import (FatalInjectedFault, FaultPolicy,
                              InjectedFault, RetryBudgetExceeded,
                              StageCheckpointer, arm, arm_schedule, fires,
                              plan_fingerprint, reset)
from repro.resilience import faults
from repro.workflow.engine import Task, WorkflowEngine, WorkflowError


@pytest.fixture(autouse=True)
def _clean_faults():
    reset()
    yield
    reset()


def _dataset(tmp_path, n=64, name="ds"):
    rng = np.random.default_rng(3)
    cols = {"a": np.arange(n, dtype=np.float32),
            "b": (np.arange(n) % 8).astype(np.float32),
            "c": rng.normal(size=n).astype(np.float32)}
    root = str(tmp_path / name)
    write_dataset(root, [(cols, n)], format="hpt", rows_per_group=8)
    return root


def _pipeline(path, ctx, **kw):
    return (LazyFrame.read_parquet(path, ctx, **kw)
            .filter([pred("a", "<", 48.0)])
            .groupby(["b"], [("c", "sum"), ("c", "count")])
            .sort_values("b"))


def _rows(df):
    return {k: np.asarray(v) for k, v in df.to_numpy().items()}


# ---------------------------------------------------------------------------
# chaos registry
# ---------------------------------------------------------------------------
def test_arm_counts_down_fires_once_then_disarms():
    arm("scan.read", "io_error", nth=2)
    faults.fire("scan.read")                    # 1st occurrence: counts down
    with pytest.raises(InjectedFault):
        faults.fire("scan.read")                # 2nd: fires
    faults.fire("scan.read")                    # disarmed: clean no-op
    assert fires("scan.read") == 1 and fires() == 1


def test_fault_kinds_map_to_exception_families(tmp_path):
    arm("x", "fatal")
    with pytest.raises(FatalInjectedFault):
        faults.fire("x")
    arm("x", "disk_full")
    with pytest.raises(InjectedFault) as e:
        faults.fire("x")
    assert e.value.errno == 28                  # ENOSPC
    p = str(tmp_path / "run0.hpt")
    arm("x", "partial_write")
    with pytest.raises(InjectedFault):
        faults.fire("x", path=p)
    assert os.path.exists(p + ".tmp")           # torn half-write left behind
    with pytest.raises(ValueError, match="unknown fault kind"):
        arm("x", "meteor_strike")


def test_env_arming_and_spill_backcompat(monkeypatch):
    monkeypatch.setenv(faults.FAULTS_ENV, "scan.read:io_error:1")
    reset()
    with pytest.raises(InjectedFault):
        faults.fire("scan.read")
    faults.fire("scan.read")                    # one-shot under stable env
    monkeypatch.setenv(faults.FAULTS_ENV, "")
    monkeypatch.setenv(faults.SPILL_FAULT_ENV, "disk_full:1")
    reset()
    with pytest.raises(InjectedFault):          # legacy knob → spill.write
        faults.fire("spill.write")


def test_arm_schedule_is_seed_deterministic():
    sched1 = arm_schedule(11, ["scan.read", "spill.write"], n_faults=3)
    reset()
    sched2 = arm_schedule(11, ["scan.read", "spill.write"], n_faults=3)
    assert sched1 == sched2
    reset()
    assert arm_schedule(12, ["scan.read", "spill.write"],
                        n_faults=3) != sched1


# ---------------------------------------------------------------------------
# retry policy
# ---------------------------------------------------------------------------
def test_policy_retries_transient_until_success():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    pol = FaultPolicy(max_retries=3)
    assert pol.run(flaky, site="t", sleep=lambda s: None) == "ok"
    assert calls["n"] == 3


def test_policy_fatal_fails_fast_no_retry():
    calls = {"n": 0}

    def bad():
        calls["n"] += 1
        raise ValueError("deterministic bug")

    pol = FaultPolicy(max_retries=5)
    with pytest.raises(ValueError, match="deterministic bug"):
        pol.run(bad, site="t", sleep=lambda s: None)
    assert calls["n"] == 1                      # never retried


def test_policy_jax_runtime_error_is_fatal():
    """A refused kernel or a device OOM fails the same way every time."""
    calls = {"n": 0}

    def oom():
        calls["n"] += 1
        raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: out of HBM")

    pol = FaultPolicy(max_retries=5)
    assert not pol.is_retryable(jax.errors.JaxRuntimeError("x"))
    with pytest.raises(jax.errors.JaxRuntimeError, match="RESOURCE_EXHAUSTED"):
        pol.run(oom, site="t", sleep=lambda s: None)
    assert calls["n"] == 1


def test_policy_budget_exhaustion_is_itself_fatal():
    pol = FaultPolicy(max_retries=2)

    def always():
        raise OSError("down")

    with pytest.raises(RetryBudgetExceeded, match="all 3 attempts"):
        pol.run(always, site="t", sleep=lambda s: None)
    # nested policies must not multiply budgets: the outer loop sees a
    # fatal type and fails fast
    outer = FaultPolicy(max_retries=9)
    calls = {"n": 0}

    def inner():
        calls["n"] += 1
        return pol.run(always, site="t", sleep=lambda s: None)

    with pytest.raises(RetryBudgetExceeded):
        outer.run(inner, site="outer", sleep=lambda s: None)
    assert calls["n"] == 1


def test_policy_backoff_deterministic_and_capped():
    pol = FaultPolicy(backoff_base=0.01, backoff_factor=2.0,
                      backoff_max=0.05, jitter=0.1)
    d = [pol.delay(k, site="s") for k in range(8)]
    assert d == [pol.delay(k, site="s") for k in range(8)]  # reproducible
    assert all(x <= 0.05 * 1.1 + 1e-12 for x in d)          # capped
    assert d[1] > d[0]                                      # grows


# ---------------------------------------------------------------------------
# hardened IO: typed corruption + quarantine
# ---------------------------------------------------------------------------
def test_inconsistent_hpt_header_raises_typed_error(tmp_path):
    p = str(tmp_path / "bad.hpt")
    cols = {"x": np.arange(100, dtype=np.int32)}
    write_hpt(p, cols, 100)
    raw = bytearray(open(p, "rb").read())
    # header JSON is near the front; claim more rows than the buffer holds
    hdr_end = raw.index(b"}", raw.index(b"num_rows")) + 1
    txt = raw[:hdr_end + 200].decode("latin1")
    assert '"num_rows": 100' in txt
    patched = raw.replace(b'"num_rows": 100', b'"num_rows": 150', 1)
    open(p, "wb").write(patched)
    with pytest.raises(CorruptFragmentError) as e:
        read_hpt(p)
    msg = str(e.value)
    assert "bad.hpt" in msg and "150" in msg and "600" in msg \
        and "400" in msg  # file, claimed rows, expected + actual bytes
    assert isinstance(e.value, ValueError)      # fatal family: never retried


def test_truncated_hpt_still_integrity_error(tmp_path):
    p = str(tmp_path / "cut.hpt")
    write_hpt(p, {"x": np.arange(64, dtype=np.float32)}, 64)
    raw = open(p, "rb").read()
    open(p, "wb").write(raw[:-12])
    with pytest.raises(HptIntegrityError):
        read_hpt(p)


def test_scan_quarantine_skips_corrupt_run_with_sidecar(tmp_path):
    ctx = local_context()
    path = _dataset(tmp_path)
    frag = sorted(f for f in os.listdir(path) if f.endswith(".hpt"))[2]
    raw = open(os.path.join(path, frag), "rb").read()
    open(os.path.join(path, frag), "wb").write(raw[:-8])
    # default: typed raise naming the file
    with pytest.raises(CorruptFragmentError, match=frag.replace(".", r"\.")):
        LazyFrame.read_parquet(path, ctx).collect(strict=False)
    # quarantine: pipeline completes, rows from the bad run are dropped,
    # stats + sidecar record exactly what was lost
    rec = T.Collector("q")
    out = (LazyFrame.read_parquet(path, ctx, on_error="quarantine")
           .collect(strict=False, telemetry=rec))
    got = _rows(out)
    lost = np.arange(16, 24, dtype=np.float32)  # fragment 2 of 8-row groups
    assert not np.isin(lost, got["a"]).any()
    assert rec.metrics.counters["scan.fragments_quarantined"] == 1
    assert rec.metrics.counters["scan.rows_quarantined"] == 8
    side = json.load(open(os.path.join(path, "_hptmt_quarantine.json")))
    assert len(side["quarantined"]) == 1
    assert side["quarantined"][0]["rows"] == 8
    assert frag in side["quarantined"][0]["path"]
    with pytest.raises(ValueError, match="on_error"):
        LazyFrame.read_parquet(path, ctx, on_error="explode")


def test_scan_transient_fault_retried_by_policy(tmp_path):
    ctx = local_context()
    path = _dataset(tmp_path)
    arm("scan.read", "io_error", nth=1)
    rec = T.Collector("r")
    out = _pipeline(path, ctx).collect(
        strict=False, policy=FaultPolicy(max_retries=2, backoff_base=0.0),
        telemetry=rec)
    oracle = _pipeline(path, ctx).collect(strict=False)
    for k, v in _rows(oracle).items():
        np.testing.assert_array_equal(v, _rows(out)[k], err_msg=k)
    assert fires("scan.read") == 1
    assert rec.metrics.counters["fault.injected.scan.read"] == 1
    assert rec.metrics.counters["retry.scan.read"] == 1


# ---------------------------------------------------------------------------
# hardened checkpoint manager
# ---------------------------------------------------------------------------
def test_checkpoint_manifest_has_crc_and_restore_checks_it(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    tree = {"w": jnp.arange(8, dtype=jnp.float32), "b": jnp.ones((3,))}
    mgr.save(1, tree)
    man = json.load(open(tmp_path / "step_1" / "manifest.json"))
    assert all("crc32" in leaf for leaf in man["leaves"])
    ok = mgr.restore(jax.tree.map(jnp.zeros_like, tree))
    np.testing.assert_array_equal(np.asarray(ok["w"]), np.arange(8))
    # flip one byte on disk → named integrity error on restore
    leaf = tmp_path / "step_1" / "w.npy"
    raw = bytearray(leaf.read_bytes())
    raw[-1] ^= 0xFF
    leaf.write_bytes(bytes(raw))
    with pytest.raises(CheckpointIntegrityError, match="CRC mismatch"):
        mgr.restore(jax.tree.map(jnp.zeros_like, tree))


def test_checkpoint_dtype_drift_refuses_silent_cast(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"w": jnp.arange(4, dtype=jnp.float32)})
    with pytest.raises(CheckpointIntegrityError, match="dtype"):
        mgr.restore({"w": jnp.zeros(4, dtype=jnp.int32)})
    assert issubclass(CheckpointIntegrityError, ValueError)


# ---------------------------------------------------------------------------
# workflow engine: policy routing + journal content hash
# ---------------------------------------------------------------------------
def test_workflow_routes_retries_through_policy(tmp_path):
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return 7

    wf = WorkflowEngine(policy=FaultPolicy(max_retries=3, backoff_base=0.0,
                                           backoff_max=0.0))
    wf.add(Task("t", flaky))
    assert wf.run()["t"] == 7 and calls["n"] == 3


def test_workflow_fatal_task_fails_fast():
    calls = {"n": 0}

    def bad():
        calls["n"] += 1
        raise ValueError("bug")

    wf = WorkflowEngine().add(Task("t", bad, retries=5))
    with pytest.raises(WorkflowError, match="non-retryable ValueError"):
        wf.run()
    assert calls["n"] == 1


def test_workflow_journal_detects_stale_dag(tmp_path):
    j = str(tmp_path / "journal.json")
    wf = WorkflowEngine(j)
    wf.add(Task("a", lambda: 1)).add(Task("b", lambda a: a + 1, deps=("a",)))
    wf.run()
    entries = json.load(open(j))
    assert entries["a"]["hash"] and entries["b"]["hash"]
    # same DAG, fresh lambdas (a restart) → resumes silently
    wf2 = WorkflowEngine(j)
    wf2.add(Task("a", lambda: 99)).add(
        Task("b", lambda a: 0, deps=("a",)))
    assert wf2.run() == {}                      # everything skipped
    # changed dependency edges → stale journal must refuse, not skip
    wf3 = WorkflowEngine(j)
    wf3.add(Task("a", lambda: 1)).add(Task("b", lambda: 2))
    with pytest.raises(WorkflowError, match="stale journal"):
        wf3.run()


def test_workflow_legacy_bool_journal_still_resumes(tmp_path):
    j = str(tmp_path / "journal.json")
    with open(j, "w") as f:
        json.dump({"a": True}, f)
    wf = WorkflowEngine(j).add(Task("a", lambda: 1 / 0))
    assert wf.run() == {}                       # pre-hash entry skips


# ---------------------------------------------------------------------------
# lineage stage checkpoints
# ---------------------------------------------------------------------------
def test_plan_fingerprint_deterministic_and_sensitive(tmp_path):
    from repro.plan.rules import optimize
    ctx = local_context()
    path = _dataset(tmp_path)
    r1, _ = optimize(_pipeline(path, ctx).logical_plan)
    r2, _ = optimize(_pipeline(path, ctx).logical_plan)
    assert plan_fingerprint(r1, ctx) == plan_fingerprint(r2, ctx)
    other = (LazyFrame.read_parquet(path, ctx)
             .filter([pred("a", "<", 32.0)])     # different predicate
             .groupby(["b"], [("c", "sum"), ("c", "count")])
             .sort_values("b"))
    r3, _ = optimize(other.logical_plan)
    assert plan_fingerprint(r3, ctx) != plan_fingerprint(r1, ctx)


def test_stage_checkpointer_roundtrip_and_torn_commit_sweep(tmp_path):
    ctx = local_context()
    df = DataFrame.from_dict(
        {"k": np.arange(6, dtype=np.float32),
         "v": np.ones(6, dtype=np.float32)}, ctx)
    ck = StageCheckpointer(str(tmp_path), "fp0")
    ck.commit(2, df.table, [("plan.x", 3)], op="groupby")
    assert ck.committed_stages() == [2]
    dt, ovs = ck.restore(2)
    assert ovs == [("plan.x", 3)]
    for k in df.table.column_names:
        np.testing.assert_array_equal(np.asarray(df.table.columns[k]),
                                      np.asarray(dt.columns[k]))
    np.testing.assert_array_equal(np.asarray(df.table.counts),
                                  np.asarray(dt.counts))
    # a torn commit (crash before rename) is swept on reopen
    os.makedirs(tmp_path / "fp0" / "stage_5.tmp")
    ck2 = StageCheckpointer(str(tmp_path), "fp0")
    assert ck2.committed_stages() == [2]
    assert not os.path.exists(tmp_path / "fp0" / "stage_5.tmp")


def test_commit_crash_leaves_no_partial_stage(tmp_path):
    ctx = local_context()
    df = DataFrame.from_dict({"k": np.arange(4, dtype=np.float32)}, ctx)
    ck = StageCheckpointer(str(tmp_path), "fp1")
    arm("checkpoint.commit", "io_error", nth=1)
    with pytest.raises(InjectedFault):
        ck.commit(0, df.table, [])
    assert ck.committed_stages() == []          # nothing half-visible
    ck.commit(0, df.table, [])                  # disarmed retry succeeds
    assert ck.committed_stages() == [0]


def test_resilient_collect_bit_exact_and_resumes(tmp_path):
    ctx = local_context()
    path = _dataset(tmp_path)
    oracle = _rows(_pipeline(path, ctx).collect(strict=False))
    ckdir = str(tmp_path / "stages")
    pol = FaultPolicy(max_retries=1, checkpoint_dir=ckdir,
                      keep_checkpoints=True)
    rec = T.Collector("c1")
    got = _rows(_pipeline(path, ctx).collect(strict=False, policy=pol,
                                             telemetry=rec))
    for k, v in oracle.items():
        np.testing.assert_array_equal(v, got[k], err_msg=k)
    assert rec.metrics.counters["recovery.stages_committed"] >= 1
    [fp] = os.listdir(ckdir)                    # one fingerprint dir
    # second run resumes from the committed stage: restores, no re-commit
    rec2 = T.Collector("c2")
    got2 = _rows(_pipeline(path, ctx).collect(strict=False, policy=pol,
                                              telemetry=rec2))
    for k, v in oracle.items():
        np.testing.assert_array_equal(v, got2[k], err_msg=k)
    assert rec2.metrics.counters["recovery.stages_restored"] >= 1
    assert "recovery.resumed_from_stage" in rec2.metrics.gauges
    spans = [s.name for s in rec2.all_spans()]
    assert "recovery.restore" in spans and "recovery.collect" in spans


def test_collect_without_policy_is_zero_overhead(tmp_path):
    import tempfile
    ctx = local_context()
    path = _dataset(tmp_path)
    before = {d for d in os.listdir(tempfile.gettempdir())
              if d.startswith("hptmt-stages-")}
    lf = _pipeline(path, ctx)
    plan = lf.physical_plan()
    assert plan.stage_hook is None
    lf.collect(strict=False)
    after = {d for d in os.listdir(tempfile.gettempdir())
             if d.startswith("hptmt-stages-")}
    assert after == before                      # no stage IO, no tmp dirs
    assert fires() == 0


def test_successful_collect_removes_checkpoints_unless_kept(tmp_path):
    ctx = local_context()
    path = _dataset(tmp_path)
    ckdir = str(tmp_path / "stages")
    _pipeline(path, ctx).collect(
        strict=False, policy=FaultPolicy(checkpoint_dir=ckdir))
    assert os.listdir(ckdir) == []              # cleaned after success


# ---------------------------------------------------------------------------
# kill-and-resume: a real SIGKILL mid-commit, then bit-exact recovery
# ---------------------------------------------------------------------------
_CHILD = """
    import json, os, sys, zlib
    import numpy as np
    from repro import telemetry as T
    from repro.core import local_context
    from repro.io.dataset import write_dataset
    from repro.io.scan import pred
    from repro.plan.frame import LazyFrame
    from repro.resilience import FaultPolicy

    root, ckdir, mode = sys.argv[1], sys.argv[2], sys.argv[3]
    ds = os.path.join(root, "ds")
    if not os.path.exists(ds):
        rng = np.random.default_rng(5)
        n = 96
        cols = {"k": (np.arange(n) % 12).astype(np.float32),
                "u": np.arange(n, dtype=np.float32),
                "v": rng.normal(size=n).astype(np.float32)}
        write_dataset(ds, [(cols, n)], format="hpt", rows_per_group=12)
    ctx = local_context()
    lf = (LazyFrame.read_parquet(ds, ctx)
          .filter([pred("u", "<", 72.0)])
          .groupby(["k"], [("v", "sum"), ("v", "count")])
          .sort_values("v_sum"))  # non-key order → second exchange stage
    if mode == "plain":
        out = lf.collect(strict=False)
    else:
        rec = T.Collector("child")
        pol = FaultPolicy(max_retries=1, checkpoint_dir=ckdir,
                          keep_checkpoints=True)
        out = lf.collect(strict=False, policy=pol, telemetry=rec)
        print("RESTORED", rec.metrics.counters.get(
            "recovery.stages_restored", 0))
        print("RESUMED_FROM", rec.metrics.gauges.get(
            "recovery.resumed_from_stage", -1))
    d = out.to_numpy()
    crc = 0
    for k in sorted(d):
        crc = zlib.crc32(np.ascontiguousarray(d[k]).tobytes(), crc)
    print("CRC", f"{crc:08x}")
"""


def _run_child(tmp_path, mode, extra_env=None, check=True):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("HPTMT_FAULTS", None)
    env.update(extra_env or {})
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_CHILD),
         str(tmp_path), str(tmp_path / "stages"), mode],
        capture_output=True, text=True, timeout=560, env=env)
    if check:
        assert r.returncode == 0, f"stderr:\n{r.stderr[-4000:]}"
    return r


def test_sigkill_during_commit_then_resume_bit_exact(tmp_path):
    oracle = _run_child(tmp_path, "plain")
    ocrc = [l for l in oracle.stdout.splitlines() if l.startswith("CRC")]
    # run 1: SIGKILL the process during the FIRST stage commit — after
    # the tmp snapshot is written, before the atomic rename
    r1 = _run_child(tmp_path, "resilient",
                    {"HPTMT_FAULTS": "checkpoint.commit:crash:1"},
                    check=False)
    assert r1.returncode == -9, (r1.returncode, r1.stderr[-2000:])
    fpdirs = os.listdir(tmp_path / "stages")
    assert len(fpdirs) == 1                     # fingerprint dir exists
    # run 2: no faults — sweeps the torn commit, re-runs, commits
    r2 = _run_child(tmp_path, "resilient")
    assert ocrc[0] in r2.stdout                 # bit-exact vs oracle
    # run 3: resumes from the stage run 2 committed
    r3 = _run_child(tmp_path, "resilient")
    assert ocrc[0] in r3.stdout
    lines = dict(l.split() for l in r3.stdout.splitlines())
    assert int(lines["RESTORED"]) >= 1
    assert int(lines["RESUMED_FROM"]) >= 0


def test_crash_after_commit_resumes_without_recompute(tmp_path):
    # crash on the SECOND commit fire: stage 1 lands durably first
    r1 = _run_child(tmp_path, "resilient",
                    {"HPTMT_FAULTS": "checkpoint.commit:crash:2"},
                    check=False)
    if r1.returncode == 0:
        pytest.skip("pipeline has a single stage on this backend")
    assert r1.returncode == -9
    [fp] = os.listdir(tmp_path / "stages")
    committed = [d for d in os.listdir(tmp_path / "stages" / fp)
                 if d.startswith("stage_") and not d.endswith(".tmp")]
    assert committed                             # first stage survived
    oracle = _run_child(tmp_path, "plain")
    ocrc = [l for l in oracle.stdout.splitlines() if l.startswith("CRC")]
    r2 = _run_child(tmp_path, "resilient")
    assert ocrc[0] in r2.stdout
    assert "RESTORED 1" in r2.stdout or "RESTORED 2" in r2.stdout


# ---------------------------------------------------------------------------
# suffix-only re-execution: the jaxpr of a resumed plan must contain
# strictly fewer all_to_all ops (zero when every stage is committed)
# ---------------------------------------------------------------------------
def test_suffix_only_reexecution_4dev(tmp_path):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("HPTMT_FAULTS", None)
    script = """
        import os, sys
        import jax, numpy as np
        from repro.core import host_test_context
        from repro.dataframe.frame import DataFrame
        from repro.plan.frame import LazyFrame
        from repro.io.dataset import write_dataset
        from repro.io.scan import pred
        from repro.plan.rules import optimize
        from repro.plan.physical import PhysicalPlan
        from repro.resilience import (FaultPolicy, StageCheckpointer,
                                      plan_fingerprint, stage_hook)

        root = sys.argv[1]
        ds = os.path.join(root, "ds")
        rng = np.random.default_rng(7)
        n = 128
        cols = {"k": (np.arange(n) % 16).astype(np.float32),
                "u": np.arange(n, dtype=np.float32),
                "v": rng.normal(size=n).astype(np.float32)}
        write_dataset(ds, [(cols, n)], format="hpt", rows_per_group=16)
        ctx = host_test_context(n_shards=4)
        ckdir = os.path.join(root, "stages")

        def build():
            return (LazyFrame.read_parquet(ds, ctx)
                    .groupby(["k"], [("v", "sum")])
                    .sort_values("v_sum"))

        # full run with durable stages
        pol = FaultPolicy(checkpoint_dir=ckdir, keep_checkpoints=True)
        out1 = build().collect(strict=False, policy=pol)

        root_l, _ = optimize(build().logical_plan)
        fp = plan_fingerprint(root_l, ctx)
        ck = StageCheckpointer(ckdir, fp)
        committed = ck.committed_stages()
        assert committed, "no stages committed at 4 devices"

        fresh = PhysicalPlan(root_l, ctx)
        n_fresh = str(jax.make_jaxpr(fresh.fn)(*fresh.inputs())
                      ).count("all_to_all")
        assert n_fresh > 0, "pipeline has no exchanges at 4 devices"

        resumed = PhysicalPlan(root_l, ctx)
        resumed.stage_hook = stage_hook(ck, ctx=ctx,
                                        committed=set(committed))
        n_resumed = str(jax.make_jaxpr(resumed.fn)(*resumed.inputs())
                        ).count("all_to_all")
        # every exchange step is a stage; with all stages committed the
        # resumed program re-traces ONLY the post-exchange suffix
        assert n_resumed < n_fresh, (n_resumed, n_fresh)
        assert n_resumed == 0, (n_resumed, n_fresh)
        print("SUFFIX", n_fresh, "->", n_resumed)
    """
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script), str(tmp_path)],
        capture_output=True, text=True, timeout=560, env=env)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-4000:]}"
    assert "SUFFIX" in r.stdout
