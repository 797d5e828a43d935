"""``chip_smoke.py`` rehearsed on the CPU: its phase functions at tiny
size, with the TPU dispatch rule applied so every kernel it picks runs in
interpret mode, and its refusal to run anywhere but on a TPU."""
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from conftest import SRC

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.core import local_context  # noqa: E402
from repro.core.device import peaks  # noqa: E402
from repro.kernels import dispatch  # noqa: E402
from repro.launch import compile_cache  # noqa: E402


@pytest.fixture
def tpu_rule(monkeypatch):
    """Apply the rule as on a v5e; the picked kernels run interpreted."""
    monkeypatch.setattr(dispatch, "_platform", lambda: "tpu")
    monkeypatch.setattr(dispatch, "vmem_limit",
                        lambda: peaks("TPU v5 lite").scoped_vmem_bytes)
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    dispatch.reset_counts()
    yield
    dispatch.reset_counts()


def test_table_phase_tiny(tpu_rule):
    res = chip_smoke.table_phase(local_context(), 20_000, n_users=1_000)
    assert res["joined_rows"] == res["rolling_rows"] > 0
    assert res["by_segment_rows"] == 8
    assert res["columns_read_bytes"] > 0
    took = dispatch.counts()
    assert took["segment_reduce"]["interpret"] >= 1      # 8 segments
    assert took["segment_reduce"]["xla"] >= 1            # 1000 users
    assert took["window_scan"] == {"interpret": 1}


def test_train_phase_tiny(tpu_rule):
    res = chip_smoke.train_phase(reduced=True, seq=32, batches=(2,))
    assert res["batch"] == 2 and len(res["losses"]) == 3
    # training never takes the forward-only flash kernel
    assert set(dispatch.counts()["flash_attention"]) == {"xla"}


def test_serve_phase_tiny(tpu_rule):
    res = chip_smoke.serve_phase(reduced=True, n_req=2, prompt=16, gen=4)
    assert res["prefill_rel_err"] <= chip_smoke.SERVE_LOGIT_TOL
    assert dispatch.counts()["flash_attention"]["interpret"] >= 1


def test_table_phase_4shard_audit():
    """The four-chip phase on four host devices: exchanges, audit, and
    the interpret-mode hash-partition kernel."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = SRC + os.pathsep + ROOT
    script = textwrap.dedent("""
        import jax
        import chip_smoke
        from repro.core import HPTMTContext
        from repro.core.context import make_mesh
        from repro.core.device import peaks
        from repro.kernels import dispatch

        dispatch._platform = lambda: "tpu"
        dispatch.vmem_limit = lambda: peaks("TPU v5 lite").scoped_vmem_bytes
        ctx = HPTMTContext(mesh=make_mesh((4,), ("data",)))
        res = chip_smoke.table_phase(ctx, 40_000, n_users=1_000, audit=True)
        assert res["shards"] == 4 and res["joined_rows"] > 0
        assert dispatch.counts()["hash_partition"]["interpret"] >= 1
        print("OK")
    """)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=560, env=env, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "audit rolling: predicted_a2a=" in r.stdout
    assert r.stdout.strip().endswith("OK")


@pytest.mark.parametrize("alone", [False, True])
def test_smoke_refuses_without_tpu(tmp_path, alone):
    """No TPU (JAX held to the CPU), or no repository next to the script:
    a non-zero exit and no result line."""
    script = os.path.join(ROOT, "chip_smoke.py")
    if alone:
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(script)], capture_output=True,
                       text=True, timeout=300, env=env, cwd=tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
