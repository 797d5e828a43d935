"""Multi-device behaviour, run in subprocesses with forced host devices
(the main test process must keep seeing exactly 1 device)."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_devices(script: str, n: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={n}")
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-4000:]}"
    return r.stdout


def test_distributed_table_ops_8way():
    out = run_devices("""
        import jax, numpy as np, jax.numpy as jnp, collections
        from repro.core import (Table, DistTable, HPTMTContext, make_mesh,
                                table_ops)
        mesh = make_mesh((8,), ("data",))
        ctx = HPTMTContext(mesh=mesh)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, 64, 256).astype(np.int32)
        vals = rng.normal(size=256).astype(np.float32)
        t = Table.from_arrays({"id": jnp.asarray(ids), "v": jnp.asarray(vals)})
        dt = DistTable.from_local(t, ctx, capacity=64)

        sh, ov = table_ops.shuffle(dt, ["id"], ctx=ctx)
        assert int(ov) == 0 and int(sh.num_rows()) == 256
        loc = {}
        for s in range(8):
            st = sh.shard_table(s)
            for i in np.asarray(st.columns["id"][:int(st.num_rows)]):
                loc.setdefault(int(i), set()).add(s)
        assert all(len(v) == 1 for v in loc.values()), "keys not co-located"

        ga, ov = table_ops.groupby_aggregate(dt, ["id"], [("v","sum")], ctx=ctx)
        got = ga.to_numpy()
        exp = collections.defaultdict(float)
        for i, v in zip(ids, vals): exp[int(i)] += float(v)
        order = np.argsort(got["id"])
        np.testing.assert_allclose(
            got["v_sum"][order], [exp[k] for k in sorted(exp)], rtol=1e-4)

        srt, ov = table_ops.orderby(dt, "v", ctx=ctx)
        np.testing.assert_allclose(srt.to_numpy()["v"], np.sort(vals),
                                   rtol=1e-6)
        print("DIST-TABLE-OK")
        """)
    assert "DIST-TABLE-OK" in out


def test_array_collectives_8way():
    out = run_devices("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.core import HPTMTContext, make_mesh, array_ops
        ctx = HPTMTContext(mesh=make_mesh((8,), ("data",)))
        x = jnp.arange(8*4, dtype=jnp.float32).reshape(8, 4)
        np.testing.assert_allclose(array_ops.allreduce(x, ctx=ctx),
                                   np.asarray(x).sum(0))
        np.testing.assert_allclose(array_ops.allreduce(x, ctx=ctx, op="max"),
                                   np.asarray(x).max(0))
        np.testing.assert_allclose(array_ops.broadcast(x, ctx=ctx, root=5),
                                   np.asarray(x)[5])
        g = array_ops.allgather(jnp.arange(16., dtype=jnp.float32), ctx=ctx)
        np.testing.assert_allclose(g, np.arange(16.))
        rs = array_ops.reduce_scatter(jnp.ones((16, 2)), ctx=ctx)
        np.testing.assert_allclose(np.asarray(rs), 8 * np.ones((16, 2)))
        print("COLLECTIVES-OK")
        """)
    assert "COLLECTIVES-OK" in out


def test_sharded_train_step_4x2():
    """FSDP×TP train step on a 4×2 host mesh == single-device step."""
    out = run_devices("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.configs import get_config, reduced_config
        from repro.sharding import axes as am
        from repro.train.train_step import (TrainConfig, init_train_state,
                                            make_sharded_train_step)
        from repro.train.optimizer import OptimizerConfig
        from repro.core.context import make_mesh
        import dataclasses

        cfg = reduced_config(get_config("phi3-mini-3.8b"))
        cfg = dataclasses.replace(cfg, d_model=64, n_heads=4, n_kv_heads=4,
                                  d_ff=128)
        mesh = make_mesh((4, 2), ("data", "model"))
        tcfg = TrainConfig(optimizer=OptimizerConfig(warmup_steps=0))
        state = init_train_state(jax.random.PRNGKey(0), cfg)
        rng = jax.random.PRNGKey(1)
        batch = {"tokens": jax.random.randint(rng, (8, 32), 0,
                                              cfg.vocab_size)}
        batch["labels"] = batch["tokens"]

        with am.logical_binding(mesh):
            step, sspec, bspec = make_sharded_train_step(
                cfg, tcfg, mesh, state)
            s2, m = step(state, batch)
            loss_sharded = float(m["loss"])

        # oracle: plain jit on 1 logical device path
        from repro.train.train_step import make_train_step
        state_o = init_train_state(jax.random.PRNGKey(0), cfg)
        _, m_o = jax.jit(make_train_step(cfg, tcfg))(state_o, batch)
        assert abs(loss_sharded - float(m_o["loss"])) < 5e-2, (
            loss_sharded, float(m_o["loss"]))
        print("SHARDED-TRAIN-OK", loss_sharded)
        """)
    assert "SHARDED-TRAIN-OK" in out


def test_grad_compression_ef_allreduce():
    out = run_devices("""
        import jax, numpy as np, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.core.context import make_mesh
        from repro.train.grad_compress import ef_allreduce_mean

        mesh = make_mesh((4,), ("pod",))
        rng = np.random.default_rng(0)
        # per-pod distinct gradients (stacked on leading axis)
        gs = rng.normal(size=(4, 33)).astype(np.float32)
        errs = np.zeros_like(gs)

        def f(g, e):
            return ef_allreduce_mean(g[0], e[0], "pod")

        fn = jax.shard_map(lambda g, e: tuple(
                 x[None] for x in ef_allreduce_mean(g[0], e[0], "pod")),
                 mesh=mesh, in_specs=(P("pod"), P("pod")),
                 out_specs=(P("pod"), P("pod")))
        avg, new_err = fn(jnp.asarray(gs), jnp.asarray(errs))
        true_mean = gs.mean(0)
        # int8 quantization: within ~2/127 of max-abs scale
        scale = np.abs(gs).max() / 127
        np.testing.assert_allclose(np.asarray(avg)[0], true_mean,
                                   atol=4 * scale)
        # all pods agree on the result
        for i in range(1, 4):
            np.testing.assert_allclose(np.asarray(avg)[i],
                                       np.asarray(avg)[0], atol=1e-6)
        # error feedback: residual = input - quantized(input)
        assert np.abs(np.asarray(new_err)).max() <= scale * 1.01
        print("EF-ALLREDUCE-OK")
        """)
    assert "EF-ALLREDUCE-OK" in out


def test_embed_lookup_sharded():
    out = run_devices("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.core.context import make_mesh
        from repro.sharding import axes as am

        mesh = make_mesh((2, 4), ("data", "model"))
        embed = jnp.asarray(np.random.default_rng(0).normal(
            size=(64, 16)).astype(np.float32))
        tokens = jnp.asarray(np.random.default_rng(1).integers(
            0, 64, (8, 5)).astype(np.int32))
        with am.logical_binding(mesh):
            out = am.embed_lookup(embed, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(embed)[tokens],
                                   rtol=1e-6)
        print("EMBED-OK")
        """)
    assert "EMBED-OK" in out


def test_elastic_checkpoint_reshard():
    """Save under a 4-shard mesh, restore under a 2-shard mesh."""
    out = run_devices("""
        import jax, numpy as np, jax.numpy as jnp, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint.manager import CheckpointManager
        from repro.core.context import make_mesh

        tree = {"w": jnp.arange(32, dtype=jnp.float32).reshape(8, 4)}
        m4 = make_mesh((4,), ("data",))
        m2 = make_mesh((2,), ("data",))
        sharded = jax.device_put(tree["w"], NamedSharding(m4, P("data")))
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d)
            mgr.save(1, {"w": sharded})
            restored = mgr.restore(
                {"w": jnp.zeros((8, 4))},
                shardings={"w": NamedSharding(m2, P("data"))})
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(tree["w"]))
        assert restored["w"].sharding.mesh.shape["data"] == 2
        print("ELASTIC-OK")
        """)
    assert "ELASTIC-OK" in out


def test_moe_ep_shardmap_matches_einsum():
    """Explicit-EP shuffle MoE == auto-SPMD einsum MoE (§Perf iteration B1)."""
    out = run_devices("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced_config
        from repro.core.context import make_mesh
        from repro.models import moe as M
        from repro.sharding import axes as am

        cfg = reduced_config(get_config("qwen2-moe-a2.7b"))
        cfg = dataclasses.replace(cfg, n_experts=4, experts_per_token=2,
                                  capacity_factor=8.0)
        params = M.init_moe(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1),
                              (4, 128, cfg.d_model)).astype(jnp.bfloat16)
        y1, m1 = M._moe_ffn_einsum(params, cfg, x)
        mesh = make_mesh((2, 4), ("data", "model"))
        with am.logical_binding(mesh):
            y2, m2 = M.moe_ffn(params, cfg, x)
        a = np.asarray(y1, np.float32); b = np.asarray(y2, np.float32)
        rel = np.abs(a - b).max() / (np.abs(a).max() + 1e-9)
        assert rel < 2e-2, rel
        assert abs(float(m1["router_z_loss"]) - float(m2["router_z_loss"])) < 1e-3
        print("MOE-EP-MATCH-OK", rel)
        """)
    assert "MOE-EP-MATCH-OK" in out
