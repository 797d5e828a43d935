"""Production training launcher.

Assembles: mesh → logical-rule binding → FSDP×TP sharded train step →
HPTMT data pipeline → checkpointed loop.  On a real pod this is the entry
point per host process (`jax.distributed.initialize` + the same code); on
this container it runs with whatever host devices exist.

Usage:
    python -m repro.launch.train --arch smollm-360m --steps 20 \
        --mesh 1x1 --batch 8 --seq 128 [--ckpt DIR]
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--mesh", default="1x1",
                    help="DATAxMODEL (e.g. 16x16) or PODxDATAxMODEL")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--reduced", action="store_true",
                    help="family-preserving reduced config (CPU demo)")
    ap.add_argument("--micro", type=int, default=1)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.configs import get_config, reduced_config
    from repro.core import HPTMTContext
    from repro.core.context import make_mesh
    from repro.data.pipeline import CorpusConfig, make_training_data
    from repro.sharding import axes as am
    from repro.train.optimizer import OptimizerConfig
    from repro.train.train_step import (TrainConfig, init_train_state,
                                        make_sharded_train_step)
    from repro.train.trainer import LoopConfig, train_loop

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)

    dims = [int(d) for d in args.mesh.split("x")]
    names = (("pod", "data", "model") if len(dims) == 3
             else ("data", "model"))[:len(dims)]
    mesh = make_mesh(dims, names) if np.prod(dims) > 1 else None

    tcfg = TrainConfig(
        optimizer=OptimizerConfig(warmup_steps=max(args.steps // 20, 1),
                                  total_steps=args.steps),
        micro_batches=args.micro)
    loop = LoopConfig(total_steps=args.steps, log_every=5,
                      checkpoint_every=max(args.steps // 2, 5),
                      checkpoint_dir=args.ckpt)

    ctx = HPTMTContext(mesh=mesh) if mesh is not None else HPTMTContext()
    data = make_training_data(cfg, ctx, batch=args.batch, seq_len=args.seq,
                              ccfg=CorpusConfig(vocab_size=cfg.vocab_size))

    if mesh is None:
        state = train_loop(cfg, tcfg, loop, data)
    else:
        with am.logical_binding(mesh):
            template = init_train_state(jax.random.PRNGKey(0), cfg)
            step, sspec, _ = make_sharded_train_step(cfg, tcfg, mesh,
                                                     template)
            state = template
            import time
            for i in range(args.steps):
                batch = next(data)
                t0 = time.perf_counter()
                state, metrics = step(state, batch)
                jax.block_until_ready(metrics["loss"])
                if i % 5 == 0:
                    print(f"step {i} loss={float(metrics['loss']):.4f} "
                          f"dt={(time.perf_counter()-t0)*1e3:.0f}ms")
    print("train launcher done")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
