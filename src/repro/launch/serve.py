"""Production serving launcher: batched prefill/decode over a mesh.

Usage:
    python -m repro.launch.serve --arch phi3-mini-3.8b --reduced \
        --batch 4 --prompt-len 16 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.configs import get_config, reduced_config
    from repro.models import transformer as T
    from repro.serve.engine import Engine, ServeConfig

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)

    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    engine = Engine(cfg, params,
                    ServeConfig(max_len=args.prompt_len + args.gen + 8,
                                temperature=args.temperature))
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(1, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32)
    fe = None
    if cfg.frontend is not None or cfg.is_encoder_decoder:
        fe = jnp.asarray(0.02 * rng.normal(
            size=(args.batch, cfg.frontend_seq, cfg.d_model)), jnp.float32)
    t0 = time.perf_counter()
    out = engine.generate(prompts, n_tokens=args.gen, frontend_embeds=fe)
    dt = time.perf_counter() - t0
    print(f"generated {out.shape} in {dt:.2f}s "
          f"({out.size / dt:.0f} tok/s)")
    print("serve launcher done")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
