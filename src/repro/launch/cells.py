"""(architecture × shape) cell definitions: step functions, input specs,
shardings — shared by the dry-run, roofline, and benchmark harnesses.

``input_specs`` returns ShapeDtypeStruct stand-ins (weak-type-correct,
shardable, no device allocation); full configs are only ever lowered, never
materialized, on this container.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import ShapeCell, get_config
from repro.configs.base import ModelConfig
from repro.models import transformer as T
from repro.sharding import axes as axes_mod
from repro.sharding import partition
from repro.train.optimizer import OptimizerConfig
from repro.train.train_step import (TrainConfig, TrainState,
                                    init_train_state, make_train_step)


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def roofline_config(cfg: ModelConfig, k: int) -> ModelConfig:
    """Depth-k, fully-unrolled variant for HLO cost extrapolation.

    ``compiled.cost_analysis()`` counts loop bodies ONCE (not × trip count),
    so the full-depth scanned model under-reports FLOPs/bytes by ~n_groups.
    We compile k=1 and k=2 group variants with every scan unrolled/disabled
    (layer-group scan unrolled; SSM/mLSTM/attention seq-chunk loops widened
    to one chunk) and extrapolate linearly:
        cost(G) = (2·c1 − c2) + G·(c2 − c1).
    Lowering only — no buffers are ever allocated at these shapes.
    """
    updates = dict(n_layers=k * cfg.group_size, scan_unroll=True,
                   scan_chunk=2**30, mlstm_chunk=2**30, attn_q_chunk=2**30)
    if cfg.is_encoder_decoder:
        updates["n_encoder_layers"] = k
    return dataclasses.replace(cfg, **updates)


def slstm_flops_correction(cfg: ModelConfig, cell: ShapeCell,
                           dp_shards: int) -> float:
    """Per-device FLOPs missing from sLSTM's sequential time scan.

    The recurrent matmul (B_loc, D)·(D, 4D) runs once per timestep but is
    counted once total; add the remaining (S−1) steps analytically
    (×3 for train: fwd + two bwd matmuls)."""
    n_slstm = sum(1 for kk in cfg.block_pattern if kk == "slstm") \
        * cfg.n_groups
    if n_slstm == 0 or cell.seq_len <= 1 or cell.kind == "decode":
        return 0.0
    b_loc = max(cell.global_batch // dp_shards, 1)
    per_step = 2.0 * b_loc * cfg.d_model * 4 * cfg.d_model
    mult = 3.0 if cell.kind == "train" else 1.0
    return n_slstm * per_step * (cell.seq_len - 1) * mult


def cell_rules(cfg: ModelConfig, cell: ShapeCell,
               overrides: Optional[Dict] = None) -> Dict:
    rules = dict(axes_mod.DEFAULT_RULES)
    if cell.global_batch == 1:
        rules["batch"] = None          # long-context decode: nothing to DP
    if overrides:
        rules.update(overrides)
    return rules


def input_specs(cfg: ModelConfig, cell: ShapeCell) -> Dict[str, Any]:
    """ShapeDtypeStructs for every model input of this cell."""
    b, s = cell.global_batch, cell.seq_len
    if cell.kind in ("train", "prefill"):
        text = s - (cfg.frontend_seq if cfg.frontend == "vision" else 0)
        out = {"tokens": _sds((b, text), jnp.int32),
               "labels": _sds((b, text), jnp.int32)}
        if cfg.frontend is not None or cfg.is_encoder_decoder:
            out["frontend"] = _sds((b, cfg.frontend_seq, cfg.d_model),
                                   jnp.float32)
        if cell.kind == "prefill":
            out.pop("labels")
        return out
    # decode: one new token against a cache of length s
    return {"token": _sds((b, 1), jnp.int32),
            "pos": _sds((1,), jnp.int32)}


# ---------------------------------------------------------------------------
# step functions
# ---------------------------------------------------------------------------
def _train_cfg(cfg: ModelConfig, cell: Optional[ShapeCell] = None,
               micro_batches: Optional[int] = None,
               dp_shards: int = 16) -> TrainConfig:
    if micro_batches is None:
        # bound per-group activation carries: pick micro-batches from the
        # estimated per-device residual-stack bytes (n_groups × B_loc × S ×
        # d_model × bf16 ≲ 2 GiB), not the param count — small-d models at
        # big batches need accumulation just as much as the 67B ones
        micro_batches = 1
        if cell is not None and cell.kind == "train":
            b_loc = max(cell.global_batch // dp_shards, 1)
            stack = (cfg.n_groups * b_loc * cell.seq_len * cfg.d_model * 2
                     * (3 if set(cfg.block_pattern) & {"mlstm", "slstm",
                                                       "mamba"} else 1))
            # sLSTM's sequential time scan saves 4 f32 carries per step
            n_slstm = cfg.block_pattern.count("slstm") * cfg.n_groups
            stack += n_slstm * b_loc * cell.seq_len * cfg.d_model * 16
            # Mamba chunk scans save (B, chunk, d_inner, N) f32 per chunk
            n_mamba = cfg.block_pattern.count("mamba") * cfg.n_groups
            if n_mamba:
                stack += (b_loc * cell.seq_len * cfg.ssm_expand
                          * cfg.d_model * cfg.ssm_state_dim * 4) // 16
            micro_batches = 1
            while stack / micro_batches > 1.5e9 and micro_batches < 16:
                micro_batches *= 2
            # floor from param scale (activation estimate is approximate)
            params = cfg.param_count()
            micro_batches = max(micro_batches,
                                16 if params > 2e10 else
                                (8 if params > 2e9 else 1))
            # each micro-batch must still split across all DP shards
            micro_batches = min(micro_batches,
                                max(cell.global_batch // dp_shards, 1))
            while cell.global_batch % (micro_batches * dp_shards):
                micro_batches //= 2
            micro_batches = max(micro_batches, 1)
    return TrainConfig(optimizer=OptimizerConfig(),
                       micro_batches=micro_batches)


def make_train_fn(cfg: ModelConfig, cell: Optional[ShapeCell] = None):
    return make_train_step(cfg, _train_cfg(cfg, cell))


def make_prefill_fn(cfg: ModelConfig, cache_len: int):
    def prefill_step(params, batch):
        logits, cache, _ = T.apply_lm(
            params, cfg, batch["tokens"], mode="prefill",
            frontend_embeds=batch.get("frontend"), cache_len=cache_len,
            last_logit_only=True)
        return logits[:, -1], cache

    return prefill_step


def make_decode_fn(cfg: ModelConfig):
    def serve_step(params, cache, token, pos):
        logits, new_cache, _ = T.apply_lm(
            params, cfg, token, mode="decode", cache=cache, positions=pos)
        next_token = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_token, new_cache

    return serve_step


# ---------------------------------------------------------------------------
# lowering
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class LoweredCell:
    arch: str
    shape: str
    kind: str
    lowered: Any
    abstract_args: Tuple


def _state_shapes(cfg: ModelConfig) -> TrainState:
    rng = jax.random.PRNGKey(0)
    return jax.eval_shape(functools.partial(init_train_state, cfg=cfg), rng)


def _params_shapes(cfg: ModelConfig):
    rng = jax.random.PRNGKey(0)
    return jax.eval_shape(functools.partial(T.init_lm, cfg=cfg), rng)


def _cache_shapes(cfg: ModelConfig, batch: int, cache_len: int):
    fn = functools.partial(T.init_cache, cfg, batch, cache_len,
                           jnp.dtype(cfg.dtype))
    shapes = jax.eval_shape(fn)
    if cfg.is_encoder_decoder:
        shapes["enc_out"] = _sds(
            (batch, cfg.frontend_seq, cfg.d_model), jnp.dtype(cfg.dtype))
    return shapes


def lower_cell(arch: str, cell: ShapeCell, mesh: Mesh,
               rule_overrides: Optional[Dict] = None,
               cfg: Optional[ModelConfig] = None,
               micro_batches: Optional[int] = None) -> LoweredCell:
    cfg = cfg or get_config(arch)
    rules = cell_rules(cfg, cell, rule_overrides)
    specs = input_specs(cfg, cell)

    with axes_mod.logical_binding(mesh, rules):
        bspec = partition.batch_spec(mesh, rules)
        b_axes = bspec[0] if len(bspec) else None

        if cell.kind == "train":
            state = _state_shapes(cfg)
            pspecs = partition.param_specs(state.params, cfg, mesh, rules)
            state_sh = TrainState(
                params=jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs),
                opt=type(state.opt)(
                    mu=jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs),
                    nu=jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs),
                    count=NamedSharding(mesh, P())))
            batch_sh = {k: NamedSharding(mesh, P(b_axes))
                        for k in specs}
            dp = mesh.devices.size // mesh.shape.get("model", 1)
            fn = make_train_step(
                cfg, _train_cfg(cfg, cell, micro_batches, dp_shards=dp))
            lowered = jax.jit(
                fn, in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, None),
                donate_argnums=(0,),
            ).lower(state, specs)
            return LoweredCell(arch, cell.name, "train", lowered,
                               (state, specs))

        params = _params_shapes(cfg)
        pspecs = partition.param_specs(params, cfg, mesh, rules)
        params_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)

        if cell.kind == "prefill":
            fn = make_prefill_fn(cfg, cache_len=cell.seq_len)
            batch_sh = {k: NamedSharding(mesh, P(b_axes)) for k in specs}
            cache_shapes = jax.eval_shape(
                lambda p, b: fn(p, b)[1], params, specs)
            cache_sh = partition.cache_shardings(cache_shapes, cfg, mesh,
                                                 rules)
            lowered = jax.jit(
                fn, in_shardings=(params_sh, batch_sh),
                out_shardings=(NamedSharding(mesh, P(b_axes)), cache_sh),
            ).lower(params, specs)
            return LoweredCell(arch, cell.name, "prefill", lowered,
                               (params, specs))

        # decode — no remat (nothing to rematerialize for a 1-token step;
        # the checkpoint wrapper only adds buffer copies)
        cfg = dataclasses.replace(cfg, remat=False)
        cache = _cache_shapes(cfg, cell.global_batch, cell.seq_len)
        cache_sh = partition.cache_shardings(cache, cfg, mesh, rules)
        fn = make_decode_fn(cfg)
        tok_sh = NamedSharding(mesh, P(b_axes))
        pos_sh = NamedSharding(mesh, P())
        lowered = jax.jit(
            fn,
            in_shardings=(params_sh, cache_sh, tok_sh, pos_sh),
            out_shardings=(tok_sh, cache_sh),
            donate_argnums=(1,),
        ).lower(params, cache, specs["token"], specs["pos"])
        return LoweredCell(arch, cell.name, "decode", lowered,
                           (params, cache, specs["token"], specs["pos"]))
