"""Train-step factory: loss, grad accumulation, sharded pjit step."""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import transformer as T
from repro.sharding import partition
from repro.train.optimizer import (OptimizerConfig, OptState, adamw_update,
                                   init_opt_state)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    optimizer: OptimizerConfig = OptimizerConfig()
    micro_batches: int = 1
    moe_aux_coef: float = 0.01
    router_z_coef: float = 1e-3
    grad_compress: bool = False   # int8 EF on the pod axis (pure-DP meshes)


class TrainState(NamedTuple):
    params: Any
    opt: OptState


def init_train_state(rng, cfg: ModelConfig) -> TrainState:
    params = T.init_lm(rng, cfg)
    return TrainState(params=params, opt=init_opt_state(params))


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Masked next-token CE. labels == -1 are ignored. Returns (loss, acc).

    Sharding note: the gold logit is extracted with a one-hot contraction
    (not take_along_axis) and accuracy compares gold against the row max —
    both are plain reductions over the vocab dim, so they partition cleanly
    when logits are vocab-sharded (a vocab gather/argmax would force the
    SPMD partitioner to replicate the full logits tensor).
    """
    mask = labels >= 0
    safe = jnp.maximum(labels, 0)
    v = logits.shape[-1]
    onehot = jax.nn.one_hot(safe, v, dtype=logits.dtype)
    gold = jnp.sum(logits * onehot, axis=-1)
    logz = jax.nn.logsumexp(logits, axis=-1)
    nll = (logz - gold) * mask
    denom = jnp.maximum(jnp.sum(mask), 1)
    loss = jnp.sum(nll) / denom
    row_max = jnp.max(logits, axis=-1)
    acc = jnp.sum((gold >= row_max) * mask) / denom
    return loss, acc


_KEEP_F32 = ("router", "a_log", "dt_bias", "b_gates", "scale", "b")


def cast_params_for_compute(params, dtype):
    """bf16-cast params *before* the FSDP all-gather (ZeRO trick).

    Weights are consumed in bf16 anyway; casting the fp32 masters first
    halves every per-layer parameter all-gather.  Precision-critical leaves
    (router logits, SSM decay/bias, norm scales) stay fp32.
    """

    def leaf(path, p):
        name = ""
        for part in path[::-1]:
            if isinstance(part, jax.tree_util.DictKey):
                name = str(part.key)
                break
        if name in _KEEP_F32 or p.ndim < 2:
            return p
        return p.astype(dtype)

    return jax.tree_util.tree_map_with_path(leaf, params)


def loss_fn(params, cfg: ModelConfig, tcfg: TrainConfig, batch,
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    # NOTE (§Perf C5): bf16-casting params here (before the FSDP gather)
    # was measured to leave the collective term unchanged (activation
    # psums dominate at this batch) while costing +0.7 GiB/dev for the
    # bf16 copy — refuted and reverted; `cast_params_for_compute` is kept
    # for smaller-batch regimes where parameter gathers dominate.
    logits, _, aux = T.apply_lm(
        params, cfg, batch["tokens"], mode="train",
        frontend_embeds=batch.get("frontend"))
    labels = batch["labels"]
    if cfg.frontend == "vision":
        # image prefix positions carry no LM loss
        pad = jnp.full(labels.shape[:1] + (cfg.frontend_seq,), -1,
                       labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)
    ce, acc = cross_entropy(logits, labels)
    total = (ce + tcfg.moe_aux_coef * aux["moe_aux_loss"]
             + tcfg.router_z_coef * aux["router_z_loss"])
    metrics = {"loss": ce, "accuracy": acc, **aux}
    return total, metrics


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig):
    """Returns train_step(state, batch) → (state, metrics) (jit-compatible)."""

    def train_step(state: TrainState, batch):
        m = tcfg.micro_batches
        if m == 1:
            grads, metrics = jax.grad(
                lambda p: loss_fn(p, cfg, tcfg, batch), has_aux=True)(
                    state.params)
        else:
            # gradient accumulation over micro-batches via lax.scan: ONE
            # fwd/bwd loop pair in the HLO, so the per-group residual stack
            # is allocated once and reused across micro-steps (a Python
            # loop leaves every micro-step's stack allocated separately —
            # CPU XLA does not share while-carry buffers across loops).
            def micro(b):
                return jax.grad(
                    lambda p: loss_fn(p, cfg, tcfg, b), has_aux=True)(
                        state.params)

            def split(x):
                return x.reshape((m, x.shape[0] // m) + x.shape[1:])

            micro_batches = {k: split(v) for k, v in batch.items()}

            def body(acc, mb):
                g, met = micro(mb)
                acc_g, acc_m = acc
                acc_g = jax.tree.map(lambda a, b: a + b, acc_g, g)
                acc_m = jax.tree.map(lambda a, b: a + b, acc_m, met)
                return (acc_g, acc_m), None

            zero_g = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            zero_m = {"loss": jnp.zeros((), jnp.float32),
                      "accuracy": jnp.zeros((), jnp.float32),
                      **T.zero_metrics(cfg)}
            (grads, metrics), _ = jax.lax.scan(
                body, (zero_g, zero_m), micro_batches)
            grads = jax.tree.map(lambda g: g / m, grads)
            metrics = jax.tree.map(lambda x: x / m, metrics)

        params, opt, opt_metrics = adamw_update(
            tcfg.optimizer, state.params, grads, state.opt)
        metrics.update(opt_metrics)
        return TrainState(params, opt), metrics

    return train_step


def make_sharded_train_step(cfg: ModelConfig, tcfg: TrainConfig, mesh: Mesh,
                            state_template: TrainState, rules=None):
    """pjit the train step with FSDP×TP shardings derived from the rules."""
    pspecs = partition.param_specs(state_template.params, cfg, mesh, rules)

    def ns(spec):
        return NamedSharding(mesh, spec)

    pshard = jax.tree.map(ns, pspecs,
                          is_leaf=lambda x: isinstance(x, P))
    opt_shard = OptState(mu=pshard, nu=pshard, count=ns(P()))
    state_shard = TrainState(params=pshard, opt=opt_shard)
    bspec = partition.batch_spec(mesh, rules)
    b_axes = bspec[0] if len(bspec) else None
    batch_shard = {"tokens": ns(P(b_axes)), "labels": ns(P(b_axes))}
    if cfg.frontend is not None or cfg.is_encoder_decoder:
        batch_shard["frontend"] = ns(P(b_axes))

    step = make_train_step(cfg, tcfg)
    return jax.jit(
        step,
        in_shardings=(state_shard, batch_shard),
        out_shardings=(state_shard, None),
        donate_argnums=(0,)), state_shard, batch_shard
