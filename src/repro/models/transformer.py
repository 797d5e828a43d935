"""Model composition: layer blocks → scanned stacks → full LMs.

Supports the ten assigned architectures through ``ModelConfig``:
decoder-only dense/GQA/SWA/MLA, MoE FFNs, hybrid Mamba+attention groups
(Jamba), xLSTM stacks, encoder–decoder with stub audio frontend (Whisper),
and VLM token streams with stub patch embeddings (InternVL2).

Layer stacking uses ``lax.scan`` over *groups* (one group = one repetition
of ``cfg.block_pattern``) with per-group ``jax.checkpoint`` — the HLO holds
one group body regardless of depth (95-layer DeepSeek compiles as fast as
12-layer xLSTM), and remat keeps activation memory to one group.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.sharding.axes import constrain, embed_lookup

from . import moe as moe_mod
from . import ssm as ssm_mod
from . import xlstm as xlstm_mod
from .layers import (Params, _dense_init, gqa_attention, init_attention,
                     init_mla, init_mlp, init_rmsnorm, mla_attention, mlp,
                     rms_norm)

_ZERO_METRICS = ("moe_aux_loss", "router_z_loss", "moe_dropped_frac")


def zero_metrics(cfg: ModelConfig) -> Params:
    """The aux metrics ``apply_lm`` returns for ``cfg``, zero: the MoE
    losses, and under ``counters`` the integer counters of a held expert
    layer."""
    out = {k: jnp.zeros((), jnp.float32) for k in _ZERO_METRICS}
    if cfg.is_moe and cfg.experts_held is not None:
        out["counters"] = {k: jnp.zeros((), jnp.int32)
                           for k in moe_mod.HELD_COUNTERS}
    return out


def dense_layer_config(cfg: ModelConfig) -> ModelConfig:
    """The config of a leading dense layer: the model's attention with a
    SwiGLU of width ``d_ff`` in place of the experts."""
    import dataclasses
    return dataclasses.replace(cfg, n_experts=0, n_dense_layers=0)


def _layer_has_moe(cfg: ModelConfig, i: int, kind: str) -> bool:
    if not cfg.is_moe or cfg.d_ff == 0 or kind in ("mlstm", "slstm"):
        return False
    return i % cfg.moe_every == cfg.moe_every - 1


def _layer_has_ffn(cfg: ModelConfig, kind: str) -> bool:
    return cfg.d_ff > 0 and kind not in ("mlstm", "slstm")


# ---------------------------------------------------------------------------
# single layer
# ---------------------------------------------------------------------------
def init_layer(rng, cfg: ModelConfig, kind: str, i: int,
               cross: bool = False) -> Params:
    k_mix, k_ffn, k_cross = jax.random.split(rng, 3)
    p: Params = {}
    if kind == "attn":
        p["mixer"] = (init_mla(k_mix, cfg) if cfg.attention == "mla"
                      else init_attention(k_mix, cfg))
    elif kind == "mamba":
        p["mixer"] = ssm_mod.init_mamba(k_mix, cfg)
    elif kind == "mlstm":
        p["mixer"] = xlstm_mod.init_mlstm(k_mix, cfg)
    elif kind == "slstm":
        p["mixer"] = xlstm_mod.init_slstm(k_mix, cfg)
    else:
        raise ValueError(kind)
    if cross:
        p["cross"] = init_attention(k_cross, cfg)
    if _layer_has_moe(cfg, i, kind):
        p["ffn"] = moe_mod.init_moe(k_ffn, cfg)
    elif _layer_has_ffn(cfg, kind):
        p["ffn"] = init_mlp(k_ffn, cfg)
    return p


def apply_layer(p: Params, cfg: ModelConfig, x, kind: str, i: int, *,
                mode: str, cache, positions, enc_out=None, causal=True,
                cache_len: int = 0):
    metrics = zero_metrics(cfg)
    mix_cache = cache["mixer"] if cache is not None else None
    if kind == "attn":
        fn = mla_attention if cfg.attention == "mla" else gqa_attention
        dx, new_mix = fn(p["mixer"], cfg, x, positions=positions, mode=mode,
                         cache=mix_cache, cache_len=cache_len,
                         **({} if cfg.attention == "mla"
                            else {"causal": causal}))
    elif kind == "mamba":
        dx, new_mix = ssm_mod.mamba_mixer(p["mixer"], cfg, x, mode=mode,
                                          cache=mix_cache)
    elif kind == "mlstm":
        dx, new_mix = xlstm_mod.mlstm_mixer(p["mixer"], cfg, x, mode=mode,
                                            cache=mix_cache)
    else:  # slstm
        dx, new_mix = xlstm_mod.slstm_mixer(p["mixer"], cfg, x, mode=mode,
                                            cache=mix_cache)
    x = x + dx

    if "cross" in p:
        cdx, _ = gqa_attention(p["cross"], cfg, x, positions=positions,
                               mode="train", kv_source=enc_out, causal=False)
        x = x + cdx

    if "ffn" in p:
        if _layer_has_moe(cfg, i, kind):
            dff, m = moe_mod.moe_ffn(p["ffn"], cfg, x)
            for k, v in m.items():
                metrics[k] = jax.tree.map(jnp.add, metrics[k], v)
        else:
            dff = mlp(p["ffn"], cfg, x)
        x = x + dff
    new_cache = {"mixer": new_mix} if new_mix is not None else None
    return x, new_cache, metrics


# ---------------------------------------------------------------------------
# scanned stack of groups
# ---------------------------------------------------------------------------
def init_stack(rng, cfg: ModelConfig, cross: bool = False) -> Params:
    def one_group(key):
        ks = jax.random.split(key, cfg.group_size)
        return {f"layer_{i}": init_layer(ks[i], cfg, kind, i, cross)
                for i, kind in enumerate(cfg.block_pattern)}

    keys = jax.random.split(rng, cfg.n_groups)
    return jax.vmap(one_group)(keys)


def init_group_cache(cfg: ModelConfig, batch: int, cache_len: int,
                     dtype, pattern: Optional[Tuple[str, ...]] = None
                     ) -> Params:
    """Zero decode cache for one group (stacked by caller), or for the
    layers of ``pattern``."""
    out = {}
    for i, kind in enumerate(pattern or cfg.block_pattern):
        if kind == "attn":
            if cfg.attention == "mla":
                mix = {
                    "c_kv": jnp.zeros((batch, cache_len, cfg.kv_lora_rank),
                                      dtype),
                    "k_rope": jnp.zeros((batch, 1, cache_len,
                                         cfg.qk_rope_dim), dtype),
                    "pos": jnp.full((cache_len,), -1, jnp.int32),
                    "cursor": jnp.zeros((), jnp.int32)}
            else:
                l = cfg.decode_cache_len(cache_len)
                hk, dh = cfg.n_kv_heads, cfg.head_dim
                kv_dt = jnp.int8 if cfg.kv_quant else dtype
                mix = {"k": jnp.zeros((batch, hk, l, dh), kv_dt),
                       "v": jnp.zeros((batch, hk, l, dh), kv_dt),
                       "pos": jnp.full((l,), -1, jnp.int32),
                       "cursor": jnp.zeros((), jnp.int32)}
                if cfg.kv_quant:
                    mix["k_s"] = jnp.full((batch, hk, l, 1), 1e-8,
                                          jnp.float32)
                    mix["v_s"] = jnp.full((batch, hk, l, 1), 1e-8,
                                          jnp.float32)
        elif kind == "mamba":
            mix = ssm_mod.init_mamba_cache(cfg, batch, dtype)
        elif kind == "mlstm":
            mix = xlstm_mod.init_mlstm_cache(cfg, batch)
        else:
            mix = xlstm_mod.init_slstm_cache(cfg, batch)
        out[f"layer_{i}"] = {"mixer": mix}
    return out


def init_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype) -> Params:
    """Zero decode cache as ``apply_lm`` takes it: the scanned groups'
    stacked on a leading axis, and the leading dense layers' one entry
    each."""
    one = init_group_cache(cfg, batch, cache_len, dtype)
    cache = {"groups": jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (cfg.n_groups,) + a.shape), one)}
    if cfg.n_dense_layers:
        cache["dense"] = init_group_cache(
            cfg, batch, cache_len, dtype,
            pattern=("attn",) * cfg.n_dense_layers)
    return cache


@jax.custom_vjp
def _grad_transparent_barrier(x):
    return jax.lax.optimization_barrier(x)


def _gtb_fwd(x):
    return jax.lax.optimization_barrier(x), None


def _gtb_bwd(_, g):
    return (g,)


# optimization_barrier has no differentiation rule on older jax; keep the
# barrier in the forward pass and pass cotangents straight through.
_grad_transparent_barrier.defvjp(_gtb_fwd, _gtb_bwd)


def apply_stack(stacked: Params, cfg: ModelConfig, x, *, mode: str,
                caches=None, positions=None, enc_out=None, causal=True,
                cache_len: int = 0):
    def body(carry, inp):
        x, aux = carry
        # barrier: stops XLA hoisting the bf16→f32 norm upcast out of the
        # (rematerialized) body — without it the scan's saved per-group
        # residual stack is materialized in f32, doubling activation memory.
        x = _grad_transparent_barrier(x)
        gp = inp[0] if isinstance(inp, tuple) else inp
        gc = inp[1] if isinstance(inp, tuple) else None
        new_caches = {}
        for i, kind in enumerate(cfg.block_pattern):
            lc = gc[f"layer_{i}"] if gc is not None else None
            x, nc, m = apply_layer(
                gp[f"layer_{i}"], cfg, x, kind, i, mode=mode, cache=lc,
                positions=positions, enc_out=enc_out, causal=causal,
                cache_len=cache_len)
            for k, v in m.items():
                aux[k] = jax.tree.map(jnp.add, aux[k], v)
            if nc is not None:
                new_caches[f"layer_{i}"] = nc
        ys = new_caches if new_caches else None
        return (x, aux), ys

    if cfg.remat:
        body = jax.checkpoint(body)

    aux0 = zero_metrics(cfg)
    xs = stacked if caches is None else (stacked, caches)
    (x, aux), new_caches = jax.lax.scan(
        body, (x, aux0), xs, unroll=True if cfg.scan_unroll else 1)
    return x, new_caches, aux


# ---------------------------------------------------------------------------
# full language model
# ---------------------------------------------------------------------------
def init_lm(rng, cfg: ModelConfig) -> Params:
    ks = jax.random.split(rng, 5)
    p: Params = {
        "embed": _dense_init(ks[0], (cfg.vocab_size, cfg.d_model),
                             fan_in=cfg.d_model),
        "final_norm": init_rmsnorm(cfg.d_model),
        "decoder": init_stack(ks[1], cfg, cross=cfg.is_encoder_decoder),
    }
    if cfg.n_dense_layers:
        dcfg = dense_layer_config(cfg)
        kd = jax.random.split(ks[4], cfg.n_dense_layers)
        p["dense"] = {f"layer_{i}": init_layer(kd[i], dcfg, "attn", 0)
                      for i in range(cfg.n_dense_layers)}
    if not cfg.tie_embeddings:
        p["lm_head"] = _dense_init(ks[2], (cfg.d_model, cfg.vocab_size))
    if cfg.is_encoder_decoder:
        enc_cfg = encoder_config(cfg)
        p["encoder"] = init_stack(ks[3], enc_cfg)
        p["enc_norm"] = init_rmsnorm(cfg.d_model)
    return p


def encoder_config(cfg: ModelConfig):
    import dataclasses
    return dataclasses.replace(
        cfg, n_layers=cfg.n_encoder_layers, block_pattern=("attn",),
        n_experts=0, window=None)


def _encode(params: Params, cfg: ModelConfig, frontend_embeds: jnp.ndarray):
    enc_cfg = encoder_config(cfg)
    f = frontend_embeds.shape[1]
    pos = jnp.arange(f, dtype=jnp.int32)
    h, _, _ = apply_stack(params["encoder"], enc_cfg, frontend_embeds,
                          mode="train", positions=pos, causal=False)
    return rms_norm(params["enc_norm"], h, cfg.norm_eps)


def apply_lm(params: Params, cfg: ModelConfig, tokens: jnp.ndarray, *,
             mode: str = "train", cache: Optional[Params] = None,
             positions: Optional[jnp.ndarray] = None,
             frontend_embeds: Optional[jnp.ndarray] = None,
             cache_len: int = 0, last_logit_only: bool = False,
             ) -> Tuple[jnp.ndarray, Optional[Params], Dict[str, Any]]:
    """tokens (B, S) → logits (B, S, V).

    ``frontend_embeds``: audio frames (enc-dec) or image patches (VLM,
    prepended to the token stream).  ``positions`` default to
    ``arange(S)`` (train/prefill) and must be given for decode.
    ``last_logit_only``: serving prefill needs logits for the final
    position only — skipping the (B,S,V) head matmul + its TP reduction is
    a large collective/memory win.  Leading dense layers
    (``cfg.n_dense_layers``) run before the scanned groups, each with its
    own cache entry under ``cache["dense"]``.
    """
    dtype = jnp.dtype(cfg.dtype)
    b, s = tokens.shape
    x = embed_lookup(params["embed"], tokens).astype(dtype)
    x = constrain(x, "batch", "seq", "embed")

    enc_out = None
    if cfg.is_encoder_decoder:
        if mode == "decode":
            enc_out = cache["enc_out"]
        else:
            enc_out = _encode(params, cfg, frontend_embeds.astype(dtype))
    elif cfg.frontend == "vision" and mode != "decode":
        # VLM: image patch embeddings prefix the token stream
        x = jnp.concatenate([frontend_embeds.astype(dtype), x], axis=1)
        s = x.shape[1]

    if positions is None:
        positions = jnp.arange(s, dtype=jnp.int32)

    new_dense = {}
    if cfg.n_dense_layers:
        dcfg = dense_layer_config(cfg)
        for i in range(cfg.n_dense_layers):
            name = f"layer_{i}"
            x, nc, _ = apply_layer(
                params["dense"][name], dcfg, x, "attn", 0, mode=mode,
                cache=cache["dense"][name] if cache is not None else None,
                positions=positions, cache_len=cache_len)
            if nc is not None:
                new_dense[name] = nc

    groups_cache = cache["groups"] if cache is not None else None
    x, new_groups, aux = apply_stack(
        params["decoder"], cfg, x, mode=mode, caches=groups_cache,
        positions=positions, enc_out=enc_out, cache_len=cache_len)

    if last_logit_only:
        x = x[:, -1:]
    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"]).astype(dtype)
    logits = x @ head
    logits = constrain(logits, "batch", "seq", "vocab")

    new_cache = None
    if mode in ("prefill", "decode") and new_groups is not None:
        new_cache = {"groups": new_groups}
        if new_dense:
            new_cache["dense"] = new_dense
        if cfg.is_encoder_decoder:
            new_cache["enc_out"] = enc_out
    return logits.astype(jnp.float32), new_cache, aux
