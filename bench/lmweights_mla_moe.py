"""Weights of a DeepSeek-V2 block on one chip of an expert-parallel
deployment (latent attention, leading dense layers, the held routed
experts, shared experts, untied head), made from a seed on the device in
bfloat16, one jitted call per leaf, in the parameter layout the program
takes (leading dense layers one entry each, the others stacked on a
leading axis).

The program and the reference are both given these weights.  Projections
are normal with variance 1/fan-in, the embedding normal with variance
1/hidden, norm scales one.
"""
from __future__ import annotations

import functools
import math

from lmweights import key_of, nest


def shapes(cfg: dict) -> dict:
    """``{path: (shape, fan_in)}`` of every weight; fan-in 0 marks a norm
    scale (ones)."""
    d, v, h = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    nope, rd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    big_f, f = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = cfg["n_shared_experts"] * f
    e, held = cfg["published"]["n_routed_experts"], cfg["n_routed_experts"]
    n_dense = cfg["first_k_dense_replace"]
    n = cfg["num_hidden_layers"] - n_dense

    def mixer(lead):
        return {
            "wq": (lead + (d, h * (nope + rd)), d),
            "wdkv": (lead + (d, r + rd), d),
            "wukv": (lead + (r, h * (nope + vd)), r),
            "wo": (lead + (h * vd, d), h * vd),
            "norm/scale": (lead + (d,), 0),
            "kv_norm/scale": (lead + (r,), 0),
        }

    out = {"embed": ((v, d), d), "lm_head": ((d, v), d),
           "final_norm/scale": ((d,), 0)}
    for i in range(n_dense):
        pre = f"dense/layer_{i}/"
        out.update({pre + "mixer/" + k: s for k, s in mixer(()).items()})
        out.update({pre + "ffn/w_gate": ((d, big_f), d),
                    pre + "ffn/w_in": ((d, big_f), d),
                    pre + "ffn/w_out": ((big_f, d), big_f),
                    pre + "ffn/norm/scale": ((d,), 0)})
    pre = "decoder/layer_0/"
    out.update({pre + "mixer/" + k: s for k, s in mixer((n,)).items()})
    out.update({pre + "ffn/norm/scale": ((n, d), 0),
                pre + "ffn/router": ((n, d, e), d),
                pre + "ffn/w_gate": ((n, held, d, f), d),
                pre + "ffn/w_in": ((n, held, d, f), d),
                pre + "ffn/w_out": ((n, held, f, d), f),
                pre + "ffn/shared/w_gate": ((n, d, fs), d),
                pre + "ffn/shared/w_in": ((n, d, fs), d),
                pre + "ffn/shared/w_out": ((n, fs, d), fs)})
    return out


@functools.lru_cache(maxsize=None)
def _leaf_fn(shape: tuple, fan_in: int):
    import jax
    import jax.numpy as jnp

    def leaf(key, i):
        if fan_in == 0:
            return jnp.ones(shape, jnp.bfloat16)
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        return (x / math.sqrt(fan_in)).astype(jnp.bfloat16)

    return jax.jit(leaf)


def make_params(cfg: dict, seed: int):
    """The weights of ``cfg`` for ``seed``, on the default device."""
    key = key_of(seed)
    return nest({path: _leaf_fn(shape, fan_in)(key, i)
                 for i, (path, (shape, fan_in)) in enumerate(
                     shapes(cfg).items())})
