"""Weights of a dense GQA decoder (the Llama block of SmolLM), made from a
seed on the device in one jitted call, in float32, in the parameter
layout the program takes (layers stacked on a leading axis).

The program and the reference are both given these weights; neither
makes its own.  Projections are normal with variance 1/fan-in, the
embedding (tied to the output head) normal with variance 1/hidden, norm
scales one.
"""
from __future__ import annotations

import math


def key_of(seed: int):
    """A PRNG key from any non-negative seed (wider than 32 bits too)."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def shapes(cfg: dict) -> dict:
    """``{path: (shape, fan_in)}`` of every weight; fan-in 0 marks a norm
    scale (ones)."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    n, dh = cfg["num_hidden_layers"], d // h
    return {
        "embed": ((v, d), d),
        "final_norm/scale": ((d,), 0),
        "decoder/layer_0/mixer/wq": ((n, d, h * dh), d),
        "decoder/layer_0/mixer/wk": ((n, d, hk * dh), d),
        "decoder/layer_0/mixer/wv": ((n, d, hk * dh), d),
        "decoder/layer_0/mixer/wo": ((n, h * dh, d), h * dh),
        "decoder/layer_0/mixer/norm/scale": ((n, d), 0),
        "decoder/layer_0/ffn/w_gate": ((n, d, f), d),
        "decoder/layer_0/ffn/w_in": ((n, d, f), d),
        "decoder/layer_0/ffn/w_out": ((n, f, d), f),
        "decoder/layer_0/ffn/norm/scale": ((n, d), 0),
    }


def nest(flat: dict) -> dict:
    out = {}
    for path, v in flat.items():
        *parts, last = path.split("/")
        d = out
        for p in parts:
            d = d.setdefault(p, {})
        d[last] = v
    return out


def make_params(cfg: dict, seed: int):
    """The weights of ``cfg`` for ``seed``, on the default device."""
    import jax
    import jax.numpy as jnp

    spec = shapes(cfg)

    def build(key):
        flat = {}
        for i, (path, (shape, fan_in)) in enumerate(spec.items()):
            if fan_in == 0:
                flat[path] = jnp.ones(shape, jnp.float32)
            else:
                flat[path] = jax.random.normal(
                    jax.random.fold_in(key, i), shape,
                    jnp.float32) / math.sqrt(fan_in)
        return nest(flat)

    return jax.jit(build)(key_of(seed))
