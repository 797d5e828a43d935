"""DeepSeek-V2-Lite's mechanisms against the plain reference of the
benchmark (``bench/configs/deepseek-v2-lite-ep8_ref.py``) at a small size on
seeded random weights: prefill then decode through the serving path, the
held share of the experts, dropless routing, YaRN, and a decode step that
never expands the latent cache."""
import dataclasses
import importlib.util
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.kernels import dispatch
from repro.models import moe
from repro.models import transformer as T
from repro.models.layers import mla_softmax_scale, mlp, rms_norm, \
    yarn_frequencies
from repro.serve.engine import Engine, ServeConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "bench", "configs", "deepseek-v2-lite-ep8_ref.py")
    spec = importlib.util.spec_from_file_location("deepseek_v2_lite_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def ref_config(cfg) -> dict:
    """The reference's configuration keys for a program config."""
    y = cfg.yarn
    return {
        "num_attention_heads": cfg.n_heads, "rms_norm_eps": cfg.norm_eps,
        "qk_nope_head_dim": cfg.qk_nope_dim, "qk_rope_head_dim": cfg.qk_rope_dim,
        "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
        "rope_theta": cfg.rope_theta,
        "rope_scaling": {"factor": y.factor, "beta_fast": y.beta_fast,
                         "beta_slow": y.beta_slow, "mscale": y.mscale,
                         "mscale_all_dim": y.mscale_all_dim,
                         "original_max_position_embeddings":
                             y.original_max_position},
        "num_experts_per_tok": cfg.experts_per_token,
        "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": 1,
        "experts_held": list(cfg.experts_held)}


def small(**kw):
    """DeepSeek-V2-Lite reduced: one dense layer, two MoE layers, MLA
    without a query LoRA, YaRN, half of the experts held."""
    cfg = reduced_config(get_config("deepseek-v2-lite"))
    assert (cfg.n_dense_layers, cfg.n_layers, cfg.q_lora_rank) == (1, 3, 0)
    assert cfg.experts_held == (0, cfg.n_experts // 2)
    return dataclasses.replace(cfg, **kw)


def test_prefill_then_decode_matches_reference_forward(ref):
    """Prefill, then decode step by step through the latent cache: every
    step's logits equal the reference's full forward at that position.
    Computed in float32, the program differs from the float32 reference
    only in the order of its sums and in the latent form of decode, so
    the tolerance is a float32 one."""
    cfg = small(dtype="float32", n_experts=8, experts_per_token=6,
                experts_held=(2, 6))
    params = T.init_lm(jax.random.PRNGKey(21), cfg)
    b, s, extra = 2, 24, 6
    tokens = jax.random.randint(jax.random.PRNGKey(22), (b, s + extra), 0,
                                cfg.vocab_size)
    want = np.asarray(ref.forward(ref_config(cfg), "float32", params, tokens))
    scale = np.max(np.abs(want))
    logits, cache, _ = T.apply_lm(params, cfg, tokens[:, :s], mode="prefill",
                                  cache_len=s + extra)
    np.testing.assert_allclose(np.asarray(logits), want[:, :s],
                               atol=1e-4 * scale)
    for i in range(extra):
        dec, cache, _ = T.apply_lm(params, cfg, tokens[:, s + i:s + i + 1],
                                   mode="decode", cache=cache,
                                   positions=jnp.array([s + i], jnp.int32))
        np.testing.assert_allclose(np.asarray(dec[:, 0]), want[:, s + i],
                                   atol=1e-4 * scale)


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4), ("bfloat16", 0.1)])
def test_engine_serves_the_reference_best_tokens(ref, dtype, tol):
    """``Engine.generate``'s greedy tokens, fed back to the reference's
    full forward: each served token's logit lies within ``tol`` of the
    reference's best at its position.  float32: summation order only;
    bfloat16 (the configuration's compute): a few bf16 roundings of
    logits of unit scale."""
    cfg = small(dtype=dtype)
    params = T.init_lm(jax.random.PRNGKey(23), cfg)
    b, s, gen = 2, 16, 8
    prompts = jax.random.randint(jax.random.PRNGKey(24), (b, s), 1,
                                 cfg.vocab_size)
    eng = Engine(cfg, params, ServeConfig(max_len=s + gen))
    served = eng.generate(prompts, n_tokens=gen)
    seq = jnp.concatenate([prompts, jnp.asarray(served)], axis=1)[:, :-1]
    logits = np.asarray(ref.forward(ref_config(cfg), "float32", params, seq))
    got = np.take_along_axis(logits[:, s - 1:], served[..., None], -1)[..., 0]
    gaps = logits[:, s - 1:].max(-1) - got
    assert gaps.max() <= tol, gaps


def test_held_shares_add_up_to_the_uncut_layer(ref):
    """Sixteen experts in four shares of four: the shares' partial
    outputs, the shared experts counted once, add up to the reference's
    layer with every expert held."""
    cfg = small(dtype="float32", n_experts=16, experts_per_token=4,
                experts_held=(0, 16))
    params = moe.init_moe(jax.random.PRNGKey(25), cfg)
    x = jax.random.normal(jax.random.PRNGKey(26), (2, 12, cfg.d_model))
    parts = []
    for e0 in range(0, 16, 4):
        share = dataclasses.replace(cfg, experts_held=(e0, e0 + 4))
        p = {**params, **{w: params[w][e0:e0 + 4]
                          for w in ("w_gate", "w_in", "w_out")}}
        y, m = moe.moe_ffn(p, share, x)
        assert float(m["counters"]["moe_dropped_pairs"]) == 0
        parts.append(np.asarray(y))
    xn = rms_norm(params["norm"], x, cfg.norm_eps)
    shared = np.asarray(mlp(params["shared"], cfg, xn, skip_norm=True))
    got = sum(parts) - (len(parts) - 1) * shared
    want = np.asarray(ref.moe_ffn(ref_config(cfg), "float32", x, params) - x)
    np.testing.assert_allclose(got, want, atol=1e-5 * np.max(np.abs(want)))


@pytest.mark.parametrize("renorm", [True, False],
                         ids=["renormalised", "as_scored"])
def test_held_layer_matches_the_capacity_layer(renorm):
    """With every expert held, the dropless held layer equals the
    capacity layer given room for every pair, with the top-k weights
    renormalised or left as scored: both read ``norm_topk_prob``."""
    cfg = small(dtype="float32", n_experts=8, experts_per_token=2,
                experts_held=(0, 8), norm_topk_prob=renorm)
    params = moe.init_moe(jax.random.PRNGKey(29), cfg)
    cap = dataclasses.replace(cfg, experts_held=None, capacity_factor=8.0)
    pad = moe.padded_experts(cap) - 8
    cap_params = {**params,
                  "router": jnp.pad(params["router"], ((0, 0), (0, pad))),
                  **{w: jnp.pad(params[w], ((0, pad), (0, 0), (0, 0)))
                     for w in ("w_gate", "w_in", "w_out")}}
    x = jax.random.normal(jax.random.PRNGKey(30), (2, 12, cfg.d_model))
    got, _ = moe.moe_ffn(params, cfg, x)
    want, m = moe.moe_ffn(cap_params, cap, x)
    assert float(m["moe_dropped_frac"]) == 0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5 * np.max(np.abs(np.asarray(want))))


@pytest.mark.parametrize("seq", [48, 1], ids=["prefill", "decode"])
def test_every_token_on_one_expert_drops_nothing(ref, seq):
    """Every token routes to the same two held experts: the held layer
    computes all of them (a capacity of k/E of the tokens would drop most)
    and agrees with the reference."""
    cfg = small(dtype="float32", n_experts=8, experts_per_token=2,
                experts_held=(0, 4))
    params = moe.init_moe(jax.random.PRNGKey(27), cfg)
    params["router"] = jnp.zeros_like(params["router"]).at[:, 0].set(1.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(28),
                                  (4, seq, cfg.d_model))) + 0.1
    dispatch.reset_counts()
    y, m = jax.jit(lambda p, x: moe.moe_ffn(p, cfg, x))(params, x)
    assert dispatch.counts() == {"moe": {"held_dropless": 1}}
    dispatch.reset_counts()
    tokens = 4 * seq
    assert float(m["counters"]["moe_held_pairs"]) == 2 * tokens
    assert float(m["counters"]["moe_dropped_pairs"]) == 0
    want = np.asarray(ref.moe_ffn(ref_config(cfg), "float32", x, params) - x)
    np.testing.assert_allclose(np.asarray(y), want,
                               atol=1e-5 * np.max(np.abs(want)))


def test_yarn_matches_the_published_formula():
    """Inverse frequencies and scales of DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding`` and attention, written out here."""
    cfg = get_config("deepseek-v2-lite")
    y, dim, base = cfg.yarn, cfg.qk_rope_dim, cfg.rope_theta

    def corr(rot):
        return (dim * math.log(y.original_max_position / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    low, high = max(math.floor(corr(32)), 0), min(math.ceil(corr(1)), dim - 1)
    assert (low, high) == (10, 23)
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    freq_inter = freq_extra / 40.0
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    want = freq_inter * (1 - mask) + freq_extra * mask
    inv, scale = yarn_frequencies(y, dim, base)
    np.testing.assert_allclose(np.asarray(inv), want, rtol=1e-6)
    assert scale == 1.0                        # mscale == mscale_all_dim
    m = 0.1 * 0.707 * math.log(40) + 1.0
    assert mla_softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)


def test_mla_decode_step_holds_no_expanded_cache():
    """One decode step at DeepSeek-V2-Lite's attention widths (16 heads,
    latent 512, rope 64, nope and v 128) lowers with no array of shape
    (B, H, L, ·) — no K or V expanded from the cache — and no f32 copy of
    the latent cache, and counts the latent path in both layers."""
    full = get_config("deepseek-v2-lite")
    cfg = dataclasses.replace(full, n_layers=2, vocab_size=256, d_ff=256,
                              moe_d_ff=64, n_experts=8, experts_held=(0, 2))
    b, l, h, r = 2, 40, full.n_heads, full.kv_lora_rank
    params = jax.eval_shape(lambda: T.init_lm(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: T.init_cache(cfg, b, l, jnp.bfloat16))
    token = jax.ShapeDtypeStruct((b, 1), jnp.int32)

    def step(params, cache, token):
        return T.apply_lm(params, cfg, token, mode="decode", cache=cache,
                          positions=jnp.array([8], jnp.int32))[:2]

    dispatch.reset_counts()
    text = jax.jit(step).lower(params, cache, token).as_text()
    assert dispatch.counts() == {"decode_attention": {"latent": 2},
                                 "moe": {"held_dropless": 1}}
    dispatch.reset_counts()
    assert re.search(rf"tensor<{b}x{l}x{r}xbf16>", text)     # the cache
    assert not re.search(rf"tensor<{b}x{h}x{l}x\d+x", text)   # expanded
    assert not re.search(rf"tensor<{b}x{l}x{r}xf32>", text)   # upcast
