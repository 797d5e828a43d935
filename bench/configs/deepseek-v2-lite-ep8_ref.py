"""Plain reference of DeepSeek-V2-Lite on one chip of an expert-parallel
deployment: the forward pass and the logit gaps of served tokens, in
straightforward ``jax.numpy``.

Follows the published architecture (HuggingFace ``DeepseekV2ForCausalLM``,
``config.json`` of deepseek-ai/DeepSeek-V2-Lite): token embedding; per
layer a pre-norm (RMSNorm) multi-head latent attention and a pre-norm
feed-forward, each added to the residual; a final RMSNorm; logits against
the untied head.

* Attention, expanded: the query projected directly (no query LoRA); K
  and V up-projected from the RMS-normed latent of ``kv_lora_rank``; one
  rope key of ``qk_rope_head_dim`` shared by every head; YaRN rope
  frequencies and the softmax scale ``(nope + rope)^-0.5 * mscale^2``
  (``yarn_get_mscale(factor, mscale_all_dim)``) under a causal mask.
  Departure (the configuration's ``assumed``): the rope dims rotate as
  halves, without the published code's de-interleave; with random weights
  that is a fixed permutation of the rope columns of ``wq`` and ``wdkv``.
* The first ``first_k_dense_replace`` layers: a SwiGLU of
  ``intermediate_size``.
* The other layers: softmax scores over every routed expert the router
  has, greedy top-``num_experts_per_tok``, weights renormalised only where
  ``norm_topk_prob``, else times ``routed_scaling_factor``; the routed
  part summed over the experts this chip holds (``experts_held``), what
  the others would add left out; plus the shared experts, one SwiGLU.

No kernels, no cache, no batching tricks; nothing of the program is
imported.  ``precision`` is ``"float32"`` (every matmul at
``Precision.HIGHEST``) or ``"fp8"``: every matmul's operands scaled per
tensor into float8 e4m3 and multiplied with float32 accumulation, the
control one step below the bfloat16 compute the configuration states.
The weights come in bfloat16 and are upcast one layer at a time.

``logit_gaps`` compares served tokens; ``routed_output`` gives one MoE
layer's held routed part alone, the small term of the residual that the
logits barely show.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _f8(x):
    """``x`` scaled per tensor into float8 e4m3, rounded there, and held
    in float32 with its scale."""
    scale = F8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(F8).astype(jnp.float32), scale


def mm(spec: str, a, b, precision: str):
    """``einsum(spec, a, b)`` in ``precision``; float8 operands multiply
    exactly in float32 and accumulate there."""
    if precision == "fp8":
        (a8, sa), (b8, sb) = _f8(a), _f8(b)
        return jnp.einsum(spec, a8, b8, precision=HIGHEST) / (sa * sb)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


# ---------------------------------------------------------------------------
# YaRN (DeepSeek-V2's DeepseekV2YarnRotaryEmbedding)
# ---------------------------------------------------------------------------
def yarn_get_mscale(scale: float, mscale: float) -> float:
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(cfg: dict):
    """``(inv_freq (dim/2,), cos/sin scale)`` for ``qk_rope_head_dim``."""
    rs, dim, base = cfg["rope_scaling"], cfg["qk_rope_head_dim"], \
        cfg["rope_theta"]
    factor, orig = rs["factor"], rs["original_max_position_embeddings"]

    def correction_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    i = np.arange(0, dim, 2, dtype=np.float64)
    extra = 1.0 / base ** (i / dim)
    inter = 1.0 / (factor * base ** (i / dim))
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    inv = inter * (1 - mask) + extra * mask
    scale = (yarn_get_mscale(factor, rs["mscale"])
             / yarn_get_mscale(factor, rs["mscale_all_dim"]))
    return inv.astype(np.float32), scale


def softmax_scale(cfg: dict) -> float:
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = cfg["rope_scaling"]
    if rs.get("mscale_all_dim"):
        scale *= yarn_get_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def rope(x, cfg: dict):
    """x (B, S, H, D): rotate halves by position (``rotate_half``)."""
    s, d = x.shape[1], x.shape[-1]
    inv, scale = yarn_inv_freq(cfg)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(inv)[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :] * scale
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :] * scale
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------
def attention(cfg: dict, precision: str, x, a):
    b, s, _ = x.shape
    h, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    y = rms_norm(x, a["norm"]["scale"], eps)
    q = mm("bsd,de->bse", y, a["wq"], precision).reshape(b, s, h, nope + rd)
    q_nope, q_pe = q[..., :nope], rope(q[..., nope:], cfg)
    ckv = mm("bsd,de->bse", y, a["wdkv"], precision)
    c = rms_norm(ckv[..., :r], a["kv_norm"]["scale"], eps)
    k_pe = rope(ckv[..., None, r:], cfg)                     # (B,S,1,rd)
    kv = mm("bsr,re->bse", c, a["wukv"], precision).reshape(b, s, h,
                                                            nope + vd)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (b, s, h, rd))], -1)
    qc = jnp.concatenate([q_nope, q_pe], -1)
    scores = mm("bqhd,bkhd->bhqk", qc, k, precision) * softmax_scale(cfg)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = mm("bhqk,bkhd->bqhd", probs, kv[..., nope:], precision)
    return x + mm("bse,ed->bsd", o.reshape(b, s, h * vd), a["wo"], precision)


def swiglu(y, f, precision: str):
    g = jax.nn.silu(mm("bsd,df->bsf", y, f["w_gate"], precision))
    u = mm("bsd,df->bsf", y, f["w_in"], precision)
    return mm("bsf,fd->bsd", g * u, f["w_out"], precision)


def dense_ffn(cfg: dict, precision: str, x, f):
    return x + swiglu(rms_norm(x, f["norm"]["scale"], cfg["rms_norm_eps"]),
                      f, precision)


def held_routed(cfg: dict, precision: str, y, f):
    """The held share of the routed experts' output for the normed ``y``."""
    k = cfg["num_experts_per_tok"]
    e0, e1 = cfg["experts_held"]
    gates = jax.nn.softmax(mm("bsd,de->bse", y, f["router"], precision), -1)
    top_g, top_i = jax.lax.top_k(gates, k)
    if cfg["norm_topk_prob"]:
        top_g = top_g / jnp.sum(top_g, -1, keepdims=True)
    else:
        top_g = top_g * cfg["routed_scaling_factor"]
    # each held expert's weight per token (0 where not in its top k)
    w = jnp.sum(jax.nn.one_hot(top_i - e0, e1 - e0) * top_g[..., None], -2)
    g = jax.nn.silu(mm("bsd,edf->bsef", y, f["w_gate"], precision))
    u = mm("bsd,edf->bsef", y, f["w_in"], precision)
    out = mm("bsef,efd->bsed", g * u, f["w_out"], precision)
    return jnp.sum(out * w[..., None], axis=-2)


def moe_ffn(cfg: dict, precision: str, x, f):
    """The held share of the routed experts plus the shared experts."""
    y = rms_norm(x, f["norm"]["scale"], cfg["rms_norm_eps"])
    return (x + held_routed(cfg, precision, y, f)
            + swiglu(y, f["shared"], precision))


def forward(cfg: dict, precision: str, params, tokens):
    """tokens (B, S) int32 → logits (B, S, vocab) float32."""
    x = params["embed"][tokens].astype(jnp.float32)
    for name in sorted(params.get("dense", {})):
        p = f32(params["dense"][name])
        x = dense_ffn(cfg, precision, attention(cfg, precision, x,
                                                p["mixer"]), p["ffn"])

    def body(x, p):
        p = f32(p)
        x = attention(cfg, precision, x, p["mixer"])
        return moe_ffn(cfg, precision, x, p["ffn"]), None

    x, _ = jax.lax.scan(body, x, params["decoder"]["layer_0"])
    x = rms_norm(x, params["final_norm"]["scale"].astype(jnp.float32),
                 cfg["rms_norm_eps"])
    return mm("bsd,dv->bsv", x, params["lm_head"].astype(jnp.float32),
              precision)


@functools.lru_cache(maxsize=None)
def _gap_fn(cfg_json: str, control: bool):
    cfg = json.loads(cfg_json)

    def gaps(params, tokens, targets):
        ref = forward(cfg, "float32", params, tokens)
        best = jnp.max(ref, -1)
        if control:
            low = forward(cfg, "fp8", params, tokens)
            targets = jnp.argmax(low, -1).astype(jnp.int32)
        return best - jnp.take_along_axis(ref, targets[..., None], -1)[..., 0]

    return jax.jit(gaps)


@functools.lru_cache(maxsize=None)
def _routed_fn(cfg_json: str, precision: str):
    cfg = json.loads(cfg_json)

    def routed(f, x):
        f, x = f32(f), x.astype(jnp.float32)
        y = rms_norm(x, f["norm"]["scale"], cfg["rms_norm_eps"])
        return held_routed(cfg, precision, y, f)

    return jax.jit(routed)


def routed_output(cfg: dict, ffn, x, control: bool = False):
    """The held experts' part of one MoE layer's output for ``x`` (B, S,
    D): ``ffn`` the layer's norm, router and held experts; float32, on the
    device.  With ``control`` every matmul in fp8."""
    fn = _routed_fn(json.dumps(cfg, sort_keys=True),
                    "fp8" if control else "float32")
    return fn(ffn, x)


def logit_gaps(cfg: dict, params, tokens, targets, rows: int,
               control: bool = False):
    """Per position of ``tokens`` (N, S): how far the reference's logit of
    ``targets`` (N, S) lies below its best logit.  With ``control`` the
    target at each position is the token that the fp8 reference puts
    first.  ``rows`` sequences at a time; a host array."""
    fn = _gap_fn(json.dumps(cfg, sort_keys=True), control)
    return np.concatenate([np.asarray(fn(params, tokens[i:i + rows],
                                         targets[i:i + rows]))
                           for i in range(0, tokens.shape[0], rows)])
