"""Plain reference of SmolLM-360M (the Llama block): forward, loss,
gradients and AdamW, in straightforward ``jax.numpy``.

Follows the published architecture (HuggingFace ``LlamaForCausalLM``):
token embedding; per layer a pre-norm (RMSNorm) grouped-query attention
with rotary position embedding (rotate-half convention, theta from the
configuration) under a causal mask, and a pre-norm SwiGLU MLP, each added
to the residual; a final RMSNorm; logits against the tied embedding.  No
kernels, no cache, no batching tricks.  Nothing of the program is
imported.

``precision`` is ``"float32"`` (every matmul at ``Precision.HIGHEST``)
or ``"fp8"``: every matmul's operands scaled per tensor into float8 e4m3
and multiplied with float32 accumulation, the control one step below the
bfloat16 compute the configuration states.  Long work runs in blocks of
rows so that it fits beside nothing else on one chip.
"""
from __future__ import annotations

import functools
import json

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


def rope(x, theta):
    """x (B, S, H, D): rotate halves by position (HF ``rotate_half``)."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def layer(cfg: dict, precision: str, x, p):
    b, s, d = x.shape
    h, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, eps = d // h, cfg["rms_norm_eps"]
    a = p["mixer"]
    y = rms_norm(x, a["norm"]["scale"], eps)
    q = mm("bsd,de->bse", y, a["wq"], precision).reshape(b, s, h, dh)
    k = mm("bsd,de->bse", y, a["wk"], precision).reshape(b, s, hk, dh)
    v = mm("bsd,de->bse", y, a["wv"], precision).reshape(b, s, hk, dh)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    k, v = jnp.repeat(k, h // hk, axis=2), jnp.repeat(v, h // hk, axis=2)
    scores = mm("bqhd,bkhd->bhqk", q, k, precision) / np.sqrt(dh)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = mm("bhqk,bkhd->bqhd", probs, v, precision).reshape(b, s, h * dh)
    x = x + mm("bse,ed->bsd", o, a["wo"], precision)
    f = p["ffn"]
    y = rms_norm(x, f["norm"]["scale"], eps)
    g = jax.nn.silu(mm("bsd,df->bsf", y, f["w_gate"], precision))
    u = mm("bsd,df->bsf", y, f["w_in"], precision)
    return x + mm("bsf,fd->bsd", g * u, f["w_out"], precision)


def forward(cfg: dict, precision: str, params, tokens):
    """tokens (B, S) int32 → logits (B, S, vocab) float32."""
    x = params["embed"][tokens]

    def body(x, p):
        return jax.checkpoint(functools.partial(layer, cfg, precision))(x, p), None

    x, _ = jax.lax.scan(body, x, params["decoder"]["layer_0"])
    x = rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return mm("bsd,vd->bsv", x, params["embed"], precision)


def nll_sum(cfg, precision, params, tokens, labels):
    logits = forward(cfg, precision, params, tokens)
    logz = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.sum(logz - gold)


@functools.lru_cache(maxsize=None)
def _grad_fn(cfg_json: str, precision: str):
    cfg = json.loads(cfg_json)
    return jax.jit(jax.value_and_grad(functools.partial(nll_sum, cfg,
                                                        precision)))


def loss_and_grad(cfg: dict, precision: str, params, tokens, labels,
                  rows: int):
    """Mean next-token cross-entropy and its gradient, over blocks of
    ``rows`` sequences."""
    fn = _grad_fn(json.dumps(cfg, sort_keys=True), precision)
    total, grads = 0.0, None
    for i in range(0, tokens.shape[0], rows):
        l, g = fn(params, tokens[i:i + rows], labels[i:i + rows])
        total += float(l)
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    n = tokens.size
    return total / n, jax.tree.map(lambda g: g / n, grads)


def lr_at(opt: dict, step: int) -> float:
    """Linear warm-up, then cosine decay to ``min_lr_ratio``."""
    lr, warm = opt["learning_rate"], opt["warmup_steps"]
    if step < warm:
        return lr * step / max(warm, 1)
    prog = min(max((step - warm) / max(opt["total_steps"] - warm, 1), 0.0), 1.0)
    r = opt["min_lr_ratio"]
    return lr * (r + (1 - r) * 0.5 * (1 + np.cos(np.pi * prog)))


def adamw(opt: dict, step: int, params, grads, mu, nu, decayed):
    """One AdamW step (``step`` counts from 1) with global-norm clipping;
    ``decayed`` marks the leaves under weight decay.  Returns the new
    ``(params, mu, nu)`` and the clipped gradient."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    clip = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    grads = jax.tree.map(lambda g: g * clip, grads)
    lr = lr_at(opt, step)
    b1, b2 = opt["b1"], opt["b2"]
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)

    def upd(p, m, v, dec):
        u = (m / (1 - b1 ** step)) / (jnp.sqrt(v / (1 - b2 ** step))
                                      + opt["eps"])
        if dec:
            u = u + opt["weight_decay"] * p
        return p - lr * u

    params = jax.tree.map(upd, params, mu, nu, decayed)
    return params, mu, nu, grads


def decayed_leaves(params):
    """Weight decay on every weight matrix (per layer: the stacked axis
    is not a dimension of the weight), not on norm scales."""
    def dec(path, p):
        stacked = any(getattr(k, "key", None) == "decoder" for k in path)
        return p.ndim - (1 if stacked else 0) >= 2

    return jax.tree_util.tree_map_with_path(dec, params)


def train(cfg: dict, opt: dict, precision: str, params, batches, rows: int):
    """Train ``len(batches)`` steps from ``params``.  Returns the losses,
    the clipped gradient of the first step and the final parameters."""
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    decayed = decayed_leaves(params)
    losses, first = [], None
    for i, (tokens, labels) in enumerate(batches, start=1):
        loss, g = loss_and_grad(cfg, precision, params, tokens, labels, rows)
        params, mu, nu, g = adamw(opt, i, params, g, mu, nu, decayed)
        losses.append(loss)
        if first is None:
            first = g
        del g
    return losses, first, params


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
