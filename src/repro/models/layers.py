"""Core tensor-operator layers: norms, RoPE, attention (GQA/SWA/MLA), MLP.

Pure-JAX modules in init/apply style: ``init_*`` builds a param pytree,
the apply function is a plain function of (params, x).  Activation sharding
is annotated with logical axes (``repro.sharding.axes``); parameter sharding
is derived from param-path rules (``repro.sharding.partition``).

Attention dispatch: the XLA einsum path (below) is what the dry-run lowers
and what trains on CPU; on TPU the Pallas flash kernel
(``repro.kernels.flash_attention``) is used for the same semantics.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, Yarn
from repro.sharding.axes import constrain

Params = Dict[str, jnp.ndarray]


def _dense_init(rng, shape, fan_in=None, dtype=jnp.float32):
    fan_in = fan_in or shape[0]
    return (jax.random.normal(rng, shape, dtype) / math.sqrt(fan_in))


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def init_rmsnorm(d: int) -> Params:
    return {"scale": jnp.ones((d,), jnp.float32)}


def rms_norm(params: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """RMSNorm with f32 *accumulation* (not a full-tensor f32 upcast).

    Upcasting ``x`` first makes the layer-scan's saved residual stack a
    target for XLA's convert-mover, which then carries the whole activation
    stack in f32 (2× memory).  Reducing with ``dtype=f32`` keeps the sums
    exact while every full-size tensor stays bf16 — the same contract a
    fused TPU norm kernel provides.
    """
    dt = x.dtype
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True, dtype=jnp.float32)
    inv = jax.lax.rsqrt(var + eps)
    return (x * inv.astype(dt)) * params["scale"].astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def yarn_mscale(factor: float, mscale: float) -> float:
    """DeepSeek-V2's ``yarn_get_mscale``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(yarn: Yarn, d: int, theta: float):
    """Inverse frequencies (d/2,) and cos/sin scale of DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding``: ``theta^(-2i/d)`` below the
    correction range of ``beta_fast``, the same over ``factor`` above that
    of ``beta_slow``, a linear ramp between."""
    def correction_dim(rotations):
        return (d * math.log(yarn.original_max_position
                             / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), d - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    inv_freq = extra / yarn.factor * ramp + extra * (1.0 - ramp)
    scale = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
    return inv_freq, scale


def rope(x: jnp.ndarray, positions: jnp.ndarray,
         theta: float = 10_000.0, yarn: Optional[Yarn] = None) -> jnp.ndarray:
    """x (..., S, D) with D even; positions (..., S) absolute indices.
    Halves rotate together (``rotate_half``); ``yarn`` scales the
    frequencies as DeepSeek-V2 does."""
    d = x.shape[-1]
    half = d // 2
    if yarn is None:
        freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                        * (math.log(theta) / half))
        scale = 1.0
    else:
        freqs, scale = yarn_frequencies(yarn, d, theta)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                              axis=-1)
    return rotated.astype(x.dtype)


# ---------------------------------------------------------------------------
# masked attention core (XLA path; same semantics as kernels/flash_attention)
# ---------------------------------------------------------------------------
def _mask_for_chunk(q_pos: jnp.ndarray, kv_pos: jnp.ndarray, causal: bool,
                    window: Optional[int]) -> jnp.ndarray:
    """(cq, L) visibility from absolute positions (kv_pos == -1 → empty)."""
    qp = q_pos[:, None]
    kp = kv_pos[None, :]
    allow = kp >= 0
    if causal:
        allow = allow & (kp <= qp)
    if window is not None:
        allow = allow & ((qp - kp) < window)
    return allow


def attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
           q_pos: jnp.ndarray, kv_pos: jnp.ndarray, causal: bool = True,
           window: Optional[int] = None, sm_scale: Optional[float] = None,
           q_chunk: int = 256) -> jnp.ndarray:
    """Masked softmax attention, streamed over query chunks.

    q (B,Hq,S,D); k,v (B,Hkv,L,Dv); q_pos (S,), kv_pos (L,) absolute
    positions (-1 = empty cache slot).  Two TPU/SPMD adaptations vs the
    textbook einsum (DESIGN.md §2):

      * KV heads are repeated up to Hq *before* the contraction so the head
        dimension keeps a single sharded axis (a (b,hkv,g,s,l) reshape splits
        64 heads into 8×8, and neither factor divides a 16-way model axis);
        the Pallas kernel does GQA natively without the repeat.
      * queries stream in chunks through a rematerialized ``lax.map`` so no
        full S×L score matrix ever materializes (the XLA analogue of the
        flash kernel's VMEM tiling — scores exist one (cq, L) tile at a
        time, recomputed in the backward pass).
    """
    b, hq, s, d = q.shape
    hkv, l = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    scale = sm_scale if sm_scale is not None else d ** -0.5
    if hkv != hq:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    def one_chunk(args):
        qc, qp = args                                  # (B,H,cq,D), (cq,)
        scores = jnp.einsum("bhsd,bhld->bhsl", qc.astype(jnp.float32),
                            kf) * scale
        allow = _mask_for_chunk(qp, kv_pos, causal, window)
        scores = jnp.where(allow[None, None], scores, -1e30)
        m = jnp.max(scores, axis=-1, keepdims=True)
        p = jnp.exp(scores - jax.lax.stop_gradient(m))
        p = jnp.where(allow[None, None], p, 0.0)
        denom = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bhsl,bhld->bhsd", p, vf) / jnp.maximum(denom, 1e-30)
        return o.astype(q.dtype)

    if s <= q_chunk:
        return one_chunk((q, q_pos))

    n_chunks = -(-s // q_chunk)
    pad = n_chunks * q_chunk - s
    qp_pad = jnp.pad(q_pos, (0, pad), constant_values=-1)
    q_pad = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    q_chunks = jnp.moveaxis(
        q_pad.reshape(b, hq, n_chunks, q_chunk, d), 2, 0)
    qp_chunks = qp_pad.reshape(n_chunks, q_chunk)
    out = jax.lax.map(jax.checkpoint(one_chunk), (q_chunks, qp_chunks))
    out = jnp.moveaxis(out, 0, 2).reshape(b, hq, n_chunks * q_chunk, dv)
    return out[:, :, :s]


def _exact_pieces(x: jnp.ndarray, dtype) -> jnp.ndarray:
    """f32 ``x`` as pieces in ``dtype`` stacked on a new axis 0, whose sum
    is ``x`` exactly: one piece for f32, three for bf16 (8 significant
    bits each cover f32's 24), down to f32's normal range."""
    n = -(-24 // (jnp.finfo(dtype).nmant + 1))
    pieces = []
    for _ in range(n):
        hi = x.astype(dtype)
        pieces.append(hi)
        x = x - hi.astype(jnp.float32)
    return jnp.stack(pieces)


def attend_decode(q: jnp.ndarray, ck: jnp.ndarray, cv: jnp.ndarray, *,
                  q_pos: jnp.ndarray, kv_pos: jnp.ndarray,
                  causal: bool = True,
                  window: Optional[int] = None) -> jnp.ndarray:
    """A decode step's masked attention over a KV cache, grouped by KV head.

    q (B,Hq,S,D); ck, cv (B,Hkv,L,D/Dv) as the cache stores them; q_pos
    (S,), kv_pos (L,) absolute positions (-1 = empty slot).  Query head h
    reads KV head ``h // G`` (G = Hq // Hkv), the map of ``attend``'s
    repeat, but the cache is read once, in its own dtype: no repeated and
    no upcast copy of it exists.  The arithmetic is ``attend``'s after its
    upcast — products exact, sums in f32:

      * scores contract q with ck, accumulating in f32;
      * the f32 probabilities p enter PV as pieces in the cache's dtype
        that sum to p exactly (``_exact_pieces``), stacked on the group
        axis so that one contraction reads cv once; the pieces' f32
        partial sums are then added.

    A single query over the cache needs no query chunks and no
    rematerialisation, and serving shards no head axis, so nothing asks
    for ``attend``'s repeat here.
    """
    from repro.kernels import dispatch

    dispatch.record("decode_attention", "grouped")
    b, hq, s, d = q.shape
    hkv, l = ck.shape[1], ck.shape[2]
    dv = cv.shape[-1]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, s, d)
    scores = jnp.einsum("bkgsd,bkld->bkgsl", qg, ck,
                        preferred_element_type=jnp.float32) * d ** -0.5
    allow = _mask_for_chunk(q_pos, kv_pos, causal, window)[None, None, None]
    scores = jnp.where(allow, scores, -1e30)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.where(allow, jnp.exp(scores - m), 0.0)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    pieces = _exact_pieces(p, cv.dtype)                 # (n,B,Hkv,G,S,L)
    n = pieces.shape[0]
    pieces = jnp.moveaxis(pieces, 0, 2).reshape(b, hkv, n * g, s, l)
    o = jnp.einsum("bkgsl,bkld->bkgsd", pieces, cv,
                   preferred_element_type=jnp.float32)
    o = o.reshape(b, hkv, n, g, s, dv).sum(axis=2)
    o = o / jnp.maximum(denom, 1e-30)
    return o.reshape(b, hq, s, dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention block (supports SWA + self/cross + KV cache)
# ---------------------------------------------------------------------------
def init_attention(rng, cfg: ModelConfig, cross: bool = False) -> Params:
    d, h, hk, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(rng, 4)
    return {
        "wq": _dense_init(ks[0], (d, h * dh)),
        "wk": _dense_init(ks[1], (d, hk * dh)),
        "wv": _dense_init(ks[2], (d, hk * dh)),
        "wo": _dense_init(ks[3], (h * dh, d), fan_in=h * dh),
        "norm": init_rmsnorm(d),
    }


def gqa_attention(params: Params, cfg: ModelConfig, x: jnp.ndarray, *,
                  positions: jnp.ndarray, mode: str = "train",
                  cache: Optional[Params] = None,
                  kv_source: Optional[jnp.ndarray] = None,
                  causal: bool = True, cache_len: int = 0,
                  ) -> Tuple[jnp.ndarray, Optional[Params]]:
    """Pre-norm GQA attention. Returns (residual_delta, new_cache)."""
    b, s, d = x.shape
    h, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = x.dtype
    xn = rms_norm(params["norm"], x, cfg.norm_eps)

    q = (xn @ params["wq"].astype(dt)).reshape(b, s, h, dh).transpose(0, 2, 1, 3)
    q = constrain(q, "batch", "heads", "seq", None)
    kv_in = rms_norm(params["norm"], kv_source, cfg.norm_eps) \
        if kv_source is not None else xn
    k = (kv_in @ params["wk"].astype(dt)).reshape(
        b, kv_in.shape[1], hk, dh).transpose(0, 2, 1, 3)
    v = (kv_in @ params["wv"].astype(dt)).reshape(
        b, kv_in.shape[1], hk, dh).transpose(0, 2, 1, 3)

    is_cross = kv_source is not None
    if not is_cross:
        q = rope(q, positions[None, None, :], cfg.rope_theta)
        k = rope(k, positions[None, None, :], cfg.rope_theta)

    new_cache = None
    if mode == "decode" and not is_cross:
        # append to ring/linear cache and attend over it
        cpos = cache["pos"]
        slot = cache["cursor"]  # scalar int32 write index
        if cfg.kv_quant:
            kq, ks = kv_quantize(k)
            vq, vs = kv_quantize(v)
            ckq = jax.lax.dynamic_update_slice_in_dim(cache["k"], kq, slot,
                                                      axis=2)
            cks = jax.lax.dynamic_update_slice_in_dim(cache["k_s"], ks,
                                                      slot, axis=2)
            cvq = jax.lax.dynamic_update_slice_in_dim(cache["v"], vq, slot,
                                                      axis=2)
            cvs = jax.lax.dynamic_update_slice_in_dim(cache["v_s"], vs,
                                                      slot, axis=2)
            ck = kv_dequantize(ckq, cks, dt)
            cv = kv_dequantize(cvq, cvs, dt)
            stored = {"k": ckq, "k_s": cks, "v": cvq, "v_s": cvs}
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot,
                                                     axis=2)
            cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot,
                                                     axis=2)
            stored = {"k": ck, "v": cv}
        cpos = jax.lax.dynamic_update_slice_in_dim(
            cpos, positions.astype(jnp.int32), slot, axis=0)
        cache_len = ck.shape[2]
        cursor = (slot + s) % cache_len if cfg.window else slot + s
        new_cache = {**stored, "pos": cpos,
                     "cursor": jnp.asarray(cursor, jnp.int32)}
        o = attend_decode(q, ck, cv, q_pos=positions, kv_pos=cpos,
                          causal=causal, window=cfg.window)
    else:
        if is_cross:
            kv_pos = jnp.arange(k.shape[2], dtype=jnp.int32)
            o = attend(q, k, v, q_pos=positions, kv_pos=kv_pos,
                       causal=False, q_chunk=cfg.attn_q_chunk)
        else:
            # Pallas flash kernel (TPU target): native GQA, VMEM-tiled —
            # no KV-head repeat, no score-tile HBM traffic.  Inference
            # modes only: training keeps the rematerialized XLA path until
            # a backward kernel lands (the fwd kernel has no vjp).
            from repro.kernels import dispatch
            from repro.kernels.flash_attention import kernel as fk

            impl = dispatch.choose("flash_attention", fits=mode != "train",
                                   vmem_bytes=fk.vmem_bytes(dh),
                                   want=cfg.use_flash)
            if impl != "xla":
                o = fk.flash_attention_pallas(
                    q, k, v, causal=causal, window=cfg.window,
                    interpret=impl == "interpret")
            else:
                o = attend(q, k, v, q_pos=positions, kv_pos=positions,
                           causal=causal, window=cfg.window,
                           q_chunk=cfg.attn_q_chunk)
        if mode == "prefill" and not is_cross:
            new_cache = _build_prefill_cache(
                cfg, k, v, positions, cache_len or k.shape[2])

    o = constrain(o, "batch", "heads", "seq", None)
    y = o.transpose(0, 2, 1, 3).reshape(b, s, h * dh) @ params["wo"].astype(dt)
    return constrain(y, "batch", "seq", "embed"), new_cache


# ---------------------------------------------------------------------------
# int8 KV quantization (beyond-paper: halves resident cache + its HBM reads)
# ---------------------------------------------------------------------------
def kv_quantize(x: jnp.ndarray):
    """(B,H,L,D) → (int8 values, f32 per-vector scales (B,H,L,1))."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                    keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def kv_dequantize(q: jnp.ndarray, scale: jnp.ndarray, dtype) -> jnp.ndarray:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def _build_prefill_cache(cfg: ModelConfig, k, v, positions,
                         cache_len: int) -> Params:
    """Size a decode cache of ``cache_len`` slots from prefill K/V.

    Sliding-window archs keep a ring of the last ``window`` entries; others
    right-pad to the full decode length.  ``pos`` tracks the absolute
    position per slot (-1 = empty) so decode masking is position-exact.
    """
    b, hk, s, dh = k.shape
    if cfg.window is not None and cache_len <= cfg.window:
        w = cache_len
        if s >= w:
            # last w entries, placed at slot = pos % w (ring order)
            src = (s - w) + jnp.mod(jnp.arange(w) - s, w)
            ck, cv = k[:, :, src], v[:, :, src]
            cpos = positions[src].astype(jnp.int32)
        else:
            pad = w - s
            ck = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
            cv = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
            cpos = jnp.pad(positions.astype(jnp.int32), (0, pad),
                           constant_values=-1)
        cursor = s % w
    else:
        pad = cache_len - s
        ck = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        cv = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        cpos = jnp.pad(positions.astype(jnp.int32), (0, pad),
                       constant_values=-1)
        cursor = s
    out = {"pos": cpos, "cursor": jnp.asarray(cursor, jnp.int32)}
    if cfg.kv_quant:
        out["k"], out["k_s"] = kv_quantize(ck)
        out["v"], out["v_s"] = kv_quantize(cv)
    else:
        out["k"], out["v"] = ck, cv
    return out


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (MiniCPM3 / DeepSeek-V2 style)
# ---------------------------------------------------------------------------
def init_mla(rng, cfg: ModelConfig) -> Params:
    d, h = cfg.d_model, cfg.n_heads
    qd = cfg.qk_nope_dim + cfg.qk_rope_dim
    ks = jax.random.split(rng, 6)
    p = {
        "wdkv": _dense_init(ks[2], (d, cfg.kv_lora_rank + cfg.qk_rope_dim)),
        "wukv": _dense_init(ks[3], (cfg.kv_lora_rank,
                                    h * (cfg.qk_nope_dim + cfg.v_head_dim))),
        "wo": _dense_init(ks[4], (h * cfg.v_head_dim, d),
                          fan_in=h * cfg.v_head_dim),
        "norm": init_rmsnorm(d),
        "kv_norm": init_rmsnorm(cfg.kv_lora_rank),
    }
    if cfg.q_lora_rank:
        p["wdq"] = _dense_init(ks[0], (d, cfg.q_lora_rank))
        p["wuq"] = _dense_init(ks[1], (cfg.q_lora_rank, h * qd))
        p["q_norm"] = init_rmsnorm(cfg.q_lora_rank)
    else:
        p["wq"] = _dense_init(ks[0], (d, h * qd))
    return p


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """``(nope + rope)^-0.5``, times ``mscale_all_dim``'s YaRN factor
    squared where the config scales rope by YaRN (DeepSeek-V2)."""
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    if cfg.yarn is not None and cfg.yarn.mscale_all_dim:
        scale *= yarn_mscale(cfg.yarn.factor, cfg.yarn.mscale_all_dim) ** 2
    return scale


def mla_attention(params: Params, cfg: ModelConfig, x: jnp.ndarray, *,
                  positions: jnp.ndarray, mode: str = "train",
                  cache: Optional[Params] = None, cache_len: int = 0,
                  ) -> Tuple[jnp.ndarray, Optional[Params]]:
    """Latent attention: KV compressed to ``kv_lora_rank`` + shared RoPE key.

    The cache stores only the latent ``c_kv`` and the rope key.  Train and
    prefill expand K and V from the latent; a decode step scores and
    contracts against the latent cache itself (``attend_latent``).
    """
    b, s, d = x.shape
    h = cfg.n_heads
    nope, rdim, vdim = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    dt = x.dtype
    xn = rms_norm(params["norm"], x, cfg.norm_eps)

    if cfg.q_lora_rank:
        cq = rms_norm(params["q_norm"], xn @ params["wdq"].astype(dt),
                      cfg.norm_eps)
        q = cq @ params["wuq"].astype(dt)
    else:
        q = xn @ params["wq"].astype(dt)
    q = q.reshape(b, s, h, nope + rdim).transpose(0, 2, 1, 3)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = rope(q_rope, positions[None, None, :], cfg.rope_theta, cfg.yarn)

    dkv = xn @ params["wdkv"].astype(dt)            # (B,S,kv_lora + rdim)
    c_kv = rms_norm(params["kv_norm"], dkv[..., :r], cfg.norm_eps)
    k_rope = rope(dkv[..., None, r:].transpose(0, 2, 1, 3),
                  positions[None, None, :], cfg.rope_theta,
                  cfg.yarn)                          # (B,1,S,rdim)
    scale = mla_softmax_scale(cfg)
    wukv = params["wukv"].astype(dt)

    new_cache = None
    if mode == "decode":
        cc, cr, cpos = cache["c_kv"], cache["k_rope"], cache["pos"]
        slot = cache["cursor"]
        cc = jax.lax.dynamic_update_slice_in_dim(cc, c_kv, slot, axis=1)
        cr = jax.lax.dynamic_update_slice_in_dim(cr, k_rope, slot, axis=2)
        cpos = jax.lax.dynamic_update_slice_in_dim(
            cpos, positions.astype(jnp.int32), slot, axis=0)
        new_cache = {"c_kv": cc, "k_rope": cr, "pos": cpos,
                     "cursor": jnp.asarray(slot + s, jnp.int32)}
        o = attend_latent(q_nope, q_rope, cc, cr[:, 0],
                          wukv.reshape(r, h, nope + vdim), q_pos=positions,
                          kv_pos=cpos, sm_scale=scale)
    else:
        if mode == "prefill":
            clen = cache_len or s
            pad = clen - s
            new_cache = {
                "c_kv": jnp.pad(c_kv, ((0, 0), (0, pad), (0, 0))),
                "k_rope": jnp.pad(k_rope, ((0, 0), (0, 0), (0, pad), (0, 0))),
                "pos": jnp.pad(positions.astype(jnp.int32), (0, pad),
                               constant_values=-1),
                "cursor": jnp.asarray(s, jnp.int32)}
        # expand K/V from the latent
        kv = (c_kv @ wukv).reshape(b, s, h, nope + vdim).transpose(0, 2, 1, 3)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        k_r = jnp.broadcast_to(k_rope, (b, h) + k_rope.shape[2:])
        k = jnp.concatenate([k_nope, k_r], axis=-1)
        qc = jnp.concatenate([q_nope, q_rope], axis=-1)
        o = attend(qc, k, v, q_pos=positions, kv_pos=positions, causal=True,
                   sm_scale=scale, q_chunk=cfg.attn_q_chunk)

    y = o.transpose(0, 2, 1, 3).reshape(b, s, h * vdim) @ params["wo"].astype(dt)
    return constrain(y, "batch", "seq", "embed"), new_cache


def attend_latent(q_nope: jnp.ndarray, q_rope: jnp.ndarray,
                  c_kv: jnp.ndarray, k_rope: jnp.ndarray, wukv: jnp.ndarray,
                  *, q_pos: jnp.ndarray, kv_pos: jnp.ndarray,
                  sm_scale: float) -> jnp.ndarray:
    """A decode step's MLA over the latent cache, never expanding K or V.

    q_nope (B,H,S,nope), q_rope (B,H,S,rope); c_kv (B,L,r) and k_rope
    (B,L,rope) as the cache stores them; wukv (r,H,nope+v) the latent's
    up-projection.  ``q_nope`` is absorbed into the latent through the
    key half of ``wukv``; scores contract it with ``c_kv`` and ``q_rope``
    with ``k_rope``; the probabilities contract with ``c_kv`` into a
    latent output that the value half of ``wukv`` expands.  Every
    contraction reads the cache once in its own dtype and accumulates in
    f32; the f32 probabilities enter as exact pieces in the cache's dtype
    (``_exact_pieces``), as in ``attend_decode``.
    """
    from repro.kernels import dispatch

    dispatch.record("decode_attention", "latent")
    b, h, s, nope = q_nope.shape
    dt = c_kv.dtype
    with jax.named_scope("mla.latent"):
        q_lat = jnp.einsum("bhsn,rhn->bhsr", q_nope, wukv[..., :nope],
                           preferred_element_type=jnp.float32).astype(dt)
        scores = (jnp.einsum("bhsr,blr->bhsl", q_lat, c_kv,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bhsd,bld->bhsl", q_rope.astype(dt), k_rope,
                               preferred_element_type=jnp.float32)) * sm_scale
        allow = _mask_for_chunk(q_pos, kv_pos, True, None)[None, None]
        scores = jnp.where(allow, scores, -1e30)
        m = jnp.max(scores, axis=-1, keepdims=True)
        p = jnp.where(allow, jnp.exp(scores - m), 0.0)
        denom = jnp.sum(p, axis=-1, keepdims=True)
        pieces = _exact_pieces(p, dt)                   # (n,B,H,S,L)
        n = pieces.shape[0]
        pieces = jnp.moveaxis(pieces, 0, 1).reshape(b, n * h, s, -1)
        o_lat = jnp.einsum("bhsl,blr->bhsr", pieces, c_kv,
                           preferred_element_type=jnp.float32)
        o_lat = o_lat.reshape(b, n, h, s, -1).sum(axis=1)
        o_lat = (o_lat / jnp.maximum(denom, 1e-30)).astype(dt)
        o = jnp.einsum("bhsr,rhv->bhsv", o_lat, wukv[..., nope:],
                       preferred_element_type=jnp.float32)
    return o.astype(q_nope.dtype)


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------
def init_mlp(rng, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(rng, 3)
    return {
        "w_gate": _dense_init(ks[0], (d, f)),
        "w_in": _dense_init(ks[1], (d, f)),
        "w_out": _dense_init(ks[2], (f, d), fan_in=f),
        "norm": init_rmsnorm(d),
    }


def mlp(params: Params, cfg: ModelConfig, x: jnp.ndarray,
        skip_norm: bool = False) -> jnp.ndarray:
    dt = x.dtype
    xn = x if skip_norm else rms_norm(params["norm"], x, cfg.norm_eps)
    g = jax.nn.silu(xn @ params["w_gate"].astype(dt))
    u = xn @ params["w_in"].astype(dt)
    h = constrain(g * u, "batch", "seq", "ff")
    y = h @ params["w_out"].astype(dt)
    return constrain(y, "batch", "seq", "embed")
