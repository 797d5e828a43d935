"""Mixture-of-Experts FFN with HPTMT-shuffle token dispatch.

Routing tokens to experts is exactly the paper's shuffle operator (Fig 2)
applied to tensors: hash/top-k chooses a destination *partition* (expert),
rows are packed into capacity-bounded buckets, exchanged, processed, and
combined.  The TPU-native realization is sort-based packing (argsort by
expert id — the same group-by-destination step that
``core.exchange.exchange_rows`` performs with a counting scatter) into a
static ``(groups, E, capacity, d)`` buffer, with expert placement expressed
through sharding constraints:

  * experts sharded over the ``model`` axis (EP) when ``E %% model == 0``
    (jamba-16e, qwen2-64e-padded); the combine contraction over the sharded
    expert axis makes GSPMD insert the reduce collective;
  * otherwise expert-internal TP (ff dim over ``model``; mixtral E=8 < 16).

Overflowing tokens beyond per-group capacity are *dropped* (their combine
weight is zero) and counted — the same overflow contract as the table
shuffle; the trainer monitors the dropped fraction.

A config that names the experts this chip holds (``experts_held``) takes
the held layer instead (:func:`held_moe_ffn`): it routes over all
``n_experts``, computes only its own experts, for every token routed to
them, and drops none.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.sharding.axes import constrain

from .layers import Params, _dense_init, init_rmsnorm, mlp, rms_norm

#: integer counters of the held layer, under its metrics' ``counters``
#: and summed over layers like the metrics: token-expert pairs routed to
#: held experts, and those of them left uncomputed
HELD_COUNTERS = ("moe_held_pairs", "moe_dropped_pairs")
#: tokens the held layer routes and computes at a time
HELD_CHUNK = 16384


def padded_experts(cfg: ModelConfig, model_axis: int = 16) -> int:
    """Pad expert count so EP divides the model axis (dead experts)."""
    e = cfg.n_experts
    if e % model_axis == 0 or model_axis % e == 0:
        return e
    return -(-e // model_axis) * model_axis


def init_moe(rng, cfg: ModelConfig) -> Params:
    d, f = cfg.d_model, cfg.expert_d_ff
    if cfg.experts_held is None:
        e = e_router = padded_experts(cfg)
    else:   # the router scores every expert; only the held ones live here
        e, e_router = cfg.n_held_experts, cfg.n_experts
    ks = jax.random.split(rng, 5)
    p = {
        "norm": init_rmsnorm(d),
        "router": _dense_init(ks[0], (d, e_router)),
        "w_gate": _dense_init(ks[1], (e, d, f)),
        "w_in": _dense_init(ks[2], (e, d, f)),
        "w_out": _dense_init(ks[3], (e, f, d), fan_in=f),
    }
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * f
        ks2 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": _dense_init(ks2[0], (d, fs)),
            "w_in": _dense_init(ks2[1], (d, fs)),
            "w_out": _dense_init(ks2[2], (fs, d), fan_in=fs),
        }
    return p


def _capacity(tokens_per_group: int, k: int, e: int, factor: float) -> int:
    return max(4, math.ceil(tokens_per_group * k / e * factor))


def moe_ffn(params: Params, cfg: ModelConfig, x: jnp.ndarray,
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Dispatch to the explicit-EP shard_map path when the mesh supports it
    (E divisible by the model axis), else the einsum/auto-SPMD path.

    The auto-SPMD path lets the partitioner handle the expert scatter — and
    it emulates the shuffle with full dense all-reduces of the token buffers
    (measured: 10 GiB f32 + 4 GiB u32 AR per layer group on qwen2-moe),
    which is exactly the operator-mismatch anti-pattern the paper calls out
    (§IV: AllReduce-via-GroupBy).  The shard_map path expresses the shuffle
    directly: local pack → local expert compute on the device's expert
    slice → ONE psum combine.  A config that names the experts held here
    takes :func:`held_moe_ffn`.
    """
    if cfg.experts_held is not None:
        return held_moe_ffn(params, cfg, x)
    from repro.sharding import axes as axes_mod
    mesh = axes_mod.current_mesh()
    if mesh is not None and "model" in mesh.axis_names:
        e = params["router"].shape[1]
        if e % mesh.shape["model"] == 0:
            return _moe_ffn_ep_shardmap(params, cfg, x, mesh)
    return _moe_ffn_einsum(params, cfg, x)


def _routing(params: Params, cfg: ModelConfig, xn: jnp.ndarray):
    """Router logits → (top-k gates/ids, aux metrics). fp32 throughout."""
    e = params["router"].shape[1]
    k = cfg.experts_per_token
    logits = (xn.astype(jnp.float32) @ params["router"]).astype(jnp.float32)
    if e > cfg.n_experts:
        pad_mask = jnp.arange(e) >= cfg.n_experts
        logits = jnp.where(pad_mask, -1e30, logits)
    gates = jax.nn.softmax(logits, axis=-1)
    top_g, top_i = jax.lax.top_k(gates, k)
    if cfg.norm_topk_prob:
        top_g = top_g / jnp.maximum(jnp.sum(top_g, -1, keepdims=True), 1e-9)
    me = jnp.mean(gates.reshape(-1, e), axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_i.reshape(-1, k), e), axis=1), axis=0) / k
    aux = jnp.sum(me * ce) * cfg.n_experts
    router_z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return top_g, top_i, aux, router_z


def _pack(xg, ig, gg, e: int, cap: int, dt):
    """Sort-by-destination bucket pack (the HPTMT shuffle's local step).

    xg (g, tg, d); ig/gg (g, tg, k) → (buf (g, e, cap, d), slot, tok_idx,
    g_tok, ok)."""
    g, tg, d = xg.shape
    k = ig.shape[-1]
    flat_e = ig.reshape(g, tg * k)
    flat_g = gg.reshape(g, tg * k).astype(dt)
    order = jnp.argsort(flat_e, axis=1, stable=True)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=1)
    first = jax.vmap(lambda a: jnp.searchsorted(a, a, side="left"))(sorted_e)
    rank = jnp.arange(tg * k, dtype=jnp.int32)[None] - first.astype(jnp.int32)
    ok = rank < cap
    slot = jnp.where(ok, sorted_e * cap + rank, e * cap)
    tok_idx = order // k
    x_tok = jnp.take_along_axis(xg, tok_idx[..., None], axis=1)
    g_tok = jnp.take_along_axis(flat_g, order, axis=1)

    def scatter_rows(xt, st):
        return jnp.zeros((e * cap, d), dt).at[st].set(xt, mode="drop")

    buf = jax.vmap(scatter_rows)(x_tok, slot).reshape(g, e, cap, d)
    return buf, slot, tok_idx, g_tok, ok


def _moe_ffn_ep_shardmap(params: Params, cfg: ModelConfig, x: jnp.ndarray,
                         mesh) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Expert-parallel MoE as an explicit HPTMT shuffle (shard_map).

    Activations are batch-sharded over the DP axes and replicated over
    ``model``; experts are sharded over ``model``.  Each device packs
    buckets for *its* expert slice locally (zero dispatch communication —
    the shuffle's exchange is subsumed by the existing replication), runs
    its experts, and contributes a partial output; ONE bf16 psum over
    ``model`` combines.  Shared experts run as plain TP inside the same
    region and join the same psum.
    """
    from jax.sharding import PartitionSpec as P
    from repro.sharding import axes as axes_mod

    b, s, d = x.shape
    dt = x.dtype
    e = params["router"].shape[1]
    k = cfg.experts_per_token
    msize = mesh.shape["model"]
    e_loc = e // msize
    bspec = axes_mod.spec_for(["batch"])[0]
    dp_axes = tuple(a for a in mesh.axis_names if a != "model")

    f = cfg.expert_d_ff
    fs = cfg.n_shared_experts * f
    has_shared = "shared" in params
    shared_ok = has_shared and fs % msize == 0

    in_specs = (
        P(bspec, None, None),                 # x
        P(None),                              # norm scale
        P(None, None),                        # router
        P("model", None, None),               # w_gate
        P("model", None, None),               # w_in
        P("model", None, None),               # w_out
    )
    shared_args = ()
    if has_shared:
        sspec = "model" if shared_ok else None
        in_specs += (P(None, sspec), P(None, sspec), P(sspec, None))
        shared_args = (params["shared"]["w_gate"], params["shared"]["w_in"],
                       params["shared"]["w_out"])

    def local(xl, scale, router, wg, wi, wo, *shared):
        xn = rms_norm({"scale": scale}, xl, cfg.norm_eps)
        top_g, top_i, aux, router_z = _routing(
            {"router": router}, cfg, xn)

        if s >= 64:
            g, tg = xl.shape[0], s
            xg, ig, gg = xn, top_i, top_g
        else:
            g, tg = 1, xl.shape[0] * s
            xg = xn.reshape(1, -1, d)
            ig, gg = top_i.reshape(1, -1, k), top_g.reshape(1, -1, k)
        cap = _capacity(tg, k, e, cfg.capacity_factor)
        buf, slot, tok_idx, g_tok, ok = _pack(xg, ig, gg, e, cap, dt)
        dropped = 1.0 - jnp.mean(ok.astype(jnp.float32))

        # my expert slice
        m_idx = jax.lax.axis_index("model")
        start = m_idx * e_loc * cap
        buf_flat = buf.reshape(g, e * cap, d)
        mine = jax.lax.dynamic_slice_in_dim(buf_flat, start, e_loc * cap,
                                            axis=1)
        mine = mine.reshape(g, e_loc, cap, d)
        wg_ = wg.astype(dt)
        wi_ = wi.astype(dt)
        wo_ = wo.astype(dt)
        hidden = jax.nn.silu(jnp.einsum("gecd,edf->gecf", mine, wg_)) \
            * jnp.einsum("gecd,edf->gecf", mine, wi_)
        out = jnp.einsum("gecf,efd->gecd", hidden, wo_)

        # scatter my experts' rows back into the full slot space (local)
        out_flat = jnp.zeros((g, e * cap, d), dt)
        out_flat = jax.lax.dynamic_update_slice_in_dim(
            out_flat, out.reshape(g, e_loc * cap, d), start, axis=1)
        safe = jnp.minimum(slot, e * cap - 1)
        y_tok = jnp.take_along_axis(out_flat, safe[..., None], axis=1)
        y_tok = jnp.where(ok[..., None], y_tok, 0.0) * g_tok[..., None]

        def combine_rows(yt, ti):
            return jnp.zeros((tg, d), dt).at[ti].add(yt)

        y = jax.vmap(combine_rows)(y_tok, tok_idx).reshape(xl.shape)

        if shared:
            swg, swi, swo = (w.astype(dt) for w in shared)
            hsh = jax.nn.silu(xn @ swg) * (xn @ swi)
            y_sh = hsh @ swo
            if shared_ok:
                y = y + y_sh           # partial: joins the model psum
            else:
                y = y + y_sh / msize   # replicated weights: avoid double-add
        # ONE combine for routed (+shared) partials — the shuffle's reduce
        y = jax.lax.psum(y, "model")

        # aux metrics: identical across model; mean across DP shards
        metrics = (aux, router_z, dropped)
        if dp_axes:
            metrics = tuple(
                jax.lax.pmean(v, dp_axes) for v in metrics)
        return y, metrics[0], metrics[1], metrics[2]

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(bspec, None, None), P(), P(), P()),
        check_vma=False)
    args = (x, params["norm"]["scale"], params["router"],
            params["w_gate"], params["w_in"], params["w_out"]) + shared_args
    y, aux, router_z, dropped = fn(*args)
    return y, {"moe_aux_loss": aux, "router_z_loss": router_z,
               "moe_dropped_frac": dropped}


def _moe_ffn_einsum(params: Params, cfg: ModelConfig, x: jnp.ndarray,
                    ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """x (B, S, D) → (y, metrics{aux_loss, router_z, dropped_frac})."""
    b, s, d = x.shape
    dt = x.dtype
    e = params["router"].shape[1]
    k = cfg.experts_per_token

    xn = rms_norm(params["norm"], x, cfg.norm_eps)

    # --- routing (fp32) ------------------------------------------------------
    logits = (xn.astype(jnp.float32) @ params["router"]).astype(jnp.float32)
    # mask padded (dead) experts out of routing
    if e > cfg.n_experts:
        pad_mask = jnp.arange(e) >= cfg.n_experts
        logits = jnp.where(pad_mask, -1e30, logits)
    gates = jax.nn.softmax(logits, axis=-1)                    # (B,S,E)
    top_g, top_i = jax.lax.top_k(gates, k)                     # (B,S,k)
    if cfg.norm_topk_prob:
        top_g = top_g / jnp.maximum(jnp.sum(top_g, -1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch) + router z-loss
    me = jnp.mean(gates.reshape(-1, e), axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_i.reshape(-1, k), e), axis=1), axis=0) / k
    aux = jnp.sum(me * ce) * (cfg.n_experts ** 1)
    router_z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))

    # --- group & pack (HPTMT shuffle: sort by destination, bucket) -----------
    # groups: per-batch-row when sequences are long, whole batch when decoding
    if s >= 64:
        g, tg = b, s
        xg = xn
        ig, gg = top_i, top_g
    else:
        g, tg = 1, b * s
        xg = xn.reshape(1, b * s, d)
        ig, gg = top_i.reshape(1, -1, k), top_g.reshape(1, -1, k)

    cap = _capacity(tg, k, e, cfg.capacity_factor)
    flat_e = ig.reshape(g, tg * k)
    flat_g = gg.reshape(g, tg * k).astype(dt)
    order = jnp.argsort(flat_e, axis=1, stable=True)           # (g, tg*k)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=1)
    first = jax.vmap(lambda a: jnp.searchsorted(a, a, side="left"))(sorted_e)
    rank = jnp.arange(tg * k, dtype=jnp.int32)[None] - first.astype(jnp.int32)
    ok = rank < cap
    slot = jnp.where(ok, sorted_e * cap + rank, e * cap)
    tok_idx = order // k                                       # source token
    dropped = 1.0 - jnp.mean(ok.astype(jnp.float32))

    x_tok = jnp.take_along_axis(xg, tok_idx[..., None], axis=1)  # (g,tg*k,d)
    g_tok = jnp.take_along_axis(flat_g, order, axis=1)

    def scatter_rows(xt, st):
        return jnp.zeros((e * cap, d), dt).at[st].set(xt, mode="drop")

    buf = jax.vmap(scatter_rows)(x_tok, slot)                  # (g, e*cap, d)
    buf = buf.reshape(g, e, cap, d)
    buf = constrain(buf, "batch", "expert", None, "embed")

    # --- expert compute (einsum over stacked expert weights) -----------------
    wg = params["w_gate"].astype(dt)
    wi = params["w_in"].astype(dt)
    wo = params["w_out"].astype(dt)
    hidden = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, wg)) \
        * jnp.einsum("gecd,edf->gecf", buf, wi)
    hidden = constrain(hidden, "batch", "expert", None, "moe_ff")
    out = jnp.einsum("gecf,efd->gecd", hidden, wo)
    out = constrain(out, "batch", "expert", None, "embed")

    # --- combine (inverse shuffle: gather + weighted scatter-add) ------------
    out_flat = out.reshape(g, e * cap, d)
    safe = jnp.minimum(slot, e * cap - 1)
    y_tok = jnp.take_along_axis(out_flat, safe[..., None], axis=1)
    y_tok = jnp.where(ok[..., None], y_tok, 0.0) * g_tok[..., None]

    def combine_rows(yt, ti):
        return jnp.zeros((tg, d), dt).at[ti].add(yt)

    y = jax.vmap(combine_rows)(y_tok, tok_idx).reshape(b, s, d)

    if cfg.n_shared_experts:
        sp = params["shared"]
        gsh = jax.nn.silu(xn @ sp["w_gate"].astype(dt))
        ush = xn @ sp["w_in"].astype(dt)
        y = y + (gsh * ush) @ sp["w_out"].astype(dt)

    y = constrain(y, "batch", "seq", "embed")
    metrics = {"moe_aux_loss": aux, "router_z_loss": router_z,
               "moe_dropped_frac": dropped}
    return y, metrics


def _held_rows(tokens: int, k: int, n_held: int) -> int:
    """Rows of the held layer's sorted buffer: every pair a chunk of
    ``tokens`` can route to held experts (at most ``min(k, n_held)`` per
    token, its top-k being distinct)."""
    return tokens * min(k, n_held)


def held_moe_ffn(params: Params, cfg: ModelConfig, x: jnp.ndarray,
                 ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """The expert layer of one chip's share: x (B, S, D) → (y, metrics).

    Routes every token over all ``cfg.n_experts`` (softmax in f32, top-k,
    renormalised only where ``cfg.norm_topk_prob``), computes the held
    experts
    ``cfg.experts_held`` for the tokens routed to them, and adds the
    shared experts.  The pairs of held experts are sorted by expert and
    fed to a grouped matmul (``jax.lax.ragged_dot``) over a buffer that
    has room for every pair the chunk can route here, so none is dropped;
    tokens go through in chunks of ``HELD_CHUNK``.  What the other
    experts would add is not computed here.
    """
    from repro.kernels import dispatch

    dispatch.record("moe", "held_dropless")
    b, s, d = x.shape
    dt = x.dtype
    t = b * s
    xn3 = rms_norm(params["norm"], x, cfg.norm_eps)
    xn = xn3.reshape(t, d)
    chunk = min(t, HELD_CHUNK)
    n_chunks = -(-t // chunk)
    pad = n_chunks * chunk - t
    valid = jnp.arange(n_chunks * chunk) < t
    xc = jnp.pad(xn, ((0, pad), (0, 0))).reshape(n_chunks, chunk, d)
    vc = valid.reshape(n_chunks, chunk)

    y, aux, z, held, dropped = jax.lax.map(
        lambda args: _held_chunk(params, cfg, *args), (xc, vc))
    aux, z = jnp.mean(aux), jnp.mean(z)
    held, dropped = jnp.sum(held), jnp.sum(dropped)
    y = y.reshape(n_chunks * chunk, d)[:t]
    if "shared" in params:
        y = y + mlp(params["shared"], cfg, xn3, skip_norm=True).reshape(
            t, d).astype(jnp.float32)
    y = constrain(y.astype(dt).reshape(b, s, d), "batch", "seq", "embed")
    return y, {"moe_aux_loss": aux, "router_z_loss": z,
               "moe_dropped_frac": dropped / jnp.maximum(held, 1),
               "counters": {"moe_held_pairs": held,
                            "moe_dropped_pairs": dropped}}


def _held_chunk(params: Params, cfg: ModelConfig, xn, valid):
    """One chunk of tokens through the held layer: xn (T, D), valid (T,)
    → (routed part of y in f32, aux loss, z loss, held pairs, dropped
    pairs)."""
    t, d = xn.shape
    dt = xn.dtype
    e, k = cfg.n_experts, cfg.experts_per_token
    e0, e1 = cfg.experts_held
    n_held = e1 - e0
    with jax.named_scope("moe.route"):
        logits = jnp.dot(xn.astype(jnp.float32),
                         params["router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        gates = jax.nn.softmax(logits, axis=-1)                  # (T, E)
        top_g, top_i = jax.lax.top_k(gates, k)                   # (T, k)
        if cfg.norm_topk_prob:
            top_g = top_g / jnp.maximum(jnp.sum(top_g, -1, keepdims=True),
                                        1e-20)
        w = jnp.where(valid[:, None], top_g, 0.0)
        me = jnp.sum(gates * valid[:, None], 0) / jnp.maximum(
            jnp.sum(valid), 1)
        ce = jnp.sum(jax.nn.one_hot(top_i, e) * valid[:, None, None],
                     (0, 1)) / jnp.maximum(jnp.sum(valid) * k, 1)
        aux = jnp.sum(me * ce) * e
        z = jnp.sum(jnp.square(jax.nn.logsumexp(logits, -1)) * valid) \
            / jnp.maximum(jnp.sum(valid), 1)
        # held pairs first, by expert; the rest after them
        flat = top_i.reshape(-1)
        mine = (flat >= e0) & (flat < e1) & jnp.repeat(valid, k)
        local = jnp.where(mine, flat - e0, n_held)
        order = jnp.argsort(local, stable=True)
        rows = _held_rows(t, k, n_held)
        sizes = jnp.sum(jax.nn.one_hot(local, n_held, dtype=jnp.int32), 0)
        held = jnp.sum(sizes)
        # groups past the buffer's end would be cut short: count them
        ends = jnp.minimum(jnp.cumsum(sizes), rows)
        kept = jnp.diff(ends, prepend=0)
        dropped = held - jnp.sum(kept)
    with jax.named_scope("moe.experts"):
        src = order[:rows]
        xs = xn[src // k]                                        # (rows, D)
        g = jax.lax.ragged_dot(xs, params["w_gate"].astype(dt), kept)
        u = jax.lax.ragged_dot(xs, params["w_in"].astype(dt), kept)
        out = jax.lax.ragged_dot(jax.nn.silu(g) * u,
                                 params["w_out"].astype(dt), kept)
        computed = jnp.arange(rows) < ends[-1]
        out = jnp.where(computed[:, None], out, 0.0)
    with jax.named_scope("moe.combine"):
        # each pair's row in the sorted buffer, or none
        where = jnp.zeros((t * k,), jnp.int32).at[order].set(
            jnp.arange(t * k, dtype=jnp.int32))
        hit = mine & (where < ends[-1])
        pair_out = jnp.where(hit[:, None],
                             out[jnp.minimum(where, rows - 1)], 0.0)
        y = jnp.sum(pair_out.reshape(t, k, d).astype(jnp.float32)
                    * w[..., None], axis=1)
    return y, aux, z, held, dropped
