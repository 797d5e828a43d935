"""Per-architecture smoke tests (deliverable f): every assigned arch, at a
family-preserving reduced config, runs one forward + one train step on CPU
with shape assertions and NaN checks; plus prefill↔decode consistency."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, reduced_config
from repro.kernels import dispatch
from repro.models import transformer as T
from repro.models.layers import attend, attend_decode
from repro.train.optimizer import OptimizerConfig
from repro.train.train_step import TrainConfig, init_train_state, \
    make_train_step

ARCH_IDS = sorted(ARCHS)


def _batch(cfg, b=2, s=32, rng=None):
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    tokens = jax.random.randint(rng, (b, s), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.frontend is not None or cfg.is_encoder_decoder:
        batch["frontend"] = 0.02 * jax.random.normal(
            rng, (b, cfg.frontend_seq, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward(arch):
    cfg = reduced_config(get_config(arch))
    params = T.init_lm(jax.random.PRNGKey(1), cfg)
    batch = _batch(cfg)
    logits, _, aux = T.apply_lm(params, cfg, batch["tokens"], mode="train",
                                frontend_embeds=batch.get("frontend"))
    b, s = batch["tokens"].shape
    exp_s = s + (cfg.frontend_seq if cfg.frontend == "vision" else 0)
    assert logits.shape == (b, exp_s, cfg.vocab_size)
    assert not np.any(np.isnan(logits)), f"{arch}: NaN logits"
    assert all(np.isfinite(float(v)) for v in jax.tree.leaves(aux))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch):
    cfg = reduced_config(get_config(arch))
    tcfg = TrainConfig(optimizer=OptimizerConfig(warmup_steps=1,
                                                 total_steps=10))
    state = init_train_state(jax.random.PRNGKey(2), cfg)
    step = jax.jit(make_train_step(cfg, tcfg))
    batch = _batch(cfg)
    state2, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"])), f"{arch}: non-finite loss"
    assert float(metrics["grad_norm"]) > 0
    # params actually changed
    delta = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                         state.params, state2.params)
    assert max(jax.tree.leaves(delta)) > 0


SMOLLM_HEADS = "smollm-360m-heads-15x5"


def _smollm_heads():
    """SmolLM reduced in width but with its 15/5 heads: three query heads
    per KV head over five KV heads."""
    full = get_config("smollm-360m")
    cfg = reduced_config(full)
    d_model = full.n_heads * cfg.d_head
    return dataclasses.replace(cfg, n_heads=full.n_heads,
                               n_kv_heads=full.n_kv_heads, d_model=d_model,
                               d_ff=4 * d_model)


@pytest.mark.parametrize("arch", ARCH_IDS + [SMOLLM_HEADS])
def test_prefill_decode_consistency(arch):
    cfg = (_smollm_heads() if arch == SMOLLM_HEADS
           else reduced_config(get_config(arch)))
    if cfg.is_moe:  # capacity dropping differs between grouping modes
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    b, s = 2, 48
    params = T.init_lm(jax.random.PRNGKey(3), cfg)
    batch = _batch(cfg, b, s, jax.random.PRNGKey(4))
    pref = cfg.frontend_seq if cfg.frontend == "vision" else 0
    cache_len = s + pref + 4
    full_logits, _, _ = T.apply_lm(
        params, cfg, batch["tokens"], mode="prefill",
        frontend_embeds=batch.get("frontend"), cache_len=cache_len)
    _, cache, _ = T.apply_lm(
        params, cfg, batch["tokens"][:, :s - 1], mode="prefill",
        frontend_embeds=batch.get("frontend"), cache_len=cache_len)
    dec, _, _ = T.apply_lm(
        params, cfg, batch["tokens"][:, s - 1:], mode="decode", cache=cache,
        positions=jnp.array([s - 1 + pref], jnp.int32))
    a = np.asarray(dec[:, 0])
    e = np.asarray(full_logits[:, -1])
    rel = np.max(np.abs(a - e)) / (np.max(np.abs(e)) + 1e-9)
    assert rel < 3e-2, f"{arch}: decode inconsistent with prefill ({rel})"


@pytest.mark.parametrize("cache", ["full", "empty_slots", "window"])
@pytest.mark.parametrize("hq,hkv", [(15, 5), (4, 4), (8, 2)])
def test_attend_decode_matches_repeat_path(hq, hkv, cache):
    """The grouped decode contraction over a bf16 cache == ``attend``'s
    repeat-and-upcast path, to f32 rounding: query head h reads KV head
    h // G, and the same mask, softmax and f32 sums apply."""
    b, l, d = 3, 96, 64
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(hq * 10 + hkv), 3)
    # bf16 cache and bf16-valued queries; f32 queries keep the outputs f32
    q = jax.random.normal(kq, (b, hq, 1, d)).astype(jnp.bfloat16)
    q = q.astype(jnp.float32)
    k = jax.random.normal(kk, (b, hkv, l, d)).astype(jnp.bfloat16)
    v = jax.random.normal(kv, (b, hkv, l, d)).astype(jnp.bfloat16)
    kv_pos = jnp.arange(l, dtype=jnp.int32)
    window = 16 if cache == "window" else None
    if cache == "empty_slots":
        kv_pos = jnp.where(kv_pos < l - 30, kv_pos, -1)
    q_pos = jnp.array([int(jnp.max(kv_pos))], jnp.int32)
    want = attend(q, k, v, q_pos=q_pos, kv_pos=kv_pos, window=window)
    got = attend_decode(q, k, v, q_pos=q_pos, kv_pos=kv_pos, window=window)
    assert got.dtype == want.dtype == jnp.float32
    rel = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert rel <= 1e-5, rel


@pytest.mark.parametrize("cache", ["linear", "ring"])
@pytest.mark.parametrize("path", ["grouped", "latent"])
def test_decode_step_entries_join_the_cache_softmax(path, cache):
    """A decode step's own entries, given beside the cache, enter the same
    max-subtract, exp and sum as the cache's: the output equals attending
    over the cache with them written into the slot they take, to f32
    rounding, and whatever that slot held before is not read."""
    from repro.models.layers import _unwritten, attend_latent

    b, l, slot = 2, 48, 40
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    bf = lambda k, shape: jax.random.normal(k, shape).astype(jnp.bfloat16)
    # positions: a linear cache filled up to the slot, or a ring of l slots
    # (no window) whose slot holds the position one turn back, which the
    # step would still see if it were not overwritten
    kv_pos = jnp.arange(l, dtype=jnp.int32)
    if cache == "linear":
        kv_pos = jnp.where(kv_pos < slot, kv_pos, -1)
        q_pos = jnp.array([slot], jnp.int32)
    else:
        kv_pos = jnp.where(kv_pos <= slot, kv_pos + l, kv_pos)
        q_pos = jnp.array([slot + l], jnp.int32)
    written_pos = kv_pos.at[slot].set(q_pos[0])
    if path == "grouped":
        hq, hkv, d = 15, 5, 64
        q = bf(ks[0], (b, hq, 1, d)).astype(jnp.float32)
        k, v = bf(ks[1], (b, hkv, l, d)), bf(ks[2], (b, hkv, l, d))
        kn, vn = bf(ks[3], (b, hkv, 1, d)), bf(ks[4], (b, hkv, 1, d))
        want = attend_decode(q, k.at[:, :, slot].set(kn[:, :, 0]),
                             v.at[:, :, slot].set(vn[:, :, 0]), q_pos=q_pos,
                             kv_pos=written_pos)
        got = attend_decode(q, k, v, q_pos=q_pos,
                            kv_pos=_unwritten(kv_pos, slot, 1),
                            new=(kn, vn, q_pos))
    else:
        h, nope, rope, r, dv = 4, 16, 8, 32, 16
        qn = bf(ks[0], (b, h, 1, nope)).astype(jnp.float32)
        qr = bf(ks[5], (b, h, 1, rope)).astype(jnp.float32)
        c, kr = bf(ks[1], (b, l, r)), bf(ks[2], (b, l, rope))
        cn, krn = bf(ks[3], (b, 1, r)), bf(ks[4], (b, 1, rope))
        w = bf(jax.random.PRNGKey(8), (r, h, nope + dv))
        want = attend_latent(qn, qr, c.at[:, slot].set(cn[:, 0]),
                             kr.at[:, slot].set(krn[:, 0]), w, q_pos=q_pos,
                             kv_pos=written_pos, sm_scale=0.1)
        got = attend_latent(qn, qr, c, kr, w, q_pos=q_pos,
                            kv_pos=_unwritten(kv_pos, slot, 1), sm_scale=0.1,
                            new=(cn, krn, q_pos))
    rel = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert rel <= 1e-5, rel


@pytest.mark.parametrize("slot", [0, 150, 299])
def test_write_slot_writes_only_its_slot(slot):
    """``write_slot`` writes a layer stack's entries at the cursor's slot
    of each positional leaf, on its own slot axis, and nothing else, and
    replaces every other leaf whole."""
    from repro.models.layers import write_slot

    g, b, h, l = 2, 2, 3, 300
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    cache = {"k": jax.random.normal(ks[0], (g, b, h, l, 64)),
             "c_kv": jax.random.normal(ks[1], (g, b, l, 128)),
             "pos": jnp.zeros((g, l), jnp.int32),
             "cursor": jnp.full((g,), slot, jnp.int32)}
    new = {"k": jax.random.normal(ks[2], (g, b, h, 1, 64)),
           "c_kv": jax.random.normal(ks[3], (g, b, 1, 128)),
           "pos": jnp.full((g, 1), 7, jnp.int32),
           "cursor": jnp.full((g,), slot + 1, jnp.int32)}
    got = jax.jit(write_slot)(cache, new)
    for key in ("k", "c_kv", "pos"):
        want = cache[key].at[..., slot, :] if key != "pos" else \
            cache[key].at[:, slot]
        want = want.set(new[key][..., 0, :] if key != "pos"
                        else new[key][:, 0])
        np.testing.assert_array_equal(got[key], want)
    np.testing.assert_array_equal(got["cursor"], new["cursor"])


def test_decode_step_holds_no_repeated_or_f32_cache():
    """One decode step of a one-layer model at SmolLM's head widths lowers
    with no KV head repeat and no f32 copy of the cache, and counts the
    grouped path and the in-place cache write once."""
    cfg = dataclasses.replace(get_config("smollm-360m"), n_layers=1,
                              vocab_size=256)
    b, l = 2, 40
    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    assert (hq, hkv, d) == (15, 5, 64)
    params = jax.eval_shape(lambda: T.init_lm(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: T.init_cache(cfg, b, l, jnp.bfloat16))
    token = jax.ShapeDtypeStruct((b, 1), jnp.int32)

    def step(params, cache, token):
        return T.apply_lm(params, cfg, token, mode="decode", cache=cache,
                          positions=jnp.array([5], jnp.int32))[:2]

    dispatch.reset_counts()
    text = jax.jit(step).lower(params, cache, token).as_text()
    assert dispatch.counts() == {"decode_attention": {"grouped": 1},
                                 "decode_cache": {"in_place": 1}}
    g = hq // hkv

    def has(dims, dtype=r"\w+"):
        lead = r"tensor<(?:\d+x)*"
        return re.search(lead + "x".join(map(str, dims)) + "x" + dtype + ">",
                         text) is not None

    assert has((b, hkv, l, d), "bf16")           # the cache itself
    assert not has((b, hq, l, d))                # KV heads repeated
    assert not has((b, hkv, g, l, d))            # ... or broadcast
    assert not has((b, hkv, l, d), "f32")        # an upcast copy

    dispatch.reset_counts()
    tokens = jax.ShapeDtypeStruct((b, l), jnp.int32)
    jax.jit(lambda p, t: T.apply_lm(p, cfg, t, mode="train")[0]).lower(
        params, tokens)
    assert "decode_attention" not in dispatch.counts()
    dispatch.reset_counts()


def test_sliding_window_ring_cache():
    """SWA ring cache gives the same logits as an oversized linear cache."""
    cfg = reduced_config(get_config("mixtral-8x7b"))
    cfg = dataclasses.replace(cfg, capacity_factor=8.0, window=16)
    params = T.init_lm(jax.random.PRNGKey(5), cfg)
    b, s = 1, 40
    tokens = jax.random.randint(jax.random.PRNGKey(6), (b, s), 0,
                                cfg.vocab_size)
    # full forward (train mode applies the window mask over all positions)
    full, _, _ = T.apply_lm(params, cfg, tokens, mode="train")
    # prefill s-1 then decode the last token through the ring
    _, cache, _ = T.apply_lm(params, cfg, tokens[:, :-1], mode="prefill",
                             cache_len=cfg.window)
    dec, _, _ = T.apply_lm(params, cfg, tokens[:, -1:], mode="decode",
                           cache=cache,
                           positions=jnp.array([s - 1], jnp.int32))
    rel = (np.max(np.abs(np.asarray(dec[:, 0]) - np.asarray(full[:, -1])))
           / (np.max(np.abs(np.asarray(full[:, -1]))) + 1e-9))
    assert rel < 3e-2, f"ring cache mismatch {rel}"


def test_multi_step_decode_matches_prefill():
    """Three decode steps == logits of a longer prefill (dense arch)."""
    cfg = reduced_config(get_config("phi3-mini-3.8b"))
    params = T.init_lm(jax.random.PRNGKey(7), cfg)
    b, s, extra = 2, 16, 3
    tokens = jax.random.randint(jax.random.PRNGKey(8), (b, s + extra), 0,
                                cfg.vocab_size)
    full, _, _ = T.apply_lm(params, cfg, tokens, mode="prefill",
                            cache_len=s + extra)
    _, cache, _ = T.apply_lm(params, cfg, tokens[:, :s], mode="prefill",
                             cache_len=s + extra)
    for i in range(extra):
        dec, cache, _ = T.apply_lm(params, cfg, tokens[:, s + i:s + i + 1],
                                   mode="decode", cache=cache,
                                   positions=jnp.array([s + i], jnp.int32))
        a, e = np.asarray(dec[:, 0]), np.asarray(full[:, s + i])
        rel = np.max(np.abs(a - e)) / (np.max(np.abs(e)) + 1e-9)
        assert rel < 2e-2, f"step {i}: {rel}"


def _step_by_step(cfg, params, prompts, n, max_len, temperature, eos, rng):
    """Greedy or sampled generation one synchronous step at a time: the
    key split on the host before each step, every token read before the
    next step, a stop once every row has served ``eos``."""
    from repro.serve.engine import sample

    step = jax.jit(lambda p, c, t, pos: T.apply_lm(
        p, cfg, t, mode="decode", cache=c, positions=pos.reshape(1,))[:2])
    logits, cache, _ = T.apply_lm(params, cfg, prompts, mode="prefill",
                                  cache_len=max_len, last_logit_only=True)
    token = sample(logits[:, -1], rng, temperature)
    out = [np.asarray(token)]
    for i in range(n - 1):
        rng, sub = jax.random.split(rng)
        logits, cache = step(params, cache, token,
                             jnp.asarray(prompts.shape[1] + i, jnp.int32))
        token = sample(logits[:, -1], sub, temperature)
        out.append(np.asarray(token))
        if eos >= 0 and np.all(out[-1] == eos):
            break
    return np.concatenate(out, axis=1)


#: reduced configs of ``test_generate_matches_step_by_step_decode``: a
#: dense KV cache, MLA's latent cache beside a leading dense layer's with
#: held experts, a sliding-window ring and the int8 cache with its scales
_GENERATE_MODELS = {
    "dense": lambda: reduced_config(get_config("smollm-360m")),
    "mla_moe": lambda: reduced_config(get_config("deepseek-v2-lite")),
    "ring": lambda: dataclasses.replace(
        reduced_config(get_config("mixtral-8x7b")), capacity_factor=8.0,
        window=8),
    "int8": lambda: dataclasses.replace(
        reduced_config(get_config("smollm-360m")), kv_quant=True),
}


@pytest.mark.parametrize(
    "temperature,stop,model,calls",
    [(0.0, False, "dense", 1), (1.0, False, "dense", 1),
     (0.0, True, "dense", 1), (0.0, False, "mla_moe", 1),
     (0.0, False, "ring", 1), (0.0, False, "int8", 1),
     (1.0, False, "dense", 2)],
    ids=["greedy", "sampled", "eos", "mla_moe", "ring", "int8", "twice"])
def test_generate_matches_step_by_step_decode(temperature, stop, model,
                                              calls):
    """``Engine.generate`` reads each token one step behind the device,
    splits the key inside the step and donates the cache to it: it serves
    the tokens of a loop that splits on the host, reads every step and
    donates nothing, and stops after the same token when every row
    reaches ``eos_id``.  So do a ring that wraps (fewer slots than the
    call's positions), the latent cache, the int8 cache, and a second
    call on one engine, which must find no donated buffer read again."""
    from repro.serve.engine import Engine, ServeConfig

    cfg = dataclasses.replace(_GENERATE_MODELS[model](), dtype="float32")
    params = T.init_lm(jax.random.PRNGKey(31), cfg)
    b, s, n = 1, 12, 10
    max_len = cfg.window or s + n
    prompts = jax.random.randint(jax.random.PRNGKey(32), (b, s), 1,
                                 cfg.vocab_size)
    rng = jax.random.PRNGKey(33)
    want = _step_by_step(cfg, params, prompts, n, max_len, temperature, -1,
                         rng)
    eos = int(want[0, 4]) if stop else -1
    if stop:
        want = _step_by_step(cfg, params, prompts, n, max_len, temperature,
                             eos, rng)
        assert want.shape[1] <= 5
    eng = Engine(cfg, params, ServeConfig(max_len=max_len,
                                          temperature=temperature,
                                          eos_id=eos))
    for _ in range(calls):
        np.testing.assert_array_equal(eng.generate(prompts, n, rng=rng),
                                      want)


def test_param_count_analytic_close_to_actual():
    for arch in ("phi3-mini-3.8b", "smollm-360m", "mixtral-8x7b"):
        cfg = reduced_config(get_config(arch))
        params = T.init_lm(jax.random.PRNGKey(0), cfg)
        actual = sum(int(np.prod(p.shape))
                     for p in jax.tree.leaves(params))
        analytic = cfg.param_count()
        # analytic ignores norm scales / gate biases / expert padding
        assert abs(actual - analytic) / actual < 0.25, (
            f"{arch}: analytic {analytic} vs actual {actual}")


def test_full_configs_match_assignment():
    """Exact published hyperparameters (spot checks per arch)."""
    a = get_config("jamba-v0.1-52b")
    assert (a.n_layers, a.d_model, a.n_heads, a.n_kv_heads, a.d_ff,
            a.vocab_size) == (32, 4096, 32, 8, 14336, 65536)
    assert a.n_experts == 16 and a.experts_per_token == 2
    assert a.block_pattern.count("attn") == 1  # 1:7 interleave
    m = get_config("mixtral-8x7b")
    assert m.window == 4096 and m.n_experts == 8
    q = get_config("qwen2-moe-a2.7b")
    assert q.n_experts == 60 and q.experts_per_token == 4
    assert q.n_shared_experts == 4 and q.vocab_size == 151936
    d = get_config("deepseek-67b")
    assert d.n_layers == 95 and d.d_model == 8192 and d.d_ff == 22016
    mc = get_config("minicpm3-4b")
    assert mc.attention == "mla" and mc.n_layers == 62
    x = get_config("xlstm-125m")
    assert x.d_ff == 0 and set(x.block_pattern) == {"mlstm", "slstm"}
    w = get_config("whisper-medium")
    assert w.is_encoder_decoder and w.frontend == "audio"
    i = get_config("internvl2-76b")
    assert i.frontend == "vision" and i.n_layers == 80


def test_mla_absorbed_decode_matches_naive():
    """MiniCPM3's reduced decode scores and contracts against the latent
    cache (the absorbed form, counted as the latent path) and gives the
    logits of its own full forward, which expands K and V."""
    cfg = reduced_config(get_config("minicpm3-4b"))
    params = T.init_lm(jax.random.PRNGKey(9), cfg)
    b, s = 2, 24
    tokens = jax.random.randint(jax.random.PRNGKey(10), (b, s), 0,
                                cfg.vocab_size)
    full, _, _ = T.apply_lm(params, cfg, tokens, mode="train")
    _, cache, _ = T.apply_lm(params, cfg, tokens[:, :-1], mode="prefill",
                             cache_len=s + 2)
    dispatch.reset_counts()
    absorbed, _, _ = T.apply_lm(params, cfg, tokens[:, -1:], mode="decode",
                                cache=cache,
                                positions=jnp.array([s - 1], jnp.int32))
    # one traced call per layer of the scanned group, one in-place write
    # of the stacked cache
    assert dispatch.counts() == {"decode_attention":
                                 {"latent": cfg.group_size},
                                 "decode_cache": {"in_place": 1}}
    dispatch.reset_counts()
    a, e = np.asarray(absorbed[:, 0]), np.asarray(full[:, -1])
    # bf16 activations: the latent and the expanded form round at
    # different points, each a few bf16 ulps of the logits
    rel = np.max(np.abs(a - e)) / (np.max(np.abs(e)) + 1e-9)
    assert rel < 2e-2, f"absorbed MLA deviates: {rel}"


def test_int8_kv_cache_decode_close_to_full_precision():
    """Beyond-paper opt: int8 KV cache ≈ bf16 cache decode logits."""
    cfg = reduced_config(get_config("phi3-mini-3.8b"))
    params = T.init_lm(jax.random.PRNGKey(11), cfg)
    b, s = 2, 24
    tokens = jax.random.randint(jax.random.PRNGKey(12), (b, s), 0,
                                cfg.vocab_size)
    outs = {}
    for quant in (False, True):
        c = dataclasses.replace(cfg, kv_quant=quant)
        _, cache, _ = T.apply_lm(params, c, tokens[:, :-1], mode="prefill",
                                 cache_len=s + 2)
        if quant:
            assert cache["groups"]["layer_0"]["mixer"]["k"].dtype == jnp.int8
        dec, cache2, _ = T.apply_lm(params, c, tokens[:, -1:], mode="decode",
                                    cache=cache,
                                    positions=jnp.array([s - 1], jnp.int32))
        if quant:
            assert cache2["groups"]["layer_0"]["mixer"]["v"].dtype == jnp.int8
        outs[quant] = np.asarray(dec[:, 0])
    rel = (np.max(np.abs(outs[True] - outs[False]))
           / (np.max(np.abs(outs[False])) + 1e-9))
    assert rel < 0.05, f"int8 KV deviates too much: {rel}"


def test_flash_kernel_path_in_model():
    """Model forward with the Pallas kernel (interpret) == XLA attend path."""
    cfg = reduced_config(get_config("phi3-mini-3.8b"))
    params = T.init_lm(jax.random.PRNGKey(13), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(14), (2, 64), 0,
                                cfg.vocab_size)
    xla, _, _ = T.apply_lm(params, cfg, tokens, mode="train")
    cfg_fl = dataclasses.replace(cfg, use_flash=True)
    flash, _, _ = T.apply_lm(params, cfg_fl, tokens, mode="train")
    a, e = np.asarray(flash), np.asarray(xla)
    rel = np.max(np.abs(a - e)) / (np.max(np.abs(e)) + 1e-9)
    assert rel < 2e-2, f"flash model path deviates: {rel}"
