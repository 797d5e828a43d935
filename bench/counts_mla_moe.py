"""Operations and bytes of serving a DeepSeek-V2 block on one chip's
share of its routed experts, computed from a configuration file's shapes.

The yardstick's own counts, of what the algorithm needs: attention in the
expanded form where a prompt is prefilled (K and V up-projected once per
token), in the latent form where a decode step reads the cache (the
query absorbed into the latent, the latent output up-projected); routed
experts at the expected held share of a token's top k, ``k * held / E``,
and, read by a decode step, at the expected share of held experts that
some token of the batch reaches, ``1 - (1 - k/E)^B``; the head only where
logits are produced (the last prompt position, every decode step).
"""
from __future__ import annotations

BYTES = {"bfloat16": 2, "float32": 4}


def _dims(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    f = cfg["moe_intermediate_size"]
    n_dense = cfg["first_k_dense_replace"]
    return dict(d=d, h=h, nope=nope, rd=rd, vd=vd, r=r, f=f,
                fs=cfg["n_shared_experts"] * f, big_f=cfg["intermediate_size"],
                e=cfg["published"]["n_routed_experts"],
                held=cfg["n_routed_experts"], k=cfg["num_experts_per_tok"],
                v=cfg["vocab_size"], n_dense=n_dense,
                n_moe=cfg["num_hidden_layers"] - n_dense)


def _proj_params(m: dict) -> int:
    """Attention weights every token multiplies by, in either form: the
    query, the latent's down-projection and the output."""
    return (m["d"] * m["h"] * (m["nope"] + m["rd"])
            + m["d"] * (m["r"] + m["rd"]) + m["h"] * m["vd"] * m["d"])


def _ffn_flops(m: dict) -> float:
    """FFN FLOPs of one token over all layers."""
    dense = m["n_dense"] * 2.0 * 3 * m["d"] * m["big_f"]
    routed = m["k"] * m["held"] / m["e"] * 3 * m["d"] * m["f"]
    moe = m["n_moe"] * 2.0 * (m["d"] * m["e"] + 3 * m["d"] * m["fs"] + routed)
    return dense + moe


def prefill_flops(cfg: dict, batch: int, prompt: int) -> float:
    """A causal prefill of ``batch`` prompts of ``prompt`` tokens, logits
    of the last position only."""
    m = _dims(cfg)
    layers = m["n_dense"] + m["n_moe"]
    tokens = batch * prompt
    per_token = layers * 2.0 * (_proj_params(m)
                                + m["r"] * m["h"] * (m["nope"] + m["vd"]))
    ctx = (prompt + 1) / 2.0
    attn = layers * 2.0 * m["h"] * (m["nope"] + m["rd"] + m["vd"]) * ctx
    return (tokens * (per_token + attn + _ffn_flops(m))
            + batch * 2.0 * m["d"] * m["v"])


def decode_step_flops(cfg: dict, batch: int, ctx: float) -> float:
    """One decode step of ``batch`` tokens, each attending over ``ctx``
    cached tokens (itself included), in the latent form."""
    m = _dims(cfg)
    layers = m["n_dense"] + m["n_moe"]
    absorb = m["h"] * m["nope"] * m["r"] + m["h"] * m["r"] * m["vd"]
    attn = m["h"] * (m["r"] + m["rd"] + m["r"]) * ctx
    per_token = (layers * 2.0 * (_proj_params(m) + absorb + attn)
                 + _ffn_flops(m) + 2.0 * m["d"] * m["v"])
    return batch * per_token


def decode_step_bytes(cfg: dict, batch: int, ctx: float) -> float:
    """Bytes one decode step must move: every weight it multiplies by
    once (held experts at the share the batch reaches), the latent cache
    of ``ctx`` tokens per row read and one slot written, the embedding
    rows gathered."""
    m = _dims(cfg)
    w = BYTES[cfg["precision"]["weights"]]
    a = BYTES[cfg["precision"]["compute"]]
    layers = m["n_dense"] + m["n_moe"]
    attn = layers * (_proj_params(m) + m["r"] * m["h"] * (m["nope"] + m["vd"])
                     + m["d"] + m["r"])
    dense = m["n_dense"] * (3 * m["d"] * m["big_f"] + m["d"])
    reach = 1.0 - (1.0 - m["k"] / m["e"]) ** batch
    moe = m["n_moe"] * (m["d"] * m["e"] + 3 * m["d"] * m["fs"] + m["d"]
                        + reach * m["held"] * 3 * m["d"] * m["f"])
    head = m["d"] * m["v"] + m["d"]
    weights = w * (attn + dense + moe + head + batch * m["d"])
    cache = a * layers * (m["r"] + m["rd"]) * batch * (ctx + 1)
    return weights + cache


def _decode_ctx(prompt: int, gen: int):
    """``(steps, mean context)`` of a call's decode steps: step ``j``
    attends over ``prompt + j`` cached tokens and itself."""
    steps = gen - 1
    return steps, (prompt + 1 + (steps - 1) / 2.0 if steps > 0 else 0.0)


def generate_flops(cfg: dict, batch: int, prompt: int, gen: int) -> float:
    """One ``generate`` call: the prefill, then ``gen - 1`` decode steps."""
    steps, ctx = _decode_ctx(prompt, gen)
    return prefill_flops(cfg, batch, prompt) + steps * decode_step_flops(
        cfg, batch, ctx)


def decode_bytes(cfg: dict, batch: int, prompt: int, gen: int) -> float:
    """The decode steps of one ``generate`` call."""
    steps, ctx = _decode_ctx(prompt, gen)
    return steps * decode_step_bytes(cfg, batch, ctx)
