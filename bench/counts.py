"""Operations and bytes the benchmarked work needs, computed from shapes.

These are the yardstick's own counts: what the algorithm needs, not what
an implementation happens to do.  They take a configuration file's dict
(``bench/configs/<name>.json``).
"""
from __future__ import annotations


def lm_matmul_params(cfg: dict) -> int:
    """Weights that take part in a matmul per token: every layer's
    attention and SwiGLU projections plus the output head (the embedding
    gather does no arithmetic)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // h
    layer = d * h * dh + 2 * d * hk * dh + h * dh * d + 3 * d * f
    return cfg["num_hidden_layers"] * layer + cfg["vocab_size"] * d


def lm_attn_flops(cfg: dict, ctx: float) -> float:
    """Forward FLOPs of one query token's attention over ``ctx`` keys,
    all layers: ``QK^T`` and ``PV`` at two FLOPs per multiply-add."""
    d = cfg["hidden_size"]
    return cfg["num_hidden_layers"] * 4.0 * d * ctx


def lm_forward_flops(cfg: dict, tokens: int, ctx_mean: float) -> float:
    """Forward FLOPs of ``tokens`` tokens whose queries see ``ctx_mean``
    keys on average."""
    return tokens * (2.0 * lm_matmul_params(cfg) + lm_attn_flops(cfg, ctx_mean))


def lm_train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward FLOPs per token of a causal sequence of ``seq``
    (backward = 2x forward, nothing recomputed counted)."""
    return 3.0 * lm_forward_flops(cfg, 1, (seq + 1) / 2.0)


def lm_generate_flops(cfg: dict, batch: int, prompt: int, gen: int) -> float:
    """FLOPs of one ``generate`` call: a causal prefill of ``prompt``
    tokens, then ``gen - 1`` decode steps, step ``j`` attending over
    ``prompt + j`` cached keys and itself."""
    prefill = lm_forward_flops(cfg, batch * prompt, (prompt + 1) / 2.0)
    steps = gen - 1
    ctx_mean = prompt + 1 + (steps - 1) / 2.0 if steps > 0 else 0.0
    return prefill + lm_forward_flops(cfg, batch * steps, ctx_mean)
