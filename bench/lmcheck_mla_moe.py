"""The program's configuration of a DeepSeek-V2 block (latent attention,
leading dense layers, routed and shared experts) held against the
configuration file, with the share of routed experts this chip holds."""
from __future__ import annotations


def fields(cfg: dict) -> dict:
    """The program's ``ModelConfig`` fields that the file states."""
    from repro.configs.base import Yarn

    rs = cfg["rope_scaling"]
    return {
        "d_model": cfg["hidden_size"], "d_ff": cfg["intermediate_size"],
        "moe_d_ff": cfg["moe_intermediate_size"],
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "n_layers": cfg["num_hidden_layers"],
        "vocab_size": cfg["vocab_size"], "norm_eps": cfg["rms_norm_eps"],
        "rope_theta": cfg["rope_theta"],
        "tie_embeddings": cfg["tie_word_embeddings"],
        "attention": "mla", "q_lora_rank": cfg["q_lora_rank"] or 0,
        "kv_lora_rank": cfg["kv_lora_rank"],
        "qk_nope_dim": cfg["qk_nope_head_dim"],
        "qk_rope_dim": cfg["qk_rope_head_dim"],
        "v_head_dim": cfg["v_head_dim"],
        "yarn": Yarn(factor=rs["factor"],
                     original_max_position=rs[
                         "original_max_position_embeddings"],
                     beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
                     mscale=rs["mscale"], mscale_all_dim=rs["mscale_all_dim"]),
        "n_dense_layers": cfg["first_k_dense_replace"],
        "n_experts": cfg["published"]["n_routed_experts"],
        "experts_per_token": cfg["num_experts_per_tok"],
        "n_shared_experts": cfg["n_shared_experts"],
        "norm_topk_prob": cfg["norm_topk_prob"],
        "experts_held": tuple(cfg["experts_held"]),
    }


def program_config(cfg: dict):
    """The program's ``ModelConfig`` of ``cfg["arch"]`` holding the
    file's share of the routed experts; raises where a size differs from
    the configuration file, or where the file scales the gate weights (the
    program takes them as they are)."""
    import dataclasses

    from repro.configs import get_config

    if cfg["routed_scaling_factor"] != 1:
        raise ValueError(f"{cfg['name']}: routed_scaling_factor "
                         f"{cfg['routed_scaling_factor']} is not 1")
    e0, e1 = cfg["experts_held"]
    if e1 - e0 != cfg["n_routed_experts"]:
        raise ValueError(f"{cfg['name']}: experts_held {cfg['experts_held']}"
                         f" is not n_routed_experts {cfg['n_routed_experts']}")
    pcfg = dataclasses.replace(get_config(cfg["arch"]),
                               experts_held=(e0, e1))
    bad = {f: (v, getattr(pcfg, f)) for f, v in fields(cfg).items()
           if getattr(pcfg, f) != v}
    if bad:
        raise ValueError(f"program config of {cfg['arch']} differs from "
                         f"{cfg['name']}: {bad}")
    return pcfg
