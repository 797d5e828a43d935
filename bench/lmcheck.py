"""Shared pieces of the language-model cells: the program's configuration
held against the configuration file, and the per-leaf norms that the
training comparison reads."""
from __future__ import annotations

import numpy as np

#: configuration-file key -> the program's ModelConfig field
FIELDS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads",
          "num_hidden_layers": "n_layers", "vocab_size": "vocab_size",
          "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
          "tie_word_embeddings": "tie_embeddings"}


def program_config(cfg: dict):
    """The program's ``ModelConfig`` of ``cfg["arch"]``; raises where a
    size differs from the configuration file."""
    from repro.configs import get_config

    pcfg = get_config(cfg["arch"])
    bad = {k: (cfg[k], getattr(pcfg, f)) for k, f in FIELDS.items()
           if cfg[k] != getattr(pcfg, f)}
    if bad:
        raise ValueError(f"program config of {cfg['arch']} differs from "
                         f"{cfg['name']}: {bad}")
    return pcfg


def leaf_norms(tree) -> dict:
    """``{leaf name: norm}``, each stacked layer its own leaf
    (``decoder/layer_0/mixer/wq#7``), computed on the device."""
    import jax
    import jax.numpy as jnp

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    names, norms = [], []
    for path, x in flat:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        x = x.astype(jnp.float32)
        if name.startswith("decoder/"):
            n = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
            names += [f"{name}#{i}" for i in range(x.shape[0])]
            norms.append(n)
        else:
            names.append(name)
            norms.append(jnp.sqrt(jnp.sum(x * x))[None])
    return dict(zip(names, np.asarray(jnp.concatenate(norms)).tolist()))


def worst_leaf_gap(got: dict, want: dict, keep=None) -> float:
    """Largest ``|got - want|`` over leaves, each as a share of the larger
    of the reference's norm of that leaf and of the median leaf."""
    names = [n for n in want if keep is None or n in keep]
    med = float(np.median([want[n] for n in names]))
    return max(abs(got[n] - want[n]) / max(want[n], med, 1e-30)
               for n in names)
