"""Serving engine: batched prefill + lockstep decode with typed caches.

Cache kinds per architecture family (DESIGN.md §4): full KV, sliding-window
ring (SWA), MLA latent, Mamba conv+SSM state, xLSTM matrix/scalar state —
all built by ``models.transformer.init_cache`` / prefill and stepped by the
same ``apply_lm``.  The engine decodes all sequences in lockstep (equal
lengths), the standard batched-serving regime the decode shape cells model.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.configs.base import ModelConfig
from repro.models import transformer as T


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_len: int = 512
    temperature: float = 0.0          # 0 → greedy
    eos_id: int = -1                  # -1 → never stop early


def make_prefill_step(cfg: ModelConfig, cache_len: int):
    def prefill(params, tokens, frontend_embeds=None):
        logits, cache, aux = T.apply_lm(
            params, cfg, tokens, mode="prefill",
            frontend_embeds=frontend_embeds, cache_len=cache_len,
            last_logit_only=True)
        return logits[:, -1], cache, aux.get("counters", {})

    return prefill


def sample(logits: jnp.ndarray, rng, temperature: float = 0.0) -> jnp.ndarray:
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    return jax.random.categorical(
        rng, logits / temperature, axis=-1).astype(jnp.int32)[:, None]


class Engine:
    """Simple batched generation driver over jitted prefill/decode steps.

    The decode loop reads each step's token one step behind the device:
    step i+1 is queued before the host waits for step i's token, so the
    device starts a step while the host reads the last one.  The key split
    and the position advance on the device, inside the step.

    Under an active telemetry collector a call records a ``serve.prefill``
    span (the call to its first token on the host) and a ``serve.decode``
    span (the decode loop, to its last token on the host), and the integer
    counters ``apply_lm`` returns (``aux["counters"]``), summed on the
    device over the call's steps and read once at its end.  Without a
    collector both spans are the shared no-op and nothing is read.
    """

    def __init__(self, cfg: ModelConfig, params, serve_cfg: ServeConfig):
        import dataclasses
        self.cfg = dataclasses.replace(cfg, remat=False)  # no grads at serve
        self.params = params
        self.scfg = serve_cfg
        self._prefill = jax.jit(make_prefill_step(self.cfg,
                                                  serve_cfg.max_len))
        self._decode = jax.jit(self._decode_fn)

    def _decode_fn(self, params, cache, token, pos, rng, counts):
        rng, sub = jax.random.split(rng)
        logits, new_cache, aux = T.apply_lm(
            params, self.cfg, token, mode="decode", cache=cache,
            positions=pos.reshape(1,))
        nxt = sample(logits[:, -1], sub, self.scfg.temperature)
        counts = jax.tree.map(jnp.add, counts, aux.get("counters", {}))
        return nxt, new_cache, pos + 1, rng, counts

    def _read(self, out, token) -> bool:
        """Append ``token`` read to the host to ``out``; True once every
        row has ended."""
        out.append(np.asarray(token))
        return self.scfg.eos_id >= 0 and bool(
            np.all(out[-1] == self.scfg.eos_id))

    def generate(self, prompts: jnp.ndarray, n_tokens: int,
                 frontend_embeds=None, rng=None) -> np.ndarray:
        """prompts (B, S) int32 → generated (B, n_tokens) int32."""
        rec = telemetry.current()
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        b, s = prompts.shape
        prefix = (self.cfg.frontend_seq
                  if self.cfg.frontend == "vision" else 0)
        with telemetry.span("serve.prefill", batch=b, prompt=s):
            last_logits, cache, counts = self._prefill(
                self.params, prompts, frontend_embeds)
            token = sample(last_logits, rng, self.scfg.temperature)
            out = [np.asarray(token)]
        with telemetry.span("serve.decode", batch=b):
            pos = jnp.asarray(s + prefix, jnp.int32)
            queued = collections.deque()    # tokens not yet read
            for _ in range(n_tokens - 1):
                token, cache, pos, rng, counts = self._decode(
                    self.params, cache, token, pos, rng, counts)
                queued.append(token)
                if len(queued) > 1 and self._read(out, queued.popleft()):
                    queued.clear()
                    break
            while queued and not self._read(out, queued.popleft()):
                pass
        if rec is not None:
            for k, v in counts.items():
                rec.metrics.count("serve." + k, int(v))
        return np.concatenate(out, axis=1)
