"""Driver of serving cells: ``Engine.generate`` in a closed loop.

Set-up makes the weights from the seed (``bench/lmweights.py``), builds
the program's ``Engine`` for prompts of ``prompt`` tokens and ``generate``
greedy tokens, and runs one call, which compiles (or loads) prefill and
decode.  A unit is one ``generate`` call on a fresh batch of ``batch``
prompts drawn from the seed; its work is the tokens it generated.

After the window the engine is freed, a sample of the finished requests
drawn from the seed, the same number from every call of the window, is
fed to the plain reference (prompt and served
tokens in one forward pass), and the number compared is the widest gap
by which a served token's logit lies below the reference's best logit at
its position.
"""
from __future__ import annotations

import numpy as np

from limits import limits_of
from lmcheck import program_config
from lmweights import make_params


class Driver:
    unit_name = "generate"

    def __init__(self, cfg: dict, traffic: dict, seed: int, ref, cell: str):
        self.cfg, self.traffic, self.seed, self.ref = cfg, traffic, seed, ref
        self.cell = cell
        self.batch, self.prompt = traffic["batch"], traffic["prompt"]
        self.gen = traffic["generate"]
        self.calls = 0
        self.served = []            # generated tokens per call

    def prompts(self, call: int) -> np.ndarray:
        """Prompts of call ``call``: tokens uniform over the vocabulary
        but the first id."""
        rng = np.random.default_rng([self.seed, call])
        return rng.integers(1, self.cfg["vocab_size"],
                            (self.batch, self.prompt)).astype(np.int32)

    def setup(self) -> None:
        from repro.serve.engine import Engine, ServeConfig

        pcfg = program_config(self.cfg)
        self.engine = Engine(pcfg, make_params(self.cfg, self.seed),
                             ServeConfig(max_len=self.prompt + self.gen))
        self.unit()                 # warm-up: compiles, or loads the cache
        self.served.clear()
        self.calls = 0

    def unit(self) -> int:
        import jax.numpy as jnp

        prompts = jnp.asarray(self.prompts(self.calls))
        out = self.engine.generate(prompts, n_tokens=self.gen)
        self.served.append(np.asarray(out))
        self.calls += 1
        return int(out.size)

    def spans(self):
        return []

    def notes(self):
        return [f"decode: calls={self.calls} batch={self.batch} "
                f"prompt={self.prompt} generate={self.gen}"]

    def release(self) -> None:
        self.engine = None

    def sample(self):
        """``(tokens, targets)`` of the checked requests: each prompt with
        its served tokens, and at every position the token served next
        (``-1`` where the position is prompt)."""
        rng = np.random.default_rng(self.seed)
        per_call = min(self.traffic["checked_per_call"], self.batch)
        seqs = []
        for call, out in enumerate(self.served):
            for row in sorted(rng.choice(self.batch, per_call,
                                         replace=False)):
                seqs.append(self._request(call, int(row), out[row]))
        return (np.stack([s for s, _ in seqs]),
                np.stack([t for _, t in seqs]))

    def _request(self, call: int, row: int, served: np.ndarray):
        """``(tokens, targets)`` of one request, shifted by one."""
        seq = np.concatenate([self.prompts(call)[row], served])
        tgt = np.full(seq.shape, -1, np.int32)
        tgt[self.prompt - 1:self.prompt - 1 + len(served)] = served
        return seq[:-1], tgt[:-1]

    def gap(self, control: bool = False) -> float:
        seqs, targets = self.sample()
        params = make_params(self.cfg, self.seed)
        gaps = self.ref.logit_gaps(self.cfg, params, seqs,
                                   np.maximum(targets, 0),
                                   self.traffic["reference_rows"], control)
        return float(np.max(gaps[targets >= 0]))

    def check(self) -> dict:
        lim = limits_of(self.cell)
        return {"served_gap": {"value": self.gap(), "limit": lim["served_gap"]}}

    def control(self) -> dict:
        lim = limits_of(self.cell)
        return {"served_gap": {"value": self.gap(control=True),
                               "limit": lim["served_gap"]}}
