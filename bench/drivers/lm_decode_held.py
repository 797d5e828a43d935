"""Driver of serving cells of a model whose chip holds a share of the
routed experts (``bench/lmcheck_mla_moe.py``): ``lm_decode``'s closed loop
of ``Engine.generate`` calls, with the weights of that layout
(``bench/lmweights_mla_moe.py``).

Each call runs under a telemetry collector of its own; the driver hands
the harness the calls' ``serve.prefill`` and ``serve.decode`` spans, and
sums the engine's per-call counters of the held expert layer.  Besides
``served_gap`` it compares ``moe_dropped``, token-expert pairs routed to
a held expert and not computed over every call of the window, and
``routed_gap``: the held experts move each layer's output by a few
percent of the residual, too little for the served tokens to show a
wrong expert, so the program's held layer (``moe.moe_ffn``, the
function the timed path runs, on the cell's weights) is held against the
reference's routed part on sampled tokens of every MoE layer.
"""
from __future__ import annotations

import os

import numpy as np

from harness import load_module
from limits import limits_of
from lmcheck_mla_moe import program_config
from lmweights import key_of
from lmweights_mla_moe import make_params

Decode = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "lm_decode.py")).Driver


class Driver(Decode):

    def __init__(self, cfg: dict, traffic: dict, seed: int, ref, cell: str):
        super().__init__(cfg, traffic, seed, ref, cell)
        self.recs = []              # one collector per call

    def setup(self) -> None:
        from repro.serve.engine import Engine, ServeConfig

        pcfg = program_config(self.cfg)
        self.engine = Engine(pcfg, make_params(self.cfg, self.seed),
                             ServeConfig(max_len=self.prompt + self.gen))
        self.unit()                 # warm-up: compiles, or loads the cache
        self.served.clear()
        self.recs.clear()
        self.calls = 0

    def unit(self) -> int:
        from repro import telemetry

        rec = telemetry.Collector("bench")
        with telemetry.using(rec):
            tokens = super().unit()
        self.recs.append(rec)
        return tokens

    def spans(self):
        """Program spans as ``(name, start, end)`` on ``time.perf_counter``."""
        return [(s.name, r.epoch + s.t0_us / 1e6,
                 r.epoch + (s.t0_us + s.dur_us) / 1e6)
                for r in self.recs for s in r.all_spans()]

    def counter(self, name: str):
        """A held-layer counter summed over the window's calls, or None
        where the program counted none."""
        key = "serve." + name
        vals = [r.metrics.counters[key] for r in self.recs
                if key in r.metrics.counters]
        return int(sum(vals)) if vals else None

    def notes(self):
        return super().notes() + [
            f"moe: held_pairs={self.counter('moe_held_pairs')} "
            f"dropped_pairs={self.counter('moe_dropped_pairs')}"]

    def gap(self, control: bool = False) -> float:
        seqs, targets = self.sample()
        params = make_params(self.cfg, self.seed)
        gaps = self.ref.logit_gaps(self.cfg, params, seqs,
                                   np.maximum(targets, 0),
                                   self.traffic["reference_rows"], control)
        return float(np.max(gaps[targets >= 0]))

    def routed_gap(self, control: bool = False) -> float:
        """Widest relative gap, over the MoE layers, of the held experts'
        routed output: ``||R - R_ref|| / ||R_ref||`` over sampled tokens,
        ``R`` the program's held layer without its shared experts (with
        ``control`` the fp8 reference in its place), ``R_ref`` the
        reference's.  The tokens, normal inputs drawn from the seed, are
        more than the program's layer routes at a time
        (``moe.HELD_CHUNK``), so two chunks and their padding are held to
        the reference too."""
        import jax
        import jax.numpy as jnp

        from repro.models import moe

        pcfg = program_config(self.cfg)
        ffn = make_params(self.cfg, self.seed)["decoder"]["layer_0"]["ffn"]
        ffn = {k: v for k, v in ffn.items() if k != "shared"}
        layer = jax.jit(lambda p, x: moe.moe_ffn(p, pcfg, x)[0])
        draw = jax.jit(lambda key: jax.random.normal(
            key, (1, moe.HELD_CHUNK + 512, pcfg.d_model)).astype(pcfg.dtype))
        key = jax.random.fold_in(key_of(self.seed), 1 << 30)   # not a weight's
        gaps = []
        for i in range(jax.tree.leaves(ffn)[0].shape[0]):
            f = jax.tree.map(lambda a: a[i], ffn)
            x = draw(jax.random.fold_in(key, i))
            want = self.ref.routed_output(self.cfg, f, x)
            got = (self.ref.routed_output(self.cfg, f, x, control=True)
                   if control else layer(f, x).astype(jnp.float32))
            gaps.append(float(jnp.linalg.norm(got - want)
                              / jnp.linalg.norm(want)))
        return max(gaps)

    def _checks(self, control: bool) -> dict:
        lim = limits_of(self.cell)
        return {"served_gap": {"value": self.gap(control),
                               "limit": lim["served_gap"]},
                "routed_gap": {"value": self.routed_gap(control),
                               "limit": lim["routed_gap"]},
                "moe_dropped": {"value": self.counter("moe_dropped_pairs"),
                                "limit": lim["moe_dropped"]}}

    def check(self) -> dict:
        return self._checks(control=False)

    def control(self) -> dict:
        return self._checks(control=True)
