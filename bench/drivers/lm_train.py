"""Driver of training cells: the program's jitted train step, steps back
to back, fed by the program's table pipeline.

Set-up makes the weights from the seed (``bench/lmweights.py``), builds
the train state and the step as ``repro.launch.train`` does
(``make_train_step`` under ``jax.jit`` with the state donated), and the
batch iterator of ``make_training_data`` over a corpus made from the seed
(select → join → orderby → ``to_numpy`` through the table engine).  It
then drives that same step through its first ``checked_steps`` steps on
batches from that same iterator, which compiles (or loads) the step, and
keeps what the reference compares: each step's loss, the per-leaf norms
of the first gradient as the optimizer holds it (AdamW's first moment
after one step is ``(1 - b1)`` times the clipped gradient), and the
per-leaf norms of the parameters' change over those steps.  The window
continues the same state with the same step and feed; a unit is one
step, its work ``batch * seq`` tokens.

After the window the program's state is freed and the plain float32
reference trains the same weights on the same batches.
"""
from __future__ import annotations

import math
import os
import tempfile

import numpy as np

from limits import limits_of
from lmcheck import leaf_norms, program_config, worst_leaf_gap
from lmweights import make_params

#: a leaf whose reference gradient norm is under this share of the median
#: leaf's moves under AdamW by round-off alone: its change is not compared
STILL_LEAF = 1e-3


def corpus_arrays(corpus: dict, vocab: int, seed: int) -> dict:
    """``{"docs": cols, "tokens": cols}`` of the training corpus: the
    program's synthetic corpus (quality uniform, tokens with a mild
    structure a model can learn), with document lengths drawn once,
    whatever the seed, and dealt out in an order drawn from the seed."""
    n, mean = corpus["n_docs"], corpus["mean_doc_len"]
    lens = np.clip(np.random.default_rng(0).poisson(mean, n), 8, None)
    rng = np.random.default_rng(seed)
    lens = rng.permutation(lens)
    quality = rng.uniform(size=n).astype(np.float32)
    doc_ids = np.repeat(np.arange(n), lens).astype(np.int32)
    positions = np.concatenate([np.arange(k) for k in lens]).astype(np.int32)
    toks = ((doc_ids * 31 + positions * 7) % (vocab - 2) + 1).astype(np.int32)
    return {"docs": {"doc_id": np.arange(n, dtype=np.int32),
                     "quality": quality, "n_tokens": lens.astype(np.int32)},
            "tokens": {"doc_id": doc_ids, "position": positions,
                       "token": toks}}


class Driver:
    unit_name = "train_step"

    def __init__(self, cfg: dict, traffic: dict, seed: int, ref, cell: str):
        self.cfg, self.traffic, self.seed, self.ref = cfg, traffic, seed, ref
        self.cell = cell
        self.batch, self.seq = traffic["batch"], traffic["seq"]

    def setup(self) -> None:
        import jax

        from repro.core import HPTMTContext
        from repro.data.pipeline import CorpusConfig, make_training_data
        from repro.io import write_dataset
        from repro.train.optimizer import OptimizerConfig, init_opt_state
        from repro.train.train_step import (TrainConfig, TrainState,
                                            make_train_step)

        pcfg = program_config(self.cfg)
        opt = self.traffic["optimizer"]
        tcfg = TrainConfig(optimizer=OptimizerConfig(**opt))
        params = make_params(self.cfg, self.seed)
        self.state = TrainState(params, init_opt_state(params))
        c = self.traffic["corpus"]
        with tempfile.TemporaryDirectory(prefix="bench-corpus-") as root:
            for name, cols in corpus_arrays(c, self.cfg["vocab_size"],
                                            self.seed).items():
                n = len(next(iter(cols.values())))
                write_dataset(os.path.join(root, name), [(cols, n)],
                              format="hpt")
            self.data = make_training_data(
                pcfg, HPTMTContext(), batch=self.batch, seq_len=self.seq,
                ccfg=CorpusConfig(n_docs=c["n_docs"],
                                  mean_doc_len=c["mean_doc_len"],
                                  vocab_size=self.cfg["vocab_size"],
                                  quality_threshold=c["quality_threshold"],
                                  seed=self.seed),
                data_root=root)
            first = next(self.data)
        step = jax.jit(make_train_step(pcfg, tcfg), donate_argnums=(0,))
        self.step = step.lower(self.state, first).compile()
        self.memory = self.step.memory_analysis()
        self.batches, self.losses = [], []
        b1 = opt["b1"]
        for i in range(self.traffic["checked_steps"]):
            batch = first if i == 0 else next(self.data)
            self.batches.append((np.asarray(batch["tokens"]),
                                 np.asarray(batch["labels"])))
            self.losses.append(self._step(batch))
            if i == 0:
                self.grad_norms = {k: v / (1 - b1) for k, v in
                                   leaf_norms(self.state.opt.mu).items()}
        p0 = make_params(self.cfg, self.seed)
        self.change_norms = leaf_norms(jax.tree.map(
            lambda a, b: a - b, self.state.params, p0))
        del p0

    def _step(self, batch) -> float:
        import jax

        self.state, metrics = self.step(self.state, batch)
        jax.block_until_ready((self.state, metrics))
        loss = float(metrics["loss"])
        if not math.isfinite(loss):
            raise FloatingPointError(f"loss {loss}")
        return loss

    def unit(self) -> int:
        self._step(next(self.data))
        return self.batch * self.seq

    def spans(self):
        return []

    def notes(self):
        m = self.memory
        need = (None if m is None else m.argument_size_in_bytes
                + m.output_size_in_bytes - m.alias_size_in_bytes
                + m.temp_size_in_bytes)
        return [f"train: compiled step bytes={need} "
                f"(memory_analysis: {m}); first losses={self.losses}"]

    def release(self) -> None:
        self.state = self.step = self.data = None

    def reference(self, precision: str = "float32", rows_kept=None):
        """Losses, first-gradient norms and change norms of the reference
        trained on the same weights and batches."""
        import jax

        batches = self.batches
        if rows_kept is not None:
            batches = [(t[:rows_kept], l[:rows_kept]) for t, l in batches]
        params = make_params(self.cfg, self.seed)
        losses, first, params = self.ref.train(
            self.cfg, self.traffic["optimizer"], precision, params, batches,
            self.traffic["reference_rows"])
        grad = leaf_norms(first)
        del first
        p0 = make_params(self.cfg, self.seed)
        change = leaf_norms(jax.tree.map(lambda a, b: a - b, params, p0))
        return losses, grad, change

    def compare(self, got, want) -> dict:
        """Compared numbers of ``got`` against ``want``, each a
        ``(losses, grad norms, change norms)``."""
        lim = limits_of(self.cell)
        (gl, gg, gc), (wl, wg, wc) = got, want
        med = float(np.median(list(wg.values())))
        moving = {n for n, v in wg.items() if v >= STILL_LEAF * med}
        out = {"loss_gap": max(abs(a - b) for a, b in zip(gl, wl)),
               "grad_gap": worst_leaf_gap(gg, wg),
               "change_gap": worst_leaf_gap(gc, wc, moving)}
        print(f"train readings (compared where a limit is set): "
              f"{out}", flush=True)
        return {k: {"value": v, "limit": lim[k]} for k, v in out.items()
                if k in lim}

    def check(self) -> dict:
        want = self.reference()
        self.want = want
        return self.compare((self.losses, self.grad_norms,
                             self.change_norms), want)

    def control(self) -> dict:
        """Compared numbers of the fp8 reference in the program's place."""
        return self.compare(self.reference("fp8"), self.want)

    def half_batch(self) -> dict:
        """Compared numbers of the reference that leaves half of every
        batch out and takes the mean over the rest."""
        return self.compare(self.reference(rows_kept=self.batch // 2),
                            self.want)
