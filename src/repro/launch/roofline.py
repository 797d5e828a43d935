"""Roofline analysis from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), in seconds:

    compute    = HLO_FLOPs / (chips × peak bf16 FLOP/s)
    memory     = HLO_bytes / (chips × peak HBM bytes/s)
    collective = Σ per-op collective cost, ICI-hop-weighted, / link bytes/s

The peaks are those of :data:`DRYRUN_KIND` in ``core/device.py``: the
dry-run meshes (``launch/mesh.py``) describe v5e pods.  Code that measures
on a real device looks up that device's kind there itself.

cost_analysis() supplies FLOPs/bytes; collective bytes are parsed from the
compiled HLO text (all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute operand sizes).  Per-chip collective cost applies the
standard ring factors: all-gather/reduce-scatter move (n-1)/n of the shard
bytes per link, all-reduce 2(n-1)/n, all-to-all (n-1)/n of the local bytes.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

from repro.core.device import ChipPeaks, peaks


#: The chip the dry-run production meshes describe (256-chip v5e pods).
DRYRUN_KIND = "TPU v5 lite"

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

_COLL_RE = re.compile(
    r"(\w[\w.\-]*)\s*=\s*(?:\([^)]*\)|(\w+)\[[^\]]*\]|[\w\[\],\s]*?)?\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of an HLO shape string like 'bf16[16,128]{1,0}'."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveStats:
    counts: Dict[str, int]
    bytes_by_kind: Dict[str, int]
    cost_s: float          # per-chip link-seconds (ring model)

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())


def parse_collectives(hlo_text: str, replica_groups_size: Optional[int] = None
                      ) -> CollectiveStats:
    """Sum output-shape bytes of every collective op in the HLO; the ring
    cost is priced at the dry-run chip's link bandwidth."""
    ici_bw = peaks(DRYRUN_KIND).ici_bw
    counts: Dict[str, int] = {}
    bytes_by: Dict[str, int] = {}
    cost = 0.0
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(
            r".*?=\s*(.+?)\s+(all-gather|all-reduce|reduce-scatter"
            r"|all-to-all|collective-permute)(?:-start)?\(", line)
        if not m or line.startswith("//"):
            continue
        shape_str, kind = m.group(1), m.group(2)
        b = _shape_bytes(shape_str)
        if b == 0:
            continue
        counts[kind] = counts.get(kind, 0) + 1
        bytes_by[kind] = bytes_by.get(kind, 0) + b
        # group size from replica_groups
        g = replica_groups_size
        gm = re.search(r"replica_groups=\{\{([\d,]+)\}", line)
        if gm:
            g = len(gm.group(1).split(","))
        gm2 = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
        if gm2:
            g = int(gm2.group(2))
        if g is None or g <= 1:
            g = 2
        frac = (g - 1) / g
        if kind == "all-gather":
            # output is the gathered buffer; each link moves (g-1)/g of it
            cost += b * frac / ici_bw
        elif kind == "reduce-scatter":
            # b is the scattered output shard; ring moves (g-1)·b per chip
            cost += b * (g - 1) / ici_bw
        elif kind == "all-reduce":
            cost += 2 * b * frac / ici_bw
        elif kind == "all-to-all":
            cost += b * frac / ici_bw
        elif kind == "collective-permute":
            cost += b / ici_bw
    return CollectiveStats(counts, bytes_by, cost)


@dataclasses.dataclass
class Roofline:
    flops: float          # per-device (cost_analysis of the SPMD module)
    hbm_bytes: float      # per-device
    collectives: CollectiveStats
    n_chips: int
    model_flops: float = 0.0   # global analytic 6·N·D / 2·N·tok

    @property
    def chip(self) -> ChipPeaks:
        return peaks(DRYRUN_KIND)

    @property
    def compute_s(self) -> float:
        return self.flops / self.chip.bf16_flops

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / self.chip.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.collectives.cost_s

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_s(self) -> float:
        """Roofline step time = max of the three terms (perfect overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_frac(self) -> float:
        if self.model_flops and self.flops:
            return self.model_flops / (self.flops * self.n_chips)
        return float("nan")

    @property
    def mfu(self) -> float:
        """Model-FLOPs utilization at the roofline step time."""
        if not self.model_flops or not self.step_s:
            return float("nan")
        return self.model_flops / (self.step_s * self.n_chips
                                   * self.chip.bf16_flops)

    def summary(self) -> Dict:
        return {
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "hlo_flops": self.flops,
            "hlo_bytes": self.hbm_bytes,
            "collective_bytes": self.collectives.total_bytes,
            "collective_counts": self.collectives.counts,
            "model_flops": self.model_flops,
            "useful_flops_frac": self.useful_flops_frac,
            "roofline_step_s": self.step_s,
            "mfu_at_roofline": self.mfu,
        }


def model_flops_for(cfg, cell) -> float:
    """MODEL_FLOPS: 6·N·D for training, 2·N_active per generated/processed
    token for inference (standard convention)."""
    n_active = cfg.active_param_count()
    if cell.kind == "train":
        tokens = cell.global_batch * cell.seq_len
        return 6.0 * n_active * tokens
    if cell.kind == "prefill":
        tokens = cell.global_batch * cell.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * cell.global_batch


def tpu_adjusted_terms(cfg, cell, n_chips: int, measured: "Roofline",
                       model_axis: int = 16) -> Dict[str, float]:
    """TPU-target estimates for the memory/collective terms.

    The measured terms come from XLA:CPU, which (a) promotes bf16 dots to
    f32 (collectives carry 2× the bytes) and (b) barely fuses elementwise
    chains (per-opcode attribution shows `convert`+`add`+`multiply`
    dominate measured bytes).  On the TPU target:

      * collective ≈ measured / 2 (bf16 payloads);
      * memory = analytic first-principles traffic — parameters (bf16 read
        for fwd + remat + bwd, f32 grad/optimizer streams), activations
        (~22 bf16 tensor passes per layer per token, ×3 for fwd/remat/bwd),
        flash-kernel attention (Q+O once, K+V streamed once per 128-row
        query block — the Pallas kernel's exact HBM pattern), logits, and
        for decode the KV-cache read+write.

    Compute is trusted as measured (dot FLOPs count exactly).
    """
    dp = max(n_chips // model_axis, 1)
    p_dev = cfg.param_count() / n_chips
    d, l = cfg.d_model, cfg.n_layers
    if cell.kind == "train":
        tok_dev = cell.global_batch * cell.seq_len / dp
        param_traffic = p_dev * (3 * 2 + 2 * 4 + 16 + 8)
        act = l * tok_dev * d * 2 * 22 * 3 / model_axis  # TP-sharded hidden
        passes = 3
    elif cell.kind == "prefill":
        tok_dev = cell.global_batch * cell.seq_len / dp
        param_traffic = p_dev * 2
        act = l * tok_dev * d * 2 * 22 / model_axis
        passes = 1
    else:  # decode
        tok_dev = cell.global_batch / max(dp, 1)
        param_traffic = p_dev * 2
        # KV cache read + write per token
        kv = 2 * cfg.n_kv_heads * cfg.head_dim * \
            cfg.decode_cache_len(cell.seq_len)
        act = tok_dev * (l * kv * 2 * 2 / model_axis
                         + l * d * 2 * 22 / model_axis)
        passes = 1

    # flash attention: K+V streamed once per 128-row query block
    attn = 0.0
    n_attn = sum(1 for k in cfg.block_pattern if k == "attn") * cfg.n_groups
    if n_attn and cell.kind != "decode":
        s_loc = cell.seq_len
        b_loc = cell.global_batch / dp
        kv_bytes = 2 * cfg.n_kv_heads * cfg.head_dim * min(
            cell.seq_len, cfg.window or cell.seq_len) * 2
        n_qblk = -(-s_loc // 128)
        attn = n_attn * b_loc * (n_qblk * kv_bytes / model_axis
                                 + 2 * s_loc * cfg.n_heads * cfg.head_dim
                                 * 2 / model_axis) * passes
    logits_tok = 1 if cell.kind != "train" else \
        cell.global_batch * cell.seq_len / dp
    logits = logits_tok * cfg.vocab_size / model_axis * 4 * (3 if
             cell.kind == "train" else 1)

    mem_bytes = param_traffic + act + attn + logits
    chip = measured.chip
    hbm_bw, peak_flops = chip.hbm_bw, chip.bf16_flops
    return {
        "memory_s_tpu": mem_bytes / hbm_bw,
        "collective_s_tpu": measured.collective_s / 2,
        "step_s_tpu": max(measured.compute_s, mem_bytes / hbm_bw,
                          measured.collective_s / 2),
        "mfu_tpu": (measured.model_flops
                    / (max(measured.compute_s, mem_bytes / hbm_bw,
                           measured.collective_s / 2)
                       * n_chips * peak_flops)
                    if measured.model_flops else float("nan")),
    }


def analyze(compiled, n_chips: int, cfg=None, cell=None,
            hlo_text: Optional[str] = None) -> Roofline:
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    colls = parse_collectives(text)
    mf = model_flops_for(cfg, cell) if cfg is not None else 0.0
    return Roofline(flops=flops, hbm_bytes=byts, collectives=colls,
                    n_chips=n_chips, model_flops=mf)
