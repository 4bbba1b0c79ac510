"""Roofline analysis from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), all in seconds:

  compute    = HLO_FLOPs / (chips × 197e12)         [bf16 MXU peak]
  memory     = HLO_bytes / (chips × 819e9)          [HBM]
  collective = Σ collective-operand-bytes / (chips × 50e9)   [ICI]

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()``.
collective_bytes is parsed from ``compiled.as_text()``: we sum the
*output* shape bytes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute instruction (output size ≈ wire bytes per
participating device for AG/AR; a standard approximation). The dominant
term is the bottleneck the perf loop attacks.
"""
from __future__ import annotations

import dataclasses
import json
import re

import numpy as np

from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# matches e.g.:  %ag = bf16[2,1024,128]{2,1,0} all-gather(...)
_INSTR_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|(\w+)\[([\d,]*)\][^ ]*)\s+([\w-]+)")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    nb = _DTYPE_BYTES.get(dtype)
    if nb is None:
        return 0
    n = 1
    if dims.strip():
        for d in dims.split(","):
            n *= int(d)
    return n * nb


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum output bytes per collective kind over the whole module."""
    out = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = _INSTR_RE.search(stripped)
        if not m:
            continue
        op = m.group(4)
        # ops like all-gather-start / all-reduce-done
        base = None
        for k in _COLLECTIVES:
            if op == k or op.startswith(k + "-"):
                base = k
                break
        if base is None:
            continue
        if op.endswith("-done"):
            continue                      # counted at -start
        if m.group(1) is not None:        # tuple shape
            total = sum(_shape_bytes(t, d)
                        for t, d in _SHAPE_RE.findall(m.group(1)))
        else:
            total = _shape_bytes(m.group(2), m.group(3))
        out[base] += total
    return out


_HEAVY_OPS = (" dot(", " convolution(", " gather(", " scatter(",
              " reduce(", " reduce-window(", " sort(", " custom-call(",
              " all-gather(", " all-reduce(", " all-to-all(",
              " reduce-scatter(", " dynamic-slice(",
              " dynamic-update-slice(")


def fused_bytes(hlo_text: str) -> int:
    """TPU-fusion-adjusted HBM traffic estimate.

    The CPU backend leaves elementwise chains unfused, so raw
    ``bytes accessed`` over-counts HBM traffic by ~10-50x vs a TPU
    compile. On TPU, elementwise ops fuse into the adjacent heavy op, so
    traffic ≈ Σ (operand + output bytes) of heavy ops (dots, reductions,
    gathers/scatters, collectives). We parse every heavy instruction's
    inline shapes (output first, then operands) and sum.
    """
    total = 0
    for line in hlo_text.splitlines():
        if not any(op in line for op in _HEAVY_OPS):
            continue
        shapes = _SHAPE_RE.findall(line)
        total += sum(_shape_bytes(t, d) for t, d in shapes)
    return total


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops: float                  # per-device HLO flops (SPMD module)
    hbm_bytes: float              # per-device bytes accessed
    coll_bytes: dict
    per_device_mem: int           # from memory_analysis
    model_flops: float = 0.0      # 6*N*D (or family analogue)
    hbm_bytes_fused: float = 0.0  # fusion-adjusted traffic estimate

    @property
    def t_compute(self) -> float:
        # cost_analysis() reports the per-device partitioned module
        return self.flops / PEAK_FLOPS_BF16

    @property
    def t_memory(self) -> float:
        """Fusion-adjusted memory term (headline; raw term kept alongside —
        see fused_bytes docstring for why raw CPU numbers overcount)."""
        b = self.hbm_bytes_fused or self.hbm_bytes
        return b / HBM_BW

    @property
    def t_memory_raw(self) -> float:
        return self.hbm_bytes / HBM_BW

    @property
    def t_collective(self) -> float:
        # per-device wire bytes: HLO shapes are already per-partition under
        # SPMD, so bytes / ICI_BW is per-chip link time
        return sum(self.coll_bytes.values()) / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def roofline_fraction(self) -> float:
        """useful-compute-time / dominant-term time (1.0 = at roofline)."""
        t_useful = self.model_flops / (self.chips * PEAK_FLOPS_BF16)
        t_dom = max(self.t_compute, self.t_memory, self.t_collective)
        return t_useful / t_dom if t_dom > 0 else 0.0

    @property
    def flops_efficiency(self) -> float:
        """MODEL_FLOPS / (HLO_FLOPs x chips) — useful fraction of compute."""
        tot = self.flops * self.chips
        return self.model_flops / tot if tot else 0.0

    def to_dict(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips, "flops": self.flops,
            "hbm_bytes": self.hbm_bytes, "coll_bytes": self.coll_bytes,
            "per_device_mem": self.per_device_mem,
            "model_flops": self.model_flops,
            "hbm_bytes_fused": self.hbm_bytes_fused,
            "t_compute": self.t_compute, "t_memory": self.t_memory,
            "t_memory_raw": self.t_memory_raw,
            "t_collective": self.t_collective,
            "bottleneck": self.bottleneck,
            "roofline_fraction": self.roofline_fraction,
            "flops_efficiency": self.flops_efficiency,
        }


def model_flops_for(arch_id: str, shape_name: str) -> float:
    """Useful FLOPs per step: 6·N·D for LM training (N = active params),
    2·N·D for inference; family analogues elsewhere."""
    from repro.configs.base import get_config
    arch = get_config(arch_id)
    shape = arch.shape(shape_name)
    if arch.family == "lm":
        n_active = arch.model.n_active_params()
        if shape.kind == "train":
            tokens = shape["global_batch"] * shape["seq_len"]
            return 6.0 * n_active * tokens
        if shape.kind == "prefill":
            tokens = shape["global_batch"] * shape["seq_len"]
            return 2.0 * n_active * tokens
        tokens = shape["global_batch"]            # one token per stream
        return 2.0 * n_active * tokens
    if arch.family == "encoder":
        n = arch.model.n_params()
        tokens = shape["global_batch"] * min(shape["seq_len"],
                                             arch.model.max_len)
        mult = {"train": 6.0, "serve": 2.0}[shape.kind]
        if shape.name.startswith("dpo"):
            mult = 6.0 * 2 + 2.0 * 2          # 2 policy fwd+bwd, 2 ref fwd
        return mult * n * tokens
    if arch.family == "gnn":
        from repro.launch.specs import _gnn_dims
        cfg = arch.model
        n, e = _gnn_dims(shape)
        n_trunc = cfg.n_coeff
        c = cfg.d_hidden
        so2 = sum(2 * ((cfg.l_max - m + 1) * 2 * c) * ((cfg.l_max - m + 1) * c)
                  * (1 if m == 0 else 2) for m in range(cfg.m_max + 1))
        wig = sum((2 * l + 1) ** 2 * 2 for l in range(cfg.l_max + 1))
        per_edge = so2 + 2 * wig * 2 * c          # conv + rotate in/out
        per_node = 2 * (cfg.l_max + 1) ** 2 * c * c * 2 * 2  # FFN
        fwd = cfg.n_layers * (e * per_edge + n * per_node)
        return 3.0 * fwd                           # fwd + bwd
    if arch.family == "recsys":
        cfg = arch.model
        if shape.name == "retrieval_cand":
            return 2.0 * shape["n_candidates"] * cfg.embed_dim
        b = shape["batch"]
        dims_chain = []
        if cfg.kind == "dlrm":
            f = cfg.n_sparse + 1
            d_int = f * (f - 1) // 2 + cfg.bot_mlp[-1]
            dims_chain = [(cfg.n_dense,) + cfg.bot_mlp,
                          (d_int,) + cfg.top_mlp]
            inter = f * f * cfg.embed_dim
        elif cfg.kind == "deepfm":
            dims_chain = [(cfg.n_sparse * cfg.embed_dim,) + cfg.mlp + (1,)]
            inter = cfg.n_sparse * cfg.embed_dim * 2
        elif cfg.kind == "autoint":
            inter = cfg.n_attn_layers * (
                3 * cfg.n_sparse * cfg.embed_dim * cfg.d_attn
                + 2 * cfg.n_sparse ** 2 * cfg.d_attn)
            dims_chain = [(cfg.n_sparse * cfg.d_attn, 1)]
        else:  # dien
            inter = cfg.seq_len * 6 * (2 * cfg.embed_dim + cfg.gru_dim) \
                * cfg.gru_dim * 2
            dims_chain = [(cfg.gru_dim + 2 * cfg.embed_dim,) + cfg.mlp + (1,)]
        mlp_fl = sum(2 * a * bb for chain in dims_chain
                     for a, bb in zip(chain[:-1], chain[1:]))
        lookup = cfg.n_sparse * cfg.embed_dim
        mult = 3.0 if shape.kind == "train" else 1.0
        return mult * b * 2 * (mlp_fl / 2 + inter + lookup)
    return 0.0


def summarize(records: list[dict]) -> str:
    """Markdown table for EXPERIMENTS.md §Roofline."""
    hdr = ("| arch | shape | mesh | chips | t_comp (ms) | t_mem (ms) | "
           "t_coll (ms) | bottleneck | HLO GFLOPs | model/HLO | roofline frac |")
    sep = "|" + "---|" * 11
    rows = [hdr, sep]
    for r in records:
        rows.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} | {r['chips']} | "
            f"{r['t_compute']*1e3:.2f} | {r['t_memory']*1e3:.2f} | "
            f"{r['t_collective']*1e3:.2f} | {r['bottleneck']} | "
            f"{r['flops']/1e9:.0f} | {r['flops_efficiency']*100:.0f}% | "
            f"{r['roofline_fraction']*100:.1f}% |")
    return "\n".join(rows)
