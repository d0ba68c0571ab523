"""Roofline terms from a compiled dry-run artifact.

    compute term    = HLO_FLOPs        / (chips * peak_FLOP/s)
    memory term     = HLO_bytes        / (chips * HBM_bw)
    collective term = collective_bytes / (chips * link_bw)

``cost_analysis`` supplies FLOPs/bytes; collective bytes are parsed from the
optimized (post-SPMD) HLO text: we sum the *output* operand sizes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute.
Hardware constants: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link
ICI (3 links/chip assumed shared; we charge the per-link figure).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

__all__ = ["HW", "RooflineReport", "analyze_compiled", "collective_bytes",
           "parse_hlo_collectives", "model_flops"]


@dataclasses.dataclass(frozen=True)
class HW:
    peak_flops: float = 197e12        # bf16 / chip
    hbm_bw: float = 819e9             # bytes/s / chip
    ici_bw: float = 50e9              # bytes/s / link


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(bf16|f64|f32|f16|f8e4m3|f8e5m2|s64|u64|s32|u32|"
                       r"s16|u16|s8|u8|pred|c64|c128)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(", re.M)


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_hlo_collectives(hlo_text: str) -> dict:
    """Sum output bytes per collective kind from optimized HLO text."""
    out: dict[str, int] = {}
    counts: dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        shape_str, kind, suffix = m.group(1), m.group(2), m.group(3)
        if suffix == "-done":
            continue  # async pair: count the -start only
        b = _shape_bytes(shape_str)
        out[kind] = out.get(kind, 0) + b
        counts[kind] = counts.get(kind, 0) + 1
    out["_counts"] = counts
    return out


def collective_bytes(hlo_text: str) -> int:
    d = parse_hlo_collectives(hlo_text)
    return sum(v for k, v in d.items() if not k.startswith("_"))


def model_flops(cfg, shape, text_tokens: Optional[int] = None) -> float:
    """6·N·D (dense) or 6·N_active·D (MoE); D = tokens processed.

    enc-dec: encoder params see encoder tokens, decoder params decoder
    tokens (cross-attention keys priced with the decoder side).
    """
    mult = {"train": 6.0, "prefill": 2.0, "decode": 2.0}[shape.mode]
    if cfg.arch_type == "encdec":
        d, L = cfg.d_model, cfg.num_layers
        per_enc = 2 * d * cfg.attn_dim + 2 * d * cfg.kv_dim + 3 * d * cfg.d_ff
        per_dec = 2 * (2 * d * cfg.attn_dim + 2 * d * cfg.kv_dim)             + 3 * d * cfg.d_ff
        n_enc = cfg.num_encoder_layers * per_enc
        n_dec = L * per_dec + cfg.vocab_size * d
        se = shape.seq_len // 2
        sd = shape.seq_len - se
        if shape.mode == "decode":
            return mult * n_dec * shape.global_batch
        return mult * shape.global_batch * (n_enc * se + n_dec * sd)
    if shape.mode == "decode":
        tokens = shape.global_batch     # one token per sequence
    else:
        tokens = shape.global_batch * shape.seq_len
    n = cfg.active_param_count()
    return mult * n * tokens


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    coll_bytes: float
    coll_detail: dict
    model_flops_: float
    per_device_hbm: float              # peak memory per device (bytes)

    def terms(self, hw: HW | None = None) -> dict:
        hw = hw or HW()
        t_c = self.hlo_flops / (self.chips * hw.peak_flops)
        t_m = self.hlo_bytes / (self.chips * hw.hbm_bw)
        t_x = self.coll_bytes / (self.chips * hw.ici_bw)
        dom = max((("compute", t_c), ("memory", t_m), ("collective", t_x)),
                  key=lambda kv: kv[1])
        return {
            "compute_s": t_c, "memory_s": t_m, "collective_s": t_x,
            "bottleneck": dom[0], "bound_s": dom[1],
            "useful_flop_frac": (self.model_flops_ / self.hlo_flops
                                 if self.hlo_flops else 0.0),
        }

    def row(self) -> dict:
        t = self.terms()
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "flops_T": round(self.hlo_flops / 1e12, 2),
            "bytes_G": round(self.hlo_bytes / 1e9, 2),
            "coll_G": round(self.coll_bytes / 1e9, 3),
            "compute_ms": round(t["compute_s"] * 1e3, 3),
            "memory_ms": round(t["memory_s"] * 1e3, 3),
            "collective_ms": round(t["collective_s"] * 1e3, 3),
            "bottleneck": t["bottleneck"],
            "useful_frac": round(t["useful_flop_frac"], 3),
            "hbm_per_dev_GB": round(self.per_device_hbm / 2**30, 3),
        }


def analyze_compiled(compiled, lowered_text: Optional[str], arch: str,
                     shape_name: str, mesh_name: str, chips: int,
                     cfg=None, shape=None) -> RooflineReport:
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    text = lowered_text if lowered_text is not None else compiled.as_text()
    coll = parse_hlo_collectives(text)
    cbytes = sum(v for k, v in coll.items() if not k.startswith("_"))
    mem = compiled.memory_analysis()
    per_dev = 0.0
    for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes"):
        per_dev += float(getattr(mem, attr, 0.0) or 0.0)
    # arguments+outputs alias (donation); temp is the honest peak extra
    mf = model_flops(cfg, shape) if cfg is not None and shape is not None \
        else 0.0
    return RooflineReport(arch, shape_name, mesh_name, chips, flops, byts,
                          cbytes, coll, mf, per_dev)
