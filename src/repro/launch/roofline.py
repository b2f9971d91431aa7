"""Roofline-term extraction from a compiled dry-run artifact.

compute term    = HLO_FLOPs(per-device) / peak_FLOP/s
memory term     = HLO_bytes(per-device) / HBM_bw
collective term = Σ_ops factor·local_payload_bytes / link_bw

The post-SPMD optimized HLO module is the *per-device* program, so shapes
printed on collective ops are local payloads.  Ring-algorithm cost factors:
all-reduce 2·(n-1)/n ≈ 2, all-gather/reduce-scatter/all-to-all (n-1)/n ≈ 1,
collective-permute 1.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict

from repro.launch.mesh import PRODUCTION_KIND, chip_peaks

_PEAK = chip_peaks(PRODUCTION_KIND)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
}

_COLL_FACTOR = {
    "all-reduce": 2.0,
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
    "all-reduce-start": 2.0,
    "all-gather-start": 1.0,
    "collective-permute-start": 1.0,
}

# e.g.:  %ag = bf16[16,4096,128]{2,1,0} all-gather(%x), ...
_COLL_RE = re.compile(
    r"=\s*(?:\()?\s*([a-z0-9]+)\[([\d,]*)\][^a-z]*\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
# tuple-result collectives:  (bf16[...], bf16[...]) all-reduce(...)
_TUPLE_RE = re.compile(
    r"=\s*\(([^)]*)\)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-device collective payload bytes (factor-weighted) by op kind."""
    out: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue  # counted at -start
        m = _COLL_RE.search(line)
        if m:
            dtype, dims, kind = m.group(1), m.group(2), m.group(3)
            b = _shape_bytes(dtype, dims) * _COLL_FACTOR.get(kind, 1.0)
            out[kind] = out.get(kind, 0.0) + b
            continue
        m = _TUPLE_RE.search(line)
        if m:
            shapes, kind = m.group(1), m.group(2)
            b = sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(shapes))
            out[kind] = out.get(kind, 0.0) + b * _COLL_FACTOR.get(kind, 1.0)
    return out


@dataclasses.dataclass
class Roofline:
    flops_per_device: float
    bytes_per_device: float
    coll_bytes_per_device: float
    coll_breakdown: Dict[str, float]
    model_flops_global: float
    n_devices: int

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / _PEAK["flops_bf16"]

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / _PEAK["hbm_bw"]

    @property
    def collective_s(self) -> float:
        return self.coll_bytes_per_device / _PEAK["ici_bw"]

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_s(self) -> float:
        """Roofline step time: max of the three terms (perfect overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — how much compiled compute is useful."""
        hlo_global = self.flops_per_device * self.n_devices
        return self.model_flops_global / hlo_global if hlo_global else 0.0

    @property
    def mfu(self) -> float:
        """Roofline-model MFU: useful FLOPs / (chips · peak · step_s)."""
        denom = self.n_devices * _PEAK["flops_bf16"] * self.step_s
        return self.model_flops_global / denom if denom else 0.0

    def to_dict(self) -> Dict[str, Any]:
        return {
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "coll_bytes_per_device": self.coll_bytes_per_device,
            "coll_breakdown": self.coll_breakdown,
            "model_flops_global": self.model_flops_global,
            "n_devices": self.n_devices,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "step_s": self.step_s,
            "useful_flops_ratio": self.useful_flops_ratio,
            "mfu": self.mfu,
        }


def model_flops(cfg, seq: int, batch: int, kind: str) -> float:
    """MODEL_FLOPS = 6·N·D for training (fwd+bwd), 2·N·D forward-only;
    MoE uses active params."""
    n = cfg.active_param_count() if cfg.family == "moe" \
        else cfg.param_count()
    tokens = batch * seq if kind in ("train", "prefill") else batch
    mult = 6.0 if kind == "train" else 2.0
    return mult * n * tokens


def attention_flops(cfg, seq: int, batch: int, kind: str) -> float:
    """Forward attention-score/value FLOPs (not in 6·N·D), global."""
    if cfg.family == "ssm":
        return 0.0
    layers = cfg.n_layers if cfg.family != "hybrid" else \
        len(cfg.hybrid_layer_ids)
    hq, hd = cfg.n_heads, cfg.hd
    if kind == "decode":
        # one query against the whole cache: QK^T + PV
        return 4.0 * batch * hq * hd * seq * layers
    keys = min(seq, cfg.window) if cfg.window else seq
    # causal ⇒ on average half the keys are live
    per_layer = 2.0 * batch * hq * hd * seq * keys * (0.5 if not cfg.window
                                                      else 1.0) * 2.0
    total = per_layer * layers
    if cfg.family == "audio":
        # encoder self-attn (non-causal, seq frames) + decoder cross-attn
        enc = 4.0 * batch * hq * hd * seq * seq * cfg.enc_layers
        total += enc
    return total


def analytic_hlo_flops(cfg, seq: int, batch: int, kind: str,
                       remat: str = "full") -> float:
    """Analytic floor for compiled FLOPs (global, all devices).

    Needed because XLA:CPU lowers large dots to library custom-calls that
    cost_analysis reports as 0 FLOPs — the reported 'flops' then
    underestimates by the full matmul volume.  fwd = 2·N·D + attention;
    train = fwd·3 (+1 fwd recompute under full remat)."""
    n = cfg.active_param_count() if cfg.family == "moe" \
        else cfg.param_count()
    tokens = batch * seq if kind in ("train", "prefill") else batch
    fwd = 2.0 * n * tokens + attention_flops(cfg, seq, batch, kind)
    if kind == "train":
        return fwd * (4.0 if remat == "full" else 3.0)
    return fwd


def analyze(compiled, cfg, seq: int, batch: int, kind: str,
            n_devices: int, remat: str = "full") -> Roofline:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):           # older jax returns [dict]
        cost = cost[0]
    flops = float(cost.get("flops", 0.0))
    flops = max(flops,
                analytic_hlo_flops(cfg, seq, batch, kind, remat) / n_devices)
    byts = float(cost.get("bytes accessed", 0.0))
    text = compiled.as_text()
    coll = collective_bytes(text)
    return Roofline(
        flops_per_device=flops,
        bytes_per_device=byts,
        coll_bytes_per_device=sum(coll.values()),
        coll_breakdown=coll,
        model_flops_global=model_flops(cfg, seq, batch, kind),
        n_devices=n_devices,
    )
