"""Shared architecture config + parameter/sharding helpers.

Every assigned architecture is an ``ArchConfig``; families:
  dense   — decoder-only GQA transformer (yi, qwen3, llama3, nemotron,
            internvl backbone)
  moe     — mixture-of-experts transformer (mixtral, qwen3-moe)
  ssm     — Mamba2 / SSD (attention-free)
  hybrid  — Mamba2 backbone + shared transformer blocks used in turn at
            fixed sites, each with per-site adapters (zamba2)
  audio   — whisper encoder-decoder (conv frontend stubbed)
  vlm     — internvl (ViT frontend stubbed; backbone = dense)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    arch_id: str
    family: str                       # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0                 # 0 → d_model // n_heads
    qk_norm: bool = False
    activation: str = "swiglu"        # swiglu | squared_relu
    rope_theta: float = 1e4
    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    capacity_factor: float = 1.25
    # SSM
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 256
    conv_width: int = 4
    ssm_groups: int = 1               # B/C groups; head h uses h // (H/G)
    conv_bias: bool = False
    # hybrid (zamba2): the layers whose Mamba input gets a shared block's
    # output; site k runs shared block k % n_shared_blocks with its own
    # rank-``adapter_rank`` MLP adapter and output linear
    hybrid_layer_ids: Tuple[int, ...] = ()
    n_shared_blocks: int = 0
    adapter_rank: int = 0
    attn_scale: Optional[float] = None    # None → hd^-0.5
    # attention variants
    window: Optional[int] = None      # sliding-window attention (mixtral)
    # enc-dec (whisper)
    enc_layers: int = 0
    # frontends (stubs)
    frontend: Optional[str] = None    # 'audio' | 'vision' | None
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def vocab_padded(self) -> int:
        """Vocab rounded up to 256 so the embedding shards on any mesh axis
        (logits over padding ids are trained down by the CE loss; labels
        never reference them)."""
        return (self.vocab + 255) // 256 * 256

    @property
    def is_subquadratic(self) -> bool:
        """Can this arch run long_500k (sub-quadratic token mixing)?"""
        return self.family in ("ssm", "hybrid") or self.window is not None

    def mamba_layer_params(self) -> int:
        """One Mamba2 layer with its input norm: in_proj to (z, xBC, dt),
        the depthwise conv over xBC, A_log, D, dt_bias, the gated norm and
        out_proj."""
        d = self.d_model
        di = self.ssm_expand * d
        h = di // self.ssm_head_dim
        conv = di + 2 * self.ssm_groups * self.ssm_state
        return (d * (di + conv + h) + conv * self.conv_width
                + (conv if self.conv_bias else 0) + 3 * h + di + di * d + d)

    def shared_block_params(self) -> int:
        """One zamba2 shared block: the norm over concat(x, embeddings),
        attention from 2·d to d, the MLP's norm, gate-up and down."""
        d, ff, hd, hq, hkv = (self.d_model, self.d_ff, self.hd,
                              self.n_heads, self.n_kv_heads)
        attn = 2 * d * hd * (hq + 2 * hkv) + hq * hd * d
        return 2 * d + attn + d + 2 * d * ff + ff * d

    def site_params(self) -> int:
        """One hybrid site's own weights: the MLP adapter and the linear."""
        d = self.d_model
        return d * self.adapter_rank + self.adapter_rank * 2 * self.d_ff \
            + d * d

    def param_count(self) -> int:
        """Total parameters N (for 6·N·D roofline bookkeeping)."""
        d, ff, v, L = self.d_model, self.d_ff, self.vocab, self.n_layers
        hd, hq, hkv = self.hd, self.n_heads, self.n_kv_heads
        if self.family == "ssm":
            return int(L * self.mamba_layer_params() + 2 * v * d + d)
        if self.family == "hybrid":       # embeddings tied to the head
            return int(L * self.mamba_layer_params()
                       + self.n_shared_blocks * self.shared_block_params()
                       + len(self.hybrid_layer_ids) * self.site_params()
                       + v * d + d)
        attn = d * hd * (hq + 2 * hkv) + hq * hd * d
        if self.activation == "swiglu":
            mlp = 3 * d * ff
        else:
            mlp = 2 * d * ff
        if self.family in ("moe",):
            mlp = self.n_experts * 3 * d * self.moe_d_ff + d * self.n_experts
        per_layer = attn + mlp + 2 * d
        total = L * per_layer + v * d * 2   # tied-off embed + lm head
        if self.family == "audio":
            total += self.enc_layers * (attn + mlp + 2 * d)
            total += L * (attn + d * hd * (hq + 2 * hkv) // 1)  # cross-attn
        return int(total)

    def active_param_count(self) -> int:
        """Active params per token (MoE: only top_k experts count)."""
        if self.family != "moe":
            return self.param_count()
        d, L = self.d_model, self.n_layers
        hd, hq, hkv = self.hd, self.n_heads, self.n_kv_heads
        attn = d * hd * (hq + 2 * hkv) + hq * hd * d
        mlp = self.top_k * 3 * d * self.moe_d_ff + d * self.n_experts
        return int(L * (attn + mlp + 2 * d) + self.vocab * d * 2)


# --------------------------------------------------------------- init utils

def dense_init(key, shape, scale_axis: int = 0, dtype=jnp.bfloat16):
    scale = (shape[scale_axis]) ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def stacked(keys, fn):
    """vmap an init fn over a leading layer axis."""
    return jax.vmap(fn)(keys)


# ----------------------------------------------------------- sharding rules

def logical_to_mesh_axes(multi_pod: bool) -> Dict[str, Any]:
    batch = ("pod", "data") if multi_pod else ("data",)
    return {
        "batch": batch, "vocab": "model", "heads": "model", "kv_heads": None,
        "ff": "model", "embed": None, "experts": "model", "seq": None,
        "kv_seq": "data", "layers": None, "ssm_inner": "model",
    }


def spec(*logical: Optional[str], multi_pod: bool = False) -> P:
    rules = logical_to_mesh_axes(multi_pod)
    axes = []
    for name in logical:
        if name is None:
            axes.append(None)
        else:
            axes.append(rules[name])
    return P(*axes)
