"""Transformer building blocks: RoPE, GQA attention (train + KV-cache
decode), MLP variants, norms.  Pure functions over param pytrees; layer
stacks are scanned (stacked leading dim) to keep HLO size O(1) in depth.

Pointwise datapaths route through the paper's overlay JIT where expressible
(see overlay_ops.py): squared-ReLU and gating products are overlay kernels.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.rmsnorm import ops as rn_ops
from repro.models import overlay_ops
from repro.models.common import ArchConfig, dense_init


# ------------------------------------------------------------------- norms

def rmsnorm(x, w, eps: float = 1e-6, impl: str = "ref"):
    return rn_ops.rmsnorm(x, w, eps=eps, impl=impl)


# -------------------------------------------------------------------- rope

def rope_freqs(hd: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x, pos, theta: float):
    """x: (B, H, S, D); pos: (S,) or (B, S) absolute positions."""
    b, h, s, d = x.shape
    freqs = rope_freqs(d, theta)                           # (D/2,)
    if pos.ndim == 1:
        ang = pos[:, None].astype(jnp.float32) * freqs[None, :]   # (S, D/2)
        ang = ang[None, None]                              # (1,1,S,D/2)
    else:
        ang = pos[:, None, :, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------- attention

def init_attention(key, cfg: ArchConfig,
                   d_in: Optional[int] = None) -> Dict[str, Any]:
    """Projections from width ``d_in`` (default d_model) to the heads and
    from the heads back to d_model."""
    d, hd, hq, hkv = cfg.d_model, cfg.hd, cfg.n_heads, cfg.n_kv_heads
    d_in = d_in or d
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d_in, hq * hd), dtype=cfg.dtype),
        "wk": dense_init(ks[1], (d_in, hkv * hd), dtype=cfg.dtype),
        "wv": dense_init(ks[2], (d_in, hkv * hd), dtype=cfg.dtype),
        "wo": dense_init(ks[3], (hq * hd, d), dtype=cfg.dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), cfg.dtype)
        p["k_norm"] = jnp.ones((hd,), cfg.dtype)
    return p


def _split_heads(x, n, hd):
    b, s, _ = x.shape
    return x.reshape(b, s, n, hd).transpose(0, 2, 1, 3)     # (B, H, S, D)


def _merge_heads(x):
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def attention(p, x, cfg: ArchConfig, *, pos, kv: Optional[Tuple] = None,
              causal: bool = True, attn_impl: str = "ref",
              memory=None) -> Any:
    """Full-sequence attention (training / prefill).

    memory: if given (B, Sm, d), cross-attention keys/values come from it.
    """
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = _split_heads(x @ p["wq"], hq, hd)
    src = memory if memory is not None else x
    k = _split_heads(src @ p["wk"], hkv, hd)
    v = _split_heads(src @ p["wv"], hkv, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if memory is None:                                     # self-attn: RoPE
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    out = fa_ops.attention(q, k, v, causal=causal and memory is None,
                           window=cfg.window, scale=cfg.attn_scale,
                           impl=attn_impl)
    return _merge_heads(out) @ p["wo"]


def decode_qkv(p, x, cur_pos, cfg: ArchConfig):
    """The new position's query (B, Hq, 1, hd), key and value (B, Hkv, 1,
    hd) for x: (B, 1, d), with qk-norm where ``cfg.qk_norm`` and the query
    and key rotated to position ``cur_pos``."""
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = _split_heads(x @ p["wq"], hq, hd)                  # (B,Hq,1,hd)
    k_new = _split_heads(x @ p["wk"], hkv, hd)             # (B,Hkv,1,hd)
    v_new = _split_heads(x @ p["wv"], hkv, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k_new = rmsnorm(k_new, p["k_norm"], cfg.norm_eps)
    posv = jnp.full((1,), cur_pos, jnp.int32)
    q = apply_rope(q, posv, cfg.rope_theta)
    k_new = apply_rope(k_new, posv, cfg.rope_theta)
    return q, k_new, v_new


def decode_mask(s: int, cur_pos, cfg: ArchConfig, *, inclusive: bool):
    """(S,) cache positions one query at ``cur_pos`` attends to: up to
    ``cur_pos`` itself where ``inclusive``, else only those before it, and
    none a window or more behind it where ``cfg.window``."""
    kpos = jnp.arange(s)
    mask = kpos <= cur_pos if inclusive else kpos < cur_pos
    if cfg.window is not None:
        mask &= kpos > cur_pos - cfg.window
    return mask


def attend_decode(p, q, cache_k, cache_v, mask, cfg: ArchConfig, out_dtype,
                  row: Optional[Tuple] = None):
    """One query position q: (B, Hq, 1, hd) over a slab of keys and values
    (B, Hkv, S, hd) at the positions where ``mask`` (S,) holds, then the
    output projection: (B, 1, d).  Logits and softmax are float32.

    row: optionally the new position's own key and value (B, Hkv, 1, hd),
    attended beside the slab as one more position, so that the slab can be
    read as it was before the row is written into it."""
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    kf = cache_k.astype(jnp.float32)
    vf = cache_v.astype(jnp.float32)
    scale = hd ** -0.5 if cfg.attn_scale is None else cfg.attn_scale
    qf = q.astype(jnp.float32) * scale
    b = q.shape[0]
    group = hq // hkv
    qg = qf.reshape(b, hkv, group, 1, hd)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kf)
    logits = jnp.where(mask[None, None, None, None], logits, -1e30)
    if row is not None:
        k_row, v_row = (r.astype(jnp.float32) for r in row)
        own = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k_row)
        logits = jnp.concatenate([logits, own], axis=-1)
    pr = jax.nn.softmax(logits, axis=-1)
    if row is None:
        out = jnp.einsum("bhgqk,bhkd->bhgqd", pr, vf)
    else:
        s = cache_k.shape[2]
        out = (jnp.einsum("bhgqk,bhkd->bhgqd", pr[..., :s], vf)
               + jnp.einsum("bhgqk,bhkd->bhgqd", pr[..., s:], v_row))
    out = out.reshape(b, hq, 1, hd).astype(out_dtype)
    return _merge_heads(out) @ p["wo"]


def attention_decode(p, x, cache_k, cache_v, cur_pos, cfg: ArchConfig,
                     attn_impl: str = "ref"):
    """One-token decode over one layer's own cache. x: (B, 1, d); cache:
    (B, Hkv, S, hd); cur_pos: () scalar.  Writes the new key and value at
    ``cur_pos`` first, then attends over the updated slab (positions up to
    ``cur_pos``); returns (out (B, 1, d), cache_k, cache_v).  A layer
    stack whose cache is one stacked array writes its row in place instead
    (``DenseLM.forward_decode``)."""
    q, k_new, v_new = decode_qkv(p, x, cur_pos, cfg)
    cache_k = lax.dynamic_update_slice(
        cache_k, k_new.astype(cache_k.dtype), (0, 0, cur_pos, 0))
    cache_v = lax.dynamic_update_slice(
        cache_v, v_new.astype(cache_v.dtype), (0, 0, cur_pos, 0))
    mask = decode_mask(cache_k.shape[2], cur_pos, cfg, inclusive=True)
    return (attend_decode(p, q, cache_k, cache_v, mask, cfg, x.dtype),
            cache_k, cache_v)


# -------------------------------------------------------------------- MLPs

def init_mlp(key, cfg: ArchConfig, d_ff: Optional[int] = None) -> Dict:
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.activation == "swiglu":
        return {"w_gate": dense_init(ks[0], (d, ff), dtype=cfg.dtype),
                "w_up": dense_init(ks[1], (d, ff), dtype=cfg.dtype),
                "w_down": dense_init(ks[2], (ff, d), dtype=cfg.dtype)}
    return {"w_up": dense_init(ks[0], (d, ff), dtype=cfg.dtype),
            "w_down": dense_init(ks[1], (ff, d), dtype=cfg.dtype)}


def init_adapted_mlp(key, cfg: ArchConfig) -> Dict:
    """A gated MLP whose gate and up projections are one matrix."""
    d, ff = cfg.d_model, cfg.d_ff
    k1, k2 = jax.random.split(key)
    return {"w_gate_up": dense_init(k1, (d, 2 * ff), dtype=cfg.dtype),
            "w_down": dense_init(k2, (ff, d), dtype=cfg.dtype)}


def init_adapter(key, cfg: ArchConfig) -> Dict:
    """A rank-``adapter_rank`` adapter of ``w_gate_up`` (LoRA)."""
    d, ff, r = cfg.d_model, cfg.d_ff, cfg.adapter_rank
    k1, k2 = jax.random.split(key)
    return {"lora_a": dense_init(k1, (d, r), dtype=cfg.dtype),
            "lora_b": dense_init(k2, (r, 2 * ff), dtype=cfg.dtype)}


def adapted_mlp(p, adapter, x):
    """gelu(g)·u, where [g, u] = x·W_gate_up + (x·A)·B with the caller's
    adapter (A, B), then W_down."""
    gu = x @ p["w_gate_up"] + (x @ adapter["lora_a"]) @ adapter["lora_b"]
    g, u = jnp.split(gu, 2, axis=-1)
    return overlay_ops.gated_gelu(g, u) @ p["w_down"]


def mlp(p, x, cfg: ArchConfig):
    if cfg.activation == "swiglu":
        g = x @ p["w_gate"]
        u = x @ p["w_up"]
        return overlay_ops.gated_silu(g, u) @ p["w_down"]
    h = x @ p["w_up"]
    return overlay_ops.squared_relu(h) @ p["w_down"]


# ------------------------------------------------------------ LM head/embed

def init_lm(key, cfg: ArchConfig) -> Dict:
    ks = jax.random.split(key, 3)
    v = cfg.vocab_padded
    return {
        "embed": dense_init(ks[0], (v, cfg.d_model), dtype=cfg.dtype),
        "unembed": dense_init(ks[1], (cfg.d_model, v), dtype=cfg.dtype),
        "final_norm": jnp.ones((cfg.d_model,), cfg.dtype),
    }


def cross_entropy(logits, labels):
    """logits: (B, S, V) f32-ish; labels: (B, S) int32 → scalar mean nll."""
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)
