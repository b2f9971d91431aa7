"""Plain reference of a dense decoder-only transformer, in float32.

It follows the configuration file as run: RMSNorm (x * rsqrt(mean(x^2) +
eps) * w), rotary embedding of the whole head in the rotate-half layout
with frequencies theta^(-2i/hd), grouped-query causal softmax attention
scaled by hd^-0.5, and a SwiGLU (silu(x Wg) * x Wu) or squared-ReLU
(relu(x Wu)^2) MLP, pre-norm residual blocks, a final norm and an untied
unembedding.  It imports nothing of the program: it is given the weights
the benchmark drew, as the tree the program is served with.

It runs layer by layer (one compiled layer, indexed by layer number), so
that only one layer's weights are widened to float32 at a time, under
``default_matmul_precision("highest")``.  ``lowp`` rounds every matrix
product's operands to float8 (e4m3) first: that is the control, the same
computation one precision step below the configuration's bfloat16.
"""

from __future__ import annotations

import functools
from typing import Mapping

import jax
import jax.numpy as jnp


def _round(x, lowp: bool):
    x = x.astype(jnp.float32)
    if lowp:
        x = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _mm(a, w, lowp: bool):
    return _round(a, lowp) @ _round(w, lowp)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, theta):
    """x: (B, T, H, hd) at positions 0..T-1, rotate-half layout."""
    t, hd = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(c: Mapping, lowp: bool, x, layers, i):
    lp = jax.tree.map(lambda a: a[i], layers)
    b, t, d = x.shape
    hq, hkv, hd = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    at = lp["attn"]
    h = _norm(x, lp["ln1"], c["norm_eps"])
    q = _rope(_mm(h, at["wq"], lowp).reshape(b, t, hq, hd), c["rope_theta"])
    k = _rope(_mm(h, at["wk"], lowp).reshape(b, t, hkv, hd),
              c["rope_theta"])
    v = _mm(h, at["wv"], lowp).reshape(b, t, hkv, hd)
    k = jnp.repeat(k, hq // hkv, axis=2)
    v = jnp.repeat(v, hq // hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, t, hq * hd)
    x = x + _mm(o, at["wo"], lowp)
    h = _norm(x, lp["ln2"], c["norm_eps"])
    m = lp["mlp"]
    if c["activation"] == "swiglu":
        a = jax.nn.silu(_mm(h, m["w_gate"], lowp)) * _mm(h, m["w_up"], lowp)
    else:
        a = jnp.square(jax.nn.relu(_mm(h, m["w_up"], lowp)))
    return x + _mm(a, m["w_down"], lowp)


def _head(c: Mapping, lowp: bool, x, lm):
    return _mm(_norm(x, lm["final_norm"], c["norm_eps"]), lm["unembed"],
               lowp)


def logits(c: Mapping, params, tokens, lowp: bool = False):
    """Logits (B, T, V) in float32 of the model on ``tokens`` (B, T)."""
    with jax.default_matmul_precision("highest"):
        layer = jax.jit(functools.partial(_layer, c, lowp))
        x = params["lm"]["embed"][tokens].astype(jnp.float32)
        for i in range(c["n_layers"]):
            x = layer(x, params["layers"], jnp.int32(i))
        return jax.jit(functools.partial(_head, c, lowp))(x, params["lm"])


def served_gaps(ref_logits, tokens, first: int):
    """For each served token ``tokens[:, t + 1]`` with t >= ``first``: how
    far its reference logit lies below the reference's best at t."""
    lg = ref_logits[:, first:-1]
    served = jnp.take_along_axis(lg, tokens[:, first + 1:, None], -1)[..., 0]
    return jnp.max(lg, -1) - served


def lowp_gaps(ref_logits, lowp_logits, first: int):
    """The same gap for the token that the lower precision puts first."""
    lg = ref_logits[:, first:-1]
    pick = jnp.argmax(lowp_logits[:, first:-1], -1)
    return jnp.max(lg, -1) - jnp.take_along_axis(lg, pick[..., None],
                                                 -1)[..., 0]
