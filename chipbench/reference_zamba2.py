"""Plain reference of Zamba2 (arXiv:2411.15242), in float32.

It follows the configuration file's published keys and the forward pass of
transformers' ``models/zamba2/modeling_zamba2.py`` (4.57.6):

- each layer ``x + mixer(norm(x + t))``, where ``t`` is the shared block's
  output at a site of ``hybrid_layer_ids`` and 0 elsewhere;
- the mixer: ``in_proj`` to (z, xBC, dt); a causal depthwise conv of width
  ``mamba_d_conv`` over xBC with its bias, then silu; x, B and C split,
  B and C in ``mamba_ngroups`` groups, head h reading group
  h // (heads / groups); dt = softplus(dt + dt_bias), A = -exp(A_log);
  per position state ← state·exp(dt·A) + dt·x ⊗ B, y = state·C + D·x;
  then RMSNorm(y·silu(z)) over each group's channels, and ``out_proj``;
- site k runs shared block k mod ``num_mem_blocks`` on
  RMSNorm(concat(x, embeddings)): causal attention whose heads span the
  concat width, rotary over the whole head (rotate-half, theta
  ``rope_theta``), scaled by (head_dim / 2)^-0.5; RMSNorm; the MLP
  gelu(g)·u (exact erf) with [g, u] = h·W_gate_up + (h·A_k)·B_k, site k's
  adapter; W_down; no residual inside the block; then site k's linear;
- a final RMSNorm and the embedding as the head.

It imports nothing of the program: it is given the weights the benchmark
drew, as the tree the program is served with (``lm``, ``layers``,
``blocks``, ``sites``).  Departures from ``modeling_zamba2.py``: the
recurrence runs one position at a time (the torch path's chunked SSD is
the same sum in another order); dt is not clamped below at
``time_step_min``, as the fused path computes it (the torch fallback
clamps); the state is float32 throughout; the weights are random.

It runs layer by layer (one compiled function per kind of layer), so that
only one layer's weights are widened to float32 at a time, under
``default_matmul_precision("highest")``.  ``lowp`` rounds every matrix
product's operands to float8 (e4m3) first: that is the control, the same
computation one precision step below the configuration's bfloat16.
"""

from __future__ import annotations

import functools
from typing import Mapping

import jax
import jax.numpy as jnp

from chipbench.reference import _mm, _norm, _rope


def _conv(x, w, b):
    """Causal depthwise conv: x (B, T, C), w (K, C), b (C,)."""
    k, t = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, i:i + t] * w[i].astype(jnp.float32)
               for i in range(k)) + b.astype(jnp.float32)


def _scan(xh, dt, A, Bg, Cg):
    """The recurrence, one position at a time.  xh (B, T, H, P); dt
    (B, T, H); Bg, Cg (B, T, G, N); returns y (B, T, H, P)."""
    b, t, h, p = xh.shape
    g, n = Bg.shape[-2:]
    Bh = jnp.repeat(Bg, h // g, axis=2)                  # (B, T, H, N)
    Ch = jnp.repeat(Cg, h // g, axis=2)

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = s * jnp.exp(dt_t * A)[..., None, None] \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t)

    s0 = jnp.zeros((b, h, p, n), jnp.float32)
    _, y = jax.lax.scan(step, s0, tuple(jnp.moveaxis(a, 1, 0)
                                        for a in (xh, dt, Bh, Ch)))
    return jnp.moveaxis(y, 0, 1)


def _mamba_layer(c: Mapping, lowp: bool, x, t, lp):
    m = lp["mamba"]
    d, di = c["hidden_size"], c["mamba_expand"] * c["hidden_size"]
    g, n, hp = c["mamba_ngroups"], c["mamba_d_state"], c["mamba_headdim"]
    h = di // hp
    b, T, _ = x.shape
    zxbcdt = _mm(_norm(x + t, lp["ln"], c["rms_norm_eps"]), m["in_proj"],
                 lowp)
    z, xbc, dt = jnp.split(zxbcdt, [di, zxbcdt.shape[-1] - h], -1)
    xbc = jax.nn.silu(_conv(xbc, m["conv_w"], m["conv_b"]))
    xs, Bm, Cm = jnp.split(xbc, [di, di + g * n], -1)
    dt = jax.nn.softplus(dt + m["dt_bias"])
    A = -jnp.exp(m["A_log"].astype(jnp.float32))
    xh = xs.reshape(b, T, h, hp)
    y = _scan(xh, dt, A, Bm.reshape(b, T, g, n), Cm.reshape(b, T, g, n))
    y = (y + m["D"][:, None] * xh).reshape(b, T, di) * jax.nn.silu(z)
    yg = y.reshape(b, T, g, di // g)
    y = _norm(yg, m["norm"].reshape(g, -1), 1e-5).reshape(b, T, di)
    return x + _mm(y, m["out_proj"], lowp)


def _block(c: Mapping, lowp: bool, x, emb, bp, sp):
    b, T, d = x.shape
    hq, hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["attention_head_dim"])
    eps = c["rms_norm_eps"]
    h = _norm(jnp.concatenate([x, emb], -1), bp["ln_in"], eps)
    at = bp["attn"]
    q = _rope(_mm(h, at["wq"], lowp).reshape(b, T, hq, hd), c["rope_theta"])
    k = _rope(_mm(h, at["wk"], lowp).reshape(b, T, hkv, hd),
              c["rope_theta"])
    v = _mm(h, at["wv"], lowp).reshape(b, T, hkv, hd)
    k = jnp.repeat(k, hq // hkv, axis=2)
    v = jnp.repeat(v, hq // hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (hd / 2) ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", pr, v).reshape(b, T, hq * hd)
    h = _norm(_mm(o, at["wo"], lowp), bp["ln_ff"], eps)
    ad = sp["adapter"]
    gu = _mm(h, bp["mlp"]["w_gate_up"], lowp) \
        + _mm(_mm(h, ad["lora_a"], lowp), ad["lora_b"], lowp)
    gate, up = jnp.split(gu, 2, -1)
    out = _mm(jax.nn.gelu(gate, approximate=False) * up,
              bp["mlp"]["w_down"], lowp)
    return _mm(out, sp["linear"], lowp)


def _head(c: Mapping, lowp: bool, x, lm):
    return _mm(_norm(x, lm["final_norm"], c["rms_norm_eps"]),
               lm["embed"].T, lowp)


def logits(c: Mapping, params, tokens, lowp: bool = False):
    """Logits (B, T, V) in float32 of the model on ``tokens`` (B, T)."""
    sites = {layer: k for k, layer in enumerate(c["hybrid_layer_ids"])}
    with jax.default_matmul_precision("highest"):
        layer = jax.jit(functools.partial(_mamba_layer, c, lowp))
        block = jax.jit(functools.partial(_block, c, lowp))
        emb = params["lm"]["embed"][tokens].astype(jnp.float32)
        x = emb
        for i, lp in enumerate(params["layers"]):
            t = jnp.zeros_like(x)
            if i in sites:
                k = sites[i]
                t = block(x, emb, params["blocks"][k % c["num_mem_blocks"]],
                          params["sites"][k])
            x = layer(x, t, lp)
        return jax.jit(functools.partial(_head, c, lowp))(x, params["lm"])
