"""The dense decode as it was before its cache rows were written in place,
kept as the reference the tests compare the serve step with: each layer
writes its new key and value into its own slab first, then attends over
the updated slab, and the layer scan takes the stacked cache as xs and
gives the new slabs back as ys."""

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers as L


def attention_decode(p, x, cache_k, cache_v, cur_pos, cfg):
    """Write, then attend: x (B, 1, d), one layer's cache (B, Hkv, S, hd)."""
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = L._split_heads(x @ p["wq"], hq, hd)
    k_new = L._split_heads(x @ p["wk"], hkv, hd)
    v_new = L._split_heads(x @ p["wv"], hkv, hd)
    if cfg.qk_norm:
        q = L.rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k_new = L.rmsnorm(k_new, p["k_norm"], cfg.norm_eps)
    posv = jnp.full((1,), cur_pos, jnp.int32)
    q = L.apply_rope(q, posv, cfg.rope_theta)
    k_new = L.apply_rope(k_new, posv, cfg.rope_theta)
    cache_k = lax.dynamic_update_slice(
        cache_k, k_new.astype(cache_k.dtype), (0, 0, cur_pos, 0))
    cache_v = lax.dynamic_update_slice(
        cache_v, v_new.astype(cache_v.dtype), (0, 0, cur_pos, 0))
    s = cache_k.shape[2]
    kf = cache_k.astype(jnp.float32)
    vf = cache_v.astype(jnp.float32)
    scale = hd ** -0.5 if cfg.attn_scale is None else cfg.attn_scale
    qf = q.astype(jnp.float32) * scale
    b = q.shape[0]
    qg = qf.reshape(b, hkv, hq // hkv, 1, hd)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kf)
    kpos = jnp.arange(s)
    mask = kpos <= cur_pos
    if cfg.window is not None:
        mask &= kpos > cur_pos - cfg.window
    logits = jnp.where(mask[None, None, None, None], logits, -1e30)
    pr = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", pr, vf).reshape(b, hq, 1, hd)
    out = out.astype(x.dtype)
    return L._merge_heads(out) @ p["wo"], cache_k, cache_v


def forward_decode(model, params, cache, tokens, cur_pos):
    """``DenseLM.forward_decode`` with the slabs scanned as xs and ys."""
    cfg = model.cfg
    x = params["lm"]["embed"][tokens]

    def step(x, packed):
        lp, ck, cv = packed
        h = L.rmsnorm(x, lp["ln1"], cfg.norm_eps)
        a, ck, cv = attention_decode(lp["attn"], h, ck, cv, cur_pos, cfg)
        x = x + a
        h = L.rmsnorm(x, lp["ln2"], cfg.norm_eps)
        x = x + L.mlp(lp["mlp"], h, cfg)
        return x, (ck, cv)

    x, (new_k, new_v) = lax.scan(
        step, x, (params["layers"], cache["k"], cache["v"]))
    x = L.rmsnorm(x, params["lm"]["final_norm"], cfg.norm_eps)
    return x @ params["lm"]["unembed"], {"k": new_k, "v": new_v}
