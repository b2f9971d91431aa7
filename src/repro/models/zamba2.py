"""Zamba2 (Zyphra; arXiv:2411.15242), as transformers'
``models/zamba2/modeling_zamba2.py`` writes it.

A backbone of Mamba2 layers, each ``x + mixer(norm(x + t))``.  At the
hybrid sites (``cfg.hybrid_layer_ids``) ``t`` is the output of a shared
transformer block, and 0 elsewhere; the residual is ``x``, not ``x + t``.
Site k runs block k mod ``n_shared_blocks`` (ABAB for two blocks) on
``concat(x, emb)``, where ``emb`` is the token embedding:

    h = norm(concat(x, emb))                  width 2·d
    a = attention(h)                          2·d → heads → d, no residual
    h = norm(a)
    [g, u] = h·W_gate_up + (h·A_k)·B_k        rank-r adapter of site k
    t = (gelu(g)·u·W_down)·W_k                site k's own d × d linear

The embedding is tied to the head.  Every Mamba layer, shared block and
site has its own entry in a list, and both forward passes loop over them
in Python: the compiled step names each weight and each piece of state
directly, so no step slices or copies a stacked array, and each cache
buffer (per-layer conv and SSM state, per-site keys and values) is
updated in place where the caller donates it.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.models.common import ArchConfig, dense_init, spec
from repro.models.mamba2 import (MambaLM, init_mamba_block, mamba_block,
                                 mamba_specs, ssm_dims)


def shared_block(bp, site, x, emb, cfg: ArchConfig, *, pos=None,
                 cache=None, attn_impl: str = "ref"):
    """Shared block ``bp`` at one site: returns ``t``, the site linear's
    output, and, with ``cache = (k, v, cur_pos)`` (one-token decode), the
    site's updated keys and values."""
    h = L.rmsnorm(jnp.concatenate([x, emb], axis=-1), bp["ln_in"],
                  cfg.norm_eps)
    if cache is None:
        a, new = L.attention(bp["attn"], h, cfg, pos=pos,
                             attn_impl=attn_impl), None
    else:
        ck, cv, cur_pos = cache
        a, ck, cv = L.attention_decode(bp["attn"], h, ck, cv, cur_pos, cfg,
                                       attn_impl=attn_impl)
        new = (ck, cv)
    h = L.rmsnorm(a, bp["ln_ff"], cfg.norm_eps)
    t = L.adapted_mlp(bp["mlp"], site["adapter"], h) @ site["linear"]
    return t, new


class HybridLM(MambaLM):
    def __init__(self, cfg: ArchConfig, **kw):
        super().__init__(cfg, **kw)
        # layer index -> site index, both static
        self.sites = {layer: k for k, layer in
                      enumerate(cfg.hybrid_layer_ids)}

    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        d = cfg.d_model
        k_emb, k_layers, k_blocks, k_sites = jax.random.split(key, 4)

        def block(k):
            ka, km = jax.random.split(k)
            return {"ln_in": jnp.ones((2 * d,), cfg.dtype),
                    "attn": L.init_attention(ka, cfg, d_in=2 * d),
                    "ln_ff": jnp.ones((d,), cfg.dtype),
                    "mlp": L.init_adapted_mlp(km, cfg)}

        def site(k):
            ka, kl = jax.random.split(k)
            return {"adapter": L.init_adapter(ka, cfg),
                    "linear": dense_init(kl, (d, d), dtype=cfg.dtype)}

        return {
            "lm": {"embed": dense_init(k_emb, (cfg.vocab_padded, d),
                                       dtype=cfg.dtype),
                   "final_norm": jnp.ones((d,), cfg.dtype)},
            "layers": [{"mamba": init_mamba_block(k, cfg),
                        "ln": jnp.ones((d,), cfg.dtype)}
                       for k in jax.random.split(k_layers, cfg.n_layers)],
            "blocks": [block(k) for k in
                       jax.random.split(k_blocks, cfg.n_shared_blocks)],
            "sites": [site(k) for k in
                      jax.random.split(k_sites, len(self.sites))],
        }

    def param_specs(self, multi_pod: bool = False) -> Dict[str, Any]:
        cfg = self.cfg
        sp = functools.partial(spec, multi_pod=multi_pod)
        layer = {"mamba": mamba_specs(cfg, multi_pod), "ln": sp(None)}
        block = {"ln_in": sp(None),
                 "attn": {"wq": sp("embed", "heads"),
                          "wk": sp("embed", "heads"),
                          "wv": sp("embed", "heads"),
                          "wo": sp("heads", "embed")},
                 "ln_ff": sp(None),
                 "mlp": {"w_gate_up": sp("embed", "ff"),
                         "w_down": sp("ff", "embed")}}
        site = {"adapter": {"lora_a": sp("embed", None),
                            "lora_b": sp(None, "ff")},
                "linear": sp("embed", None)}
        return {"lm": {"embed": sp("vocab", "embed"),
                       "final_norm": sp(None)},
                "layers": [layer] * cfg.n_layers,
                "blocks": [block] * cfg.n_shared_blocks,
                "sites": [site] * len(self.sites)}

    def _head(self, params, x):
        """Final norm, then the tied embedding as the head."""
        x = L.rmsnorm(x, params["lm"]["final_norm"], self.cfg.norm_eps)
        return jnp.einsum("bsd,vd->bsv", x, params["lm"]["embed"])

    def _block_of(self, params, i):
        k = self.sites[i]
        return params["blocks"][k % self.cfg.n_shared_blocks], \
            params["sites"][k]

    # ------------------------------------------------------------ training
    def _mamba_train(self, x, t, lp):
        h = L.rmsnorm(x if t is None else x + t, lp["ln"], self.cfg.norm_eps)
        return x + mamba_block(lp["mamba"], h, self.cfg,
                               ssd_dtype=self.ssd_dtype)

    def forward_train(self, params, tokens,
                      input_embeds: Optional[Any] = None,
                      last_only: bool = False):
        cfg = self.cfg
        emb = params["lm"]["embed"][tokens]
        x = emb
        pos = jnp.arange(tokens.shape[1])
        block = self._remat(functools.partial(
            shared_block, cfg=cfg, pos=pos, attn_impl=self.attn_impl))
        layer = self._remat(self._mamba_train)
        for i, lp in enumerate(params["layers"]):
            t = None
            if i in self.sites:
                with jax.named_scope("shared_block"):
                    t, _ = block(*self._block_of(params, i), x, emb)
            with jax.named_scope("mamba_layer"):
                x = layer(x, t, lp)
        if last_only:
            x = x[:, -1:]
        return self._head(params, x)

    # ------------------------------------------------------------- serving
    def init_cache(self, batch: int, seq: int, dtype=None) -> Dict[str, Any]:
        cfg = self.cfg
        di, h, n = ssm_dims(cfg)
        dt = dtype or cfg.dtype
        conv = (batch, cfg.conv_width - 1, di + 2 * cfg.ssm_groups * n)
        kv = (batch, cfg.n_kv_heads, seq, cfg.hd)
        return {
            "conv": [jnp.zeros(conv, dt) for _ in range(cfg.n_layers)],
            "state": [jnp.zeros((batch, h, cfg.ssm_head_dim, n), jnp.float32)
                      for _ in range(cfg.n_layers)],
            "k": [jnp.zeros(kv, dt) for _ in self.sites],
            "v": [jnp.zeros(kv, dt) for _ in self.sites],
        }

    def cache_specs(self, multi_pod: bool = False, seq_sharded: bool = False,
                    model_axis: int = 16) -> Dict[str, Any]:
        cfg = self.cfg
        data = ("pod", "data") if multi_pod else "data"
        heads_ok = cfg.n_kv_heads % model_axis == 0
        if seq_sharded:   # batch=1 long-context: shard heads or positions
            conv, state = P(None, None, "model"), P(None, "model", None, None)
            kv = P(None, "model", "data", None) if heads_ok else \
                P(None, None, ("pod", "data", "model") if multi_pod
                  else ("data", "model"), None)
        else:
            conv = P(data, None, "model")
            state = P(data, "model", None, None)
            kv = P(data, "model", None, None) if heads_ok else \
                P(data, None, "model", None)
        return {"conv": [conv] * cfg.n_layers,
                "state": [state] * cfg.n_layers,
                "k": [kv] * len(self.sites), "v": [kv] * len(self.sites)}

    def forward_decode(self, params, cache, tokens, cur_pos):
        cfg = self.cfg
        emb = params["lm"]["embed"][tokens]               # (B, 1, d)
        x = emb
        conv, state = list(cache["conv"]), list(cache["state"])
        ks, vs = list(cache["k"]), list(cache["v"])
        for i, lp in enumerate(params["layers"]):
            t = None
            if i in self.sites:
                k = self.sites[i]
                with jax.named_scope("shared_block"):
                    t, (ks[k], vs[k]) = shared_block(
                        *self._block_of(params, i), x, emb, cfg,
                        cache=(ks[k], vs[k], cur_pos),
                        attn_impl=self.attn_impl)
            with jax.named_scope("mamba_layer"):
                h = L.rmsnorm(x if t is None else x + t, lp["ln"],
                              cfg.norm_eps)
                o, conv[i], state[i] = mamba_block(
                    lp["mamba"], h, cfg, conv_state=conv[i],
                    ssm_state=state[i], decode=True)
                x = x + o
        return self._head(params, x), {"conv": conv, "state": state,
                                       "k": ks, "v": vs}
