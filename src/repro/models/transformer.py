"""Dense decoder-only GQA transformer (yi-6b, qwen3-14b, llama3-8b,
nemotron-4-15b, and the internvl2 backbone).

Layer stack is ``lax.scan``-ed over stacked params: HLO size is O(1) in
depth, which keeps 80+ layer dry-run compiles tractable.  Training bodies are
``jax.checkpoint``-ed (full remat policy by default; the §Perf hillclimb
flips to dots-saveable).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.models.common import ArchConfig, spec


class DenseLM:
    def __init__(self, cfg: ArchConfig,
                 remat_policy: str = "full",
                 attn_impl: str = "ref",
                 parallel_block: bool = False):
        self.cfg = cfg
        self.remat_policy = remat_policy
        self.attn_impl = attn_impl
        # PaLM-style parallel attention+MLP block: one TP all-reduce per
        # layer instead of two.  BEYOND-PAPER VARIANT: changes layer
        # topology, so it is never the default for an assigned arch —
        # recorded separately in EXPERIMENTS.md §Perf.
        self.parallel_block = parallel_block
        # context-parallel activations: a NamedSharding pinned to the
        # (B, S, d) layer-boundary activations (seq sharded over 'model'),
        # set by the launcher for prefill cells — §Perf iteration B3.
        self.act_sharding = None

    # ------------------------------------------------------------- params
    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        k_lm, k_layers = jax.random.split(key)

        def one_layer(k):
            ka, km, kn = jax.random.split(k, 3)
            return {
                "attn": L.init_attention(ka, cfg),
                "mlp": L.init_mlp(km, cfg),
                "ln1": jnp.ones((cfg.d_model,), cfg.dtype),
                "ln2": jnp.ones((cfg.d_model,), cfg.dtype),
            }

        layer_keys = jax.random.split(k_layers, cfg.n_layers)
        return {"lm": L.init_lm(k_lm, cfg),
                "layers": jax.vmap(one_layer)(layer_keys)}

    def param_specs(self, multi_pod: bool = False) -> Dict[str, Any]:
        cfg = self.cfg
        sp = functools.partial(spec, multi_pod=multi_pod)
        attn = {"wq": sp("embed", "heads"), "wk": sp("embed", "heads"),
                "wv": sp("embed", "heads"), "wo": sp("heads", "embed")}
        if cfg.qk_norm:
            attn["q_norm"] = sp(None)
            attn["k_norm"] = sp(None)
        if cfg.activation == "swiglu":
            mlp = {"w_gate": sp("embed", "ff"), "w_up": sp("embed", "ff"),
                   "w_down": sp("ff", "embed")}
        else:
            mlp = {"w_up": sp("embed", "ff"), "w_down": sp("ff", "embed")}
        layer = {"attn": attn, "mlp": mlp, "ln1": sp(None), "ln2": sp(None)}
        # prepend scan axis
        layer = jax.tree.map(lambda s: P(*((None,) + tuple(s))), layer,
                             is_leaf=lambda x: isinstance(x, P))
        return {"lm": {"embed": sp("vocab", "embed"),
                       "unembed": sp("embed", "vocab"),
                       "final_norm": sp(None)},
                "layers": layer}

    # ------------------------------------------------------------ training
    def _layer_train(self, x, lp, pos):
        cfg = self.cfg
        h = L.rmsnorm(x, lp["ln1"], cfg.norm_eps)
        if self.parallel_block:
            # attn and MLP read the same normed input; their row-parallel
            # partial sums add BEFORE the single all-reduce
            return x + L.attention(lp["attn"], h, cfg, pos=pos,
                                   attn_impl=self.attn_impl) \
                     + L.mlp(lp["mlp"], h, cfg)
        x = x + L.attention(lp["attn"], h, cfg, pos=pos,
                            attn_impl=self.attn_impl)
        h = L.rmsnorm(x, lp["ln2"], cfg.norm_eps)
        return x + L.mlp(lp["mlp"], h, cfg)

    def forward_train(self, params, tokens,
                      input_embeds: Optional[Any] = None,
                      last_only: bool = False):
        """tokens: (B, S) int32 → logits (B, S, V).

        input_embeds: optional (B, P, d) stub-frontend embeddings (vision
        patches / audio frames) that REPLACE the first P token embeddings.
        """
        cfg = self.cfg
        x = params["lm"]["embed"][tokens]                  # (B, S, d)
        if input_embeds is not None:
            p = input_embeds.shape[1]
            x = jnp.concatenate(
                [input_embeds.astype(x.dtype), x[:, p:]], axis=1)
        if self.act_sharding is not None:
            x = jax.lax.with_sharding_constraint(x, self.act_sharding)
        pos = jnp.arange(tokens.shape[1])

        body = self._layer_train
        if self.remat_policy == "full":
            body = jax.checkpoint(body, static_argnums=())
        elif self.remat_policy == "dots":
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.checkpoint_dots)

        def step(x, lp):
            out = body(x, lp, pos)
            if self.act_sharding is not None:
                out = jax.lax.with_sharding_constraint(out,
                                                       self.act_sharding)
            return out, None

        x, _ = lax.scan(step, x, params["layers"])
        if last_only:
            x = x[:, -1:]
        x = L.rmsnorm(x, params["lm"]["final_norm"], cfg.norm_eps)
        return x @ params["lm"]["unembed"]

    def loss(self, params, batch):
        logits = self.forward_train(params, batch["tokens"],
                                    batch.get("input_embeds"))
        return L.cross_entropy(logits, batch["labels"])

    # ------------------------------------------------------------- serving
    def init_cache(self, batch: int, seq: int, dtype=None) -> Dict[str, Any]:
        cfg = self.cfg
        dt = dtype or cfg.dtype
        shape = (cfg.n_layers, batch, cfg.n_kv_heads, seq, cfg.hd)
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

    def cache_specs(self, multi_pod: bool = False, seq_sharded: bool = False,
                    model_axis: int = 16) -> Dict[str, Any]:
        batch = ("pod", "data") if multi_pod else "data"
        heads_shardable = (self.cfg.n_kv_heads % model_axis == 0)
        if seq_sharded:
            # long-context, batch=1: shard the KV sequence across the whole
            # mesh (paged-cache-style); heads too if they divide
            if heads_shardable:
                s = P(None, None, "model", batch, None)
            else:
                seq_ax = ("pod", "data", "model") if multi_pod \
                    else ("data", "model")
                s = P(None, None, None, seq_ax, None)
        elif heads_shardable:
            s = P(None, batch, "model", None, None)
        else:
            # GQA kv heads < model axis: shard the sequence on 'model'
            s = P(None, batch, None, "model", None)
        return {"k": s, "v": s}

    def forward_decode(self, params, cache, tokens, cur_pos):
        """tokens: (B, 1) int32; cur_pos: scalar int32 — write position.
        Returns (logits (B, 1, V), new cache).

        The stacked cache is a carry of the layer scan.  Layer i reads its
        slab ``cache[..][i]`` as it was before this step and attends over
        the positions before ``cur_pos`` there, beside the new position's
        own key and value; then it writes that one (B, Hkv, 1, hd) row of
        keys and one of values at ``(i, 0, 0, cur_pos, 0)``.  Where the
        caller donates the cache, the writes are in place: the step copies
        neither a slab nor the whole cache."""
        cfg = self.cfg
        x = params["lm"]["embed"][tokens]                  # (B, 1, d)
        mask = L.decode_mask(cache["k"].shape[3], cur_pos, cfg,
                             inclusive=False)

        def step(carry, lp):
            x, ck, cv, i = carry
            h = L.rmsnorm(x, lp["ln1"], cfg.norm_eps)
            q, k_new, v_new = L.decode_qkv(lp["attn"], h, cur_pos, cfg)
            k_new, v_new = k_new.astype(ck.dtype), v_new.astype(cv.dtype)
            a = L.attend_decode(lp["attn"], q,
                                lax.dynamic_index_in_dim(ck, i, 0, False),
                                lax.dynamic_index_in_dim(cv, i, 0, False),
                                mask, cfg, x.dtype, row=(k_new, v_new))
            at = (i, 0, 0, cur_pos, 0)
            ck = lax.dynamic_update_slice(ck, k_new[None], at)
            cv = lax.dynamic_update_slice(cv, v_new[None], at)
            x = x + a
            h = L.rmsnorm(x, lp["ln2"], cfg.norm_eps)
            x = x + L.mlp(lp["mlp"], h, cfg)
            return (x, ck, cv, i + 1), None

        (x, new_k, new_v, _), _ = lax.scan(
            step, (x, cache["k"], cache["v"], jnp.int32(0)), params["layers"])
        x = L.rmsnorm(x, params["lm"]["final_norm"], cfg.norm_eps)
        return x @ params["lm"]["unembed"], {"k": new_k, "v": new_v}
