"""Mamba2 / SSD (state-space duality) — mamba2-370m, and the backbone blocks
of zamba2.  Chunked matmul formulation (Dao & Gu 2024): intra-chunk terms are
MXU-friendly batched matmuls; inter-chunk state is a short scan over chunks.
Decode carries an explicit (heads, head_dim, state) recurrence — O(1) per
token, which is what makes long_500k decode linear.

B and C come in ``cfg.ssm_groups`` groups of ``ssm_state``; the H heads are
split into as many runs of H/G, and head h reads group h // (H/G).  The
gated output norm is an RMSNorm over each group's d_inner/G channels.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.models import overlay_ops
from repro.models.common import ArchConfig, dense_init, spec


def ssm_dims(cfg: ArchConfig) -> Tuple[int, int, int]:
    d_inner = cfg.ssm_expand * cfg.d_model
    n_heads = d_inner // cfg.ssm_head_dim
    return d_inner, n_heads, cfg.ssm_state


def init_mamba_block(key, cfg: ArchConfig) -> Dict[str, Any]:
    d = cfg.d_model
    di, h, n = ssm_dims(cfg)
    conv = di + 2 * cfg.ssm_groups * n
    ks = jax.random.split(key, 4)
    p = {
        "in_proj": dense_init(ks[0], (d, di + conv + h), dtype=cfg.dtype),
        "conv_w": dense_init(ks[1], (cfg.conv_width, conv), dtype=cfg.dtype),
        "A_log": jnp.zeros((h,), jnp.float32),
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "norm": jnp.ones((di,), cfg.dtype),
        "out_proj": dense_init(ks[2], (di, d), dtype=cfg.dtype),
    }
    if cfg.conv_bias:
        p["conv_b"] = jnp.zeros((conv,), cfg.dtype)
    return p


def mamba_specs(cfg: ArchConfig, multi_pod: bool = False) -> Dict[str, Any]:
    sp = {
        "in_proj": P(None, "model"),
        "conv_w": P(None, "model"),
        "A_log": P("model"), "D": P("model"), "dt_bias": P("model"),
        "norm": P("model"),
        "out_proj": P("model", None),
    }
    if cfg.conv_bias:
        sp["conv_b"] = P("model")
    return sp


def _causal_conv(x, w):
    """Depthwise causal conv. x: (B, S, C); w: (K, C)."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(k):                       # K is tiny (4): unrolled taps
        out = out + xp[:, i:i + x.shape[1]] * w[i][None, None]
    return out


def _segsum(a):
    """a: (..., l) → (..., l, l): seg[i,j] = sum_{k=j+1..i} a_k on the lower
    triangle (0 on the diagonal), -inf above — exp() of this is the 1-SS
    decay matrix of SSD."""
    l = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    tri = jnp.tril(jnp.ones((l, l), bool))
    return jnp.where(tri, seg, -jnp.inf)


def ssd_chunked(xh, dt, A, Bm, Cm, chunk: int, compute_dtype=jnp.float32):
    """SSD in chunked matmul form.

    xh: (B, S, H, Pd) head inputs; dt: (B, S, H) discretisation steps;
    A: (H,) negative decay rates; Bm, Cm: (B, S, G, N), head h reading
    group h // (H/G).  Returns (B, S, H, Pd) in f32 and the final state
    (B, H, Pd, N).

    compute_dtype: dtype of the large intra-chunk tensors (Lmat, xdt, B, C).
    Decay exponentials and the inter-chunk state scan stay f32 for
    stability; bf16 here halves the memory-roofline term (§Perf iteration).
    """
    b, s, h, pd = xh.shape
    g, n = Bm.shape[-2:]
    e = h // g                                                 # heads/group
    c = s // chunk
    cl = chunk

    x_ = xh.reshape(b, c, cl, g, e, pd).astype(compute_dtype)
    dt_ = dt.reshape(b, c, cl, g, e)                           # f32
    B_ = Bm.reshape(b, c, cl, g, n).astype(compute_dtype)
    C_ = Cm.reshape(b, c, cl, g, n).astype(compute_dtype)
    dA = (dt_ * A.reshape(g, e)).transpose(0, 3, 4, 1, 2)      # (b,g,e,c,l)
    xdt = x_ * dt_[..., None].astype(compute_dtype)            # (b,c,l,g,e,p)

    # intra-chunk (diagonal blocks)
    Lmat = jnp.exp(_segsum(dA)).astype(compute_dtype)          # (b,g,e,c,l,l)
    y_diag = jnp.einsum("bclgn,bcsgn,bgecls,bcsgep->bclgep", C_, B_, Lmat,
                        xdt, preferred_element_type=jnp.float32)

    # chunk-final states
    dA_cum = jnp.cumsum(dA, axis=-1)                           # (b,g,e,c,l)
    decay_states = jnp.exp(dA_cum[..., -1:] - dA_cum)          # (b,g,e,c,l)
    states = jnp.einsum("bclgn,bgecl,bclgep->bcgepn", B_,
                        decay_states.astype(compute_dtype), xdt,
                        preferred_element_type=jnp.float32)

    # inter-chunk recurrence
    chunk_decay = jnp.exp(dA_cum[..., -1])                     # (b,g,e,c)

    def scan_fn(prev, inp):
        st, dec = inp                                  # (b,g,e,p,n),(b,g,e)
        new = prev * dec[..., None, None] + st
        return new, prev

    states_t = states.transpose(1, 0, 2, 3, 4, 5)              # (c,b,g,e,p,n)
    decay_t = chunk_decay.transpose(3, 0, 1, 2)                # (c,b,g,e)
    init = jnp.zeros_like(states_t[0])
    final_state, prev_states = lax.scan(scan_fn, init, (states_t, decay_t))
    prev_states = prev_states.transpose(1, 2, 3, 0, 4, 5)      # (b,g,e,c,p,n)

    state_decay = jnp.exp(dA_cum)                              # (b,g,e,c,l)
    y_off = jnp.einsum("bclgn,bgecpn,bgecl->bclgep", C_,
                       prev_states.astype(compute_dtype),
                       state_decay.astype(compute_dtype),
                       preferred_element_type=jnp.float32)

    y = (y_diag + y_off).reshape(b, s, h, pd)
    return y, final_state.reshape(b, h, pd, n)


def split_xbc(xBC, cfg: ArchConfig):
    """The conv's output → (x (…, d_inner), B and C (…, G, N))."""
    di, _, n = ssm_dims(cfg)
    gn = cfg.ssm_groups * n
    xs, Bm, Cm = jnp.split(xBC, [di, di + gn], axis=-1)
    shape = (*xBC.shape[:-1], cfg.ssm_groups, n)
    return xs, Bm.reshape(shape), Cm.reshape(shape)


def ssm_step(state, xh, dt, A, Bm, Cm):
    """One position of the recurrence: state ← state·exp(dt·A) + dt·x ⊗ B,
    read out through C.  state: (B, H, Pd, N) f32; xh: (B, H, Pd);
    dt: (B, H) f32; Bm, Cm: (B, G, N).  Returns (y (B, H, Pd), state).
    B and C are repeated over their heads rather than the state split by
    group: a split of the heads' axis would relayout the whole state."""
    e = state.shape[1] // Bm.shape[1]
    Bh = jnp.repeat(Bm.astype(jnp.float32), e, axis=1)         # (B, H, N)
    Ch = jnp.repeat(Cm.astype(jnp.float32), e, axis=1)
    dA = jnp.exp(dt * A[None, :])[..., None, None]
    xdt = xh.astype(jnp.float32) * dt[..., None]
    new = state * dA + jnp.einsum("bhp,bhn->bhpn", xdt, Bh)
    return jnp.einsum("bhpn,bhn->bhp", new, Ch), new


def gated_norm(y, z, w, cfg: ArchConfig):
    """RMSNorm(y·silu(z)) over each of the G groups of channels."""
    y = overlay_ops.ssm_gate(y, z)
    g = cfg.ssm_groups
    # one group keeps the unreshaped norm: through the (…, 1, di) reshape
    # XLA reduces in another order, and mamba2-370m's bfloat16 logits
    # move by up to 0.07 on the CPU
    if g == 1:
        return L.rmsnorm(y, w, cfg.norm_eps)
    yg = y.reshape(*y.shape[:-1], g, y.shape[-1] // g)
    return L.rmsnorm(yg, w.reshape(g, -1), cfg.norm_eps).reshape(y.shape)


def mamba_block(p, x, cfg: ArchConfig,
                conv_state=None, ssm_state=None, decode: bool = False,
                ssd_dtype=jnp.float32):
    """Full Mamba2 block. Train: (B,S,d)→(B,S,d). Decode: one step with
    carried (conv_state (B,K-1,di+2Gn), ssm_state (B,H,Pd,N))."""
    di, h, n = ssm_dims(cfg)
    pd = cfg.ssm_head_dim
    zxbcdt = x @ p["in_proj"]                    # (B,S, 2di+2Gn+h)
    z, xBC, dt = jnp.split(zxbcdt, [di, zxbcdt.shape[-1] - h], axis=-1)

    if not decode:
        xBC = _causal_conv(xBC, p["conv_w"])
        new_conv = None
    else:
        prev = conv_state                         # (B, K-1, di+2Gn)
        window = jnp.concatenate([prev, xBC], axis=1)          # (B, K, ·)
        xBC = jnp.einsum("bkc,kc->bc", window, p["conv_w"])[:, None]
        new_conv = window[:, 1:]
    if "conv_b" in p:
        xBC = xBC + p["conv_b"]
    xBC = jax.nn.silu(xBC.astype(jnp.float32)).astype(x.dtype)
    xs, Bm, Cm = split_xbc(xBC, cfg)

    dtp = jax.nn.softplus(dt.astype(jnp.float32) +
                          p["dt_bias"][None, None])             # (B,S,H)
    A = -jnp.exp(p["A_log"])                                    # (H,)
    xh = xs.reshape(*xs.shape[:2], h, pd)

    with jax.named_scope("ssm_update"):
        if not decode:
            y, new_ssm = ssd_chunked(xh, dtp, A, Bm, Cm, cfg.ssm_chunk,
                                     compute_dtype=ssd_dtype)
        else:
            y, new_ssm = ssm_step(ssm_state, xh[:, 0], dtp[:, 0], A,
                                  Bm[:, 0], Cm[:, 0])
            y = y[:, None]
    y = y + p["D"][None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(*xs.shape[:2], di).astype(x.dtype)
    out = gated_norm(y, z, p["norm"], cfg) @ p["out_proj"]
    if decode:
        return out, new_conv, new_ssm
    return out


class MambaLM:
    """Decoder-only Mamba2 LM (attention-free)."""

    def __init__(self, cfg: ArchConfig, remat_policy: str = "full",
                 attn_impl: str = "ref", ssd_dtype=jnp.float32):
        self.cfg = cfg
        self.remat_policy = remat_policy
        self.attn_impl = attn_impl
        self.ssd_dtype = ssd_dtype

    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        k_lm, k_layers = jax.random.split(key)

        def one_layer(k):
            return {"mamba": init_mamba_block(k, cfg),
                    "ln": jnp.ones((cfg.d_model,), cfg.dtype)}

        layer_keys = jax.random.split(k_layers, cfg.n_layers)
        return {"lm": L.init_lm(k_lm, cfg),
                "layers": jax.vmap(one_layer)(layer_keys)}

    def param_specs(self, multi_pod: bool = False) -> Dict[str, Any]:
        sp = functools.partial(spec, multi_pod=multi_pod)
        layer = {"mamba": mamba_specs(self.cfg, multi_pod), "ln": sp(None)}
        layer = jax.tree.map(lambda s: P(*((None,) + tuple(s))), layer,
                             is_leaf=lambda x: isinstance(x, P))
        return {"lm": {"embed": sp("vocab", "embed"),
                       "unembed": sp("embed", "vocab"),
                       "final_norm": sp(None)},
                "layers": layer}

    def _remat(self, fn):
        if self.remat_policy == "full":
            return jax.checkpoint(fn)
        if self.remat_policy == "dots":
            return jax.checkpoint(
                fn, policy=jax.checkpoint_policies.checkpoint_dots)
        return fn

    def _layer_train(self, x, lp):
        h = L.rmsnorm(x, lp["ln"], self.cfg.norm_eps)
        return x + mamba_block(lp["mamba"], h, self.cfg,
                               ssd_dtype=self.ssd_dtype)

    def forward_train(self, params, tokens,
                      input_embeds: Optional[Any] = None,
                      last_only: bool = False):
        cfg = self.cfg
        x = params["lm"]["embed"][tokens]
        body = self._remat(self._layer_train)

        def step(x, lp):
            return body(x, lp), None

        x, _ = lax.scan(step, x, params["layers"])
        if last_only:
            x = x[:, -1:]
        x = L.rmsnorm(x, params["lm"]["final_norm"], cfg.norm_eps)
        return x @ params["lm"]["unembed"]

    def loss(self, params, batch):
        logits = self.forward_train(params, batch["tokens"])
        return L.cross_entropy(logits, batch["labels"])

    # ------------------------------------------------------------- serving
    def init_cache(self, batch: int, seq: int, dtype=None) -> Dict[str, Any]:
        cfg = self.cfg
        di, h, n = ssm_dims(cfg)
        return {
            "conv": jnp.zeros((cfg.n_layers, batch, cfg.conv_width - 1,
                               di + 2 * n), dtype or cfg.dtype),
            "state": jnp.zeros((cfg.n_layers, batch, h, cfg.ssm_head_dim, n),
                               jnp.float32),
        }

    def cache_specs(self, multi_pod: bool = False, seq_sharded: bool = False,
                    model_axis: int = 16) -> Dict[str, Any]:
        batch = ("pod", "data") if multi_pod else "data"
        if seq_sharded:   # batch=1 long-context: shard the state heads
            return {"conv": P(None, None, None, "model"),
                    "state": P(None, None, "model", None, None)}
        return {"conv": P(None, batch, None, "model"),
                "state": P(None, batch, "model", None, None)}

    def forward_decode(self, params, cache, tokens, cur_pos):
        cfg = self.cfg
        x = params["lm"]["embed"][tokens]               # (B,1,d)

        def step(x, packed):
            lp, conv, state = packed
            h = L.rmsnorm(x, lp["ln"], cfg.norm_eps)
            o, conv, state = mamba_block(lp["mamba"], h, cfg,
                                         conv_state=conv, ssm_state=state,
                                         decode=True)
            return x + o, (conv, state)

        x, (conv, state) = lax.scan(
            step, x, (params["layers"], cache["conv"], cache["state"]))
        x = L.rmsnorm(x, params["lm"]["final_norm"], cfg.norm_eps)
        return x @ params["lm"]["unembed"], {"conv": conv, "state": state}
