"""Pallas TPU flash attention (blockwise online-softmax), GQA-aware.

Tiling: grid = (batch*q_heads, Sq/BQ); each cell streams KV blocks of BK
through VMEM keeping running (max, denom, acc) — the classic flash recurrence.
MXU-aligned block sizes (BQ, BK multiples of 128 on the seq dims, head dim
padded to 128 by the wrapper if needed).  Causal + sliding-window masks are
applied with per-block index arithmetic; fully-masked KV blocks are skipped
via the grid's kv upper bound (causal) so wasted MXU work is bounded by one
boundary block per row.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bk: int, skv: int,
               sq: int, causal: bool, window: Optional[int], scale: float):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale            # (BQ, D)
    d = q.shape[-1]

    q_pos = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
        + (skv - sq)                                     # decode-style align

    n_kv = skv // bk
    if causal:
        # last kv block index that can contain unmasked keys for this q block
        hi = lax.min(n_kv, lax.div((qi + 1) * bq + (skv - sq) + bk - 1, bk))
    else:
        hi = n_kv

    def body(ki, carry):
        m_prev, l_prev, acc = carry
        k = k_ref[0, pl.ds(ki * bk, bk), :].astype(jnp.float32)  # (BK, D)
        v = v_ref[0, pl.ds(ki * bk, bk), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (BQ,BK)
        k_pos = ki * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_cur = jnp.max(s, axis=-1)                      # (BQ,)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l_prev * alpha + p.sum(axis=-1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    a0 = jnp.zeros((bq, d), jnp.float32)
    m, l, acc = lax.fori_loop(0, hi, body, (m0, l0, a0))
    l = jnp.where(l == 0.0, 1.0, l)                      # fully-masked rows
    o_ref[0] = (acc / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "scale", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    scale: Optional[float] = None,
                    bq: int = 128, bk: int = 128,
                    interpret: Optional[bool] = None):
    """q: (B, Hq, Sq, D); k,v: (B, Hkv, Skv, D) → (B, Hq, Sq, D)."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    bq_ = min(bq, sq)
    bk_ = min(bk, skv)
    assert sq % bq_ == 0 and skv % bk_ == 0, (sq, bq_, skv, bk_)

    qr = q.reshape(b * hq, sq, d)
    kr = k.reshape(b * hkv, skv, d)
    vr = v.reshape(b * hkv, skv, d)

    kernel = functools.partial(_fa_kernel, bq=bq_, bk=bk_, skv=skv, sq=sq,
                               causal=causal, window=window, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(b * hq, sq // bq_),
        in_specs=[
            pl.BlockSpec((1, bq_, d), lambda h, i: (h, i, 0)),
            # kv block: whole sequence for this head (streamed inside kernel)
            pl.BlockSpec((1, skv, d), lambda h, i, g=group: (h // g, 0, 0)),
            pl.BlockSpec((1, skv, d), lambda h, i, g=group: (h // g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq_, d), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        interpret=interpret_mode(interpret),
    )(qr, kr, vr)
    return out.reshape(b, hq, sq, d)
