"""Pallas TPU fused RMSNorm.

Rows tiled (BR, D) into VMEM; one pass computes the mean-square in f32 and
applies the scaled normalisation — a single HBM read + write per element
instead of XLA's potential separate reduce + scale passes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode


def _rms_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                       # (BR, D)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * (var + eps) ** -0.5 *
                  w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x, weight, eps: float = 1e-6, block_rows: int = 256,
            interpret: Optional[bool] = None):
    """x: (..., D); weight: (D,)."""
    shape = x.shape
    d = shape[-1]
    xr = x.reshape(-1, d)
    n = xr.shape[0]
    br = min(block_rows, n)
    # pad rows to a multiple of the block
    n_pad = (n + br - 1) // br * br
    if n_pad != n:
        xr = jnp.concatenate(
            [xr, jnp.zeros((n_pad - n, d), xr.dtype)], axis=0)
    out = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        grid=(n_pad // br,),
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, d), x.dtype),
        interpret=interpret_mode(interpret),
    )(xr, weight)
    return out[:n].reshape(shape)
