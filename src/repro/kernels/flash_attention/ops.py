"""Jit'd dispatch wrapper for attention: 'ref' (pure jnp, any backend) or
'pallas' (the flash kernel; interpreted on the CPU backend only)."""

from __future__ import annotations

from typing import Optional

from repro.kernels.flash_attention import ref as _ref
from repro.kernels.flash_attention.kernel import flash_attention


def attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
              scale: Optional[float] = None, impl: str = "ref",
              interpret: Optional[bool] = None):
    if impl == "ref":
        return _ref.attention(q, k, v, causal=causal, window=window,
                              scale=scale)
    if impl == "pallas":
        return flash_attention(q, k, v, causal=causal, window=window,
                               scale=scale, interpret=interpret)
    raise ValueError(f"unknown attention impl {impl!r}")
