"""Dispatch wrapper for RMSNorm ('ref' pure jnp / 'pallas')."""

from __future__ import annotations

from typing import Optional

from repro.kernels.rmsnorm import ref as _ref
from repro.kernels.rmsnorm.kernel import rmsnorm as _pallas_rmsnorm


def rmsnorm(x, weight, eps: float = 1e-6, impl: str = "ref",
            interpret: Optional[bool] = None):
    if impl == "ref":
        return _ref.rmsnorm(x, weight, eps=eps)
    if impl == "pallas":
        return _pallas_rmsnorm(x, weight, eps=eps, interpret=interpret)
    raise ValueError(f"unknown rmsnorm impl {impl!r}")
