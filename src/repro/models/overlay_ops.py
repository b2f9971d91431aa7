"""Model pointwise datapaths routed through the paper's overlay JIT.

Where the pointwise math is overlay-expressible (DSP ops: ±, ×, min/max,
fused mul-add), we JIT it through the full pipeline once at import of the
using model and execute its DFG in "compiled mode" (a jnp expression
generated from the routed graph — semantically the configured overlay, see
DESIGN.md §4).  Transcendentals (exp in silu/softmax, erf in gelu) are not
DSP-block ops, so gated-silu and gated-gelu split: sigmoid and erf stay jnp,
the gating product and polynomial parts run on the overlay DFG.

The JIT'd kernels are cached process-wide; their CompiledKernel objects are
inspectable (tests assert they really placed & routed).
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.core.jit import CompiledKernel, jit_compile
from repro.core.options import CompileOptions
from repro.core.overlay import OverlaySpec

_SPEC = OverlaySpec(width=8, height=8, dsp_per_fu=2)
_CACHE: Dict[str, CompiledKernel] = {}

# every overlay-expressible datapath this module JITs, by name:
# name -> (traceable python callable, arity).  One registry so the static
# analyzer (`python -m repro.analysis`) and benchmarks can sweep exactly
# the kernels serving code uses, without calling the model entry points.
KERNELS: Dict[str, tuple] = {
    "squared_relu": (lambda a: a.max(0.0) * a.max(0.0), 1),
    "gate_mul2": (lambda a, b, c: a * b * c, 3),
    "residual_add": (lambda a, b: a + b, 2),
}


def _get(name: str) -> CompiledKernel:
    if name not in _CACHE:
        fn, n_inputs = KERNELS[name]
        _CACHE[name] = jit_compile(
            fn, _SPEC, opts=CompileOptions(n_inputs=n_inputs, name=name,
                                           max_replicas=1,
                                           place_effort=0.25))
    return _CACHE[name]


def squared_relu(x):
    """max(x,0)^2 — nemotron-4's activation; fully overlay-expressible."""
    return _get("squared_relu")(x)


def gated_silu(g, u):
    """silu(g) * u.  sigmoid is transcendental (host jnp); the two products
    are the overlay datapath."""
    s = jax.nn.sigmoid(g.astype(jnp.float32)).astype(g.dtype)
    return _get("gate_mul2")(g, s, u)


def gated_gelu(g, u):
    """gelu(g) * u = g·Φ(g)·u, with Φ the normal CDF (exact, erf).  erf is
    no DSP op (host jnp); the two products are the overlay datapath, the
    one ``gated_silu`` uses."""
    gf = g.astype(jnp.float32)
    phi = (0.5 * (1.0 + jax.lax.erf(gf * 0.5 ** 0.5))).astype(g.dtype)
    return _get("gate_mul2")(g, phi, u)


def ssm_gate(y, z):
    """y * silu(z) for the Mamba2 output gate."""
    s = jax.nn.sigmoid(z.astype(jnp.float32)).astype(z.dtype)
    return _get("gate_mul2")(y, z, s)


def residual_add(x, r):
    return _get("residual_add")(x, r)


def compiled_kernels() -> Dict[str, CompiledKernel]:
    """Expose the JIT'd overlay kernels for inspection/benchmarks."""
    return dict(_CACHE)
