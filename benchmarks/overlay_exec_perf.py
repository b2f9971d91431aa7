"""Overlay-executor micro-benchmark: wall time of the Pallas executor path
(interpreted on the CPU backend, compiled elsewhere) vs the compiled-mode
jnp path, plus the analytic model of the mapped overlay (GOPS at II=1).
Rows name the backend they ran on."""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from repro.configs.paper_suite import BENCHMARKS
from repro.core.jit import jit_compile
from repro.core.overlay import OverlaySpec


def _time(fn, reps=3):
    fn()                      # warmup
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps * 1e6


def run() -> List[Dict]:
    rows = []
    spec = OverlaySpec()
    n = 1 << 16
    for name in ("chebyshev", "poly2"):
        ck = jit_compile(BENCHMARKS[name][0], spec)
        n_in = len(ck.dfg.inputs)
        xs = [np.linspace(-1, 1, n).astype(np.float32)
              for _ in range(n_in)]

        import jax
        import jax.numpy as jnp

        from repro.kernels import interpret_mode
        pallas = "pallas_interpret" if interpret_mode() else "pallas"
        jxs = [jnp.asarray(x) for x in xs]
        compiled_mode = jax.jit(lambda *a: tuple(ck.dfg.evaluate(list(a))))
        us_compiled = _time(lambda: jax.block_until_ready(
            compiled_mode(*jxs)))
        us_pallas = _time(lambda: ck.run_overlay(*xs))
        rows.append({
            "name": f"overlay_exec/{name}",
            "us_per_call": us_compiled,
            "derived": (f"compiled_mode={us_compiled:.0f}us "
                        f"{pallas}={us_pallas:.0f}us "
                        f"backend={jax.default_backend()} "
                        f"items={n} "
                        f"model_gops={ck.throughput_gops():.1f}"),
        })
    return rows
