"""Pieces every runner shares: the compile counter, percentiles, seeds,
peaks, the device record and the host spans the per-layer metrics read.

The peaks table, the compile counter and the nearest-rank percentile are
copies of the program's (``repro.launch.mesh.PEAKS``,
``chip_smoke.CompileClock``, ``repro.obs.metrics``), kept here so that a
change to the program cannot move the yardstick.
"""

from __future__ import annotations

import math
import threading
import time
from collections import defaultdict
from typing import Dict, Sequence

import numpy as np

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.  Source:
# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at
# 819 GB/s.  The VPU has no published peak, so no vector bound is given.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": dict(flops_bf16=197e12, hbm_bw=819e9, hbm_bytes=16e9),
}


def chip_peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of one chip of ``device_kind``; an unknown kind raises."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return PEAKS[device_kind]


_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Counts the XLA executables JAX compiles (or loads from the
    persistent cache), from ``jax.monitoring`` events."""

    def __init__(self):
        import jax
        self.executables = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == _BACKEND_COMPILE:
            with self._lock:
                self.executables += 1


def nearest_rank(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]) of all ``values``."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("percentile of no values")
    k = max(0, min(len(ordered) - 1, math.ceil(q / 100.0 * len(ordered)) - 1))
    return ordered[k]


def rng(seed: int, *stream: int) -> np.random.Generator:
    """NumPy generator for one stream of a run; any non-negative seed."""
    return np.random.default_rng([seed, *stream])


def jax_key(seed: int):
    """A JAX PRNG key from a seed of any size (two 31-bit words)."""
    import jax
    w = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(w[0] >> 1)),
                              int(w[1] >> 1))


class Spans:
    """Host spans and counters the harness records around its calls into
    each layer: ``add(name, seconds, **counts)`` sums per name.  Thread
    safe; read by the per-layer metric readers after the window."""

    def __init__(self):
        self._lock = threading.Lock()
        self.seconds: Dict[str, float] = defaultdict(float)
        self.count: Dict[str, int] = defaultdict(int)
        self.totals: Dict[str, float] = defaultdict(float)

    def add(self, name: str, seconds: float, **counts: float) -> None:
        with self._lock:
            self.seconds[name] += seconds
            self.count[name] += 1
            for k, v in counts.items():
                self.totals[f"{name}.{k}"] += v


def device_record(devices) -> dict:
    d = devices[0]
    return dict(platform=d.platform, kind=d.device_kind, count=len(devices))


def memory_peak_bytes(devices):
    """Peak bytes in use on the fullest of ``devices`` (None on a backend
    that keeps no memory statistics, as the CPU)."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return None
    return max(int(s["peak_bytes_in_use"]) for s in stats)


class Window:
    """The measured window: ``seconds`` from ``start()``."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.t0 = self.t_end = 0.0

    def start(self) -> float:
        self.t0 = time.perf_counter()
        self.t_end = self.t0 + self.seconds
        return self.t0

    def open(self) -> bool:
        return time.perf_counter() < self.t_end


def rel_err(got, want) -> float:
    """max |got - want| over max |want|: the widest error of an output,
    relative to the output's own scale."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return math.inf
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                   1e-30))

