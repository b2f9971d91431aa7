"""Pallas TPU kernels.

overlay_exec     — the paper's overlay, executed as a config-driven VLIW
                   interpreter over VMEM tiles (program = data → swapping
                   kernels does not recompile XLA).
flash_attention  — blockwise online-softmax attention, GQA + causal + SWA.
rmsnorm          — fused RMSNorm.

:func:`interpret_mode` is the one place that decides, from the JAX
backend, whether a kernel runs in the Pallas interpreter (CPU only) and
whether the overlay runtime executes kernels on the compiled executor or
on the NumPy reference.
"""

from __future__ import annotations

from typing import Optional


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Resolve a kernel's ``interpret`` flag: an explicit value wins;
    ``None`` means interpret on the CPU backend and compile on any other.

    Forcing ``interpret=False`` on the CPU makes the kernel call raise
    (Pallas TPU kernels do not lower for the CPU); nothing falls back.
    The overlay runtime uses the same answer for its execution path: on
    the CPU it computes with the NumPy reference, elsewhere it runs the
    compiled Pallas executor (:meth:`repro.core.runtime.Kernel.enqueue`).
    """
    if interpret is not None:
        return interpret
    import jax
    return jax.default_backend() == "cpu"
