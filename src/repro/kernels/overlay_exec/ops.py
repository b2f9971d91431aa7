"""Jit'd wrapper around the overlay-executor Pallas kernel.

``build_image`` lowers an OverlayProgram to the executor's canonical
execution image: instructions plus final PASS moves that park each output in
the last ``n_out`` register slots.  Programs padded to the same
(n_instr, n_regs, n_in, n_out) signature share one compiled executable —
swapping kernels is a scalar-operand change only (the reconfiguration
benchmark measures exactly this).  ``execute`` runs a program at its own
signature unless the caller pads it; :func:`shared_signature` is how the
runtime picks a resident program's signature for a swap.
"""

from __future__ import annotations

from typing import Iterable, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.program import OP_PASS, OverlayProgram
from repro.obs import trace as obs_trace

_LANE = 128
# a program takes a resident program's executable only if that costs it at
# most this factor in executor passes (instructions) and register rows
SHARE_MAX_PAD = 1.25


def build_image(program: OverlayProgram, pad_to: int = 0,
                pad_regs: int = 0) -> Tuple[np.ndarray, np.ndarray, int, int]:
    """→ (instrs (M,6) i32, imms (M,) f32, n_regs_total, n_out).  The
    executor takes ``instrs`` flattened to (6*M,)."""
    p = program
    n_out = len(p.out_slots)
    # layout: [program regs | (pad gap) | trash | outputs] — outputs always
    # occupy the LAST n_out slots (the executor's contract); trash absorbs
    # padding NOPs.  pad_regs unifies register-file size across programs so
    # swapped kernels share one compiled executable.
    n_regs = max(p.n_regs + 1 + n_out, pad_regs)
    if pad_regs and pad_regs < p.n_regs + 1 + n_out:
        raise ValueError("pad_regs smaller than program register file")
    out_base = n_regs - n_out
    trash = out_base - 1
    moves = [[OP_PASS, out_base + j, s, 0, 0, 0]
             for j, s in enumerate(p.out_slots)]
    instrs = np.concatenate(
        [p.instrs.reshape(-1, 6),
         np.asarray(moves, np.int32).reshape(-1, 6)], axis=0)
    imms = np.concatenate([p.imms, np.zeros((len(moves),), np.float32)])
    if pad_to:
        if pad_to < instrs.shape[0]:
            raise ValueError("pad_to smaller than program")
        extra = pad_to - instrs.shape[0]
        pad_rows = np.tile(np.asarray([[0, trash, 0, 0, 0, 0]], np.int32),
                           (extra, 1))
        instrs = np.concatenate([instrs, pad_rows], axis=0)
        imms = np.concatenate([imms, np.zeros((extra,), np.float32)])
    return instrs, imms, n_regs, n_out


def signature(program: OverlayProgram) -> Tuple[int, int, int, int]:
    """(n_instr, n_regs, n_in, n_out) of the program's own executor image:
    programs run at one signature share one compiled executable."""
    n_out = len(program.out_slots)
    return (program.n_instr + n_out, program.n_regs + 1 + n_out,
            len(program.in_slots), n_out)


def shared_signature(program: OverlayProgram,
                     resident: Iterable[Tuple[int, int, int, int]]
                     ) -> Tuple[int, int, int, int]:
    """The signature to run ``program`` at, given the signatures of the
    programs resident on the executor: the smallest of them that holds the
    program's image with at most ``SHARE_MAX_PAD`` times its own
    instructions and registers, else the program's own.  Every padded
    instruction is one more executor pass over each block on every
    launch, so a program pads only where that saves a compile."""
    own = signature(program)
    fits = [s for s in resident
            if s[2:] == own[2:]
            and own[0] <= s[0] <= SHARE_MAX_PAD * own[0]
            and own[1] <= s[1] <= SHARE_MAX_PAD * own[1]]
    return min(fits, default=own)


def _pick_block(n: int, n_regs: int, n_in: int, n_out: int,
                vmem_budget: int = 2 << 20) -> int:
    """Largest lane-aligned block whose register file fits the VMEM budget."""
    per_item = (n_regs + n_in + n_out) * 4
    b = max(_LANE, (vmem_budget // per_item) // _LANE * _LANE)
    return int(min(b, 4096))


def execute(program: OverlayProgram, inputs: Sequence, *,
            interpret: Optional[bool] = None, pad_to: int = 0,
            pad_regs: int = 0) -> List[np.ndarray]:
    """Run an OverlayProgram over flat work-item arrays via the Pallas
    executor. Accepts any shaped arrays; work-items = flattened elements.
    ``pad_to``/``pad_regs`` pad the image to a shared signature.

    The host path is four spans (``repro.obs.trace``): ``launch:stage``
    (the inputs as one float32 (n_in, n_pad) array), ``launch:h2d`` (the
    transfers to the device), ``launch:wait`` (the executor's result
    ready) and ``launch:d2h`` (the output back on the host)."""
    import jax.numpy as jnp

    from repro.kernels.overlay_exec.kernel import overlay_execute

    instrs, imms, n_regs, n_out = build_image(program, pad_to=pad_to,
                                              pad_regs=pad_regs)
    with obs_trace.span("launch:stage", "launch"):
        arrs = [np.asarray(x, np.float32) for x in inputs]
        shape = arrs[0].shape
        n = int(np.prod(shape)) if shape else 1
        x = np.stack([a.ravel() for a in arrs])           # (n_in, N)
        n_in = x.shape[0]
        block = _pick_block(n, n_regs, n_in, n_out)
        n_pad = (n + block - 1) // block * block
        if n_pad != n:
            x = np.concatenate([x, np.zeros((n_in, n_pad - n), np.float32)],
                               axis=1)

    with obs_trace.span("launch:h2d", "launch"):
        d_instrs, d_imms, d_x = (jnp.asarray(instrs.ravel()),
                                 jnp.asarray(imms), jnp.asarray(x))
    out = overlay_execute(d_instrs, d_imms, d_x,
                          n_in=n_in, n_out=n_out,
                          n_instr=int(instrs.shape[0]), n_regs=n_regs,
                          block=block, interpret=interpret)
    with obs_trace.span("launch:wait", "launch"):
        out.block_until_ready()
    with obs_trace.span("launch:d2h", "launch"):
        out = np.asarray(out)[:, :n]
        return [out[j].reshape(shape) for j in range(n_out)]
