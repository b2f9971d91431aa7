"""Pallas TPU kernel: the config-driven overlay executor.

TPU-native adaptation of the paper's spatial overlay (DESIGN.md §2):

  * the FU array becomes the VPU's 8×128 vector lanes — each instruction is
    one fused vector op over a VMEM tile of work-items;
  * the programmable interconnect becomes a VMEM register file, with routing
    expressed as dynamic register-slot reads (scalar indices from SMEM);
  * the configuration bitstream becomes the (instrs, imms) scalar-prefetch
    operands: **a new kernel = new scalars, same compiled executable**, which
    is the paper's µs-scale reconfiguration claim transposed to TPU.

BlockSpec tiling: work-items are tiled along the last dim in lane-aligned
chunks (multiple of 128); the register file lives in VMEM scratch sized
(n_regs, block).  VMEM budget = (n_regs + n_in + n_out) * block * 4 bytes,
kept ≤ ~2 MB by the wrapper's block-size choice.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


# opcode -> datapath, indexed by the OP_* constants of repro.core.program
_OPS = (
    lambda a_, b_, c_, i_: i_,                  # NOP: load immediate
    lambda a_, b_, c_, i_: a_ + b_,             # ADD
    lambda a_, b_, c_, i_: a_ - b_,             # SUB
    lambda a_, b_, c_, i_: b_ - a_,             # RSUB
    lambda a_, b_, c_, i_: a_ * b_,             # MUL
    lambda a_, b_, c_, i_: a_ * b_ + c_,        # MULADD
    lambda a_, b_, c_, i_: a_ * b_ - c_,        # MULSUB
    lambda a_, b_, c_, i_: a_ * i_ + b_,        # IMULADD
    lambda a_, b_, c_, i_: a_ * i_ - b_,        # IMULSUB
    lambda a_, b_, c_, i_: a_,                  # PASS
    lambda a_, b_, c_, i_: jnp.abs(a_),         # ABS
    lambda a_, b_, c_, i_: -a_,                 # NEG
    lambda a_, b_, c_, i_: jnp.minimum(a_, b_),  # MIN
    lambda a_, b_, c_, i_: jnp.maximum(a_, b_),  # MAX
)


def _dispatch(op, lo: int, hi: int, args):
    """Opcode dispatch as a balanced tree of two-way conds (depth
    ceil(log2 14) = 4).  A flat ``lax.switch`` lowers to a chain of 13
    nested conds, which overflows the TPU compiler's layout pass."""
    if hi - lo == 1:
        return _OPS[lo](*args)
    mid = (lo + hi) // 2
    return lax.cond(op < mid,
                    lambda: _dispatch(op, lo, mid, args),
                    lambda: _dispatch(op, mid, hi, args))


def _exec_kernel(instr_ref, imm_ref, *refs, n_in: int, n_out: int,
                 n_instr: int, n_regs: int):
    """Grid cell: execute the whole program on one work-item tile.  ``refs``
    holds the n_in input tiles, then the output tile, then the register
    file."""
    x_refs, o_ref, regs_ref = refs[:n_in], refs[n_in], refs[n_in + 1]
    # preload inputs into the first n_in register slots (static unroll)
    for i in range(n_in):
        regs_ref[i, :] = x_refs[i][0, :]

    def body(k, carry):
        # the instruction table is flat (6*M,) int32: a 2-D (M, 6) SMEM
        # operand would be padded far past its size
        op = instr_ref[6 * k]
        d = instr_ref[6 * k + 1]
        a = instr_ref[6 * k + 2]
        b = instr_ref[6 * k + 3]
        c = instr_ref[6 * k + 4]
        imm_port = instr_ref[6 * k + 5]
        imm = imm_ref[k]

        va = regs_ref[pl.ds(a, 1), :]
        vb = regs_ref[pl.ds(b, 1), :]
        vc = regs_ref[pl.ds(c, 1), :]
        immv = jnp.full_like(va, imm)
        vb = jnp.where(imm_port == 1, immv, vb)
        vc = jnp.where(imm_port == 2, immv, vc)

        res = _dispatch(op, 0, len(_OPS), (va, vb, vc, immv))
        regs_ref[pl.ds(d, 1), :] = res
        return carry

    lax.fori_loop(0, n_instr, body, 0)

    # outputs live in the last n_out register slots (execution-image layout)
    for j in range(n_out):
        o_ref[j, :] = regs_ref[n_regs - n_out + j, :]


@functools.partial(jax.jit, static_argnames=(
    "n_out", "n_instr", "n_regs", "block", "interpret"))
def overlay_execute(instrs, imms, *xs, n_out: int, n_instr: int,
                    n_regs: int, block: int = 1024,
                    interpret: Optional[bool] = None):
    """instrs: (6*n_instr,) i32; imms: (n_instr,) f32; xs: the n_in inputs,
    each (1, N) f32 with N a multiple of ``block``, as separate operands so
    that each goes to the device as the caller holds it → (n_out, N) f32."""
    n_in = len(xs)
    n = xs[0].shape[1]
    grid = (n // block,)
    kernel = functools.partial(_exec_kernel, n_in=n_in, n_out=n_out,
                               n_instr=n_instr, n_regs=n_regs)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[pl.BlockSpec((1, block), lambda i, *_: (0, i))] * n_in,
            out_specs=pl.BlockSpec((n_out, block), lambda i, *_: (0, i)),
            scratch_shapes=[pltpu.VMEM((n_regs, block), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n_out, n), jnp.float32),
        interpret=interpret_mode(interpret),
    )(instrs, imms, *xs)
