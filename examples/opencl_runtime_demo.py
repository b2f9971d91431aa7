"""OpenCL-runtime-style session (paper §IV, pocl-on-Zynq analogue):
platform → device → context → build (JIT) → set args → enqueue → read,
including a mid-session kernel swap that reuses the configured overlay.

Runtime v2: builds debit the device's resource ledger (release() credits it
back), a shared JIT cache makes the rebuild of a seen kernel free, and the
command queue charges bitstream reconfiguration only on program switches.

    PYTHONPATH=src python examples/opencl_runtime_demo.py
"""

import numpy as np

from repro.configs.paper_suite import BENCHMARKS
from repro.core.cache import JITCache
from repro.core.options import CompileOptions
from repro.core.overlay import OverlaySpec
from repro.core.runtime import Buffer, Context, Device, Platform


def main() -> None:
    platform = Platform([Device("zynq-overlay",
                                OverlaySpec(width=8, height=8,
                                            dsp_per_fu=2))])
    dev = platform.devices[0]
    print("device info:", dev.info())
    cache = JITCache()
    ctx = Context(dev, cache=cache)

    # build + run poly1
    prog = ctx.build_program(BENCHMARKS["poly1"][0], opts=CompileOptions())
    print(f"built poly1 in {prog.build_ms:.1f} ms "
          f"({prog.compiled.plan.replicas} replicas); "
          f"overlay config {prog.compiled.bitstream.n_bytes} B, "
          f"load {prog.configure_overlay():.1f} us; "
          f"ledger: {dev.fu_used}/{dev.spec.n_fus} FUs in use")
    x = np.linspace(-2, 2, 1000).astype(np.float32)
    # the backend decides the path: the Pallas executor on a TPU, the NumPy
    # reference on the CPU (repro.kernels.interpret_mode)
    queue = ctx.create_queue()
    ev = queue.enqueue_kernel(prog.create_kernel().set_args(Buffer(x)))
    (out,) = ev.wait()
    want = ((3 * x + 5) * x - 7) * x + 9
    assert np.allclose(out.read(), want, rtol=1e-3, atol=1e-3)
    print(f"poly1 results verified (config {ev.config_us:.1f} us + "
          f"exec {ev.exec_us:.1f} us modelled)")

    # JIT a second kernel at run time — seconds, not hours.  Releasing the
    # first program credits its FUs back so the new build sees a full overlay.
    prog.release()
    prog2 = ctx.build_program(BENCHMARKS["sgfilter"][0],
                              opts=CompileOptions())
    print(f"built sgfilter in {prog2.build_ms:.1f} ms "
          f"({prog2.compiled.plan.replicas} replicas)")
    y = np.linspace(-1, 1, 1000).astype(np.float32)
    ev2 = queue.enqueue_kernel(
        prog2.create_kernel().set_args(Buffer(x), Buffer(y)))
    (out2,) = ev2.wait()
    t = 2 * x * x + 4 * x * y - 59 * y * y + 3 * x - 7 * y + 1
    assert np.allclose(out2.read(), t * x + t * y, rtol=1e-3, atol=1e-3)
    print(f"sgfilter results verified — JIT kernel swap OK "
          f"(reconfig charged: {ev2.config_us:.1f} us)")

    # rebuild poly1: the JIT cache returns the artifact without recompiling
    prog2.release()
    prog3 = ctx.build_program(BENCHMARKS["poly1"][0], opts=CompileOptions())
    print(f"rebuilt poly1 in {prog3.build_ms:.3f} ms (cache: "
          f"{cache.stats.as_dict()})")


if __name__ == "__main__":
    main()
