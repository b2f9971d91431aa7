#!/usr/bin/env python3
"""Smoke run of the system's main path on a TPU, at real sizes.

    python chip_smoke.py             # phases 1 and 2, one chip
    python chip_smoke.py --chips 4   # phase 3 only, four chips of one host

Phase 1 serves the paper's six benchmark kernels through the overlay
runtime's entry points (``Session.compile`` → ``Session.enqueue`` →
``wait``) on two overlay devices, each over 2^24 work-items, checks every
output against the kernel's NumPy oracle, and swaps between two programs
of one padded executor signature with no XLA compilation in between.
Phase 2 decodes yi-6b at its published width and depth through the serve
driver's loop and checks the logits after the prompt against
``forward_train``.  Phase 3 decodes llama3-8b, which does not fit one
chip, on a (data=1, model=4) mesh, and checks a 4-layer cut of it on one
chip against four.

Each phase prints one JSON line (compile and run seconds, largest error
against its reference, peak device bytes); the last line is
``{"ok": true, "device": {...}}``.  Any failed check raises, so the script
exits non-zero and never prints that line.  Without a TPU it fails at once.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_ITEMS = 1 << 24                     # work-items per overlay launch
OVERLAY_SPEC = dict(width=8, height=8, dsp_per_fu=2)
MAX_REPLICAS = 6
# two suite kernels of one IO arity; the second (5 instructions, 7
# registers) runs at the first's executor signature (6, 8), which the
# runtime shares with it (ops.shared_signature)
SWAP = ("chebyshev", "poly1")
F32_TOL = 1e-4                        # tests/test_overlay_exec.py's rtol
DECODE_ARCH = "yi-6b"                  # phase 2, one chip
SHARDED_ARCH = "llama3-8b"            # phase 3, model=4
DECODE = dict(batch=4, prompt_len=128, gen=16)
# bf16 logits: largest allowed ||got - ref|| / ||ref||.  Decode and the
# reference round the same bf16 math in different orders: sound runs read
# 0.006-0.021 (TPU v5e and CPU, 2 to 32 layers).  Planted decode faults
# read 0.16-0.81: one layer skipped (0.28 of 32), the KV cache written one
# slot late, the query rotated one position ahead (PERF.md, Findings)
BF16_TOL = 5e-2
CUT_LAYERS = 4                        # phase 3's one-chip comparison depth

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   _BACKEND_COMPILE)


class SmokeError(AssertionError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeError(msg)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or loading a
    compiled program from the persistent cache), and the number of
    executables it obtained, from ``jax.monitoring`` events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.executables = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.seconds += secs
        if event == _BACKEND_COMPILE:
            self.executables += 1


def device_bytes(devices, stat: str = "peak_bytes_in_use"):
    return [d.memory_stats()[stat] for d in devices]


def emit(phase: str, compile_s: float, run_s: float, max_err: float,
         devices, **extra) -> None:
    print(json.dumps(dict(phase=phase, compile_s=compile_s, run_s=run_s,
                          max_err=max_err,
                          peak_bytes_in_use=device_bytes(devices), **extra)),
          flush=True)


def max_abs_err(got, want) -> float:
    return float(np.max(np.abs(np.asarray(got, np.float64) -
                               np.asarray(want, np.float64))))


def rel_err(got, want) -> float:
    """||got - want|| / ||want|| over all elements."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# ------------------------------------------------------------ phase 1
def phase_overlay(clock: CompileClock) -> None:
    import jax

    from repro.configs.paper_suite import BENCHMARKS
    from repro.core.options import CompileOptions
    from repro.core.overlay import OverlaySpec
    from repro.core.runtime import Device
    from repro.core.session import Session
    from repro.kernels import interpret_mode
    from repro.kernels.overlay_exec.kernel import overlay_execute

    check(not interpret_mode(), "Pallas would run in interpret mode")
    spec = OverlaySpec(**OVERLAY_SPEC)
    rng = np.random.default_rng(0)
    c0, x0 = clock.seconds, overlay_execute._cache_size()
    run_s, errs, new_executables = 0.0, {}, {}
    with Session([Device("ovl0", spec), Device("ovl1", spec)]) as sess:
        check(sess.use_overlay_executor is None,
              "Session does not leave the execution path to the backend")
        futs = {name: sess.compile(src, CompileOptions(
                    max_replicas=MAX_REPLICAS), tenant=f"tenant-{i % 2}")
                for i, (name, (src, _, _)) in enumerate(BENCHMARKS.items())}
        for name, fut in futs.items():
            prog = fut.result()                      # overlay JIT (host)
            xs = [rng.uniform(-1, 1, N_ITEMS).astype(np.float32)
                  for _ in prog.compiled.dfg.inputs]
            e0 = clock.executables
            t0 = time.perf_counter()
            out = sess.enqueue(fut, *xs).wait()[0].read()
            run_s += time.perf_counter() - t0
            new_executables[name] = clock.executables - e0
            want = BENCHMARKS[name][2](*xs)
            errs[name] = max_abs_err(out, want)
            check(out.shape == (N_ITEMS,) and np.all(np.isfinite(out)),
                  f"{name}: bad output")
            check(np.allclose(out, want, rtol=F32_TOL, atol=F32_TOL),
                  f"{name}: max error {errs[name]} against its oracle")
        exec_sigs = {n: f.result().exec_signature for n, f in futs.items()}
        overlay_jit_s = sum(f.compile_us for f in futs.values()) * 1e-6
    check(None not in exec_sigs.values(),
          f"a kernel did not run on the Pallas executor: {exec_sigs}")
    check(exec_sigs[SWAP[0]] == exec_sigs[SWAP[1]],
          f"{SWAP} do not share one executor signature: {exec_sigs}")
    check(new_executables[SWAP[1]] == 0,
          f"swapping {SWAP[0]} -> {SWAP[1]} compiled "
          f"{new_executables[SWAP[1]]} XLA executables")
    emit("overlay_runtime", clock.seconds - c0, run_s, max(errs.values()),
         jax.devices()[:1], work_items=N_ITEMS,
         executor_signatures=overlay_execute._cache_size() - x0,
         swap=list(SWAP), swap_xla_compiles=new_executables[SWAP[1]],
         overlay_jit_s=overlay_jit_s, max_err_by_kernel=errs,
         exec_signature_by_kernel=exec_sigs)


# ------------------------------------------------------------ phase 2
def decode_checked(clock: CompileClock, cfg, mesh, phase: str):
    """Decode ``cfg`` through the serve driver's loop on ``mesh`` and check
    the logits after the prompt against ``forward_train``."""
    import jax

    from repro.launch.serve import decode_loop

    c0 = clock.seconds
    out = decode_loop(cfg, mesh, **DECODE)
    model, params = out["model"], out["params"]
    ref = jax.jit(lambda p, t: model.forward_train(
        p, t, last_only=True)[:, -1])(params, out["prompt"])
    ref = np.asarray(ref, np.float32)
    got = out["prompt_logits"]
    err, rel = max_abs_err(got, ref), rel_err(got, ref)
    check(got.shape == (DECODE["batch"], cfg.vocab_padded)
          and np.all(np.isfinite(got)), f"{phase}: bad logits")
    check(rel <= BF16_TOL, f"{phase}: decode logits differ from "
          f"forward_train by {rel} relative ({err} max, |ref| max "
          f"{np.abs(ref).max()})")
    devices = list(mesh.devices.flat)
    emit(phase, clock.seconds - c0, out["prefill_s"] + out["decode_s"], err,
         devices, rel_err=rel, ref_max_abs=float(np.abs(ref).max()),
         arch=cfg.arch_id, n_layers=cfg.n_layers,
         mesh=dict(mesh.shape), init_s=out["init_s"],
         prefill_s=out["prefill_s"], decode_s=out["decode_s"],
         tokens_per_s=DECODE["batch"] * DECODE["gen"] / out["decode_s"],
         **DECODE)
    return out


def phase_decode(clock: CompileClock) -> None:
    from repro.configs.registry import get_arch
    from repro.launch.mesh import make_host_mesh

    decode_checked(clock, get_arch(DECODE_ARCH), make_host_mesh(1),
                   "decode_" + DECODE_ARCH.replace("-", "_"))


# ------------------------------------------------------------ phase 3
def phase_sharded(clock: CompileClock) -> None:
    import jax

    from repro.configs.registry import get_arch
    from repro.launch.mesh import make_host_mesh

    cfg = get_arch(SHARDED_ARCH)
    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 needs 4 devices, has {len(devices)}")
    mesh4 = make_host_mesh(4, devices=devices[:4])
    check(dict(mesh4.shape) == {"data": 1, "model": 4},
          f"unexpected mesh {dict(mesh4.shape)}")

    out = decode_checked(clock, cfg, mesh4,
                         f"decode_{SHARDED_ARCH.replace('-', '_')}_model4")
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(out["params"]))
    in_use = device_bytes(devices[:4], "bytes_in_use")
    share = param_bytes / 4
    print(json.dumps(dict(phase="param_spread", param_bytes=param_bytes,
                          bytes_in_use=in_use)), flush=True)
    check(min(in_use) >= 0.8 * share and max(in_use) < 0.5 * param_bytes,
          f"parameters ({param_bytes} B) are not spread over four devices: "
          f"{in_use}")
    del out

    cut = dataclasses.replace(cfg, n_layers=CUT_LAYERS)
    one = decode_checked(clock, cut, make_host_mesh(1, devices=devices[:1]),
                         "decode_cut_1chip")
    four = decode_checked(clock, cut, mesh4, "decode_cut_4chip")
    err = max_abs_err(four["prompt_logits"], one["prompt_logits"])
    rel = rel_err(four["prompt_logits"], one["prompt_logits"])
    check(rel <= BF16_TOL, f"4-chip logits differ from 1-chip by {rel} "
          f"relative ({err} max)")
    print(json.dumps(dict(
        phase="cut_1chip_vs_4chip", max_err=err, rel_err=rel,
        n_layers=CUT_LAYERS,
        greedy_token_agreement=float(np.mean(four["tokens"] ==
                                             one["tokens"])))), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded llama3-8b phase")
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")

    from repro.launch.compile_cache import enable_compile_cache
    print(json.dumps(dict(compile_cache=enable_compile_cache())), flush=True)
    clock = CompileClock()
    if args.chips == 4:
        phase_sharded(clock)
    else:
        phase_overlay(clock)
        phase_decode(clock)
    print(json.dumps(dict(ok=True, device=dict(
        platform=dev.platform, kind=dev.device_kind,
        count=len(jax.devices())))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
