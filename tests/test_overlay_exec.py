"""Overlay-executor Pallas kernel vs pure-numpy oracle: shape/program sweeps
+ the reconfiguration property (same executable, new program)."""

import numpy as np
import pytest

from repro.core.dfg import optimize, trace
from repro.core.ir import _lower_consts
from repro.core.program import compile_program
from repro.kernels.overlay_exec import ops, ref

RTOL, ATOL = 1e-4, 1e-5

KERNELS = {
    "poly": (lambda x: x * (x * (16 * x * x - 20) * x + 5), 1),
    "mad": (lambda a, b: a * b + a - b, 2),
    "imm": (lambda x: 3.0 * x + 5.0, 1),
    "rsub": (lambda x: 7.0 - x, 1),
    "minmax": (lambda a, b: a.max(0.0) * b.min(2.0) + a.min(b), 2),
    "neg": (lambda a: -a + abs(a), 1),
    "three": (lambda a, b, c: a * b + b * c + a * c, 3),
    "multi_out": (lambda a, b: (a + b, a * b, a - b), 2),
}


def _program(name):
    fn, n = KERNELS[name]
    g = optimize(_lower_consts(trace(fn, n, name)))
    return compile_program(g), n


@pytest.mark.parametrize("name", sorted(KERNELS))
@pytest.mark.parametrize("n_items", [
    1, 7, 200, pytest.param(1000, marks=pytest.mark.slow)])
def test_kernel_matches_oracle(name, n_items):
    prog, n_in = _program(name)
    rng = np.random.default_rng(42)
    xs = [rng.standard_normal(n_items).astype(np.float32) for _ in range(n_in)]
    want = ref.execute(prog, xs)
    got = ops.execute(prog, xs, interpret=True)
    assert len(got) == len(want)
    for w, g in zip(want, got):
        np.testing.assert_allclose(g, w, rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("shape", [(4, 4), (2, 3, 5), (128,)])
def test_kernel_preserves_shape(shape):
    prog, _ = _program("poly")
    x = np.random.default_rng(0).standard_normal(shape).astype(np.float32)
    out = ops.execute(prog, [x])[0]
    assert out.shape == shape


def test_padded_programs_share_signature():
    """Two different kernels padded to one signature → same static shape:
    the reconfiguration claim (new program = new scalars, no re-trace)."""
    p1, _ = _program("imm")
    p2, _ = _program("rsub")
    n = max(p1.n_instr, p2.n_instr) + 4
    i1 = ops.build_image(p1, pad_to=n + 1)
    i2 = ops.build_image(p2, pad_to=n + 1)
    assert i1[0].shape == i2[0].shape
    # n_regs may differ; pad_to unifies instr count which drives the trace
    x = np.linspace(-1, 1, 256).astype(np.float32)
    got1 = ops.execute(p1, [x], pad_to=n + 1)[0]
    got2 = ops.execute(p2, [x], pad_to=n + 1)[0]
    np.testing.assert_allclose(got1, 3 * x + 5, rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(got2, 7 - x, rtol=RTOL, atol=ATOL)


def test_against_compiled_mode():
    """Pallas path vs DFG 'compiled mode' (jnp evaluation)."""
    fn, n = KERNELS["three"]
    g = optimize(_lower_consts(trace(fn, n)))
    prog = compile_program(g)
    rng = np.random.default_rng(1)
    xs = [rng.standard_normal(512).astype(np.float32) for _ in range(n)]
    want = g.evaluate(xs)
    got = ops.execute(prog, xs)
    for w, gg in zip(want, got):
        np.testing.assert_allclose(gg, np.asarray(w), rtol=RTOL, atol=ATOL)


def _suite_program(name):
    from repro.configs.paper_suite import BENCHMARKS
    from repro.core.jit import jit_compile
    from repro.core.options import CompileOptions
    from repro.core.overlay import OverlaySpec
    return jit_compile(BENCHMARKS[name][0],
                       OverlaySpec(width=8, height=8, dsp_per_fu=2),
                       opts=CompileOptions(max_replicas=1)).program


@pytest.mark.parametrize("resident, shared", [
    ([], None),
    ([(6, 8, 1, 1)], (6, 8, 1, 1)),
    ([(16, 16, 1, 1), (6, 8, 1, 1)], (6, 8, 1, 1)),
    ([(16, 16, 1, 1)], None),        # more than SHARE_MAX_PAD times its own
    ([(4, 8, 1, 1)], None),          # too short to hold it
    ([(6, 8, 2, 1)], None),          # another IO arity
], ids=["alone", "fits", "smallest", "too_long", "too_short", "arity"])
def test_shared_signature(resident, shared):
    prog = _suite_program("poly1")
    own = ops.signature(prog)
    assert own == (prog.n_instr + 1, prog.n_regs + 2, 1, 1) == (5, 7, 1, 1)
    assert ops.shared_signature(prog, resident) == (shared or own)


def test_session_swap_shares_executable():
    """Through the Session, poly1 runs at the signature of the resident
    chebyshev and so compiles no executor; poly2 fits none and runs at its
    own."""
    from repro.configs.paper_suite import BENCHMARKS
    from repro.core.options import CompileOptions
    from repro.core.overlay import OverlaySpec
    from repro.core.runtime import Device
    from repro.core.session import Session
    from repro.kernels.overlay_exec.kernel import overlay_execute

    spec = OverlaySpec(width=8, height=8, dsp_per_fu=2)
    x = np.linspace(-1, 1, 1024).astype(np.float32)
    progs, compiled = {}, {}
    with Session([Device("ovl0", spec), Device("ovl1", spec)],
                 use_overlay_executor=True) as sess:
        for name in ("chebyshev", "poly1", "poly2"):
            fut = sess.compile(BENCHMARKS[name][0],
                               CompileOptions(max_replicas=2))
            n0 = overlay_execute._cache_size()
            got = sess.enqueue(fut, x).wait()[0].read()
            compiled[name] = overlay_execute._cache_size() - n0
            progs[name] = fut.result()
            np.testing.assert_allclose(got, BENCHMARKS[name][2](x),
                                       rtol=RTOL, atol=1e-4)
    sig = {n: p.exec_signature for n, p in progs.items()}
    assert sig["poly1"] == sig["chebyshev"] == (6, 8, 1, 1)
    assert compiled["poly1"] == 0
    assert sig["poly2"] == ops.signature(progs["poly2"].compiled.program)
