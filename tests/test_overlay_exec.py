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
    "four": (lambda a, b, c, d: a * b + c * d - a, 4),
}

#: a kernel of each input count the launch tests cover
BY_N_IN = {1: "poly", 2: "mad", 4: "four"}


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


def _launch_inputs(n_in, n_items, layout, seed=3):
    """n_in inputs of ``n_items`` work-items each: C-contiguous float32,
    float64, or float32 strided views (every other item of a longer
    array)."""
    rng = np.random.default_rng(seed)
    if layout == "strided":
        return [rng.standard_normal(2 * n_items).astype(np.float32)[::2]
                for _ in range(n_in)]
    dtype = np.float64 if layout == "float64" else np.float32
    return [rng.standard_normal(n_items).astype(dtype) for _ in range(n_in)]


def _block(prog):
    """The executor block ``ops.execute`` picks for ``prog``."""
    _, _, n_regs, n_out = ops.build_image(prog)
    return ops._pick_block(0, n_regs, len(prog.in_slots), n_out)


@pytest.mark.parametrize("layout", ["f32_contiguous", "float64", "strided"])
@pytest.mark.parametrize("size", ["aligned", "ragged"])
@pytest.mark.parametrize("n_in", sorted(BY_N_IN))
def test_launch_matches_oracle(n_in, size, layout):
    """Every way inputs can reach the launch, aligned to the executor's
    block (taken as they are) or not (converted and padded on the host),
    agrees with the NumPy oracle."""
    prog, _ = _program(BY_N_IN[n_in])
    block = _block(prog)
    n_items = block if size == "aligned" else block + 37
    xs = _launch_inputs(n_in, n_items, layout)
    want = ref.execute(prog, [np.asarray(x, np.float32) for x in xs])
    got = ops.execute(prog, xs, interpret=True)
    assert len(got) == len(want)
    for w, g in zip(want, got):
        assert g.shape == xs[0].shape and g.dtype == np.float32
        np.testing.assert_allclose(g, w, rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("size, layout, pads", [
    ("aligned", "f32_contiguous", 0),
    ("ragged", "f32_contiguous", 1),
    ("aligned", "float64", 1),
    ("aligned", "strided", 1),
])
def test_launch_pads_on_host_only_when_it_must(size, layout, pads):
    """An aligned float32 launch opens no ``launch:pad`` span; a ragged,
    float64 or strided one opens exactly one, inside ``launch:stage``."""
    from repro.obs.trace import Tracer, activate
    prog, n_in = _program("mad")
    block = _block(prog)
    xs = _launch_inputs(n_in, block if size == "aligned" else block - 5,
                        layout)
    tr = Tracer()
    with activate(tr):
        ops.execute(prog, xs, interpret=True)
    spans = tr.spans()
    pad = [s for s in spans if s.name == "launch:pad"]
    assert len(pad) == pads
    (stage,) = [s for s in spans if s.name == "launch:stage"]
    assert all(s.parent == stage.sid for s in pad)


def test_aligned_launch_hands_the_inputs_to_the_device_as_they_are(
        monkeypatch):
    """The arrays an aligned float32 launch transfers are the caller's
    own memory, not host copies of it."""
    import jax
    prog, n_in = _program("mad")
    xs = _launch_inputs(n_in, 2 * _block(prog), "f32_contiguous")
    sent = []
    put = jax.device_put
    monkeypatch.setattr(jax, "device_put",
                        lambda arrs, *a, **kw: sent.extend(arrs) or
                        put(arrs, *a, **kw))
    got = ops.execute(prog, xs, interpret=True)
    want = ref.execute(prog, xs)
    np.testing.assert_allclose(got[0], want[0], rtol=RTOL, atol=ATOL)
    host = sent[-n_in:]
    assert all(np.shares_memory(h, x) for h, x in zip(host, xs))


def test_aligned_and_padded_launches_share_an_executable():
    """A launch padded on the host runs the executable an aligned launch of
    the same padded size compiled."""
    from repro.kernels.overlay_exec.kernel import overlay_execute
    prog, n_in = _program("mad")
    block = _block(prog)
    ops.execute(prog, _launch_inputs(n_in, 2 * block, "f32_contiguous"),
                interpret=True)
    n0 = overlay_execute._cache_size()
    for layout in ("float64", "strided"):
        ops.execute(prog, _launch_inputs(n_in, 2 * block, layout),
                    interpret=True)
    ops.execute(prog, _launch_inputs(n_in, 2 * block - 1, "f32_contiguous"),
                interpret=True)
    assert overlay_execute._cache_size() == n0


def test_launch_rejects_inputs_of_different_sizes():
    prog, _ = _program("mad")
    with pytest.raises(ValueError, match="differ in size"):
        ops.execute(prog, [np.zeros(256, np.float32),
                           np.zeros(1, np.float32)], interpret=True)


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


def test_session_swap_shares_executable(monkeypatch):
    """Through the Session, poly1 runs at the signature of the resident
    chebyshev and so compiles no executor; poly2 fits none and runs at its
    own.  The runtime is told it is on an accelerator, so it takes the
    executor, which still interprets on the CPU."""
    import repro.core.runtime
    from repro.configs.paper_suite import BENCHMARKS
    from repro.core.options import CompileOptions
    from repro.core.overlay import OverlaySpec
    from repro.core.runtime import Device
    from repro.core.session import Session
    from repro.kernels.overlay_exec.kernel import overlay_execute

    spec = OverlaySpec(width=8, height=8, dsp_per_fu=2)
    x = np.linspace(-1, 1, 1024).astype(np.float32)
    progs, compiled = {}, {}
    monkeypatch.setattr(repro.core.runtime, "interpret_mode",
                        lambda interpret=None: False)
    with Session([Device("ovl0", spec), Device("ovl1", spec)]) as sess:
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
